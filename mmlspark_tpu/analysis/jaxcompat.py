"""J001 — jax mesh-typing APIs are referenced in one place.

The one installation is jax 0.9.0 (pyproject pins it): ``jax.shard_map``,
``jax.sharding.AxisType`` and ``jax.lax.pcast`` all exist, so nothing here
is a version gate any more. The pass is kept (ROADMAP D5 removes it with
``shard_map_compat``) as a single-owner rule: ``parallel/mesh.py`` is the
one module that names these APIs, and a direct reference elsewhere carries
an inline justification.

What counts as a direct reference (AST-level, so comments/docstrings and
``getattr(obj, "name", default)``/``hasattr(obj, "name")`` probes never
trigger):

  - an attribute access ``X.shard_map`` / ``jax.lax.pcast`` / ...
  - ``from jax.experimental.shard_map import shard_map`` (or importing any
    listed name from a jax module)
  - ``import jax.experimental.shard_map``
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Set, Tuple

from .framework import AnalysisPass, Finding, SourceFile

GATED_NAMES = ("shard_map", "AxisType", "pcast", "pvary")
SHIM_MODULE = "mmlspark_tpu/parallel/mesh.py"
_HINT = "route through parallel/mesh.py (the one owner of these names)"


class JaxCompatPass(AnalysisPass):
    pass_ids = ("J001",)
    name = "jax-compat"
    description = ("direct references to jax mesh-typing APIs "
                   f"({', '.join(GATED_NAMES)}) outside parallel/mesh.py")

    def applies_to(self, rel: str) -> bool:
        return rel.startswith("mmlspark_tpu/") and rel != SHIM_MODULE

    def check(self, sf: SourceFile) -> Iterable[Finding]:
        findings: List[Finding] = []
        if sf.tree is None:
            return findings
        seen: Set[Tuple[int, str]] = set()

        def add(line: int, what: str, detail: str) -> None:
            if (line, what) in seen:
                return
            seen.add((line, what))
            findings.append(Finding(
                sf.rel, line, "J001",
                f"direct reference to jax mesh-typing API {detail} — "
                f"{_HINT}"))

        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Attribute) and node.attr in GATED_NAMES:
                add(node.lineno, node.attr, f"'.{node.attr}'")
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if "shard_map" in mod:
                    add(node.lineno, mod, f"module '{mod}'")
                elif mod == "jax" or mod.startswith("jax."):
                    for alias in node.names:
                        if alias.name in GATED_NAMES:
                            add(node.lineno, alias.name,
                                f"'{mod}.{alias.name}'")
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if "shard_map" in alias.name:
                        add(node.lineno, alias.name,
                            f"module '{alias.name}'")
        return findings
