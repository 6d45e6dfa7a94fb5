"""The accelerator: refuse to run without it, name it, read its memory peak."""

from __future__ import annotations

import os
import sys
from typing import Any, Dict, List

from .spec import ROOT


def fix_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the checkout
    (or where `JAX_COMPILATION_CACHE_DIR` already points). Set before JAX is
    imported, so that the program, which reads the same variable, takes it."""
    path = os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    os.makedirs(path, exist_ok=True)
    # store every program, also the ones that compile in under a second:
    # set-up is then the same work in every run after the first
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    return path


def require_chips(chips: int) -> List[Any]:
    """The cell's chips, or exit 4 with one line and no result."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend at all
        devices = []
        reason = str(e).splitlines()[0]
    else:
        reason = ""
    tpus = [d for d in devices if d.platform == "tpu"]
    if len(tpus) < chips:
        found = ", ".join(sorted({d.platform for d in devices})) or reason
        print(f"benchmark: needs {chips} TPU chip(s), found {len(tpus)} "
              f"({found}); no result", file=sys.stderr)
        raise SystemExit(4)
    return tpus[:chips]


def describe(devices: List[Any]) -> Dict[str, Any]:
    # the TPU's allocator keeps two books, both in `memory_stats()`: the heap
    # (arrays: weights, staged batches, outputs) and what it reserves as the
    # running program's scratch. The memory held is their sum; each peak is
    # read from JAX, the sum is this function's (PERF.md section 2).
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
