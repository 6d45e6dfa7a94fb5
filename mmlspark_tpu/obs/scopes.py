"""Device-side scopes: the stage and sublayer names the host's span tree
uses, carried into the compiled program's metadata, and read back from it.

``scope(name)`` is the one way the program names device work. It is
``jax.named_scope`` with one mark: the component is written ``~name``. XLA
keeps the name stack of every instruction as its ``op_name``, and JAX pushes
components of its own into the same stack (``jit(fused)``, ``while``,
``body``, ``cond``, ``branch_1_fun``, ``closed_call``, ``custom_vjp_call``,
``checkpoint``, ``vmap(...)``, the primitive at the end), some of which a
model also uses as names (``Residual``'s ``body``). No name JAX pushes starts
with ``~`` (a Python identifier, a transform's ``name(...)``, a primitive),
so the components the program pushed are exactly the marked ones and the
parser needs no list of JAX's. (``@`` would not do: the lowering cuts a
name at it.) A profile of the operator's own shows
``jit(fused)/~DNNModel/~layer3/~moe/while/body/closed_call/~combine/add``.
A scope is metadata: it adds no operation and costs nothing at run time, so
there is no switch.

``program_scopes(compiled)`` is the one place that maps a compiled program
to scopes: instruction name -> ``"DNNModel/layer3/moe/combine"`` for every
instruction the device reports as an event (the entry computation, loop
bodies and conditions, branches, called computations; a fusion is one event
under its own metadata, its inner instructions are none). ``""`` is an
instruction under no scope of the program. ``programs()`` lists the fused
programs alive in this process, from the compile caches that hold them;
a map is parsed on first asking and kept while its executable lives.

An executable loaded from JAX's persistent compilation cache carries the
metadata of the tree that compiled it (the cache's key leaves metadata out
by default): its map may hold another tree's scopes, or none. ``programs()``
reports what the text holds; a reader that finds a traced instruction
missing, or no scope at all, says so and gives no number.
"""

from __future__ import annotations

import os
import re
import weakref
from typing import Any, Dict, Iterator, List, NamedTuple, Tuple

MARK = "~"

#: operations whose called computations run inside the one event of the
#: caller (a fusion's body, a reducer, a comparator): never descended into
_INLINE = frozenset({
    "fusion", "reduce", "reduce-window", "select-and-scatter", "scatter",
    "sort", "map", "all-reduce", "all-reduce-start", "reduce-scatter",
    "custom-call"})

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_CALLED = re.compile(r"=\{?(%[\w.\-]+(?:,\s*%[\w.\-]+)*)\}?")
_MARKED = re.compile(r"^(?:\w+\()*" + re.escape(MARK) + r"([^()]+)\)*$")


def scope(name: str):
    """Context manager: the device work traced inside it is named ``name``
    (under the scopes already open) in the compiled program's metadata."""
    import jax

    return jax.named_scope(MARK + name)


def path_of(op_name: str) -> str:
    """``jit(fused)/~DNNModel/~layer0/while/body/~attn/dot_general`` ->
    ``DNNModel/layer0/attn``: the marked components, in order. A transform
    wraps the component it was applied under (``transpose(jvp(~layer0))``),
    and XLA joins the names of instructions it merged with ``;``: the first
    names the instruction."""
    return "/".join(m.group(1) for c in op_name.split(";")[0].split("/")
                    for m in [_MARKED.match(c)] if m)


def _operation_and_attributes(line: str, at: int) -> Tuple[str, str]:
    """(operation, the text after its operands) of an instruction line whose
    result type starts at ``at``. The type ends at the first space outside
    every bracket (a tuple's parentheses, a layout's ``{1,0:T(8,128)}``), the
    operation at the ``(`` that opens its operands, the operands where that
    parenthesis closes."""
    depth, kind, op_at = 0, "", -1
    for i in range(at, len(line)):
        ch = line[i]
        if ch in "([{":
            if depth == 0 and op_at >= 0 and not kind:
                kind = line[op_at:i]
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0 and kind:
                return kind, line[i + 1:]
        elif ch == " " and depth == 0 and op_at < 0:
            op_at = i + 1
    return kind, ""


def instructions(text: str) -> Iterator[Tuple[str, bool, str, str, str, List[str]]]:
    """(computation, is entry, instruction, operation, op_name, computations
    it calls) of every instruction line of an HLO module's text."""
    comp, entry = "", False
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp, entry = c.group(2), bool(c.group(1))
            continue
        kind, tail = _operation_and_attributes(line, m.end())
        found = _OP_NAME.search(tail)
        called = [n.strip().lstrip("%") for group in _CALLED.findall(
            tail.split("metadata=")[0].split("backend_config=")[0])
            for n in group.split(",")]
        yield comp, entry, m.group(1), kind, found.group(1) if found else "", called


def _shared(paths: List[str]) -> str:
    """The path every one of ``paths`` lies under (their common prefix)."""
    return "/".join(os.path.commonprefix([p.split("/") for p in paths]))


def parse(text: str) -> Tuple[str, Dict[str, str]]:
    """(HLO module name, {instruction: scope path}) of a module's text. An
    instruction the compiler made carries no ``op_name`` (a fusion around a
    cast it inserted, a loop it rewrote): where it calls computations it is
    under the path all the traced instructions inside them share; a plain
    one (a layout's ``copy``) stays under none."""
    head = _MODULE.match(text)
    by_comp: Dict[str, List[Tuple[str, str, str, List[str]]]] = {}
    root = ""
    for comp, entry, name, kind, op_name, called in instructions(text):
        by_comp.setdefault(comp, []).append((name, kind, op_name, called))
        if entry:
            root = comp

    def traced_in(comps: List[str], seen: set) -> List[str]:
        found: List[str] = []
        for comp in comps:
            if comp in seen:
                continue
            seen.add(comp)
            for _name, _kind, op_name, called in by_comp.get(comp, ()):
                if MARK in op_name:
                    found.append(path_of(op_name))
                found.extend(traced_in(called, seen))
        return found

    out: Dict[str, str] = {}
    seen, todo = set(), [root]
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in by_comp:
            continue
        seen.add(comp)
        for name, kind, op_name, called in by_comp[comp]:
            out[name] = path_of(op_name) if op_name or not called \
                else _shared(traced_in(called, set()))
            if kind not in _INLINE:
                todo.extend(called)
    return (head.group(1) if head else ""), out


def program_scopes(compiled: Any) -> Dict[str, str]:
    """{instruction name: scope path} of a compiled program
    (``jax.stages.Compiled``: its ``as_text()`` is the optimized HLO)."""
    return parse(compiled.as_text())[1]


class Program(NamedTuple):
    """One fused program alive in this process."""

    label: str                 # the segment's label, as the host spans name it
    module: str                # the HLO module's name (``jit_fused``)
    scopes: Dict[str, str]     # instruction name -> scope path ("": none)


# executable -> (module, scopes), parsed when first asked for; weak keys, so
# an executable a CompileCache evicted is not kept alive by its map
_PARSED: "weakref.WeakKeyDictionary[Any, Tuple[str, Dict[str, str]]]" = \
    weakref.WeakKeyDictionary()


def programs() -> List[Program]:
    """The fused programs the process's compile caches hold, each with its
    scope map. Nothing is parsed before the first call, and a program once."""
    from ..core.device_stage import live_caches

    out: List[Program] = []
    for cache in live_caches():
        for label, fn in cache.resident():
            if not hasattr(fn, "as_text"):
                continue               # a callable that is no executable
            found = _PARSED.get(fn)
            if found is None:
                found = _PARSED[fn] = parse(fn.as_text())
            out.append(Program(label, *found))
    return out
