"""Device seconds by the program's own scopes: every busy second of a traced
call under the stage and sublayer that spent it.

The second place in `benchmarks/` (after `spans.py`) that reads the program:
`mmlspark_tpu.obs.scopes.programs()`, the fused programs alive in the
process, each with the map instruction name -> scope path that the program
parsed from its compiled HLO (`DNNModel/layer3/moe/combine`; "" under no
scope). Where the program has no such module (a commit before it),
`recorded()` gives None and every reader built on this file returns None: the
metric is left out of the line, and nothing raises.

What is here, and checked on hand-built events in `selfcheck/test_scopes.py`:

- self time (`self_times`): on the device's `XLA Ops` line a `while` event
  holds its body's events and a `conditional` its branch's. Each instant goes
  to the innermost event open at it, so a loop is counted once and the self
  seconds of all events add up to the line's busy seconds;
- the program of an event: the `XLA Modules` event that holds its start, if
  its name matches the pattern (`jit_fused(<fingerprint>)`); an event under
  no such program is kept under `OTHER`;
- the scope of an event (`resolve`): the leading `%name` of its text through
  the map of the one live program whose HLO module name is the traced
  module's (`jit_fused`) and that knows every traced instruction of it. None
  that does, two that disagree, or one that carries no scope at all (an
  executable from a compile cache that another tree filled: JAX's cache key
  leaves metadata out) is a LookupError that says which, never a number;
- `by_scope` ({path: self seconds}), `under` (the seconds below the paths a
  pattern matches) and `part_ms` (a part's milliseconds a layer a batch, the
  layers counted from the distinct `layer<i>` that have the part).

The first reader of a run prints the table on standard error: paths cut to
three components, self seconds, share of the busy seconds, the top 20.

All times are seconds. Nothing here knows a cell or a metric.
"""

from __future__ import annotations

import bisect
import re
import sys
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Sequence, Set, Tuple

from .trace import Event, Trace

FUSED = r"^jit_fused\("       # every cell's program: `core/fusion.py::_build`
OTHER = "(other programs)"    # an event under no program the pattern matches
NO_SCOPE = "(no scope)"       # how the table prints the path ""
_LAYER = re.compile(r"(?:^|/)(layer\d+)(?:/|$)")
_NAME = re.compile(r"^%?([\w.\-]+)")


class Row(NamedTuple):
    """Self seconds of the events of one instruction of one program."""

    path: str          # scope path; "" under none; OTHER outside the programs
    instruction: str   # HLO instruction name (`fusion.12`, `moe_gmm.3`)
    nested: bool       # the event holds others, or lies inside one: a loop
    seconds: float


def recorded() -> Optional[List[Any]]:
    """The program's live fused programs (label, module, scopes), or None
    where the program has no scope map to give."""
    try:
        from mmlspark_tpu.obs import scopes as program
        return list(program.programs())
    except (ImportError, AttributeError):
        return None


def instruction_of(event_name: str) -> str:
    """`%while.6 = (s32[], ...) while(...)` -> `while.6`."""
    found = _NAME.match(event_name)
    if found is None:
        raise LookupError(f"no instruction name leads {event_name[:60]!r}")
    return found.group(1)


def self_times(events: Sequence[Event]) -> List[Tuple[float, int]]:
    """(self seconds, index of the enclosing event or -1) of each event, in
    the order given: every instant goes to the event that started last among
    those open at it."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    out: List[List[Any]] = [[0.0, -1] for _ in events]
    stack: List[int] = []
    at = float("-inf")

    def advance(until: float) -> None:
        nonlocal at
        while stack:
            top = stack[-1]
            end = events[top][1]
            if end > at:
                upto = min(end, until)
                if upto > at:
                    out[top][0] += upto - at
                    at = upto
            if end <= until:
                stack.pop()
            else:
                return
        at = max(at, until)

    for i in order:
        s, e, _ = events[i]
        if e <= s:
            continue
        advance(s)
        if stack:
            out[i][1] = stack[-1]
        stack.append(i)
    advance(float("inf"))
    return [(sec, parent) for sec, parent in out]


def resolve(module: str, names: Set[str], programs: Iterable[Any]) -> Dict[str, str]:
    """{instruction: scope path} for the traced instructions `names` of the
    traced program `module` (`jit_fused(123)`), from the live programs."""
    hlo = module.split("(")[0]
    alive = [p for p in programs if p.module == hlo]
    if not alive:
        raise LookupError(f"no live program's HLO module is named {hlo!r}")
    fits = [p for p in alive if names <= p.scopes.keys()]
    if not fits:
        lacks = min((sorted(names - p.scopes.keys()) for p in alive), key=len)
        raise LookupError(
            f"traced instruction {lacks[0]!r} of {module} (and {len(lacks) - 1} more of "
            f"{len(names)}) is in no map of the {len(alive)} live program(s) named "
            f"{hlo!r}: the executable that ran is not one the program lists")
    maps = [{n: p.scopes[n] for n in names} for p in fits]
    if any(m != maps[0] for m in maps[1:]):
        raise LookupError(
            f"{len(fits)} live programs named {hlo!r} know every traced instruction "
            f"of {module} and disagree on their scopes: ambiguous")
    if not any(maps[0].values()):
        raise LookupError(
            f"the program of {module} carries no scope of this tree: its executable "
            f"came from a compile cache that another tree filled (JAX's cache key "
            f"leaves metadata out), or the tree pushes none")
    return maps[0]


def rows_of(trace: Trace, programs: Iterable[Any], module_pattern: str = FUSED
            ) -> List[Row]:
    """The first device's events as rows, one per (path, instruction, nested)."""
    plane = trace.devices[0]
    rx = re.compile(module_pattern)
    modules = sorted((s, e, n) for s, e, n in plane.modules if rx.search(n))
    if not modules:
        raise LookupError(f"no device program matches {module_pattern!r}")
    starts = [m[0] for m in modules]
    events = plane.ops
    timed = self_times(events)
    holds = [False] * len(events)
    for _sec, parent in timed:
        if parent >= 0:
            holds[parent] = True

    def module_of(start: float) -> Optional[str]:
        k = bisect.bisect_right(starts, start) - 1
        return modules[k][2] if k >= 0 and start < modules[k][1] else None

    owner = [module_of(ev[0]) for ev in events]
    names = [instruction_of(ev[2]) for ev in events]
    traced: Dict[str, Set[str]] = {}
    for mod, name in zip(owner, names):
        if mod is not None:
            traced.setdefault(mod, set()).add(name)
    programs = list(programs)
    scope_of = {mod: resolve(mod, found, programs) for mod, found in traced.items()}
    acc: Dict[Tuple[str, str, bool], float] = {}
    for i, (sec, parent) in enumerate(timed):
        path = OTHER if owner[i] is None else scope_of[owner[i]][names[i]]
        key = (path, names[i], parent >= 0 or holds[i])
        acc[key] = acc.get(key, 0.0) + sec
    return [Row(*key, sec) for key, sec in acc.items()]


def table(rows: Sequence[Row], busy_s: float, depth: int = 3, top: int = 20) -> str:
    acc: Dict[str, float] = {}
    for r in rows:
        cut = "/".join(r.path.split("/")[:depth]) or NO_SCOPE
        acc[cut] = acc.get(cut, 0.0) + r.seconds
    lines = [f"device seconds by scope (self time; busy {busy_s:.6f} s, "
             f"scopes sum to {sum(acc.values()):.6f} s)"]
    for path, sec in sorted(acc.items(), key=lambda kv: -kv[1])[:top]:
        lines.append(f"  {sec:10.6f} s  {100.0 * sec / busy_s:6.2f}%  {path}")
    return "\n".join(lines)


def busy_seconds(trace: Trace) -> float:
    return sum(e - s for s, e in trace.busy(trace.devices[0]))


def of(ctx: Dict[str, Any], module_pattern: str = FUSED) -> Optional[List[Row]]:
    """The rows of a reader's `ctx`: read once, printed, and kept there (a
    LookupError too: every reader of the run raises the same)."""
    key = "scope_rows:" + module_pattern
    if key not in ctx:
        programs = recorded()
        try:
            ctx[key] = None if programs is None else \
                rows_of(ctx["trace"], programs, module_pattern)
        except LookupError as e:
            ctx[key] = e
        if isinstance(ctx[key], list):
            print(table(ctx[key], busy_seconds(ctx["trace"])), file=sys.stderr)
    if isinstance(ctx[key], LookupError):
        raise ctx[key]
    return ctx[key]


def by_scope(ctx: Dict[str, Any], module_pattern: str = FUSED
             ) -> Optional[Dict[str, float]]:
    """{scope path: self seconds} of the traced calls' device events."""
    rows = of(ctx, module_pattern)
    if rows is None:
        return None
    acc: Dict[str, float] = {}
    for r in rows:
        acc[r.path] = acc.get(r.path, 0.0) + r.seconds
    return acc


def under(paths: Dict[str, float], pattern: str) -> float:
    """Seconds of the paths the pattern matches (`(^|/)layer\\d+/moe(/|$)`)."""
    rx = re.compile(pattern)
    return sum(sec for path, sec in paths.items() if rx.search(path))


def runs(ctx: Dict[str, Any], module_pattern: str = FUSED) -> int:
    """Batches: the runs of the program in the traced calls."""
    return ctx["trace"].module_seconds(module_pattern)[1]


def part_ms(ctx: Dict[str, Any], part: str, less: Optional[str] = None,
            nested: Optional[bool] = None, a_layer: bool = True) -> Optional[float]:
    """Self milliseconds a batch (and, `a_layer`, a layer that has the part)
    of the events whose path matches `part`, less those whose instruction
    matches `less` (a kernel's own events), and only those inside a loop or
    only those outside one where `nested` says so."""
    rows = of(ctx)
    if rows is None:
        return None
    rx = re.compile(part)
    drop = re.compile(less) if less else None
    mine = [r for r in rows if r.path != OTHER and rx.search(r.path)]
    if not mine:
        raise LookupError(f"no device event under a scope matching {part!r}")
    seconds = sum(r.seconds for r in mine
                  if not (drop and drop.search(r.instruction))
                  and (nested is None or r.nested == nested))
    layers = 1
    if a_layer:
        layers = len({m.group(1) for r in mine for m in [_LAYER.search(r.path)] if m})
        if layers == 0:
            raise LookupError(f"no layer<i> in the paths matching {part!r}")
    return 1e3 * seconds / layers / runs(ctx)


def scoped_pct(ctx: Dict[str, Any]) -> Optional[float]:
    """Share of the busy seconds whose event lies under a scope of the program."""
    rows = of(ctx)
    if rows is None:
        return None
    named = sum(r.seconds for r in rows if r.path and r.path != OTHER)
    return 100.0 * named / busy_seconds(ctx["trace"])
