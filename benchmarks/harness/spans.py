"""The program's own spans, joined to the device trace on one clock.

The one place in `benchmarks/` besides the builders that reads the program:
its default span recorder, `mmlspark_tpu.obs.trace.default_tracer()`, which
a fused transform fills on the host's clock (epoch seconds; one root span
`transform` a call, about sixty spans below it, one per batch per phase at
the finest; docs/observability.md has the tree). `benchmarks/README.md`
could not be edited by the PR that added this file, so it is said here and
in PERF.md section 3. Where the program has no such recorder (a commit
before it), `recorded()` gives None and every reader built on this file
returns None: the metric is left out of the line, and nothing raises.

What is here, and checked on hand-built data in `selfcheck/test_spans.py`:

- the tree of the traced calls (`traced_calls`): the last `trace_calls`
  root spans of the recorder (the warm-up's come before them) with every
  span of their trace ids;
- self time (`Calls.self_segments`): a span's interval less what its
  children ON THE SAME THREAD cover. The ring's producer and the slot
  filler are other threads; their spans (`h2d`, `fill`) run beside the
  caller and take nothing from it;
- the clock join (`Calls.clock_offset`). The driver hands the readers the
  device's events in the trace's own time base, which need not be the
  epoch. If device time = host time + d, then for every batch k the fused
  program cannot start before its `dispatch` span opened and cannot end
  after its `compute_wait` span closed:

      max_k(module_end_k - compute_wait_end_k) <= d
                                  <= min_k(module_start_k - dispatch_start_k)

  The upper side is tight when the chip is idle at dispatch. The lower side
  is tight only for a batch the host truly waited for, and in a ring two
  deep it waits for none (a program ends under the previous batch's
  readback): so the program also records `in_flight`, a span a watching
  thread closes when the batch's outputs are ready, and where every batch
  has one its end stands in for a later `compute_wait` end.
  d is taken at the middle; the width is the alignment's uncertainty. An
  empty interval, or a count of `dispatch` spans that differs from the count
  of matching module events, is a LookupError, never a number;
- idle attribution (`Calls.idle_by_span`): each instant of device idle
  inside the window goes to the innermost span open on the calling thread
  at that instant — to `NO_SPAN` where that is the root itself, and to
  `OUTSIDE` where no traced call is open (the caller's own code between
  calls and around them).

All times are seconds. Nothing here knows a cell or a metric.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from .trace import Interval, Trace, merge, total

ROOT = "transform"
DISPATCH = "dispatch"
DRAIN = "compute_wait"
SEEN = "in_flight"
OUTSIDE = "outside transform"     # in the window, under no traced call
NO_SPAN = "no span"               # under a root and under nothing below it


@dataclass
class Span:
    name: str
    span_id: str
    parent_id: Optional[str]
    trace_id: str
    t0: float
    t1: float
    thread: str
    attrs: Dict[str, Any] = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


def from_dicts(dicts: Iterable[Dict[str, Any]]) -> List[Span]:
    """`Tracer.spans()` dicts -> Span."""
    return [Span(d["name"], d["span_id"], d.get("parent_id"), d["trace_id"],
                 float(d["t0"]), float(d["t0"]) + float(d["dur_s"]),
                 d.get("thread", ""), dict(d.get("attrs") or {}))
            for d in dicts]


def recorded() -> Optional[List[Span]]:
    """Every span the program's default recorder holds, or None where the
    program has no default recorder or it is switched off."""
    try:
        from mmlspark_tpu.obs import trace as program
        tracer = program.default_tracer()
    except (ImportError, AttributeError):
        return None
    if tracer is None:
        return None
    return from_dicts(tracer.spans())


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(whole: Interval, holes: Sequence[Interval]) -> List[Interval]:
    """`whole` less the union of `holes`, as disjoint sorted intervals."""
    out: List[Interval] = []
    at = whole[0]
    for s, e in merge(clip(holes, *whole)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if whole[1] > at:
        out.append((at, whole[1]))
    return out


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Seconds that lie in both unions (each given sorted and disjoint)."""
    i = j = 0
    acc = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            acc += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return acc


class Calls:
    """The traced calls: their root spans and every span of their traces."""

    def __init__(self, roots: Sequence[Span], spans: Sequence[Span]):
        self.roots = sorted(roots, key=lambda s: s.t0)
        ids = {r.trace_id for r in self.roots}
        self.spans = sorted((s for s in spans if s.trace_id in ids),
                            key=lambda s: s.t0)
        self.by_id = {s.span_id: s for s in self.spans}
        self.kids: Dict[str, List[Span]] = {}
        for s in self.spans:
            if s.parent_id is not None:
                self.kids.setdefault(s.parent_id, []).append(s)

    # -- the tree ---------------------------------------------------------
    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def seconds(self, name: str) -> float:
        """Summed duration of the spans of that name; LookupError if none."""
        found = self.named(name)
        if not found:
            raise LookupError(f"no span named {name!r} in the traced calls")
        return sum(s.dur for s in found)

    @property
    def batches(self) -> int:
        """Batches the traced calls dispatched (their `dispatch` spans)."""
        n = len(self.named(DISPATCH))
        if n == 0:
            raise LookupError("no dispatch span in the traced calls")
        return n

    def under(self, span: Span, name: str) -> bool:
        """Is `span`, or one of its ancestors, named `name`?"""
        at: Optional[Span] = span
        while at is not None:
            if at.name == name:
                return True
            at = self.by_id.get(at.parent_id) if at.parent_id else None
        return False

    # -- self time ----------------------------------------------------------
    def self_segments(self, span: Span) -> List[Interval]:
        """The span's interval less what its children on its own thread
        cover (a child on another thread runs beside it)."""
        holes = [(k.t0, k.t1) for k in self.kids.get(span.span_id, ())
                 if k.thread == span.thread]
        return subtract((span.t0, span.t1), holes)

    def self_seconds(self, thread: Optional[str] = None) -> Dict[str, float]:
        """{span name: self time} over the spans of one thread (default:
        the thread the roots were recorded on, the caller's)."""
        thread = self.roots[0].thread if thread is None else thread
        acc: Dict[str, float] = {}
        for s in self.spans:
            if s.thread == thread:
                acc[s.name] = acc.get(s.name, 0.0) \
                    + total(self.self_segments(s))
        return acc

    def uncovered_share(self) -> float:
        """Share of the roots' seconds that no descendant on the calling
        thread covers."""
        whole = sum(r.dur for r in self.roots)
        return sum(total(self.self_segments(r)) for r in self.roots) / whole

    # -- the clock join -------------------------------------------------------
    def clock_offset(self, trace: Trace, module_pattern: str
                     ) -> Tuple[float, float, float]:
        """(d, lowest d, highest d) with device time = host time + d, from
        the k-th `dispatch` and `compute_wait` spans and the k-th module
        event that matches. LookupError on a count mismatch or when no d
        satisfies every batch."""
        rx = re.compile(module_pattern)
        modules = sorted((s, e) for s, e, n in trace.devices[0].modules
                         if rx.search(n))
        starts = sorted(s.t0 for s in self.named(DISPATCH))
        ends = sorted(s.t1 for s in self.named(DRAIN))
        if not modules or len(starts) != len(modules) \
                or len(ends) != len(modules):
            raise LookupError(
                f"{len(starts)} dispatch and {len(ends)} compute_wait spans "
                f"against {len(modules)} device programs matching "
                f"{module_pattern!r}")
        # where the program also watches each batch until it is ready
        # (`in_flight`), that is a second, earlier "the host saw it done"
        seen = sorted(s.t1 for s in self.named(SEEN))
        if len(seen) == len(ends):
            ends = [min(a, b) for a, b in zip(ends, seen)]
        hi = min(m[0] - t for m, t in zip(modules, starts))
        lo = max(m[1] - t for m, t in zip(sorted(modules, key=lambda m: m[1]),
                                          ends))
        if lo > hi:
            raise LookupError(
                f"no clock offset fits every batch: a program would start "
                f"{lo - hi:.6f} s before its dispatch or end after its wait")
        return (lo + hi) / 2.0, lo, hi

    # -- idle attribution -----------------------------------------------------
    def window(self, window_s: float) -> Interval:
        """The driver's window on the host's clock: it opens with the first
        traced call and lasts `window_s` by the driver's own clock."""
        t0 = self.roots[0].t0
        return t0, t0 + window_s

    def idle_by_span(self, trace: Trace, d: float, window_s: float
                     ) -> Tuple[Dict[str, float], float]:
        """({span id | OUTSIDE: idle seconds}, idle seconds in all): each
        instant of the window in which the first device ran no operation,
        given to the innermost span open on the calling thread. The root's
        own id holds what lies under a root and under nothing below it."""
        win = self.window(window_s)
        busy = merge(clip(((s - d, e - d) for s, e, _ in trace.devices[0].ops),
                          *win))
        idle = subtract(win, busy)
        caller = self.roots[0].thread
        acc: Dict[str, float] = {}
        for s in self.spans:
            if s.thread != caller:
                continue
            sec = overlap(self.self_segments(s), idle)
            if sec > 0.0:
                acc[s.span_id] = acc.get(s.span_id, 0.0) + sec
        outside = overlap(subtract(win, [(r.t0, r.t1) for r in self.roots]),
                          idle)
        if outside > 0.0:
            acc[OUTSIDE] = outside
        return acc, total(idle)

    def idle_by_name(self, trace: Trace, d: float, window_s: float
                     ) -> Tuple[Dict[str, float], float]:
        """The same by span name, the roots' own share under NO_SPAN."""
        by_id, idle = self.idle_by_span(trace, d, window_s)
        roots = {r.span_id for r in self.roots}
        acc: Dict[str, float] = {}
        for key, sec in by_id.items():
            name = key if key == OUTSIDE else \
                NO_SPAN if key in roots else self.by_id[key].name
            acc[name] = acc.get(name, 0.0) + sec
        return acc, idle

    def idle_share_under(self, trace: Trace, d: float, window_s: float,
                         name: Optional[str] = None) -> float:
        """Share of the device's idle seconds that lie under a span named
        `name` (its descendants' too); with no name, under any span of the
        program below a root."""
        by_id, idle = self.idle_by_span(trace, d, window_s)
        if idle <= 0.0:
            raise LookupError("the device was never idle in the window")
        roots = {r.span_id for r in self.roots}
        sec = sum(v for k, v in by_id.items()
                  if k != OUTSIDE and k not in roots
                  and (name is None or self.under(self.by_id[k], name)))
        return sec / idle


def traced_calls(spans: Optional[Sequence[Span]], n_calls: int,
                 root: str = ROOT) -> Optional[Calls]:
    """The last `n_calls` root spans of the recorder and their trees; None
    where the program records none; LookupError where it holds fewer."""
    if spans is None:
        return None
    roots = sorted((s for s in spans if s.name == root and not s.parent_id),
                   key=lambda s: s.t0)
    if len(roots) < n_calls or n_calls <= 0:
        raise LookupError(f"{len(roots)} {root!r} root spans recorded, "
                          f"{n_calls} traced calls asked for")
    return Calls(roots[-n_calls:], spans)


def of(ctx: Dict[str, Any]) -> Optional[Calls]:
    """The traced calls of a reader's `ctx`, read once and kept there."""
    if "span_calls" not in ctx:
        ctx["span_calls"] = traced_calls(
            recorded(), int(ctx["traffic"]["trace_calls"]))
    return ctx["span_calls"]


def joined(ctx: Dict[str, Any], module_pattern: str
           ) -> Optional[Tuple[Calls, float, float]]:
    """(calls, d, slack seconds) of a reader's `ctx`, or None."""
    calls = of(ctx)
    if calls is None:
        return None
    key = "span_clock:" + module_pattern
    if key not in ctx:
        ctx[key] = calls.clock_offset(ctx["trace"], module_pattern)
    d, lo, hi = ctx[key]
    return calls, d, hi - lo
