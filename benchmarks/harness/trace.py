"""Reduction of a JAX profiler trace (`.xplane.pb`) to what the readers need.

Read with `jax.profiler.ProfileData` alone. A device plane is one whose name
starts with `/device:TPU:`; on it the line `XLA Ops` holds one event per
executed HLO operation (a Pallas kernel is one such event, a custom-call) and
`XLA Modules` one event per executed program. Host planes are not read: the
driver traces with the host tracer off, because the runtime writes a host
event for every small transpose of an input's layout, millions a call, and
they slow the host several times over (PERF.md section 6). So the window's
length comes from the driver's own clock around the traced calls, and an idle
gap is named by the program the device ran next, which is what the host was
getting ready.

All times are seconds. Nothing here knows a cell, a kernel or a metric.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]            # (start, end), seconds
Event = Tuple[float, float, str]          # (start, end, name)

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval]) -> List[Interval]:
    """The idle stretches between the merged `busy` intervals."""
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


@dataclass
class DevicePlane:
    name: str
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    """One traced window: the device's events, and the window's length by the
    driver's clock."""

    devices: List[DevicePlane]
    window_s: float

    def busy(self, plane: DevicePlane) -> List[Interval]:
        return merge((s, e) for s, e, _ in plane.ops)

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the device planes."""
        return sum(total(self.busy(p)) for p in self.devices) / len(self.devices)

    def _matching(self, events_of, pattern: str) -> Tuple[float, int]:
        rx = re.compile(pattern)
        seconds, count = 0.0, 0
        for p in self.devices:
            for s, e, n in events_of(p):
                if rx.search(n):
                    seconds += e - s
                    count += 1
        if count == 0:
            raise LookupError(f"no device event matches {pattern!r}")
        return seconds / len(self.devices), count

    def op_seconds(self, pattern: str) -> Tuple[float, int]:
        """(seconds, events) of the device operations whose name matches.
        Raises LookupError when none does: a reader never reads 0."""
        return self._matching(lambda p: p.ops, pattern)

    def module_seconds(self, pattern: str) -> Tuple[float, int]:
        return self._matching(lambda p: p.modules, pattern)

    def top_ops(self, k: int = 10) -> List[List]:
        acc: Dict[str, float] = {}
        for p in self.devices:
            for s, e, n in p.ops:
                acc[n] = acc.get(n, 0.0) + (e - s)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n[:120], sec / len(self.devices)] for n, sec in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """Idle seconds of the first device, by the program it ran next (what
        the host was getting ready), and what lies outside its first and last
        operation (the window's ends, by the driver's clock)."""
        plane = self.devices[0]
        busy = self.busy(plane)
        modules = sorted(plane.modules)
        acc: Dict[str, float] = {}
        at = 0          # the first program that starts after the gap does
        for gs, ge in gaps(busy):
            while at < len(modules) and modules[at][0] <= gs:
                at += 1
            if at > 0 and modules[at - 1][1] >= ge:
                name = "inside " + modules[at - 1][2]
            elif at < len(modules):
                name = "host, before " + modules[at][2]
            else:
                name = "host, after the last program"
            acc[name] = acc.get(name, 0.0) + (ge - gs)
        ends = self.window_s - (busy[-1][1] - busy[0][0])
        if ends > 0:
            acc["host, before the first and after the last operation"] = ends
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n[:120], sec] for n, sec in top]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_planes(path: str):
    """[(device plane name, [(line name, [Event, ...]), ...]), ...] of one file."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = []
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                lines.append((line.name, [
                    (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                     ev.name) for ev in line.events]))
        planes.append((plane.name, lines))
    return planes


def reduce_planes(planes, window_s: float) -> Trace:
    devices: List[DevicePlane] = []
    for pname, lines in planes:
        if not pname.startswith(DEVICE_PLANE):
            continue
        dp = DevicePlane(pname)
        for lname, events in lines:
            if lname == OPS_LINE:
                dp.ops.extend(events)
            elif lname == MODULES_LINE:
                dp.modules.extend(events)
        if dp.ops:
            devices.append(dp)
    if not devices:
        raise ValueError("the trace holds no device operation")
    return Trace(devices, window_s)


def read_trace(trace_dir: str, window_s: float) -> Trace:
    return reduce_planes(read_planes(find_xplane(trace_dir)), window_s)
