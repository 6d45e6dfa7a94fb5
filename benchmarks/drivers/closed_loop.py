"""Closed loop, one caller: whole calls back to back until the window's
seconds have passed at the end of a call.

Serves every mix whose requests are calls that each wait for the last (a
DataFrame transformed again and again, whole fits back to back). The rate is
all the work of every completed call over all the time from the first call's
start to the last call's end. What a call is, and how much work it does,
belongs to the configuration's builder and the traffic file's parameters.

Traffic parameters read here: `rate_metric` (the end-to-end metric the rate is
reported under) and `trace_calls` (calls made under the profiler in a traced
run).

A traced run makes `trace_calls` calls under the profiler and no others. Only
the device's events are traced: with the host tracer on, the runtime's own
events slow a call two to five times (PERF.md section 6), and every counter
read in the window would measure the profiler; with it off a traced call
takes as long as any other.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
import time
import traceback
from typing import Any, Dict, List


class _Loop:
    """Whole calls back to back; what they did and what is kept of them."""

    def __init__(self, subject):
        self.subject = subject
        self.kept: List[Any] = []
        self.attempted = self.failed = self.calls = self.failed_calls = 0
        self.work = self.seconds = 0.0
        self.call_seconds: List[float] = []

    def calls_until(self, done) -> None:
        subject = self.subject
        t0 = time.perf_counter() - self.seconds
        while not done():
            self.attempted += subject.items_per_call
            try:
                out = subject.call()
            except Exception:  # noqa: BLE001 - a failed call is counted, not fatal
                traceback.print_exc(file=sys.stderr)
                self.failed += subject.items_per_call
                self.failed_calls += 1
            else:
                self.calls += 1
                self.work += subject.work(out)
                self.failed += subject.failed_items(out)
                self.kept.append(subject.keep(out))
                del out
            self.call_seconds.append(time.perf_counter() - t0 - self.seconds)
            self.seconds = time.perf_counter() - t0


def run(cell, builder, chips: List[Any], seed: int, seconds: float,
        traced: bool, t_start: float) -> Dict[str, Any]:
    import jax

    from benchmarks import peaks
    from benchmarks.harness import device, layers, trace
    from benchmarks.harness.compiles import CompileCounter

    traffic = cell.traffic
    compiles = CompileCounter()
    subject = builder.build(cell.config, traffic, seed, chips)
    subject.warm()
    setup_s = time.perf_counter() - t_start
    compiles_before = compiles.count

    loop = _Loop(subject)
    if traced:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        # the device's events alone: no Python tracer, no host tracer
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        done = int(traffic["trace_calls"])
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            loop.calls_until(lambda: loop.calls + loop.failed_calls >= done)
        finally:
            jax.profiler.stop_trace()
    else:
        loop.calls_until(lambda: loop.seconds >= seconds)
    work, window_s = loop.work, loop.seconds
    compiles_in_window = compiles.count - compiles_before
    if loop.calls == 0 or not work:
        print("benchmark: no call completed in the window; no result",
              file=sys.stderr)
        raise SystemExit(3)

    dev = device.describe(chips)
    counters = subject.counters()
    counters["compiles_in_window"] = compiles_in_window
    metrics: Dict[str, Dict[str, Any]] = {}
    extra: Dict[str, Any] = {}
    if traced:
        tr = trace.read_trace(trace_dir, window_s)
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev["busy_s"] = tr.busy_s
        dev["window_s"] = tr.window_s
        ctx = {"trace": tr, "work": work, "window_s": window_s,
               "counters": counters, "config": cell.config,
               "traffic": traffic, "device_kind": dev["kind"], "peaks": peaks}
        metrics = layers.read_layers(cell, ctx)
        extra["breakdown"] = {"device_ops": tr.top_ops(10),
                              "idle_gaps": tr.idle_gaps(10)}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        rate = traffic["rate_metric"]
        metrics[rate] = {"value": work / window_s, "unit": units[rate]}
        metrics["setup_s"] = {"value": setup_s, "unit": units["setup_s"]}

    subject.free()
    t_ref = time.perf_counter()
    compared = subject.check(loop.kept)
    return {"attempted": loop.attempted, "failed": loop.failed, "metrics": metrics,
            "device": dev, **extra, "calls": loop.calls, "window_s": window_s,
            "call_seconds": loop.call_seconds,
            "compiles_in_window": compiles_in_window,
            "reference_s": time.perf_counter() - t_ref, "compared": compared}
