"""Kernels: the selective scans (`ssm_scan`, the Pallas kernel of
`models/ssm.py`: the recurrence alone, not the projections, the convolution
or the gate) against their roofline: least time of the nine layers' scans for
a batch's real tokens, `xc` and `delta` in and `y` out at 2 bytes a channel
and `B`, `C` at 4 (`work/phi4flash.py`: 277,632 bytes a token), over the
kernel's device seconds a batch. Memory binds by `peaks.py`. **The share
reads low by construction:** the kernel's own limit is the vector unit (an
exp and six multiply-adds a channel a state a step), for which `peaks.py` has
no peak, and it moves float32 where the roofline counts 2 bytes. The pattern
is anchored at the instruction's own name: a consumer of the kernel's output
names it among its operands (PERF.md section 7)."""

from benchmarks.harness import spec

KERNEL_PATTERN = r"^%?ssm_scan"
MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, _events = ctx["trace"].op_seconds(KERNEL_PATTERN)    # raises if none
    _, runs = ctx["trace"].module_seconds(MODULE_PATTERN)
    tokens = ctx["counters"].get("real_tokens")
    if not tokens:
        return None
    work = spec.bench_module("work", "phi4flash")
    flops, moved = work.selective_scan(ctx["config"], tokens / runs)
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least / (seconds / runs)
