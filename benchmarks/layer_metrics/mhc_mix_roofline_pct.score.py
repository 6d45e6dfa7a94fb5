"""Kernels: the residual path's stream mix (`mhc_pre` and `mhc_post`, the
Pallas kernels of `models/residual.py`) against its roofline: least time of
12 sublayers' streams read twice and written once and each sublayer's output
read once, float32, for a batch's real tokens (`work/xing4.py`; memory binds),
over the two kernels' device seconds a batch. The same bytes whatever dtype
or kernel later implements the mix; the sigmoids and Sinkhorn steps between
the kernels are plain XLA and not in the denominator."""

from benchmarks.harness import spec

KERNEL_PATTERN = r"mhc_pre|mhc_post"
MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, _events = ctx["trace"].op_seconds(KERNEL_PATTERN)    # raises if none
    _, runs = ctx["trace"].module_seconds(MODULE_PATTERN)
    tokens = ctx["counters"].get("real_tokens")
    if not tokens:
        return None
    work = spec.bench_module("work", "xing4")
    flops, moved = work.stream_mix(ctx["config"], tokens / runs)
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least / (seconds / runs)
