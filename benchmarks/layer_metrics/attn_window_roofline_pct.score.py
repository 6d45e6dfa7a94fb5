"""Kernels: the sliding layers' attention cores (`attn_window`, the Pallas
kernel of `models/transformer.py`: scores, softmax, values; not the
projections) against their roofline at `T x window`: least time of a batch's
real tokens reading `window` keys each in the four sliding layers
(`work/exaone_moe.py`), over the kernel's device seconds a batch. The kernel
reads whole key blocks around the window, so it computes about three times
the keys the window holds: that is what this share shows."""

from benchmarks.harness import spec

KERNEL_PATTERN = r"attn_window"
MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, _events = ctx["trace"].op_seconds(KERNEL_PATTERN)    # raises if none
    _, runs = ctx["trace"].module_seconds(MODULE_PATTERN)
    tokens = ctx["counters"].get("real_tokens")
    if not tokens:
        return None
    work = spec.bench_module("work", "exaone_moe")
    flops, moved = work.window_attention(ctx["config"], tokens / runs)
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least / (seconds / runs)
