"""Kernels: the differential attention cores (`attn_window_diff` and
`attn_full_diff`, the Pallas kernel of `models/transformer.py` under its two
names: both score maps, both softmaxes, the products with the value pair,
lambda, the subtraction and the norm after it; not the projections) against
their roofline: least time of the sixteen layers' cores for the real tokens
of a call's rows, 768 FLOP a pair a (query, key), a window layer's query
reading min(512, t + 1) keys and a full or cross layer's t + 1
(`work/phi4flash.py`), over the kernels' device seconds of the traced calls.
The kernel contracts a 64-wide score over a whole tile of 128 lanes and
computes whole blocks on the diagonal and at a window's edges: both cost it
time and earn nothing. The pattern is anchored at the instruction's own name
(PERF.md section 7)."""

from benchmarks.harness import spec

KERNEL_PATTERN = r"^%?attn_(window|full)_diff"
MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, _events = ctx["trace"].op_seconds(KERNEL_PATTERN)    # raises if none
    _, runs = ctx["trace"].module_seconds(MODULE_PATTERN)
    lengths = spec.bench_module("layer_metrics", "phi4flash_mfu_pct.score").lengths_of(ctx)
    calls = runs / float(len(lengths))          # a row is a batch here
    if not calls:
        return None
    work = spec.bench_module("work", "phi4flash")
    flops, moved = work.differential_cores(ctx["config"], lengths)
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least * calls / seconds
