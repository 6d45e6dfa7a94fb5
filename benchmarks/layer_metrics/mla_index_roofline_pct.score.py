"""Kernels: the indexer's kernel (`mla_index`, the Pallas kernel of
`models/transformer.py`: the 64 heads' scores of a block of queries against
every causal key, their weighted ReLU sum, the exact top-2,048 a query and the
selection's bits; not the indexer's projections) against its roofline: least
time of the five layers' scores for the real tokens of a call's rows, 16,384
FLOP a causal (query, key) pair (`work/axk2.py`; compute binds), over the
kernel's device seconds of the traced calls. **The share reads low by
construction:** the weighted ReLU sum and the selection's passes run on the
vector unit, for which `peaks.py` has no peak, and are not counted. The
pattern is anchored at the instruction's own name (PERF.md section 7)."""

from benchmarks.harness import spec

KERNEL_PATTERN = r"^%?mla_index"
MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, _events = ctx["trace"].op_seconds(KERNEL_PATTERN)    # raises if none
    _, runs = ctx["trace"].module_seconds(MODULE_PATTERN)
    lengths = spec.bench_module("layer_metrics", "axk2_mfu_pct.score").lengths_of(ctx)
    calls = runs / float(len(lengths))          # a row is a batch here
    if not calls:
        return None
    work = spec.bench_module("work", "axk2")
    flops, moved = work.indexer(ctx["config"], lengths)
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least * calls / seconds
