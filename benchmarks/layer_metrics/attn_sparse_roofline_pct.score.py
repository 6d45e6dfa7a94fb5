"""Kernels: the sparse latent-attention cores (`attn_mla_sparse`, the Pallas
kernel of `models/transformer.py`: both score products, the softmax over the
selected keys, the values; not the projections, the indexer or the
selection) against their roofline: least time of the five layers' cores for
the real tokens of a call's rows, 40,960 FLOP a (query, selected key) pair at
`min(t + 1, 2048)` keys a query, and 1,152 bytes a selected key of a query
past position 2,048 (`work/axk2.py`; memory binds), over the kernel's device
seconds of the traced calls. The kernel visits every causal key block and
masks it by the selection, so the keys it reads and does not keep cost it
time and earn nothing. The pattern is anchored at the instruction's own name
(PERF.md section 7)."""

from benchmarks.harness import spec

KERNEL_PATTERN = r"^%?attn_mla_sparse"
MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, _events = ctx["trace"].op_seconds(KERNEL_PATTERN)    # raises if none
    _, runs = ctx["trace"].module_seconds(MODULE_PATTERN)
    lengths = spec.bench_module("layer_metrics", "axk2_mfu_pct.score").lengths_of(ctx)
    calls = runs / float(len(lengths))          # a row is a batch here
    if not calls:
        return None
    work = spec.bench_module("work", "axk2")
    flops, moved = work.sparse_core(ctx["config"], lengths)
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least * calls / seconds
