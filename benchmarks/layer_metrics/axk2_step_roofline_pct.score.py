"""Kernels: the fused program (gather, five decoder layers with their
indexers, selections, sparse cores and expert layers, the head and the
log-softmax) against the roofline of the work its callers asked for: least
time of one batch's REAL tokens (`work/axk2.py`, the indexer and the core at
the rows' own lengths) over the program's mean device time per execution in
the trace. Compute binds. The parts that stay plain XLA (every projection,
the gated norms, SwiGLUs, the router, the sort and the gathers of the
dispatch, the head) have no reader of their own and are bounded by this one.
The module is matched as the other cells match it: `jit_fused(<fingerprint>)`."""

from benchmarks.harness import spec

MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, runs = ctx["trace"].module_seconds(MODULE_PATTERN)   # raises if none
    tokens = ctx["counters"].get("real_tokens")
    if not tokens:
        return None
    work = spec.bench_module("work", "axk2")
    lengths = spec.bench_module("layer_metrics", "axk2_mfu_pct.score").lengths_of(ctx)
    tokens = tokens / runs                                        # a batch
    flops = work.flops_per_token(ctx["config"], lengths) * tokens
    moved = sum(work.bytes_per_batch(ctx["config"], tokens).values())
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least / (seconds / runs)
