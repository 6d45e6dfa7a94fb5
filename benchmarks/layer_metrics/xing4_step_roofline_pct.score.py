"""Kernels: the fused program (gather, six decoder layers with their stream
mixes, the head and the log-softmax) against the roofline of the work its
callers asked for: least time of one batch's REAL tokens (`work/xing4.py`)
over the program's mean device time per execution in the trace. Compute
binds. The parts that stay plain XLA (projections, SwiGLUs, the router, the
sort and the gathers of the dispatch, the maps' sigmoids and Sinkhorn steps,
the head) have no reader of their own and are bounded by this one. The module
is matched as the other cells match it: `jit_fused(<fingerprint>)`.
"""

from benchmarks.harness import spec

MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, runs = ctx["trace"].module_seconds(MODULE_PATTERN)   # raises if none
    tokens = ctx["counters"].get("real_tokens")
    if not tokens:
        return None
    work = spec.bench_module("work", "xing4")
    tokens = tokens / runs                                        # a batch
    flops = work.flops_per_token(ctx["config"]) * tokens
    moved = sum(work.bytes_per_batch(ctx["config"], tokens).values())
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least / (seconds / runs)
