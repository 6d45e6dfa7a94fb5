"""Kernels: the fused program (gather, two scans of `cap` dependent steps,
head) against the roofline of the work its callers asked for.

Least time for one batch = max(FLOPs / peak FLOP/s, bytes / peak bytes/s) of
the batch's REAL tokens (`work/bilstm.py`; the traced calls' real tokens over
their batches), over the program's mean device time per execution in the
trace. Compute binds. With every row padded to the cap the program computes
about nine positions for each real token, so this reads near a ninth of what
the same kernels would read on packed rows: that is the point of it.

The module is matched as the image cell's roofline matches it: the program
gives its executable no stable name (`jit_fused(<fingerprint>)`).
"""

from benchmarks.harness import spec

MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, runs = ctx["trace"].module_seconds(MODULE_PATTERN)   # raises if none
    tokens = ctx["counters"].get("real_tokens")
    if not tokens:
        return None
    work = spec.bench_module("work", "bilstm")
    tokens = tokens / runs                                        # a batch
    flops = work.flops_per_token(ctx["config"]) * tokens
    moved = sum(work.bytes_per_batch(ctx["config"], tokens).values())
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least / (seconds / runs)
