"""Kernels: the grouped expert products (`moe_gmm`, the Pallas kernel of
`models/moe.py`: gate/up and down of every sparse layer) against their
roofline in the configuration that holds every expert: least time of a
batch's real tokens' 4 visits a sparse layer, the 64 experts' weights read
once a layer (`work/xing4.py`), over the kernel's device seconds a batch.
Rows padded to whole tiles (64 groups of some 1,024 rows in tiles of 512) and
pads' visits cost the kernel time and earn nothing. `moe_gmm_roofline_pct.score`
reads `work/exaone_moe.py` and stays K-EXAONE's."""

from benchmarks.harness import spec

KERNEL_PATTERN = r"moe_gmm"
MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, _events = ctx["trace"].op_seconds(KERNEL_PATTERN)    # raises if none
    _, runs = ctx["trace"].module_seconds(MODULE_PATTERN)
    tokens = ctx["counters"].get("real_tokens")
    if not tokens:
        return None
    work = spec.bench_module("work", "xing4")
    flops, moved = work.expert_products(ctx["config"], tokens / runs)
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least / (seconds / runs)
