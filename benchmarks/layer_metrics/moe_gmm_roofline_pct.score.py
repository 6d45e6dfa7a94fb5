"""Kernels: the grouped expert products (`moe_gmm`, the Pallas kernel of
`models/moe.py`: gate/up and down of every sparse layer) against their
roofline: least time of a batch's real tokens' EXPECTED visits, the 16 held
experts' weights read once a layer (`work/exaone_moe.py`), over the kernel's
device seconds a batch. Rows padded to whole tiles, pads' visits and a hot
expert's surplus cost the kernel time and earn nothing."""

from benchmarks.harness import spec

KERNEL_PATTERN = r"moe_gmm"
MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, _events = ctx["trace"].op_seconds(KERNEL_PATTERN)    # raises if none
    _, runs = ctx["trace"].module_seconds(MODULE_PATTERN)
    tokens = ctx["counters"].get("real_tokens")
    if not tokens:
        return None
    work = spec.bench_module("work", "exaone_moe")
    flops, moved = work.expert_products(ctx["config"], tokens / runs)
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least / (seconds / runs)
