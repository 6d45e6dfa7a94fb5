"""Kernels: the latent-attention cores (`attn_mla`, the Pallas kernel of
`models/transformer.py`: both score products, softmax, values; not the
projections) against their roofline: least time of the six layers' cores at
(T + 1) / 2 keys a query for a batch's real tokens, scores 192 wide and values
128 (`work/xing4.py`), over the kernel's device seconds a batch. The kernel
pads the 64 rotary lanes to 128 and computes whole blocks on the diagonal:
both cost it time and earn nothing."""

from benchmarks.harness import spec

KERNEL_PATTERN = r"attn_mla"
MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, _events = ctx["trace"].op_seconds(KERNEL_PATTERN)    # raises if none
    _, runs = ctx["trace"].module_seconds(MODULE_PATTERN)
    tokens = ctx["counters"].get("real_tokens")
    if not tokens:
        return None
    work = spec.bench_module("work", "xing4")
    flops, moved = work.latent_attention(ctx["config"], tokens / runs)
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least / (seconds / runs)
