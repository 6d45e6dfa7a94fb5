"""Kernels: the six latent-attention cores (`attn_mla`, the Pallas kernel of
`models/transformer.py`, at content heads of 192 lanes: both score products
as one 256-lane contraction, softmax, values of 256; not the projections)
against their roofline: least time of the five layers' and the MTP module's
cores at (T + 1) / 2 keys a query for a batch's real tokens (`work/glm47.py`:
scores 192 + 64 wide, values 256), over the kernel's device seconds a batch.
The pattern is anchored on the instruction's name, so neither a consumer
that names the kernel among its operands nor `attn_mla_sparse` is counted.
Computing whole blocks on the diagonal costs the kernel time and earns
nothing. A program whose cores do not run the kernel has no such event:
nothing to read."""

from benchmarks.harness import spec

KERNEL_PATTERN = r"^%?attn_mla\b"
MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, _events = ctx["trace"].op_seconds(KERNEL_PATTERN)    # raises if none
    _, runs = ctx["trace"].module_seconds(MODULE_PATTERN)
    tokens = ctx["counters"].get("real_tokens")
    if not tokens:
        return None
    work = spec.bench_module("work", "glm47")
    flops, moved = work.latent_attention(ctx["config"], tokens / runs)
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least / (seconds / runs)
