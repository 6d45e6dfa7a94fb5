"""Kernels: the attention sublayer's device self time a layer a batch outside
its core's kernel: `attn_ms.score` less the events of `attn_window`,
`attn_full` and `attn_mla`: the projections, norms and positions, the casts
and pads that lay out the kernel's operands, the output projection
(`harness/scopes.py`)."""

from benchmarks.harness import scopes, spec

KERNEL_PATTERN = r"attn_window|attn_full|attn_mla"


def read(ctx):
    part = spec.bench_module("layer_metrics", "attn_ms.score").PART
    return scopes.part_ms(ctx, part, less=KERNEL_PATTERN)
