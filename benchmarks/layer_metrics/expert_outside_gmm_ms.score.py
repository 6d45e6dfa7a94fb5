"""Expert layer: the sparse layer's device self time a layer a batch outside
its kernel: `expert_layer_ms.score` less the `moe_gmm` kernel's own events:
the router, the sort, the gather of the visit rows, the activation between
the two grouped products, the combine (`harness/scopes.py`)."""

from benchmarks.harness import scopes, spec

KERNEL_PATTERN = r"moe_gmm"


def read(ctx):
    part = spec.bench_module("layer_metrics", "expert_layer_ms.score").PART
    return scopes.part_ms(ctx, part, less=KERNEL_PATTERN)
