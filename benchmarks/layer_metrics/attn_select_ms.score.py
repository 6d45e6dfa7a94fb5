"""Sparse attention: the device self time of choosing the keys, a layer a
batch: every event of the fused program under the scopes `layer<i>/attn/index`
(the indexer's three projections, its LayerNorm and rotary positions) and
`layer<i>/attn/select` (the scores and the exact top-2,048: the kernel
`mla_index`, or its plain form), over the layers that have them and the
program's runs in the traced calls (`harness/scopes.py`). What the selection
costs beside the core it spares."""

from benchmarks.harness import scopes

PART = r"(^|/)layer\d+/attn/(index|select)(/|$)"


def read(ctx):
    return scopes.part_ms(ctx, PART)
