"""Kernels: device self time of an attention sublayer, a layer a batch:
every event under the scope `layer<i>/attn` (its norm, `proj_in`, `core`,
`proj_out`, and the row loop's own slicing and stacking), over the layers
and the program's runs in the traced calls (`harness/scopes.py`)."""

from benchmarks.harness import scopes

PART = r"(^|/)layer\d+/attn(/|$)"


def read(ctx):
    return scopes.part_ms(ctx, PART)
