"""Kernels: device self time of a Gated Memory Unit, a layer a batch: every
event under the scope `layer<i>/gmu` (its norm, `proj_in`, the `gate` with
layer 16's memory, `proj_out`, the loop over pieces' own slicing), over the
layers that have one and the program's runs in the traced calls
(`harness/scopes.py`)."""

from benchmarks.harness import scopes

PART = r"(^|/)layer\d+/gmu(/|$)"


def read(ctx):
    return scopes.part_ms(ctx, PART)
