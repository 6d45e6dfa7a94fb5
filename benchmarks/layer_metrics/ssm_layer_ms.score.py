"""Kernels: device self time of a Mamba sublayer, a layer a batch: every
event under the scope `layer<i>/ssm` (its norm, `proj_in`, `conv`, `scan`
with the products that make `delta`, `B`, `C` and the kernel's layout copies,
`gate`, `proj_out`, and the loop over pieces' own slicing), over the layers
that have one and the program's runs in the traced calls
(`harness/scopes.py`)."""

from benchmarks.harness import scopes

PART = r"(^|/)layer\d+/ssm(/|$)"


def read(ctx):
    return scopes.part_ms(ctx, PART)
