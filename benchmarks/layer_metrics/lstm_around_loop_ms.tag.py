"""Kernels: device self time a batch under the scope `bilstm` outside the two
loops' bodies: the reverses, the layout copies, the input products and the
joining of the two directions (`harness/scopes.py`)."""

from benchmarks.harness import scopes

PART = r"(^|/)bilstm(/|$)"


def read(ctx):
    return scopes.part_ms(ctx, PART, nested=False, a_layer=False)
