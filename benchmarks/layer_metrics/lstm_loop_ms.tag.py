"""Kernels: device self time a batch inside the two scan loops of the BiLSTM:
the events under the scopes `bilstm/fwd` and `bilstm/bwd` that are a loop or
lie inside one (the loops' bodies and the loops' own time between them), over
the program's runs in the traced calls (`harness/scopes.py`)."""

from benchmarks.harness import scopes

PART = r"(^|/)bilstm/(fwd|bwd)(/|$)"


def read(ctx):
    return scopes.part_ms(ctx, PART, nested=True, a_layer=False)
