"""Expert layer: the combine's device self time a sparse layer a batch: every
event of the fused program under the scope `layer<i>/moe/combine`
(`models/moe.ExpertLayer`: each visit's row, weighted, onto its token;
whatever implements it, the scan over choices or the kernel `moe_combine`),
over the layers that have one and the program's runs in the traced calls
(`harness/scopes.py`)."""

from benchmarks.harness import scopes

PART = r"(^|/)layer\d+/moe/combine(/|$)"


def read(ctx):
    return scopes.part_ms(ctx, PART)
