"""Expert layer: the gather's device self time a sparse layer a batch: every
event of the fused program under the scope `layer<i>/moe/gather`
(`models/moe.ExpertLayer`: each padded visit row's group, visit and token;
where the first grouped product fetches its rows itself, that is all, else
also XLA's gather of the rows, `xg`), over the layers that have one and the
program's runs in the traced calls (`harness/scopes.py`)."""

from benchmarks.harness import scopes

PART = r"(^|/)layer\d+/moe/gather(/|$)"


def read(ctx):
    return scopes.part_ms(ctx, PART)
