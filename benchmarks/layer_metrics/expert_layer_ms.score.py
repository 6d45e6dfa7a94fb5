"""Expert layer: device self time of a sparse layer, a batch: every event of
the fused program under the scope `layer<i>/moe` (`models/moe.ExpertLayer`:
`route`, `sort`, `gather`, `experts`, `combine`, and the loop's own slicing),
over the layers that have one and the program's runs in the traced calls
(`harness/scopes.py`)."""

from benchmarks.harness import scopes

PART = r"(^|/)layer\d+/moe(/|$)"


def read(ctx):
    return scopes.part_ms(ctx, PART)
