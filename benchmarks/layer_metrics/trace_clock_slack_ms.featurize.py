"""Device: width of the interval of clock offsets (device trace against the
program's spans) that every batch of the traced call allows: the uncertainty
of the join the two idle shares rest on (`harness/spans.py`)."""

from benchmarks.harness import spans, spec


def read(ctx):
    pattern = spec.bench_module(
        "layer_metrics", "resnet50_segment_roofline_pct.featurize").MODULE_PATTERN
    found = spans.joined(ctx, pattern)
    if found is None:
        return None
    return 1e3 * found[2]
