"""Device: share of the device's idle seconds in the traced window that lie
under any span of the program below a call's root, on the calling thread: what
is left is the caller's own time and the root's uncovered stretch
(`harness/spans.py`)."""

from benchmarks.harness import spans, spec


def read(ctx):
    pattern = spec.bench_module(
        "layer_metrics", "resnet50_segment_roofline_pct.featurize").MODULE_PATTERN
    found = spans.joined(ctx, pattern)
    if found is None:
        return None
    calls, d, _slack = found
    return 100.0 * calls.idle_share_under(ctx["trace"], d, ctx["window_s"])
