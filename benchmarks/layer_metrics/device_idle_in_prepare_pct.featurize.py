"""Device: share of the device's idle seconds in the traced window that lie
under a `prepare` span of the calling thread, once the program's spans and
the device trace are on one clock (`harness/spans.py`: the join is by the
fused program's module events, matched as the segment roofline matches them)."""

from benchmarks.harness import spans, spec


def read(ctx):
    pattern = spec.bench_module(
        "layer_metrics", "resnet50_segment_roofline_pct.featurize").MODULE_PATTERN
    found = spans.joined(ctx, pattern)
    if found is None:
        return None
    calls, d, _slack = found
    return 100.0 * calls.idle_share_under(ctx["trace"], d, ctx["window_s"],
                                          "prepare")
