"""Entry points: share of the traced window (the driver's clock) that lies
outside every root `transform` span: the caller's own code between and around
the calls — here the benchmark's column-to-matrix loop (`harness/spans.py`)."""

from benchmarks.harness import spans


def read(ctx):
    calls = spans.of(ctx)
    if calls is None or not ctx["window_s"]:
        return None
    inside = sum(r.dur for r in calls.roots)
    return 100.0 * (ctx["window_s"] - inside) / ctx["window_s"]
