"""Model step: the whole step's share of the chip's bf16 peak. Real tokens
per second of the traced window (host clock, every call and gap counted)
times the FLOPs one real token needs (`work/xing4.py`: 3.134 GFLOP), over the
peak. It bounds every kernel's claim in this cell; padded positions and visits
above their expectation earn nothing."""

from benchmarks.harness import spec


def read(ctx):
    if not ctx["work"] or not ctx["window_s"]:
        return None
    work = spec.bench_module("work", "xing4")
    flops_per_s = ctx["work"] / ctx["window_s"] * work.flops_per_token(ctx["config"])
    peak = ctx["peaks"].peaks_for(ctx["device_kind"])["flops_per_s"]
    return 100.0 * flops_per_s / peak
