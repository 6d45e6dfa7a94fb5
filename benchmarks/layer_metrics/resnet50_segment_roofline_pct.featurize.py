"""Kernels: the fused segment's XLA program against its roofline.

Least time for one batch = max(FLOPs / peak FLOP/s, bytes / peak bytes/s),
with the operations and bytes the algorithm needs (`work/resnet50.py`: the
convolutions' multiply-accumulates; weights once + uint8 input + float32
features), over the segment's mean device time per execution in the trace.
Compute binds by two orders of magnitude (85 ms against 0.5 ms at batch 2048).

The program gives its executable no stable name: the trace shows the module
as `jit_fused(<fingerprint>)`, and that is what is matched here. A stable
name is asked of the `tracing` issue (PERF.md, Open questions).
"""

from benchmarks.harness import spec

MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, runs = ctx["trace"].module_seconds(MODULE_PATTERN)   # raises if none
    work = spec.bench_module("work", "resnet50")
    batch = int(ctx["config"]["assumed"]["batch_size"])
    flops = work.flops_per_image(ctx["config"]) * batch
    moved = sum(work.bytes_per_batch(ctx["config"], batch).values())
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least / (seconds / runs)
