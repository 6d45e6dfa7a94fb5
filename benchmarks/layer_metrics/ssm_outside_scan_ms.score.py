"""Kernels: the Mamba sublayer's device self time a layer a batch outside
its kernel: `ssm_layer_ms.score` less the `ssm_scan` kernel's own events:
the four projections, the convolution, the copies that lay `delta` and `xc`
out a tile a time step and `y` back, the gate (`harness/scopes.py`)."""

from benchmarks.harness import scopes, spec

KERNEL_PATTERN = r"^ssm_scan"        # the instruction's name, not its text


def read(ctx):
    part = spec.bench_module("layer_metrics", "ssm_layer_ms.score").PART
    return scopes.part_ms(ctx, part, less=KERNEL_PATTERN)
