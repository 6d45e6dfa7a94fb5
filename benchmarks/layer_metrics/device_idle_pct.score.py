"""Device: share of the traced window in which no operation ran on the chip.
The scoring cell's name for the reader `device_idle_pct.featurize`: one arithmetic, an entry a
cell family, because the per-layer entries list their cells."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "device_idle_pct.featurize").read
