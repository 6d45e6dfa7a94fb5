"""Device: share of the traced window in which no operation ran on the chip.
The token cell's name for the reader `device_idle_pct.featurize`: one arithmetic, two
entries, because the two cells report different end-to-end metrics."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "device_idle_pct.featurize").read
