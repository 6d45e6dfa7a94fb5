"""Device: share of the traced window's busy seconds whose operation resolves to a scope of the program.
The token cell's name for the reader `device_scoped_pct.featurize`: one arithmetic, two
entries, because the two cells report different end-to-end metrics."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "device_scoped_pct.featurize").read
