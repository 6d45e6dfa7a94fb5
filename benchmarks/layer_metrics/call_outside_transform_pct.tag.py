"""Entry points: share of the traced window that lies outside every root `transform` span: the caller's own code between the calls.
The token cell's name for the reader `call_outside_transform_pct.featurize`: one arithmetic, two
entries, because the two cells report different end-to-end metrics."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "call_outside_transform_pct.featurize").read
