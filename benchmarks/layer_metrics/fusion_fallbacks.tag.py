"""Fusion: stages that fell back to the host (a ragged or object token column does) plus programs built inside the window. Must read 0.
The token cell's name for the reader `fusion_fallbacks.featurize`: one arithmetic, two
entries, because the two cells report different end-to-end metrics."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "fusion_fallbacks.featurize").read
