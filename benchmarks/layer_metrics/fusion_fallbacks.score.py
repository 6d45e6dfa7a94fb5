"""Fusion: stages that fell back to the host plus programs built inside the window. Must read 0.
The scoring cell's name for the reader `fusion_fallbacks.featurize`: one arithmetic, an entry a
cell family, because the per-layer entries list their cells."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "fusion_fallbacks.featurize").read
