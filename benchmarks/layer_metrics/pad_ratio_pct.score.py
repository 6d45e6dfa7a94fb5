"""Token columns: real tokens of the traced calls over the positions the program shipped to the chip for them (rows padded to the 4,096 cap).
The scoring cell's name for the reader `pad_ratio_pct.tag`: one arithmetic, an entry a
cell family, because the per-layer entries list their cells."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "pad_ratio_pct.tag").read
