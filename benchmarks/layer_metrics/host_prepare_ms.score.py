"""Host prep: host seconds under the `prepare` spans of the traced calls (validity masks, the stack of a partition's token rows), over the calls' batches.
The scoring cell's name for the reader `host_prepare_ms.featurize`: one arithmetic, an entry a
cell family, because the per-layer entries list their cells."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "host_prepare_ms.featurize").read
