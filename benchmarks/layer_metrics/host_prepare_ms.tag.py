"""Host prep: host seconds under the `prepare` spans of the traced calls (validity masks, the stack of a partition's token rows into one int32 array), over the calls' batches.
The token cell's name for the reader `host_prepare_ms.featurize`: one arithmetic, two
entries, because the two cells report different end-to-end metrics."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "host_prepare_ms.featurize").read
