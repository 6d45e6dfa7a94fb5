"""Host finalize: host seconds under the `emit` spans of the traced calls, over the calls' batches.
The scoring cell's name for the reader `host_emit_ms.featurize`: one arithmetic, an entry a
cell family, because the per-layer entries list their cells."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "host_emit_ms.featurize").read
