"""Host finalize: host seconds under the `emit` spans of the traced calls (concatenate the read-back logits, the stage's `finalize`, the rows of the output column), over the calls' batches.
The token cell's name for the reader `host_emit_ms.featurize`: one arithmetic, two
entries, because the two cells report different end-to-end metrics."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "host_emit_ms.featurize").read
