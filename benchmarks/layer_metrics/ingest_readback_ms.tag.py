"""Ingest: seconds of the `readback` spans of the traced calls (the device-to-host fetch of a batch's logits, one vector a position), over the calls' batches.
The token cell's name for the reader `ingest_readback_ms.featurize`: one arithmetic, two
entries, because the two cells report different end-to-end metrics."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "ingest_readback_ms.featurize").read
