"""Ingest: seconds of the `readback` spans of the traced calls (a batch's log-probabilities, one number a position, and its `expert_load`), over the calls' batches.
The scoring cell's name for the reader `ingest_readback_ms.featurize`: one arithmetic, an entry a
cell family, because the per-layer entries list their cells."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "ingest_readback_ms.featurize").read
