"""Ingest: mean time the device side waited for the host to fill and ship a batch of token rows (`BatchTiming.queue_s`), over the traced window's batches.
The scoring cell's name for the reader `ingest_queue_ms.featurize`: one arithmetic, an entry a
cell family, because the per-layer entries list their cells."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "ingest_queue_ms.featurize").read
