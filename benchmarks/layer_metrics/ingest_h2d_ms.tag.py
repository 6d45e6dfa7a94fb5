"""Ingest: mean host-to-device transfer time of a batch (`BatchTiming.h2d_s`), over the traced window's batches.
The token cell's name for the reader `ingest_h2d_ms.featurize`: one arithmetic, two
entries, because the two cells report different end-to-end metrics."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "ingest_h2d_ms.featurize").read
