"""Ingest: seconds of the `fill` spans of the traced calls (the token rows into the staging slot), over the calls' batches.
The token cell's name for the reader `ingest_fill_ms.featurize`: one arithmetic, two
entries, because the two cells report different end-to-end metrics."""

from benchmarks.harness import spec

read = spec.bench_module("layer_metrics", "ingest_fill_ms.featurize").read
