"""Ingest: seconds of the `fill` spans of the traced call (pad, rows into the
staging slot; on the slot filler's thread, else the ring's producer's), over
the call's batches (`harness/spans.py`)."""

from benchmarks.harness import spans


def read(ctx):
    calls = spans.of(ctx)
    if calls is None:
        return None
    return 1e3 * calls.seconds("fill") / calls.batches
