"""Ingest: seconds of the `readback` spans of the traced call (device to
host fetch of a batch's outputs, `BatchTiming.readback_s`; the span's `bytes`
says how much came back), over the call's batches (`harness/spans.py`)."""

from benchmarks.harness import spans


def read(ctx):
    calls = spans.of(ctx)
    if calls is None:
        return None
    return 1e3 * calls.seconds("readback") / calls.batches
