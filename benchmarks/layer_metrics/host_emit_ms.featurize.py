"""Host finalize: host seconds under the `emit` spans of the traced call
(concatenate the read-back batches, every stage's `finalize`, scatter over the
validity mask), over the call's batches (`harness/spans.py`)."""

from benchmarks.harness import spans


def read(ctx):
    calls = spans.of(ctx)
    if calls is None:
        return None
    return 1e3 * calls.seconds("emit") / calls.batches
