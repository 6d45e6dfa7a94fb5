"""Host prep: host seconds under the `prepare` spans of the traced call (a
partition's validity masks, every stage's `prepare` hook — the host resize is
one — and the stack), over the call's batches (`harness/spans.py`)."""

from benchmarks.harness import spans


def read(ctx):
    calls = spans.of(ctx)
    if calls is None:
        return None
    return 1e3 * calls.seconds("prepare") / calls.batches
