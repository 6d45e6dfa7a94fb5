"""Entry points: share of the traced calls' root `transform` spans that no
descendant span on the calling thread covers: the stretch of a call in which
the program does something it has not named (`harness/spans.py`)."""

from benchmarks.harness import spans


def read(ctx):
    calls = spans.of(ctx)
    if calls is None:
        return None
    return 100.0 * calls.uncovered_share()
