"""The span join on hand-built data: the tree, self time per thread, the
clock offset recovered from `dispatch` / `compute_wait` against the device's
module events, idle attribution, and the nine readers built on them."""

import pytest

from benchmarks.harness import spans, spec, trace

D = 1234.5                       # the planted offset: device = host + D
MAIN, FILL, PUT = "MainThread", "slot-fill", "device-prefetch"
PATTERN = r"^jit_fused\("


def S(name, sid, parent, t0, t1, thread=MAIN, tid="call", **attrs):
    return spans.Span(name, sid, parent, tid, t0, t1, thread, attrs)


def call_spans():
    """One call of two batches in one partition, 10.0 s, after a warm-up
    call whose root must not be taken."""
    return [
        S("transform", "w", None, 900.0, 901.0, tid="warm"),
        S("dispatch", "wd", "w", 900.1, 900.2, tid="warm", batch=0),
        S("transform", "r", None, 1000.0, 1010.0),
        S("segment:A+B", "s", "r", 1000.1, 1009.9),
        S("partition", "p", "s", 1000.2, 1009.8),
        S("prepare", "pr", "p", 1000.2, 1004.2),
        S("prepare:A", "pra", "pr", 1000.3, 1004.0),
        S("fill", "f0", "p", 1004.2, 1004.6, FILL, batch=0),
        S("fill", "f1", "p", 1004.6, 1005.0, FILL, batch=1),
        S("h2d", "h0", "p", 1004.6, 1004.8, PUT, batch=0),
        S("h2d", "h1", "p", 1005.0, 1005.2, PUT, batch=1),
        S("queue", "q0", "p", 1004.2, 1004.8, batch=0),
        S("dispatch", "d0", "p", 1004.8, 1004.9, batch=0),
        S("queue", "q1", "p", 1004.9, 1005.2, batch=1),
        S("dispatch", "d1", "p", 1005.2, 1005.3, batch=1),
        S("compute_wait", "c0", "p", 1005.3, 1005.803, batch=0),
        S("readback", "b0", "p", 1005.803, 1006.3, batch=0),
        S("compute_wait", "c1", "p", 1006.3, 1006.803, batch=1),
        S("readback", "b1", "p", 1006.803, 1007.3, batch=1),
        S("emit", "e", "p", 1007.3, 1009.3),
        S("finalize:B", "eb", "e", 1007.4, 1009.2),
    ]


def device(modules):
    """A trace whose programs are `modules` (host seconds), each one
    operation long, on the device's clock."""
    planes = [("/device:TPU:0", [
        ("XLA Modules", [(s + D, e + D, "jit_fused(77)") for s, e in modules]
         + [(1.0, 2.0, "jit_other(3)")]),
        ("XLA Ops", [(s + D, e + D, "%fusion.1") for s, e in modules]),
    ])]
    return trace.reduce_planes(planes, window_s=10.5)


# the chip starts 2 ms after each dispatch opens (when it is free) and each
# wait ends 1 ms after its program does
MODULES = [(1004.802, 1005.802), (1005.802, 1006.802)]
WINDOW_S = 10.5
IDLE_S = WINDOW_S - 2.0


@pytest.fixture()
def calls():
    return spans.traced_calls(call_spans(), 1)


@pytest.fixture()
def tr():
    return device(MODULES)


def test_the_last_roots_are_the_traced_calls(calls):
    assert [r.span_id for r in calls.roots] == ["r"]
    assert all(s.trace_id == "call" for s in calls.spans)
    assert calls.batches == 2
    assert spans.traced_calls(None, 1) is None
    with pytest.raises(LookupError):
        spans.traced_calls(call_spans(), 3)


def test_interval_arithmetic():
    assert spans.subtract((0, 10), [(2, 3), (2.5, 4), (9, 12)]) == [(0, 2), (4, 9)]
    assert spans.overlap([(0, 2), (4, 9)], [(1, 5), (8, 20)]) == 3


def test_planted_offset_is_recovered_within_the_slack(calls, tr):
    d, lo, hi = calls.clock_offset(tr, PATTERN)
    assert lo == pytest.approx(D - 0.001) and hi == pytest.approx(D + 0.002)
    assert lo <= D <= hi and abs(d - D) <= hi - lo
    # a time base that is the epoch already gives an offset near 0
    epoch = trace.reduce_planes([("/device:TPU:0", [
        ("XLA Modules", [(s, e, "jit_fused(77)") for s, e in MODULES]),
        ("XLA Ops", [(s, e, "%fusion.1") for s, e in MODULES])])], 10.5)
    assert calls.clock_offset(epoch, PATTERN)[0] == pytest.approx(0.0005)


def test_a_watched_batch_tightens_the_lower_side(tr):
    # the host looks at both batches late (a ring two deep: under the other
    # batch's readback), but a watching thread saw each one ready in time
    late = [S(s.name, s.span_id, s.parent_id, s.t0, s.t1 + 0.3, s.thread,
              **s.attrs) if s.name == "compute_wait" else s
            for s in call_spans()]
    loose = spans.traced_calls(late, 1).clock_offset(tr, PATTERN)
    assert loose[2] - loose[1] == pytest.approx(0.303)
    seen = [S("in_flight", "i0", "p", 1004.9, 1005.8025, "device-watch", batch=0),
            S("in_flight", "i1", "p", 1005.3, 1006.8025, "device-watch", batch=1)]
    d, lo, hi = spans.traced_calls(late + seen, 1).clock_offset(tr, PATTERN)
    assert (lo, hi) == (pytest.approx(D - 0.0005), pytest.approx(D + 0.002))
    assert lo <= D <= hi
    # a watcher on another thread takes no self time from the caller
    assert "in_flight" not in spans.traced_calls(late + seen, 1).self_seconds()


def test_a_program_before_its_dispatch_is_an_error(calls):
    # the second program would have to start before its dispatch span opened
    # for the first to end before its wait closed: no offset fits both
    early = device([(1004.802, 1005.802), (1005.100, 1006.802)])
    with pytest.raises(LookupError):
        calls.clock_offset(early, PATTERN)


def test_span_count_must_match_the_program_count(calls):
    with pytest.raises(LookupError):
        calls.clock_offset(device(MODULES[:1]), PATTERN)
    with pytest.raises(LookupError):
        calls.clock_offset(device(MODULES), r"^jit_renamed\(")


def test_self_time_is_per_thread(calls):
    own = calls.self_seconds()
    assert own["transform"] == pytest.approx(0.2)
    assert own["segment:A+B"] == pytest.approx(0.2)
    assert own["partition"] == pytest.approx(0.5)
    assert own["prepare"] == pytest.approx(0.3)
    assert own["emit"] == pytest.approx(0.2)
    assert "fill" not in own and "h2d" not in own
    assert sum(own.values()) == pytest.approx(10.0)      # the whole call
    assert calls.self_seconds(FILL) == {"fill": pytest.approx(0.8)}
    # a producer-thread span over the partition's own stretch takes nothing
    beside = spans.traced_calls(
        call_spans() + [S("h2d", "h9", "p", 1009.4, 1009.7, PUT)], 1)
    assert beside.self_seconds()["partition"] == pytest.approx(0.5)
    # the same span on the caller's thread would
    inline = spans.traced_calls(
        call_spans() + [S("h2d", "h9", "p", 1009.4, 1009.7, MAIN)], 1)
    assert inline.self_seconds()["partition"] == pytest.approx(0.2)


def test_idle_goes_to_the_innermost_span_and_sums_to_the_total(calls, tr):
    by_name, idle = calls.idle_by_name(tr, D, WINDOW_S)
    assert idle == pytest.approx(IDLE_S)
    assert idle == pytest.approx(tr.window_s - tr.busy_s)
    assert sum(by_name.values()) == pytest.approx(idle)
    want = {"prepare:A": 3.7, "prepare": 0.3, "queue": 0.6, "dispatch": 0.002,
            "compute_wait": 0.001, "readback": 0.497, "finalize:B": 1.8,
            "emit": 0.2, "partition": 0.5, "segment:A+B": 0.2,
            spans.NO_SPAN: 0.2, spans.OUTSIDE: 0.5}
    assert by_name == {k: pytest.approx(v, abs=1e-9) for k, v in want.items()}
    assert calls.idle_share_under(tr, D, WINDOW_S, "prepare") \
        == pytest.approx(4.0 / IDLE_S)
    assert calls.idle_share_under(tr, D, WINDOW_S) == pytest.approx(7.8 / IDLE_S)


READINGS = {
    "host_prepare_ms.featurize": 2000.0,
    "ingest_fill_ms.featurize": 400.0,
    "ingest_readback_ms.featurize": 497.0,
    "host_emit_ms.featurize": 1000.0,
    "transform_uncovered_pct.featurize": 2.0,
    "call_outside_transform_pct.featurize": 100.0 * 0.5 / 10.5,
    "device_idle_in_prepare_pct.featurize": 100.0 * 4.0 / IDLE_S,
    "device_idle_named_pct.featurize": 100.0 * 7.8 / IDLE_S,
    "trace_clock_slack_ms.featurize": 3.0,
}


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_each_reader_returns_the_hand_computed_value(metric, calls, tr):
    ctx = {"trace": tr, "window_s": WINDOW_S, "traffic": {"trace_calls": 1},
           "span_calls": calls}
    reader = spec.bench_module("layer_metrics", metric)
    # the idle shares are read at the middle of the interval, half a
    # millisecond from the planted offset
    assert reader.read(ctx) == pytest.approx(READINGS[metric], abs=0.02)


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_each_reader_reads_nothing_from_a_program_without_spans(metric, tr):
    ctx = {"trace": tr, "window_s": WINDOW_S, "traffic": {"trace_calls": 1},
           "span_calls": None}
    assert spec.bench_module("layer_metrics", metric).read(ctx) is None


def test_every_new_reader_is_an_entry_of_the_benchmark():
    bench = spec.load_json(spec.ROOT + "/BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for metric in READINGS:
        assert entries[metric]["source"] == "program_span"
        assert entries[metric]["workloads"] == ["resnet50.featurize"]


def test_the_programs_recorder_is_what_is_read():
    from mmlspark_tpu.obs import trace as program

    mine = program.Tracer(service="batch")
    old = program.set_default_tracer(mine)
    try:
        for k in range(3):                      # three calls, one batch each
            root = mine.ingress()
            mine.record("dispatch", mine.child(root), 10.0 * k + 1, 1.0)
            mine.record("transform", root, 10.0 * k, 5.0, rows=8)
        got = spans.traced_calls(spans.recorded(), 2)
        assert [r.t0 for r in got.roots] == [10.0, 20.0]
        assert got.batches == 2 and got.roots[0].attrs == {"rows": 8}
        assert got.roots[0].thread and got.uncovered_share() == pytest.approx(0.8)
        program.set_default_tracer(None)
        assert spans.recorded() is None
    finally:
        program.set_default_tracer(old)
