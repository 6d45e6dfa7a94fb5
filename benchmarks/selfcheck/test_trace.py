"""The trace reduction on a hand-built trace: busy union, idle share,
per-name time, and the naming of idle gaps."""

import pytest

from benchmarks.harness import trace

PLANES = [
    ("/host:CPU", [("main", [(0.0, 20.0, "anything")])]),
    ("/device:TPU:0", [
        # a program's event starts a little before its first operation and
        # ends a little after its last
        ("XLA Modules", [(0.999, 2.001, "jit_step(1)"), (4.999, 9.001, "jit_step(1)"),
                         (10.999, 12.001, "jit_other(2)")]),
        ("XLA Ops", [(1.0, 2.0, "%fusion.1"), (5.0, 7.0, "%kernel.7 = custom-call"),
                     (6.0, 8.0, "%fusion.2"), (8.5, 9.0, "%fusion.3"),
                     (11.0, 12.0, "%kernel.9 = custom-call")]),
        ("Async XLA Ops", [(0.0, 20.0, "%copy-start")]),
    ]),
    ("/device:CUSTOM:other", [("XLA Ops", [(0.0, 20.0, "noise")])]),
]


@pytest.fixture()
def tr():
    return trace.reduce_planes(PLANES, window_s=20.0)


def test_merge_and_gaps():
    assert trace.merge([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert trace.gaps([(1, 2), (3, 4), (4, 5)]) == [(2, 3)]
    assert trace.total([(0, 2), (3, 4)]) == 3


def test_only_the_chip_planes_count(tr):
    assert [p.name for p in tr.devices] == ["/device:TPU:0"]


def test_busy_union_and_idle_share(tr):
    # ops cover [1,2] + [5,8] + [8.5,9] + [11,12]: overlapping ops count once,
    # and the async line is not work on the chip's cores
    assert tr.busy_s == pytest.approx(5.5)
    assert 1 - tr.busy_s / tr.window_s == pytest.approx(0.725)


def test_time_by_name(tr):
    seconds, n = tr.op_seconds(r"%kernel\.\d+ = custom-call")
    assert (seconds, n) == (pytest.approx(3.0), 2)
    seconds, n = tr.module_seconds(r"^jit_step\(")
    assert (seconds, n) == (pytest.approx(5.004), 2)


def test_no_match_raises_and_never_reads_zero(tr):
    with pytest.raises(LookupError):
        tr.op_seconds(r"renamed_kernel")


def test_idle_gaps_are_named_by_the_program_that_ran_next(tr):
    by_name = dict(tr.idle_gaps())
    assert by_name["host, before jit_step(1)"] == pytest.approx(3.0)     # [2,5]
    assert by_name["host, before jit_other(2)"] == pytest.approx(2.0)    # [9,11]
    assert by_name["inside jit_step(1)"] == pytest.approx(0.5)           # [8,8.5]
    # the window is 20 s by the driver's clock, the operations span [1,12]
    assert by_name["host, before the first and after the last operation"] \
        == pytest.approx(9.0)
    assert sum(by_name.values()) == pytest.approx(tr.window_s - tr.busy_s)


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_planes([PLANES[0]], 1.0)
