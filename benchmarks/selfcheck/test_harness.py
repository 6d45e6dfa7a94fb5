"""The rest of a run with the harness's look for a chip skipped, at tiny
sizes on the CPU: a sound run is correct, the lower-precision control and
each planted fault come out not correct, a failed call is counted as failed
and not as wrong, and without a TPU the command refuses to run."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmarks.harness import check, spec
from benchmarks.selfcheck.planted import with_control, with_fault
from benchmarks.selfcheck.tiny import tiny_cell

CELL = "resnet50.featurize"


def _run(cell, builder, seed=7, seconds=0.2, traced=False):
    import jax

    driver = cell.module("drivers", cell.traffic["driver"])
    return driver.run(cell, builder, jax.devices()[:1], seed, seconds, traced,
                      time.perf_counter())


def test_sound_run_is_correct_and_reports_the_contract_keys():
    cell = tiny_cell(CELL)
    res = _run(cell, cell.module("builders", cell.config["builder"]))
    assert check.verdict(res["compared"])
    assert res["failed"] == 0 and res["attempted"] == res["calls"] * 16
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert res["compiles_in_window"] == 0
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_traced_run_reports_every_per_layer_metric(monkeypatch):
    """The traced branch and every reader, on a trace built by hand (the CPU
    writes no device plane) and the v5e's peaks under the CPU's name."""
    from benchmarks import peaks
    from benchmarks.harness import trace

    planes = [("/device:TPU:0", [
        ("XLA Modules", [(1.0, 1.5, "jit_fused(123)"), (3.0, 3.5, "jit_fused(123)")]),
        ("XLA Ops", [(1.0, 1.5, "%fusion.1"), (3.0, 3.5, "%fusion.1")])])]
    monkeypatch.setattr(trace, "read_trace",
                        lambda d, window_s: trace.reduce_planes(planes, window_s))
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    cell = tiny_cell(CELL)
    res = _run(cell, cell.module("builders", cell.config["builder"]), traced=True)
    assert set(res["metrics"]) == {m["name"] for m in cell.per_layer}
    assert res["device"]["busy_s"] == pytest.approx(1.0)
    assert res["device"]["window_s"] > 0
    assert res["calls"] == cell.traffic["trace_calls"]
    assert len(res["breakdown"]["device_ops"]) == 1
    assert check.verdict(res["compared"])


def test_an_unlisted_device_is_an_error_and_not_a_silent_reader():
    from benchmarks import peaks

    with pytest.raises(ValueError):
        peaks.peaks_for("some other chip")


def _swap_rows(feats, n):
    out = feats.copy()
    out[[0, 1]] = out[[1, 0]]          # row order broken where it is produced
    return out[::-1].copy()


def _scale(feats, n):
    return feats * np.float32(1.08)    # every answer altered a little


@pytest.mark.parametrize("fault", [_swap_rows, _scale])
def test_an_altered_answer_is_not_correct(fault):
    cell = tiny_cell(CELL)
    res = _run(cell, with_fault(cell, fault))
    assert not check.verdict(res["compared"])


def test_a_failed_call_is_counted_failed_and_not_wrong():
    def fail_second(feats, n):
        if n == 2:
            raise RuntimeError("refused")
        return feats

    cell = tiny_cell(CELL)
    res = _run(cell, with_fault(cell, fail_second))
    assert res["failed"] == 16 and res["attempted"] == (res["calls"] + 1) * 16
    assert check.verdict(res["compared"])


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_fp8_control_is_not_correct(seed):
    """The reference in the program's place, one precision below bfloat16,
    through the run's own sampling, comparison and verdict."""
    cell = tiny_cell(CELL)
    res = _run(cell, with_control(cell, "fp8"), seed=seed)
    assert res["failed"] == 0 and res["calls"] >= 1
    assert not check.verdict(res["compared"])


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode != 0
    assert run.stdout.strip() == ""
    assert "no result" in run.stderr
