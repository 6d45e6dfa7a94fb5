"""The token cell `bilstm.tag` at a tiny size on the CPU, through the harness
as it stands (`spec.load_cell`, `closed_loop.run`, the cell's own builder,
reference, work file and readers). The tiny sizes are this file's own:
`tiny.py` keys its table by configuration and is not edited. Never a source
of a device number."""

import dataclasses
import time
import types

import numpy as np
import pytest

from benchmarks.harness import check, spans, spec, trace
from benchmarks.selfcheck.control_on_chip_bilstm import with_reference
from benchmarks.selfcheck.planted import with_fault
from benchmarks.selfcheck.tiny import _override

CELL = "bilstm.tag"
RATE = "tokens_per_s"
SEED = 4294970129                    # over 32 signed bits, as the driver's are
TINY_CONFIG = {"vocab_size": 500, "embed_dim": 10, "hidden_size": 12,
               "assumed.batch_size": 32}
TINY_TRAFFIC = {"check_rows_per_call": 4, "check_rows_last_call": 8,
                "trace_calls": 2}
ROWS = 32 * 4                        # batch x batches_per_call
# the program against the reference on the CPU at the tiny size: the head's
# operands are bfloat16 (2**-9 a value), the LSTM's products float32
TINY_GAP = 0.02


def tiny_cell() -> spec.Cell:
    cell = spec.load_cell(CELL)
    return dataclasses.replace(cell, config=_override(cell.config, TINY_CONFIG),
                               traffic=_override(cell.traffic, TINY_TRAFFIC))


def real_builder(cell, **patches):
    """The cell's builder, keeping the subject it built as `.subject`;
    `patches` replace attributes of the subject (a planted counting fault)."""
    real = cell.module("builders", cell.config["builder"])
    made = types.SimpleNamespace(subject=None)

    def build(config, traffic, seed, chips):
        made.subject = real.build(config, traffic, seed, chips)
        for name, make in patches.items():
            setattr(made.subject, name, make(made.subject))
        return made.subject

    made.build = build
    return made


def _run(cell, builder, seconds=0.3, traced=False, seed=SEED):
    import jax

    driver = cell.module("drivers", cell.traffic["driver"])
    return driver.run(cell, builder, jax.devices()[:1], seed, seconds, traced,
                      time.perf_counter())


def _tokens(res) -> float:
    return res["metrics"][RATE]["value"] * res["window_s"]


def _exactly(tokens):
    return pytest.approx(tokens, rel=1e-9)      # a token more or less shows


def test_the_cell_is_in_the_benchmark_with_its_rate_and_nine_readers():
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {RATE, "setup_s"}
    assert len(cell.per_layer) == 9
    assert all(m["moves"] == RATE and m["name"].endswith(".tag")
               for m in cell.per_layer)
    image = spec.load_cell("resnet50.featurize")      # and takes nothing from it
    assert RATE not in {m["name"] for m in image.end_to_end}
    assert len(image.per_layer) == 15


def test_every_seed_draws_the_same_lengths_in_another_order():
    from benchmarks.harness import token_rows

    traffic = spec.load_cell(CELL).traffic
    ids_a, len_a = token_rows.padded_rows(traffic, 4096, 130000, 0, 1)
    ids_b, len_b = token_rows.padded_rows(traffic, 4096, 130000, 0, SEED)
    assert (np.sort(len_a) == np.sort(len_b)).all() and (len_a != len_b).any()
    assert len_a.min() >= 1 and len_a.max() == 128 == ids_a.shape[1]
    assert 13.5 < len_a.mean() < 14.5 and np.median(len_a) == 11
    real = np.arange(128)[None, :] < len_a[:, None]
    assert (ids_a[real] >= 1).all() and (ids_a[~real] == 0).all()
    assert ids_a.dtype == np.int32 and (ids_a != ids_b).any()


def test_untraced_reports_the_rate_and_setup_and_counts_real_tokens():
    cell = tiny_cell()
    made = real_builder(cell)
    res = _run(cell, made)
    assert set(res["metrics"]) == {RATE, "setup_s"}
    assert res["metrics"][RATE]["unit"] == "tokens/s"
    assert res["calls"] >= 2 and res["failed"] == 0
    assert res["attempted"] == res["calls"] * ROWS             # rows, not tokens
    assert res["compiles_in_window"] == 0
    assert _tokens(res) == _exactly(res["calls"] * int(made.subject.lengths.sum()))
    assert check.verdict(res["compared"])
    (gap,) = res["compared"]
    assert gap.name == "logit_gap" and gap.value < TINY_GAP


def test_padded_positions_counted_as_work_read_about_nine_times_high():
    cell = tiny_cell()
    made = real_builder(
        cell, work=lambda s: lambda col: float(len(col) * col[0].shape[0]))
    res = _run(cell, made)
    real = res["calls"] * int(made.subject.lengths.sum())
    assert _tokens(res) == _exactly(res["calls"] * ROWS * 128)
    assert 8.0 < _tokens(res) / real < 10.5


def test_traced_run_reports_all_nine_per_layer_names(monkeypatch):
    """The traced branch and every reader: the live tiny calls' spans and
    counters, a device plane built by hand (the CPU writes none) and the
    v5e's peaks under the CPU's name."""
    from benchmarks import peaks

    planes = [("/device:TPU:0", [
        ("XLA Modules", [(0.1 * k, 0.1 * k + 0.05, "jit_fused(123)") for k in range(8)]),
        ("XLA Ops", [(0.1 * k, 0.1 * k + 0.05, "%while.1") for k in range(8)])])]
    monkeypatch.setattr(trace, "read_trace",
                        lambda d, window_s: trace.reduce_planes(planes, window_s))
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    cell = tiny_cell()
    made = real_builder(cell)
    res = _run(cell, made, traced=True)
    assert res["calls"] == 2 == cell.traffic["trace_calls"]
    assert set(res["metrics"]) == {m["name"] for m in cell.per_layer}
    value = {k: v["value"] for k, v in res["metrics"].items()}
    lengths = made.subject.lengths
    assert value["pad_ratio_pct.tag"] == pytest.approx(
        100.0 * lengths.sum() / (ROWS * 128))
    assert value["fusion_fallbacks.tag"] == 0
    assert res["device"]["busy_s"] == pytest.approx(0.4)
    assert check.verdict(res["compared"])


def test_each_new_reader_on_a_context_built_by_hand():
    from benchmarks import peaks

    cell = spec.load_cell(CELL)
    work = spec.bench_module("work", "bilstm")

    def S(name, sid, parent, t0, t1, thread="MainThread"):
        return spans.Span(name, sid, parent, "hand", t0, t1, thread, {})

    tree = [S("transform", "r", None, 0.0, 2.0)]
    for k in range(4):                    # four batches: 2 s of spans in all
        at = 0.5 * k
        tree += [S("prepare", f"p{k}", "r", at, at + 0.02),
                 S("dispatch", f"d{k}", "r", at + 0.02, at + 0.03),
                 S("readback", f"b{k}", "r", at + 0.13, at + 0.18),
                 S("emit", f"e{k}", "r", at + 0.18, at + 0.24)]
    planes = [("/device:TPU:0", [
        ("XLA Modules", [(0.5 * k + 0.03, 0.5 * k + 0.13, "jit_fused(9)") for k in range(4)]),
        ("XLA Ops", [(0.5 * k + 0.03, 0.5 * k + 0.13, "%while.2") for k in range(4)])])]
    records = [types.SimpleNamespace(queue_s=0.004, h2d_s=0.001, bytes_in=8192 * 128 * 4)
               for _ in range(4)]
    tokens = 4 * 115000.0
    ctx = {"trace": trace.reduce_planes(planes, 2.0), "work": tokens, "window_s": 2.0,
           "counters": {"ingest_records": records, "fallbacks_total": 0,
                        "program_cache_misses_in_window": 0, "compiles_in_window": 0,
                        "real_tokens": tokens, "padded_positions": 4 * 8192 * 128},
           "config": cell.config, "traffic": {"trace_calls": 1},
           "device_kind": "TPU v5 lite", "peaks": peaks,
           "span_calls": spans.Calls(tree[:1], tree)}
    got = {m["name"]: cell.module("layer_metrics", m["name"]).read(ctx)
           for m in cell.per_layer}
    flops = work.flops_per_token(cell.config)
    assert flops == 1690800.0
    assert got == {
        "pad_ratio_pct.tag": pytest.approx(100 * 115000 / (8192 * 128)),
        "host_prepare_ms.tag": pytest.approx(20.0),
        "ingest_queue_ms.tag": pytest.approx(4.0),
        "ingest_readback_ms.tag": pytest.approx(50.0),
        "host_emit_ms.tag": pytest.approx(60.0),
        "fusion_fallbacks.tag": 0.0,
        "device_idle_pct.tag": pytest.approx(80.0),
        "bilstm_mfu_pct.tag": pytest.approx(100 * tokens / 2.0 * flops / 197e12),
        # compute binds: 115,000 tokens a batch against 0.1 s of device time
        "bilstm_scan_roofline_pct.tag": pytest.approx(
            100 * (115000 * flops / 197e12) / 0.1)}
    # a reader that finds nothing returns nothing, never 0
    empty = dict(ctx, counters={"compiles_in_window": 0}, work=0.0)
    assert cell.module("layer_metrics", "pad_ratio_pct.tag").read(empty) is None
    assert cell.module("layer_metrics", "bilstm_mfu_pct.tag").read(empty) is None
    assert cell.module("layer_metrics", "bilstm_scan_roofline_pct.tag").read(empty) is None


@pytest.mark.parametrize("variant", ["fp8", "bwd_forward", "gates"])
def test_the_control_and_each_planted_fault_are_not_correct(variant):
    """The reference in the program's place, computed one precision below
    the configuration's or with a fault planted, through the run's own
    sampling, comparison and verdict."""
    cell = tiny_cell()
    res = _run(cell, with_reference(cell, variant))
    assert res["failed"] == 0 and res["calls"] >= 1
    assert not check.verdict(res["compared"])


def _shift_positions(col, n):
    out = np.empty(len(col), dtype=object)
    for i, row in enumerate(col):
        out[i] = np.roll(row, 1, axis=0)    # every answer a position late
    return out


def _swap_rows(col, n):
    out = col.copy()
    out[[0, 1]] = out[[1, 0]]               # row order broken where it is produced
    return out[::-1].copy()


@pytest.mark.parametrize("fault", [_shift_positions, _swap_rows])
def test_an_altered_answer_is_not_correct(fault):
    cell = tiny_cell()
    res = _run(cell, with_fault(cell, fault))
    assert not check.verdict(res["compared"])


def test_a_nan_in_the_padding_fails_nothing_and_one_in_a_real_position_its_row():
    cell = tiny_cell()
    lengths = real_builder(cell).build(cell.config, cell.traffic, SEED, None).lengths
    row = int(np.argmax(lengths < 128))     # a row with padding
    n = int(lengths[row])

    def nan_in_pad(col, k):
        col[row][n:, :] = np.nan            # a row is a view: written in place
        return col

    def nan_in_real(col, k):
        if k == 1:
            col[row][n - 1, 3] = np.nan
        return col

    res = _run(cell, with_fault(cell, nan_in_pad))
    assert res["failed"] == 0 and check.verdict(res["compared"])
    assert _tokens(res) == _exactly(res["calls"] * int(lengths.sum()))
    res = _run(cell, with_fault(cell, nan_in_real))
    assert res["failed"] == 1 and res["attempted"] == res["calls"] * ROWS
    assert _tokens(res) == _exactly(res["calls"] * int(lengths.sum()) - n)
    assert check.verdict(res["compared"])   # failed, not wrong


def test_flops_a_token_agree_with_xla_for_the_plain_reference():
    """XLA counts a loop's body once, whatever its trip count, so the row
    here is one position long: a token is then one pass through each
    direction's cell and the head, at the published widths."""
    import jax
    import jax.numpy as jnp

    config = spec.load_cell(CELL).config
    ref = spec.bench_module("references", config["reference"])
    work = spec.bench_module("work", "bilstm")
    rows = 64
    w = {path: jax.ShapeDtypeStruct(shape, jnp.float32)
         for path, shape, _ in ref.weight_specs(config)}
    x = jax.ShapeDtypeStruct((rows, 1), jnp.int32)
    compiled = jax.jit(lambda w_, x_: ref.logits(config, w_, x_)).lower(w, x).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    xla = cost["flops"] / rows
    mine = work.flops_per_token(config)
    # XLA also counts the gates' element-wise work, about 30 operations a
    # hidden unit a direction: 1% of the matrix products
    assert 0.0 <= (xla - mine) / xla < 0.03, (mine, xla)
    total = sum(int(np.prod(shape)) for _, shape, _ in ref.weight_specs(config))
    assert total == work.parameters(config) == 130000 * 50 + 2 * 421200 + 5409
