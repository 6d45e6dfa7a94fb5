"""The scoring cell `kexaone.score` at a tiny size on the CPU, through the
harness as it stands (`spec.load_cell`, `closed_loop.run`, the cell's own
builder, reference, work file and readers). The tiny sizes are this file's
own. Never a source of a device number."""

import dataclasses
import time
import types

import numpy as np
import pytest

from benchmarks.harness import check, spans, spec, trace
from benchmarks.selfcheck.control_on_chip_kexaone import with_variant
from benchmarks.selfcheck.planted import with_fault
from benchmarks.selfcheck.tiny import _override

CELL = "kexaone.score"
RATE = "tokens_per_s"
SEED = 4294970129                    # over 32 signed bits, as the driver's are
CAP = 32
TINY_CONFIG = {"hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
               "num_key_value_heads": 2, "vocab_size": 64, "num_experts": 4,
               "num_experts_published": 16, "num_experts_per_tok": 4,
               "intermediate_size": 96, "moe_intermediate_size": 32,
               "sliding_windows": [4, 4, 4, 0, 4], "max_positions": CAP,
               "assumed.batch_size": 4}
TINY_TRAFFIC = {"cap": CAP, "lengths.median": 40, "lengths.min": 4,
                "check_rows_per_call": 2, "check_rows_last_call": 4,
                "trace_calls": 2}
ROWS = 4 * 4                         # batch x batches_per_call
# The limits are the chip's, set at widths of 6144. At widths of 64, with 16
# experts, bfloat16 operands read wider and the scores lie further apart: the
# tiny cell's builder gets limits of its own (a reading of 0.01-0.06; the
# planted faults read 0.2 and more), and nothing else of it changes.
TINY_LIMITS = {"LOGPROB_GAP_LIMIT": 0.1, "UNSTABLE_SHARE_LIMIT": 0.5}


def tiny_cell() -> spec.Cell:
    cell = spec.load_cell(CELL)
    cell = dataclasses.replace(cell, config=_override(cell.config, TINY_CONFIG),
                               traffic=_override(cell.traffic, TINY_TRAFFIC))
    load = cell.module

    def module(kind, name):
        found = load(kind, name)
        if kind == "builders":
            for limit, value in TINY_LIMITS.items():
                setattr(found, limit, value)
        return found

    cell.module = module
    return cell


def real_builder(cell):
    real = cell.module("builders", cell.config["builder"])
    made = types.SimpleNamespace(subject=None)

    def build(config, traffic, seed, chips):
        made.subject = real.build(config, traffic, seed, chips)
        return made.subject

    made.build = build
    return made


def _run(cell, builder, seconds=0.5, traced=False, seed=SEED):
    import jax

    driver = cell.module("drivers", cell.traffic["driver"])
    return driver.run(cell, builder, jax.devices()[:1], seed, seconds, traced,
                      time.perf_counter())


def test_the_cell_is_in_the_benchmark_with_its_rate_and_twelve_readers():
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {RATE, "setup_s"}
    assert len(cell.per_layer) == 12
    assert all(m["moves"] == RATE and m["name"].endswith(".score")
               for m in cell.per_layer)
    assert cell.chips == 1 and cell.traffic_name == "token-docs-truncated"
    for other, readers in (("bilstm.tag", 9), ("resnet50.featurize", 15)):
        assert len(spec.load_cell(other).per_layer) == readers   # nothing taken


def test_the_traffic_is_the_issues_and_the_file_keeps_the_published_widths():
    from benchmarks.harness import token_rows

    cell = spec.load_cell(CELL)
    t, c = cell.traffic, cell.config
    assert (t["cap"], t["batches_per_call"], t["partitions"], t["trace_calls"],
            t["check_rows_per_call"], t["check_rows_last_call"]) == (4096, 4, 2, 2, 2, 8)
    n = token_rows.lengths_multiset(t["lengths"], 32, 4096)
    assert (n == 4096).sum() == 21 and n.sum() == 111508 and n.min() >= 64
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["intermediate_size"], c["moe_intermediate_size"],
            c["num_experts_per_tok"], c["sliding_window"]) == (
                6144, 64, 8, 128, 18432, 2048, 8, 128)
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (5, 16, 19200)
    assert (c["num_experts_published"], c["assumed"]["batch_size"]) == (128, 8)
    assert len(c["layer_types"]) == len(c["sliding_windows"]) == 48     # copied whole


def test_work_at_the_published_widths_is_the_issues_arithmetic():
    config = spec.load_cell(CELL).config
    work = spec.bench_module("work", "exaone_moe")
    ref = spec.bench_module("references", config["reference"])
    total = sum(int(np.prod(shape)) for _, shape, _ in ref.weight_specs(config))
    # the issue's 3,711,959,040 in matrices, and the gains and selection biases
    assert total == work.parameters(config) == 3711959040 + 69376 \
        == config["parameters_on_chip"]
    assert work.expected_visits(config) == 1.0
    assert work.flops_per_token(config) == pytest.approx(2.742e9, rel=2e-3)
    assert work.attention_core_flops_per_token(config, 0) == pytest.approx(67.1e6, rel=1e-2)
    assert work.attention_core_flops_per_token(config, 128) == pytest.approx(4.13e6, rel=1e-2)
    flops, moved = work.expert_products(config, 27877.0)
    assert flops == pytest.approx(27877 * 4 * 6 * 6144 * 2048)
    assert moved > 2 * 4 * 16 * 3 * 6144 * 2048        # the weights, once a layer


def test_untraced_reports_the_rate_and_setup_and_counts_real_tokens():
    cell = tiny_cell()
    made = real_builder(cell)
    res = _run(cell, made)
    assert set(res["metrics"]) == {RATE, "setup_s"}
    assert res["calls"] >= 2 and res["failed"] == 0
    assert res["attempted"] == res["calls"] * ROWS
    assert res["compiles_in_window"] == 0
    tokens = res["metrics"][RATE]["value"] * res["window_s"]
    assert tokens == pytest.approx(res["calls"] * int(made.subject.lengths.sum()), rel=1e-9)
    assert check.verdict(res["compared"])
    gap, share = res["compared"]
    assert gap.name == "logprob_gap" and gap.value < gap.limit == 0.1
    assert share.name == "unstable_share" and 0.0 <= share.value < share.limit


def test_traced_run_reports_all_twelve_per_layer_names(monkeypatch):
    """The traced branch and every reader: the live tiny calls' spans and
    counters, a device plane built by hand (the CPU writes none) and the
    v5e's peaks under the CPU's name."""
    from benchmarks import peaks

    ops = [(0.1 * k, 0.1 * k + 0.02, "%attn_window.3") for k in range(8)] \
        + [(0.1 * k + 0.02, 0.1 * k + 0.03, "%attn_full.1") for k in range(8)] \
        + [(0.1 * k + 0.03, 0.1 * k + 0.05, "%moe_gmm.7") for k in range(8)]
    planes = [("/device:TPU:0", [
        ("XLA Modules", [(0.1 * k, 0.1 * k + 0.05, "jit_fused(123)") for k in range(8)]),
        ("XLA Ops", ops)])]
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
    assert value["pad_ratio_pct.score"] == pytest.approx(
        100.0 * lengths.sum() / (ROWS * CAP))
    assert value["fusion_fallbacks.score"] == 0
    assert value["moe_load_max_over_mean.score"] >= 1.0
    assert made.subject._load.shape == (4, 4) and made.subject._load.sum() > 0
    assert check.verdict(res["compared"])


def test_each_new_reader_on_a_context_built_by_hand():
    from benchmarks import peaks

    cell = spec.load_cell(CELL)
    work = spec.bench_module("work", "exaone_moe")
    planes = [("/device:TPU:0", [
        ("XLA Modules", [(1.0 * k, 1.0 * k + 0.5, "jit_fused(9)") for k in range(4)]),
        ("XLA Ops", [(1.0 * k, 1.0 * k + 0.02, "%attn_window.2") for k in range(4)]
         + [(1.0 * k + 0.1, 1.0 * k + 0.2, "%moe_gmm.4") for k in range(4)]
         + [(1.0 * k + 0.2, 1.0 * k + 0.5, "%fusion.1") for k in range(4)])])]
    tokens = 4 * 27877.0
    ctx = {"trace": trace.reduce_planes(planes, 4.0), "work": tokens, "window_s": 4.0,
           "counters": {"real_tokens": tokens, "padded_positions": 4 * 32768,
                        "expert_load": [[10.0] * 16, [5.0] * 15 + [85.0]]},
           "config": cell.config, "traffic": {"trace_calls": 1},
           "device_kind": "TPU v5 lite", "peaks": peaks,
           "span_calls": spans.Calls([], [])}
    read = {name: cell.module("layer_metrics", name).read for name in (
        "kexaone_mfu_pct.score", "kexaone_step_roofline_pct.score",
        "moe_gmm_roofline_pct.score", "attn_window_roofline_pct.score",
        "moe_load_max_over_mean.score", "pad_ratio_pct.score")}
    flops = work.flops_per_token(cell.config)
    assert read["kexaone_mfu_pct.score"](ctx) == pytest.approx(
        100 * tokens / 4.0 * flops / 197e12)
    assert read["kexaone_step_roofline_pct.score"](ctx) == pytest.approx(
        100 * (27877 * flops / 197e12) / 0.5)
    e_flops, _ = work.expert_products(cell.config, 27877.0)
    assert read["moe_gmm_roofline_pct.score"](ctx) == pytest.approx(
        100 * (e_flops / 197e12) / 0.1)                   # compute binds
    a_flops, a_bytes = work.window_attention(cell.config, 27877.0)
    assert read["attn_window_roofline_pct.score"](ctx) == pytest.approx(
        100 * max(a_flops / 197e12, a_bytes / 819e9) / 0.02)
    assert read["moe_load_max_over_mean.score"](ctx) == pytest.approx(85.0 / 10.0)
    assert read["pad_ratio_pct.score"](ctx) == pytest.approx(100 * 27877 / 32768)
    # a reader that finds nothing returns nothing or says so, never 0
    bare = dict(ctx, counters={}, work=0.0,
                trace=trace.reduce_planes([("/device:TPU:0", [
                    ("XLA Modules", [(0.0, 1.0, "jit_fused(9)")]),
                    ("XLA Ops", [(0.0, 1.0, "%fusion.1")])])], 1.0))
    assert read["kexaone_mfu_pct.score"](bare) is None
    assert read["moe_load_max_over_mean.score"](bare) is None
    for name in ("moe_gmm_roofline_pct.score", "attn_window_roofline_pct.score"):
        with pytest.raises(LookupError):
            read[name](bare)


@pytest.mark.parametrize("variant", ["fp8", "window_full", "rope_on_full",
                                     "no_topk_norm", "no_shared", "experts_16_31"])
def test_the_control_and_each_planted_fault_are_not_correct(variant):
    """The reference in the program's place, computed one precision below the
    configuration's or with a fault planted, through the run's own sampling,
    comparison and verdict."""
    cell = tiny_cell()
    res = _run(cell, with_variant(cell, variant), seconds=0.05)
    assert res["failed"] == 0 and res["calls"] >= 1
    assert not check.verdict(res["compared"])


def _shift_positions(col, n):
    out = np.empty(len(col), dtype=object)
    for i, row in enumerate(col):
        out[i] = np.roll(row, 1, axis=0)    # every answer a position late
    return out


def _swap_rows(col, n):
    out = col.copy()
    out[[0, 1]] = out[[1, 0]]
    return out[::-1].copy()


@pytest.mark.parametrize("fault", [_shift_positions, _swap_rows])
def test_an_altered_answer_is_not_correct(fault):
    cell = tiny_cell()
    res = _run(cell, with_fault(cell, fault), seconds=0.05)
    assert not check.verdict(res["compared"])


def test_a_nan_in_the_padding_fails_nothing_and_one_in_a_real_position_its_row():
    cell = tiny_cell()
    lengths = real_builder(cell).build(cell.config, cell.traffic, SEED, None).lengths
    row = int(np.argmax(lengths < CAP))     # a row with padding
    n = int(lengths[row])

    def nan_in_pad(col, k):
        col[row][n:] = np.nan               # a row is a view: written in place
        return col

    def nan_in_real(col, k):
        if k == 1:
            col[row][n - 1] = np.nan
        return col

    res = _run(cell, with_fault(cell, nan_in_pad), seconds=0.05)
    assert res["failed"] == 0 and check.verdict(res["compared"])
    res = _run(cell, with_fault(cell, nan_in_real), seconds=0.05)
    assert res["failed"] == 1 and res["attempted"] == res["calls"] * ROWS
    assert check.verdict(res["compared"])   # failed, not wrong
