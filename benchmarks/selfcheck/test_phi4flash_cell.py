"""The hybrid cell `phi4flash.score` at a tiny size on the CPU, through the
harness as it stands (`spec.load_cell`, `closed_loop.run`, the cell's own
builder, reference, work file and readers). The tiny sizes are this file's
own. Never a source of a device number."""

import dataclasses
import json
import time
import types

import numpy as np
import pytest

from benchmarks.harness import check, spec, trace
from benchmarks.selfcheck.control_on_chip_phi4flash import with_variants
from benchmarks.selfcheck.planted import with_fault
from benchmarks.selfcheck.tiny import _override

CELL = "phi4flash.score"
RATE = "tokens_per_s"
SEED = 4294970129                    # over 32 signed bits, as the driver's are
CAP = 48
TINY_CONFIG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
               "intermediate_size": 96, "vocab_size": 96, "num_hidden_layers": 8,
               "sliding_window": 8, "assumed.mamba_d_state": 4,
               "assumed.mamba_dt_rank": 4, "max_positions": CAP}
TINY_TRAFFIC = {"cap": CAP, "lengths.median": 70, "lengths.min": 4}
ROWS = 4                             # batch 1 x batches_per_call 4
# The limit is the chip's, set at widths of 2560. At widths of 64 a bfloat16
# operand weighs more: the tiny cell's builder gets a limit of its own (the
# program reads 0.013-0.024 there over five seeds; the mildest fault, a cross
# layer on its own keys, where one layer of eight is a cross layer, 0.048; the
# control and the others 0.14-0.80; the means 0.0032-0.0043 against 0.017 and
# more, but for the state reset, which a tiny row never reaches).
TINY_LIMITS = {"LOGPROB_GAP_LIMIT": 0.035, "LOGPROB_GAP_MEAN_LIMIT": 0.008}
GENERIC = ("pad_ratio_pct.score", "host_prepare_ms.score", "ingest_queue_ms.score",
           "ingest_readback_ms.score", "host_emit_ms.score", "fusion_fallbacks.score",
           "device_idle_pct.score", "attn_ms.score", "attn_outside_kernel_ms.score",
           "lm_head_ms.score", "device_scoped_pct.score")
NEW = ("phi4flash_mfu_pct.score", "phi4flash_step_roofline_pct.score",
       "ssm_scan_roofline_pct.score", "attn_diff_roofline_pct.score",
       "ssm_layer_ms.score", "ssm_outside_scan_ms.score", "gmu_ms.score")


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


def test_the_cell_is_in_the_benchmark_with_its_rate_and_eighteen_readers():
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {RATE, "setup_s"}
    assert sorted(m["name"] for m in cell.per_layer) == sorted([*GENERIC, *NEW])
    assert [m["name"] for m in cell.per_layer][-7:] == list(NEW)    # appended, in order
    assert all(m["moves"] == RATE for m in cell.per_layer)
    assert cell.chips == 1 and cell.traffic_name == "token-docs-truncated-32k"
    assert cell.config_name == "phi-4-mini-flash-reasoning"
    # the five expert readers stay off it, and nothing was taken from the others
    assert not any("expert" in m["name"] or "moe" in m["name"] for m in cell.per_layer)
    for other, readers in (("kexaone.score", 19), ("xing4.score", 20), ("bilstm.tag", 15),
                           ("resnet50.featurize", 16)):
        assert len(spec.load_cell(other).per_layer) == readers


def test_the_traffic_is_the_issues_and_the_file_keeps_the_published_widths():
    from benchmarks.harness import token_rows

    cell = spec.load_cell(CELL)
    t, c = cell.traffic, cell.config
    assert (t["driver"], t["rate_metric"]) == ("closed_loop", RATE)
    assert (t["cap"], t["batches_per_call"], t["partitions"], t["trace_calls"],
            t["check_rows_per_call"], t["check_rows_last_call"]) == (32768, 4, 2, 1, 1, 1)
    assert (t["lengths"]["median"], t["lengths"]["sigma"], t["lengths"]["min"],
            t["lengths"]["rows_at_cap"]) == (48000, 1.0, 512, 1)
    n = token_rows.lengths_multiset(t["lengths"], 4, 32768)
    assert list(n) == [15193] + [32768] * 3 and n.sum() == 113497
    assert c["reduced"] == [] and c["assumed"]["batch_size"] == 1
    # every number of the catalog's row under its own key: nothing is reduced
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Phi-4-mini-flash-reasoning"]
    assert row["source_url"] == c["source_url"]
    for key, value in row["config"].items():
        assert c[key] == value, key
    bench = spec.load_json(spec.ROOT + "/BENCHMARK.json")
    (entry,) = [e for e in bench["configs"] if e["name"] == cell.config_name]
    assert entry["source"] == row["source_url"] and entry["reduced"] == []


def test_work_at_the_published_widths_is_the_issues_arithmetic():
    config = spec.load_cell(CELL).config
    work = spec.bench_module("work", "phi4flash")
    ref = spec.bench_module("references", config["reference"])
    total = sum(int(np.prod(shape)) for _, shape, _ in ref.weight_specs(config))
    # the issue's 3,852,119,040 in matrices and Mamba's vectors, and the norms,
    # biases, lambda vectors and subln gains
    assert total == work.parameters(config) == 3852119040 + 443904 \
        == config["parameters_on_chip"]
    assert [work.mixer_macs(config, k) for k in ("mamba", "window", "cross", "gmu")] \
        == [41123840, 19660800, 13107200, 26214400]
    assert 2 * work.macs_per_token(config) == pytest.approx(6.678e9 + 1.024e9, rel=1e-3)
    assert work.pair_flops(config) == 15360 == 20 * 768
    assert work.flops_per_token(config, [32768]) == pytest.approx(9.78e9, rel=1e-3)
    assert work.flops_per_token(config, [15193, 32768, 32768, 32768]) \
        == pytest.approx(9.63e9, rel=1e-3)
    window, full = work.keys_seen(config, [32768])
    assert window == 32768 * 512 - 512 * 511 / 2 and full == 32768 * 32769 / 2
    assert work.keys_seen(config, [100]) == (5050.0, 5050.0)
    flops, moved = work.selective_scan(config, 1.0)
    assert moved == 277632 and flops == 9 * 6 * 5120 * 16
    assert moved / 819e9 > flops / 197e12                       # memory binds
    plan = ref.layer_plan(config)
    assert [k.replace("_memory", "") for k in plan] == work.layer_plan(config)
    assert [plan.count(k) for k in ("mamba", "mamba_memory", "window", "full", "gmu",
                                    "cross")] == [8, 1, 8, 1, 7, 7]


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
    gap, mean = res["compared"]
    assert gap.name == "logprob_gap" and mean.value < gap.value < gap.limit == 0.035
    assert mean.name == "logprob_gap_mean" and mean.value < mean.limit == 0.008


def test_the_reference_cuts_a_row_at_its_length_and_nothing_real_moves():
    cell = tiny_cell()
    config = cell.config
    ref = cell.module("references", config["reference"])
    ids = np.random.default_rng(3).integers(1, 96, (2, CAP), dtype=np.int32)
    whole = ref.score(config, SEED, ids)["logprob"]
    ref.ROW_BLOCK = 16               # the short row now stops at 32 of 48
    try:
        ids[1, 20:] = 0
        cut = ref.score(config, SEED, ids)["logprob"]
    finally:
        ref.ROW_BLOCK = 1024
    assert np.isfinite(whole).all() and np.isnan(cut[1, 32:]).all()
    assert np.abs(cut[1, :19] - whole[1, :19]).max() < 1e-4
    assert np.isfinite(cut[1, :32]).all() and np.allclose(cut[0], whole[0], atol=1e-5)


def test_each_new_reader_on_a_context_built_by_hand():
    from benchmarks import peaks

    cell = spec.load_cell(CELL)
    work = spec.bench_module("work", "phi4flash")
    lengths = [15193, 32768, 32768, 32768]
    planes = [("/device:TPU:0", [
        ("XLA Modules", [(4.0 * k, 4.0 * k + 3.6, "jit_fused(9)") for k in range(4)]),
        ("XLA Ops", [(4.0 * k, 4.0 * k + 0.4, "%ssm_scan.2 = f32[] custom-call()")
                     for k in range(4)]
         + [(4.0 * k + 0.4, 4.0 * k + 0.5, "%attn_window_diff.4") for k in range(4)]
         + [(4.0 * k + 0.5, 4.0 * k + 1.5, "%attn_full_diff.5") for k in range(4)]
         # a consumer that names the kernel among its operands is not the kernel
         + [(4.0 * k + 1.5, 4.0 * k + 3.6, "%fusion.1 = fusion(%ssm_scan.2)")
            for k in range(4)])])]
    tokens = float(sum(lengths))
    ctx = {"trace": trace.reduce_planes(planes, 16.0), "work": tokens, "window_s": 16.0,
           "counters": {"real_tokens": tokens, "padded_positions": 4 * 32768},
           "config": cell.config, "traffic": cell.traffic,
           "device_kind": "TPU v5 lite", "peaks": peaks}
    read = {name: cell.module("layer_metrics", name).read for name in NEW[:4]}
    flops = work.flops_per_token(cell.config, lengths)
    assert read["phi4flash_mfu_pct.score"](ctx) == pytest.approx(
        100 * tokens / 16.0 * flops / 197e12)
    assert read["phi4flash_step_roofline_pct.score"](ctx) == pytest.approx(
        100 * (tokens / 4 * flops / 197e12) / 3.6)
    assert read["ssm_scan_roofline_pct.score"](ctx) == pytest.approx(
        100 * (tokens / 4 * 277632 / 819e9) / 0.4)
    c_flops, c_bytes = work.differential_cores(cell.config, lengths)
    assert c_flops / 197e12 > c_bytes / 819e9                   # compute binds
    assert read["attn_diff_roofline_pct.score"](ctx) == pytest.approx(
        100 * (c_flops / 197e12) / 4.4)
    assert all(0.0 < read[name](ctx) < 100.0 for name in read)
    # the parent's program has none of the kernels: nothing to read, never 0
    bare = dict(ctx, counters={}, work=0.0,
                trace=trace.reduce_planes([("/device:TPU:0", [
                    ("XLA Modules", [(0.0, 1.0, "jit_fused(9)")]),
                    ("XLA Ops", [(0.0, 1.0, "%fusion.1")])])], 1.0))
    assert read["phi4flash_mfu_pct.score"](bare) is None
    assert read["phi4flash_step_roofline_pct.score"](bare) is None
    for name in ("ssm_scan_roofline_pct.score", "attn_diff_roofline_pct.score"):
        with pytest.raises(LookupError):
            read[name](bare)


def test_the_scope_readers_find_the_live_tiny_programs_parts(monkeypatch):
    """The three scope readers and the generic ones on a plane whose events
    are named after the live tiny program's own instructions."""
    from benchmarks.harness import scopes

    cell = tiny_cell()
    made = real_builder(cell)
    res = _run(cell, made, seconds=0.05)
    assert res["failed"] == 0
    # `free()` dropped the subject's program; build one more and keep it alive
    subject = cell.module("builders", cell.config["builder"]).build(
        cell.config, cell.traffic, SEED, None)
    subject.warm()
    # other cells' self-checks may have left programs alive in this process
    program = [p for p in scopes.recorded() if p.module == "jit_fused"
               and any("/ssm/" in path + "/" for path in p.scopes.values())][-1]
    paths = set(program.scopes.values())
    for part in ("layer0/ssm/proj_in", "layer0/ssm/conv", "layer0/ssm/scan",
                 "layer0/ssm/gate", "layer0/ssm/proj_out", "layer1/attn/core",
                 "layer6/gmu/gate", "layer7/attn/proj_in", "layer3/mlp", "embed", "head"):
        assert any(("/" + p + "/").find("/" + part + "/") >= 0 for p in paths), part
    names = sorted(program.scopes)
    events = [(0.001 * k, 0.001 * k + 0.001, "%" + n) for k, n in enumerate(names)]
    planes = [("/device:TPU:0", [
        ("XLA Modules", [(0.0, 0.001 * len(names), "jit_fused(1)")]),
        ("XLA Ops", events)])]
    ctx = {"trace": trace.reduce_planes(planes, 1.0)}
    for name in ("ssm_layer_ms.score", "ssm_outside_scan_ms.score", "gmu_ms.score",
                 "attn_ms.score", "attn_outside_kernel_ms.score", "lm_head_ms.score",
                 "device_scoped_pct.score"):
        assert cell.module("layer_metrics", name).read(ctx) > 0.0, name
    subject.free()


def test_the_control_and_every_planted_fault_are_not_correct():
    """The reference in the program's place, computed one precision below the
    configuration's or with a fault planted, through the run's own sampling,
    comparison and verdict: one window, every variant. The state is reset
    every 8,192 positions: beyond a tiny row, so that one reads correct here."""
    cell = tiny_cell()
    reference = cell.module("references", cell.config["reference"])
    variants, verdicts = ["fp8", *reference.FAULTS], {}
    res = _run(cell, with_variants(cell, variants, verdicts), seconds=0.05)
    assert res["failed"] == 0 and res["calls"] >= 1
    assert list(verdicts) == variants and len(variants) == 8
    for variant, numbers in verdicts.items():
        assert check.verdict(numbers) == (variant == "state_reset_8192"), variant


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
