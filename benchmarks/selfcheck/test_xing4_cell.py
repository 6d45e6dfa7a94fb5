"""The latent cell `xing4.score` at a tiny size on the CPU, through the
harness as it stands (`spec.load_cell`, `closed_loop.run`, the cell's own
builder, reference, work file and readers). The tiny sizes are this file's
own. Never a source of a device number."""

import dataclasses
import json
import time
import types

import numpy as np
import pytest

from benchmarks.harness import check, spans, spec, trace
from benchmarks.selfcheck.control_on_chip_xing4 import with_variants
from benchmarks.selfcheck.planted import with_fault
from benchmarks.selfcheck.tiny import _override

CELL = "xing4.score"
RATE = "tokens_per_s"
SEED = 4294970129                    # over 32 signed bits, as the driver's are
CAP = 32
TINY_CONFIG = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 24,
               "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
               "v_head_dim": 16, "vocab_size": 64, "intermediate_size": 96,
               "moe_intermediate_size": 32, "n_routed_experts": 16,
               "rope_scaling.factor": 4,
               "rope_scaling.original_max_position_embeddings": 8,
               "max_positions": CAP}
TINY_TRAFFIC = {"cap": CAP, "lengths.median": 48, "lengths.min": 4}
ROWS = 8                             # batch 1 x batches_per_call 8
# The limits are the chip's, set at widths of 3584 in bfloat16. At widths of
# 64, with 16 experts, bfloat16 operands read wider and the scores lie further
# apart: the tiny cell's builder gets limits of its own (the mean over stable
# positions reads 0.0013-0.0047 and their 99th percentile 0.0045-0.021; the
# control and the planted faults read 0.021 and 0.098 and more), and nothing
# else of it changes.
TINY_LIMITS = {"LOGPROB_GAP_LIMIT": 0.01, "LOGPROB_GAP_P99_LIMIT": 0.05,
               "UNSTABLE_SHARE_LIMIT": 0.5}
GENERIC = ("pad_ratio_pct.score", "host_prepare_ms.score", "ingest_queue_ms.score",
           "ingest_readback_ms.score", "host_emit_ms.score", "fusion_fallbacks.score",
           "device_idle_pct.score", "moe_load_max_over_mean.score")
NEW = ("xing4_mfu_pct.score", "xing4_step_roofline_pct.score",
       "attn_mla_roofline_pct.score", "mhc_mix_roofline_pct.score",
       "xing4_moe_gmm_roofline_pct.score")


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


def test_the_cell_is_in_the_benchmark_with_its_rate_and_thirteen_readers():
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {RATE, "setup_s"}
    assert [m["name"] for m in cell.per_layer] == [*GENERIC, *NEW]
    assert all(m["moves"] == RATE for m in cell.per_layer)
    assert cell.chips == 1 and cell.traffic_name == "token-docs-truncated-16k"
    assert cell.config_name == "xing4.0-29b-a4b-ep1"
    for other, readers in (("kexaone.score", 12), ("bilstm.tag", 9),
                           ("resnet50.featurize", 15)):
        assert len(spec.load_cell(other).per_layer) == readers   # nothing taken


def test_the_traffic_is_the_issues_and_the_file_keeps_the_published_widths():
    from benchmarks.harness import token_rows

    cell = spec.load_cell(CELL)
    t, c = cell.traffic, cell.config
    assert (t["cap"], t["batches_per_call"], t["partitions"], t["trace_calls"],
            t["check_rows_per_call"], t["check_rows_last_call"]) == (16384, 8, 2, 1, 1, 2)
    assert (t["lengths"]["median"], t["lengths"]["sigma"], t["lengths"]["min"],
            t["lengths"]["rows_at_cap"]) == (24000, 1.0, 256, 1)
    n = token_rows.lengths_multiset(t["lengths"], 8, 16384)
    assert list(n) == [5175, 9884, 14721] + [16384] * 5 and n.sum() == 111700
    assert c["reduced"] == ["num_hidden_layers"] and c["assumed"]["batch_size"] == 1
    assert (c["num_hidden_layers"], c["num_hidden_layers_published"]) == (6, 40)
    # every number of the catalog's row under its own key, but the one reduced
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Xing4.0-29B-A4B"]
    assert row["source_url"] == c["source_url"]
    for key, value in row["config"].items():
        assert c[key] == (6 if key == "num_hidden_layers" else value), key


def test_work_at_the_published_widths_is_the_issues_arithmetic():
    config = spec.load_cell(CELL).config
    work = spec.bench_module("work", "xing4")
    ref = spec.bench_module("references", config["reference"])
    total = sum(int(np.prod(shape)) for _, shape, _ in ref.weight_specs(config))
    # the issue's 4,175,822,848 in matrices, and the gains, biases and maps' scalars
    assert total == work.parameters(config) == 4175822848 + 54852 \
        == config["parameters_on_chip"]
    assert work.attention_macs(config) == 28409856
    assert work.flops_per_token(config) == pytest.approx(3.134e9, rel=1e-3)
    assert work.attention_core_flops_per_token(config) == pytest.approx(167.8e6, rel=1e-3)
    assert 6 * work.attention_core_flops_per_token(config) / work.flops_per_token(config) \
        == pytest.approx(0.32, abs=0.005)
    flops, moved = work.stream_mix(config, 1.0)
    assert moved == 12 * 4 * (3 * 4 * 3584 + 3584)          # 2.1 MB a token
    flops, moved = work.expert_products(config, 13962.0)
    assert flops == pytest.approx(13962 * 4 * 4 * 6 * 3584 * 1024)
    assert moved > 2 * 4 * 64 * 3 * 3584 * 1024            # the weights, once a layer
    assert ref.layer_plan(config) == work.layer_plan(config) == [False] * 2 + [True] * 4


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
    gap, tail, share = res["compared"]
    assert gap.name == "logprob_gap" and gap.value < gap.limit == 0.01
    assert tail.name == "logprob_gap_p99" and gap.value < tail.value < tail.limit == 0.05
    assert share.name == "unstable_share" and 0.0 <= share.value < share.limit


def test_traced_run_reports_all_thirteen_per_layer_names(monkeypatch):
    """The traced branch and every reader, the eight generic ones among them:
    the live tiny call's spans and counters, a device plane built by hand (the
    CPU writes none) and the v5e's peaks under the CPU's name."""
    from benchmarks import peaks

    ops = [(0.1 * k, 0.1 * k + 0.01, "%mhc_pre.3") for k in range(8)] \
        + [(0.1 * k + 0.01, 0.1 * k + 0.03, "%attn_mla.1") for k in range(8)] \
        + [(0.1 * k + 0.03, 0.1 * k + 0.04, "%mhc_post.2") for k in range(8)] \
        + [(0.1 * k + 0.04, 0.1 * k + 0.05, "%moe_gmm.7") for k in range(8)]
    planes = [("/device:TPU:0", [
        ("XLA Modules", [(0.1 * k, 0.1 * k + 0.05, "jit_fused(123)") for k in range(8)]),
        ("XLA Ops", ops)])]
    monkeypatch.setattr(trace, "read_trace",
                        lambda d, window_s: trace.reduce_planes(planes, window_s))
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    cell = tiny_cell()
    made = real_builder(cell)
    res = _run(cell, made, traced=True)
    assert res["calls"] == 1 == cell.traffic["trace_calls"]
    assert set(res["metrics"]) == {*GENERIC, *NEW}
    value = {k: v["value"] for k, v in res["metrics"].items()}
    lengths = made.subject.lengths
    assert value["pad_ratio_pct.score"] == pytest.approx(
        100.0 * lengths.sum() / (ROWS * CAP))
    assert value["fusion_fallbacks.score"] == 0
    assert value["moe_load_max_over_mean.score"] >= 1.0
    # every visit is to a held expert: 4 a position a sparse layer
    assert made.subject._load.shape == (4, 16)
    assert (made.subject._load.sum(axis=1) == ROWS * CAP * 4).all()
    assert check.verdict(res["compared"])


def test_each_new_reader_on_a_context_built_by_hand():
    from benchmarks import peaks

    cell = spec.load_cell(CELL)
    work = spec.bench_module("work", "xing4")
    planes = [("/device:TPU:0", [
        ("XLA Modules", [(1.0 * k, 1.0 * k + 0.9, "jit_fused(9)") for k in range(8)]),
        ("XLA Ops", [(1.0 * k, 1.0 * k + 0.2, "%attn_mla.2") for k in range(8)]
         + [(1.0 * k + 0.2, 1.0 * k + 0.25, "%mhc_pre.4") for k in range(8)]
         + [(1.0 * k + 0.25, 1.0 * k + 0.3, "%mhc_post.5") for k in range(8)]
         + [(1.0 * k + 0.3, 1.0 * k + 0.4, "%moe_gmm.4") for k in range(8)]
         + [(1.0 * k + 0.4, 1.0 * k + 0.9, "%fusion.1") for k in range(8)])])]
    tokens = 111700.0
    ctx = {"trace": trace.reduce_planes(planes, 8.0), "work": tokens, "window_s": 8.0,
           "counters": {"real_tokens": tokens, "padded_positions": 8 * 16384,
                        "expert_load": [[10.0] * 64, [5.0] * 63 + [325.0]]},
           "config": cell.config, "traffic": {"trace_calls": 1},
           "device_kind": "TPU v5 lite", "peaks": peaks,
           "span_calls": spans.Calls([], [])}
    read = {name: cell.module("layer_metrics", name).read for name in NEW}
    flops, batch = work.flops_per_token(cell.config), tokens / 8
    assert read["xing4_mfu_pct.score"](ctx) == pytest.approx(
        100 * tokens / 8.0 * flops / 197e12)
    assert read["xing4_step_roofline_pct.score"](ctx) == pytest.approx(
        100 * (batch * flops / 197e12) / 0.9)
    a_flops, a_bytes = work.latent_attention(cell.config, batch)
    assert a_flops / 197e12 > a_bytes / 819e9                  # compute binds
    assert read["attn_mla_roofline_pct.score"](ctx) == pytest.approx(
        100 * (a_flops / 197e12) / 0.2)
    m_flops, m_bytes = work.stream_mix(cell.config, batch)
    assert m_bytes / 819e9 > m_flops / 197e12                  # memory binds
    assert read["mhc_mix_roofline_pct.score"](ctx) == pytest.approx(
        100 * (m_bytes / 819e9) / 0.1)
    e_flops, e_bytes = work.expert_products(cell.config, batch)
    assert read["xing4_moe_gmm_roofline_pct.score"](ctx) == pytest.approx(
        100 * max(e_flops / 197e12, e_bytes / 819e9) / 0.1)
    assert all(0.0 < read[name](ctx) < 100.0 for name in NEW)
    assert cell.module("layer_metrics", "moe_load_max_over_mean.score").read(ctx) \
        == pytest.approx(325.0 / 10.0)
    # a reader that finds nothing returns nothing or says so, never 0; so it
    # is with the parent's program, which has none of the new kernels
    bare = dict(ctx, counters={}, work=0.0,
                trace=trace.reduce_planes([("/device:TPU:0", [
                    ("XLA Modules", [(0.0, 1.0, "jit_fused(9)")]),
                    ("XLA Ops", [(0.0, 1.0, "%fusion.1")])])], 1.0))
    assert read["xing4_mfu_pct.score"](bare) is None
    assert read["xing4_step_roofline_pct.score"](bare) is None
    for name in ("attn_mla_roofline_pct.score", "mhc_mix_roofline_pct.score",
                 "xing4_moe_gmm_roofline_pct.score"):
        with pytest.raises(LookupError):
            read[name](bare)


def test_the_control_and_every_planted_fault_are_not_correct():
    """The reference in the program's place, computed one precision below the
    configuration's or with a fault planted, through the run's own sampling,
    comparison and verdict: one window, every variant."""
    cell = tiny_cell()
    reference = cell.module("references", cell.config["reference"])
    variants, verdicts = ["fp8", *reference.FAULTS], {}
    res = _run(cell, with_variants(cell, variants, verdicts), seconds=0.05)
    assert res["failed"] == 0 and res["calls"] >= 1
    assert list(verdicts) == variants and len(variants) == 10
    for variant, numbers in verdicts.items():
        assert not check.verdict(numbers), variant


def test_the_mean_of_the_streams_reads_as_their_sum():
    """What the issue lists as a fault and no comparison can see: the final
    RMSNorm divides the 4 out. It is computed on request, reads correct, and
    is not among FAULTS (`streams_first` is)."""
    cell = tiny_cell()
    verdicts = {}
    _run(cell, with_variants(cell, ["streams_mean"], verdicts), seconds=0.05)
    assert check.verdict(verdicts["streams_mean"])
    assert verdicts["streams_mean"][0].value < 1e-4


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
