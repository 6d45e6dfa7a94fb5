"""The multi-token-prediction cell `glm47flash.score` at a tiny size on the
CPU, through the harness as it stands (`spec.load_cell`, `closed_loop.run`,
the cell's own builder, reference, work file and readers). The tiny sizes are
this file's own: rows of 64 positions, content heads of 48 lanes (no whole
tile, as the published 192 is none). Never a source of a device number."""

import dataclasses
import time
import types

import numpy as np
import pytest

from benchmarks.harness import check, spec, trace
from benchmarks.selfcheck.control_on_chip_glm47 import with_variants
from benchmarks.selfcheck.planted import with_fault
from benchmarks.selfcheck.tiny import _override

CELL = "glm47flash.score"
RATE = "tokens_per_s"
SEED = 4294970129                    # over 32 signed bits, as a run's may be
CAP = 64
TINY_CONFIG = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 24,
               "kv_lora_rank": 16, "qk_nope_head_dim": 48, "qk_rope_head_dim": 16,
               "v_head_dim": 64, "vocab_size": 64, "intermediate_size": 96,
               "moe_intermediate_size": 32, "n_routed_experts": 8,
               "num_experts_per_tok": 4, "max_positions": CAP}
TINY_TRAFFIC = {"cap": CAP, "lengths.median": 96, "lengths.min": 8}
ROWS = 8                             # batch 1 x batches_per_call 8
# The limits are the chip's, set at widths of 2048 in bfloat16. At widths of
# 64 a bfloat16 operand weighs more: the tiny cell's builder gets limits of
# its own, and nothing else of it changes.
TINY_LIMITS = {"LOGPROB_GAP_LIMIT": 0.012, "LOGPROB_GAP_P99_LIMIT": 0.06,
               "UNSTABLE_SHARE_LIMIT": 0.6, "MTP_LOGPROB_GAP_LIMIT": 0.012,
               "MTP_LOGPROB_GAP_P99_LIMIT": 0.06, "MTP_UNSTABLE_SHARE_LIMIT": 0.6}
GENERIC = ("pad_ratio_pct.score", "host_prepare_ms.score", "ingest_queue_ms.score",
           "ingest_readback_ms.score", "host_emit_ms.score", "fusion_fallbacks.score",
           "device_idle_pct.score", "attn_ms.score", "attn_outside_kernel_ms.score",
           "lm_head_ms.score", "device_scoped_pct.score")
EXPERT = ("moe_load_max_over_mean.score", "expert_layer_ms.score",
          "expert_outside_gmm_ms.score", "expert_combine_ms.score",
          "expert_gather_ms.score")
NEW = ("mtp_ms.score", "glm47_mfu_pct.score", "glm47_step_roofline_pct.score",
       "glm47_attn_mla_roofline_pct.score")
COMPARED = ["logprob_gap", "logprob_gap_p99", "unstable_share", "mtp_logprob_gap",
            "mtp_logprob_gap_p99", "mtp_unstable_share"]


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


def test_the_cell_is_in_the_benchmark_with_its_rate_and_twenty_readers():
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {RATE, "setup_s"}
    assert sorted(m["name"] for m in cell.per_layer) == sorted([*GENERIC, *EXPERT, *NEW])
    assert all(m["moves"] == RATE for m in cell.per_layer)
    assert cell.chips == 1 and cell.traffic_name == "token-docs-truncated-16k"
    assert cell.config_name == "glm-4.7-flash-mtp-ep1"
    bench = spec.load_json(spec.ROOT + "/BENCHMARK.json")
    assert [m["name"] for m in bench["per_layer"]][-4:] == list(NEW)   # appended, in order
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
    # nothing was taken from the other cells
    for other, readers in (("kexaone.score", 20), ("xing4.score", 21), ("bilstm.tag", 15),
                           ("resnet50.featurize", 16), ("phi4flash.score", 18),
                           ("axk2.score", 21)):
        assert len(spec.load_cell(other).per_layer) == readers


def test_the_traffic_is_xing4s_and_the_file_keeps_the_published_widths():
    from benchmarks.harness import token_rows

    cell = spec.load_cell(CELL)
    assert cell.traffic == spec.load_cell("xing4.score").traffic
    t, c = cell.traffic, cell.config
    n = token_rows.lengths_multiset(t["lengths"], 8, 16384)
    assert sorted(n) == [5175, 9884, 14721] + [16384] * 5 and n.sum() == 111700
    assert c["assumed"]["batch_size"] == 1 and c["num_nextn_predict_layers"] == 1
    assert c["reduced"] == ["num_hidden_layers"]
    assert (c["num_hidden_layers"], c["num_hidden_layers_published"]) == (5, 47)
    # the published widths: nothing but the depth is cut
    assert (c["hidden_size"], c["num_attention_heads"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["q_lora_rank"], c["kv_lora_rank"],
            c["intermediate_size"], c["moe_intermediate_size"], c["n_routed_experts"],
            c["num_experts_per_tok"], c["vocab_size"]) == (
        2048, 20, 192, 64, 256, 768, 512, 10240, 1536, 64, 4, 154880)
    bench = spec.load_json(spec.ROOT + "/BENCHMARK.json")
    (entry,) = [e for e in bench["configs"] if e["name"] == cell.config_name]
    assert entry["source"] == c["source_url"] and entry["reduced"] == c["reduced"]


def test_work_at_the_published_widths_is_the_issues_arithmetic():
    config = spec.load_cell(CELL).config
    work = spec.bench_module("work", "glm47")
    ref = spec.bench_module("references", config["reference"])
    specs = ref.weight_specs(config)
    total = sum(int(np.prod(shape)) for _, shape, _ in specs)
    assert total == work.parameters(config) == config["parameters_on_chip"]
    assert sum(int(np.prod(shape)) for _, shape, _ in specs if len(shape) > 1) \
        == 3903979520                                               # the issue's matrices
    assert work.attention_macs(config) == 21757952
    assert work.attention_core_flops_per_token(config) == pytest.approx(167.8e6, rel=1e-3)
    assert work.flops_per_token(config) == pytest.approx(3.152e9, rel=1e-3)
    assert work.mtp_flops_per_token(config) == pytest.approx(957e6, rel=1e-3)
    flops, moved = work.latent_attention(config, 111700 / 8)
    assert flops / 197e12 > moved / 819e9                           # compute binds


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
    assert check.verdict(res["compared"]), res["compared"]
    assert [c.name for c in res["compared"]] == COMPARED
    # the MTP's expert layer is the last row of the routing's counter
    load = made.subject._load                # the window's calls, summed
    assert load.shape == (5, 8) and (load.sum(axis=1) == load.sum() / 5).all()


def test_each_new_reader_on_a_context_built_by_hand():
    from benchmarks import peaks

    cell = spec.load_cell(CELL)
    work = spec.bench_module("work", "glm47")
    planes = [("/device:TPU:0", [
        ("XLA Modules", [(1.0 * k, 1.0 * k + 0.9, "jit_fused(9)") for k in range(8)]),
        ("XLA Ops", [(1.0 * k, 1.0 * k + 0.3, "%attn_mla.4 = bf16[] custom-call()")
                     for k in range(8)]
         # neither a consumer that names the kernel nor the sparse kernel is it
         + [(1.0 * k + 0.3, 1.0 * k + 0.5, "%fusion.1 = fusion(%attn_mla.4)")
            for k in range(8)]
         + [(1.0 * k + 0.5, 1.0 * k + 0.6, "%attn_mla_sparse.2") for k in range(8)])])]
    tokens = 111700.0
    ctx = {"trace": trace.reduce_planes(planes, 8.0), "work": tokens, "window_s": 8.0,
           "counters": {"real_tokens": tokens, "padded_positions": 8 * 16384},
           "config": cell.config, "traffic": cell.traffic,
           "device_kind": "TPU v5 lite", "peaks": peaks}
    read = {name: cell.module("layer_metrics", name).read for name in NEW[1:]}
    flops = work.flops_per_token(cell.config)
    assert read["glm47_mfu_pct.score"](ctx) == pytest.approx(
        100 * tokens / 8.0 * flops / 197e12)
    assert read["glm47_step_roofline_pct.score"](ctx) == pytest.approx(
        100 * (tokens / 8 * flops / 197e12) / 0.9)
    a_flops, _ = work.latent_attention(cell.config, tokens / 8)
    assert read["glm47_attn_mla_roofline_pct.score"](ctx) == pytest.approx(
        100 * (a_flops / 197e12) / 0.3)
    assert all(0.0 < read[name](ctx) < 100.0 for name in read)
    # the parent's program cannot build the cell: nothing to read, never 0
    bare = dict(ctx, counters={}, work=0.0,
                trace=trace.reduce_planes([("/device:TPU:0", [
                    ("XLA Modules", [(0.0, 1.0, "jit_fused(9)")]),
                    ("XLA Ops", [(0.0, 1.0, "%fusion.1")])])], 1.0))
    assert read["glm47_mfu_pct.score"](bare) is None
    assert read["glm47_step_roofline_pct.score"](bare) is None
    with pytest.raises(LookupError):
        read["glm47_attn_mla_roofline_pct.score"](bare)


def test_the_scope_readers_find_the_live_tiny_programs_parts():
    """The MTP reader, the generic and the expert ones on a plane whose
    events are named after the live tiny program's own instructions; the
    MTP's layer is counted as an attention and an expert layer, its logits
    are not the head."""
    from benchmarks.harness import scopes

    cell = tiny_cell()
    subject = cell.module("builders", cell.config["builder"]).build(
        cell.config, cell.traffic, SEED, None)
    subject.warm()
    # other cells' self-checks may have left programs alive in this process
    program = [p for p in scopes.recorded() if p.module == "jit_fused"
               and any("/mtp/" in "/" + path + "/" for path in p.scopes.values())][-1]
    paths = set(program.scopes.values())
    for part in ("layer0/attn/core", "layer0/mlp", "layer4/moe/route", "head", "embed",
                 "mtp/fuse", "mtp/layer5/attn/core", "mtp/layer5/moe/combine",
                 "mtp/layer5/shared", "mtp/logits"):
        assert any(("/" + p + "/").find("/" + part + "/") >= 0 for p in paths), part
    names = sorted(program.scopes)
    events = [(0.001 * k, 0.001 * k + 0.001, "%" + n) for k, n in enumerate(names)]
    planes = [("/device:TPU:0", [
        ("XLA Modules", [(0.0, 0.001 * len(names), "jit_fused(1)")]),
        ("XLA Ops", events)])]
    ctx = {"trace": trace.reduce_planes(planes, 1.0)}
    mtp_ms = cell.module("layer_metrics", "mtp_ms.score").read(ctx)
    head_ms = cell.module("layer_metrics", "lm_head_ms.score").read(ctx)
    in_mtp = sum(1 for n in names if "/mtp/" in "/" + program.scopes[n] + "/")
    in_head = sum(1 for n in names if "/head/" in "/" + program.scopes[n] + "/")
    assert mtp_ms == pytest.approx(in_mtp) and head_ms == pytest.approx(in_head)
    for name in ("attn_ms.score", "attn_outside_kernel_ms.score", "device_scoped_pct.score",
                 "expert_layer_ms.score", "expert_combine_ms.score"):
        assert cell.module("layer_metrics", name).read(ctx) > 0.0, name
    layers = {m.group(1) for p in paths for m in [scopes._LAYER.search(p)] if m}
    assert layers == {f"layer{i}" for i in range(6)}     # the MTP's layer is the sixth
    subject.free()


def test_the_control_and_every_planted_fault_are_not_correct():
    """The reference in the program's place, computed one precision below the
    configuration's or with a fault planted, through the run's own sampling,
    comparison and verdict: one window, every variant."""
    cell = tiny_cell()
    reference = cell.module("references", cell.config["reference"])
    variants, verdicts = ["fp8", *reference.FAULTS], {}
    res = _run(cell, with_variants(cell, variants, verdicts), seconds=0.05)
    assert res["failed"] == 0 and res["calls"] >= 1
    assert list(verdicts) == variants and len(variants) == 8
    for variant, numbers in verdicts.items():
        assert not check.verdict(numbers), (variant, numbers)


def _shift_positions(col, n):
    out = np.empty(len(col), dtype=object)
    for i, row in enumerate(col):
        out[i] = np.roll(row, 1, axis=-1)    # every answer a position late
    return out


def _swap_outputs(col, n):
    out = np.empty(len(col), dtype=object)
    for i, row in enumerate(col):
        out[i] = row[::-1]                   # logprob and mtp_logprob swapped
    return out


@pytest.mark.parametrize("fault", [_shift_positions, _swap_outputs])
def test_an_altered_answer_is_not_correct(fault):
    cell = tiny_cell()
    res = _run(cell, with_fault(cell, fault), seconds=0.05)
    assert not check.verdict(res["compared"])
