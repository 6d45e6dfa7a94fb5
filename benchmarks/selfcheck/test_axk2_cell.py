"""The sparse cell `axk2.score` at a tiny size on the CPU, through the
harness as it stands (`spec.load_cell`, `closed_loop.run`, the cell's own
builder, reference, work file and readers). The tiny sizes are this file's
own: rows of 64 positions against a selection of 16 keys, so the selection
drops keys. Never a source of a device number."""

import dataclasses
import json
import time
import types

import numpy as np
import pytest

from benchmarks.harness import check, spec, trace
from benchmarks.selfcheck.control_on_chip_axk2 import with_variants
from benchmarks.selfcheck.planted import with_fault
from benchmarks.selfcheck.tiny import _override

CELL = "axk2.score"
RATE = "tokens_per_s"
SEED = 4294970129                    # over 32 signed bits, as the driver's are
CAP = 64
TINY_CONFIG = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 24,
               "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
               "v_head_dim": 16, "vocab_size": 64, "intermediate_size": 96,
               "index_n_heads": 4, "index_head_dim": 16, "index_topk": 16,
               "gated_norm_rank": 4, "moe_intermediate_size": 32,
               "n_routed_experts": 4, "n_routed_experts_published": 16, "n_group": 4,
               "topk_group": 2, "num_experts_per_tok": 4,
               "rope_parameters.original_max_position_embeddings": 16,
               "max_positions": CAP}
TINY_TRAFFIC = {"cap": CAP, "lengths.median": 96, "lengths.min": 8}
ROWS = 4                             # batch 1 x batches_per_call 4
# The limits are the chip's, set at widths of 7168 in bfloat16. At widths of
# 64 a bfloat16 operand weighs more: the tiny cell's builder gets limits of
# its own, and nothing else of it changes.
TINY_LIMITS = {"LOGPROB_GAP_LIMIT": 0.012, "LOGPROB_GAP_P99_LIMIT": 0.06,
               "UNSTABLE_SHARE_LIMIT": 0.6}
GENERIC = ("pad_ratio_pct.score", "host_prepare_ms.score", "ingest_queue_ms.score",
           "ingest_readback_ms.score", "host_emit_ms.score", "fusion_fallbacks.score",
           "device_idle_pct.score", "attn_ms.score", "attn_outside_kernel_ms.score",
           "lm_head_ms.score", "device_scoped_pct.score")
EXPERT = ("moe_load_max_over_mean.score", "expert_layer_ms.score",
          "expert_outside_gmm_ms.score", "expert_combine_ms.score")
NEW = ("axk2_mfu_pct.score", "axk2_step_roofline_pct.score",
       "attn_sparse_roofline_pct.score", "mla_index_roofline_pct.score",
       "attn_select_ms.score")


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
    assert cell.chips == 1 and cell.traffic_name == "token-docs-truncated-32k"
    assert cell.config_name == "a.x-k2-688b-a33b-ep16"
    bench = spec.load_json(spec.ROOT + "/BENCHMARK.json")
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(NEW)   # appended, in order
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    # nothing was taken from the other cells
    for other, readers in (("kexaone.score", 19), ("xing4.score", 20), ("bilstm.tag", 15),
                           ("resnet50.featurize", 16), ("phi4flash.score", 18)):
        assert len(spec.load_cell(other).per_layer) == readers


def test_the_traffic_is_the_issues_and_the_file_keeps_the_published_widths():
    from benchmarks.harness import token_rows

    cell = spec.load_cell(CELL)
    t, c = cell.traffic, cell.config
    assert (t["cap"], t["batches_per_call"], t["partitions"], t["trace_calls"],
            t["check_rows_per_call"], t["check_rows_last_call"]) == (32768, 4, 2, 1, 1, 1)
    n = token_rows.lengths_multiset(t["lengths"], 4, 32768)
    assert list(n) == [15193] + [32768] * 3 and n.sum() == 113497
    assert c["assumed"]["batch_size"] == 1
    reduced = {"num_hidden_layers": (5, 61), "n_routed_experts": (16, 256),
               "vocab_size": (20480, 163840)}
    assert c["reduced"] == list(reduced)
    for key, (here, published) in reduced.items():
        assert c[key] == here and c[key + "_published"] == published
    # every other number of the catalog's row under its own key
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "A.X-K2"]
    assert row["source_url"] == c["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert c[key] == value, key
    bench = spec.load_json(spec.ROOT + "/BENCHMARK.json")
    (entry,) = [e for e in bench["configs"] if e["name"] == cell.config_name]
    assert entry["source"] == row["source_url"] and entry["reduced"] == c["reduced"]


def test_work_at_the_published_widths_is_the_issues_arithmetic():
    config = spec.load_cell(CELL).config
    work = spec.bench_module("work", "axk2")
    ref = spec.bench_module("references", config["reference"])
    total = sum(int(np.prod(shape)) for _, shape, _ in ref.weight_specs(config))
    assert total == work.parameters(config) == config["parameters_on_chip"]
    assert work.parameters(config) - 91392 == 4332650496          # the issue's matrices
    assert (work.attention_macs(config), work.index_macs(config),
            work.norm_macs(config)) == (113704960, 13959168, 458752)
    assert work.flops_per_token(config, [32768]) == pytest.approx(4.66e9, rel=1e-3)
    assert work.flops_per_token(config, [15193] + [32768] * 3) \
        == pytest.approx(4.56e9, rel=1e-3)
    causal, core, past = work.pairs(config, [100, 3000])
    assert causal == 5050 + 3000 * 3001 / 2
    assert core == 5050 + 2048 * 2049 / 2 + 952 * 2048 and past == 952 * 2048
    flops, moved = work.sparse_core(config, [32768])
    assert moved / 819e9 > flops / 197e12                       # memory binds
    flops, moved = work.indexer(config, [32768])
    assert flops / 197e12 > moved / 819e9                       # compute binds


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
    assert [c.name for c in res["compared"]] == ["logprob_gap", "logprob_gap_p99",
                                                 "unstable_share"]


def test_each_new_reader_on_a_context_built_by_hand():
    from benchmarks import peaks

    cell = spec.load_cell(CELL)
    work = spec.bench_module("work", "axk2")
    lengths = [15193, 32768, 32768, 32768]
    planes = [("/device:TPU:0", [
        ("XLA Modules", [(4.0 * k, 4.0 * k + 3.6, "jit_fused(9)") for k in range(4)]),
        ("XLA Ops", [(4.0 * k, 4.0 * k + 0.6, "%mla_index.2 = s32[] custom-call()")
                     for k in range(4)]
         + [(4.0 * k + 0.6, 4.0 * k + 1.6, "%attn_mla_sparse.4") for k in range(4)]
         # a consumer that names the kernel among its operands is not the kernel
         + [(4.0 * k + 1.6, 4.0 * k + 3.6, "%fusion.1 = fusion(%attn_mla_sparse.4)")
            for k in range(4)])])]
    tokens = float(sum(lengths))
    ctx = {"trace": trace.reduce_planes(planes, 16.0), "work": tokens, "window_s": 16.0,
           "counters": {"real_tokens": tokens, "padded_positions": 4 * 32768},
           "config": cell.config, "traffic": cell.traffic,
           "device_kind": "TPU v5 lite", "peaks": peaks}
    read = {name: cell.module("layer_metrics", name).read for name in NEW[:4]}
    flops = work.flops_per_token(cell.config, lengths)
    assert read["axk2_mfu_pct.score"](ctx) == pytest.approx(
        100 * tokens / 16.0 * flops / 197e12)
    assert read["axk2_step_roofline_pct.score"](ctx) == pytest.approx(
        100 * (tokens / 4 * flops / 197e12) / 3.6)
    i_flops, _ = work.indexer(cell.config, lengths)
    assert read["mla_index_roofline_pct.score"](ctx) == pytest.approx(
        100 * (i_flops / 197e12) / 2.4)
    _, c_bytes = work.sparse_core(cell.config, lengths)
    assert read["attn_sparse_roofline_pct.score"](ctx) == pytest.approx(
        100 * (c_bytes / 819e9) / 4.0)
    assert all(0.0 < read[name](ctx) < 100.0 for name in read)
    # the parent's program has none of the kernels: nothing to read, never 0
    bare = dict(ctx, counters={}, work=0.0,
                trace=trace.reduce_planes([("/device:TPU:0", [
                    ("XLA Modules", [(0.0, 1.0, "jit_fused(9)")]),
                    ("XLA Ops", [(0.0, 1.0, "%fusion.1")])])], 1.0))
    assert read["axk2_mfu_pct.score"](bare) is None
    assert read["axk2_step_roofline_pct.score"](bare) is None
    for name in ("attn_sparse_roofline_pct.score", "mla_index_roofline_pct.score"):
        with pytest.raises(LookupError):
            read[name](bare)


def test_the_scope_readers_find_the_live_tiny_programs_parts():
    """The new scope reader, the generic and the expert ones on a plane whose
    events are named after the live tiny program's own instructions."""
    from benchmarks.harness import scopes

    cell = tiny_cell()
    subject = cell.module("builders", cell.config["builder"]).build(
        cell.config, cell.traffic, SEED, None)
    subject.warm()
    # other cells' self-checks may have left programs alive in this process
    program = [p for p in scopes.recorded() if p.module == "jit_fused"
               and any("/attn/select" in path + "/" for path in p.scopes.values())][-1]
    paths = set(program.scopes.values())
    for part in ("layer0/attn/index", "layer0/attn/select", "layer0/attn/core",
                 "layer1/attn/proj_in", "layer1/attn/proj_out", "layer0/mlp",
                 "layer2/moe/route", "layer2/moe/combine", "layer3/attn/attn_norm",
                 "embed", "head"):
        assert any(("/" + p + "/").find("/" + part + "/") >= 0 for p in paths), part
    names = sorted(program.scopes)
    events = [(0.001 * k, 0.001 * k + 0.001, "%" + n) for k, n in enumerate(names)]
    planes = [("/device:TPU:0", [
        ("XLA Modules", [(0.0, 0.001 * len(names), "jit_fused(1)")]),
        ("XLA Ops", events)])]
    ctx = {"trace": trace.reduce_planes(planes, 1.0)}
    for name in ("attn_select_ms.score", "attn_ms.score", "attn_outside_kernel_ms.score",
                 "lm_head_ms.score", "device_scoped_pct.score", "expert_layer_ms.score",
                 "expert_combine_ms.score"):
        assert cell.module("layer_metrics", name).read(ctx) > 0.0, name
    subject.free()


def test_the_control_and_every_planted_fault_are_not_correct():
    """The reference in the program's place, computed one precision below the
    configuration's or with a fault planted, through the run's own sampling,
    comparison and verdict: one window, every variant. In a tiny row the top
    1,024 keys are every key: the dense layer, not the top 16."""
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
        out[i] = np.roll(row, 1, axis=0)    # every answer a position late
    return out


@pytest.mark.parametrize("fault", [_shift_positions])
def test_an_altered_answer_is_not_correct(fault):
    cell = tiny_cell()
    res = _run(cell, with_fault(cell, fault), seconds=0.05)
    assert not check.verdict(res["compared"])
