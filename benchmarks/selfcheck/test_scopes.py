"""Device seconds by scope on hand-built events: self time under loops, the
program that resolves a traced module, the errors that say why a number is
not given, and the ten readers built on them."""

from collections import namedtuple

import pytest

from benchmarks.harness import scopes, spec, trace

Program = namedtuple("Program", "label module scopes")

STAGE = "DNNModel"
MAP = {
    "fusion.1": f"{STAGE}/embed",
    "attn_norm.1": f"{STAGE}/layer0/attn/attn_norm",
    "attn_full.2": f"{STAGE}/layer0/attn/core",
    "dot.3": f"{STAGE}/layer0/attn/proj_out",
    "while.9": f"{STAGE}/layer1/moe",                   # the trips' loop
    "gather.4": f"{STAGE}/layer1/moe/gather",
    "moe_gmm.5": f"{STAGE}/layer1/moe/experts",
    "silu.6": f"{STAGE}/layer1/moe/experts",
    "while.11": f"{STAGE}/layer1/moe/combine",          # a loop inside the loop
    "add.7": f"{STAGE}/layer1/moe/combine",
    "attn_mla.8": f"{STAGE}/layer1/attn/core",
    "while.42": f"{STAGE}/head",
    "fusion.627": f"{STAGE}/head",
    "copy.60": "",                                      # the compiler's own
    "while.6": f"{STAGE}/bilstm/fwd", "step.1": f"{STAGE}/bilstm/fwd",
    "rev.1": f"{STAGE}/bilstm/bwd", "while.7": f"{STAGE}/bilstm/bwd",
    "step.2": f"{STAGE}/bilstm/bwd", "concat.3": f"{STAGE}/bilstm",
}
LIVE = [Program("DNNModel", "jit_fused", MAP)]


def batch(at):
    """One run of the program from `at`, 10 s long, on the device's clock."""
    ops = [
        (0.0, 0.5, "%fusion.1 = f32[8,16]{1,0:T(8,128)} fusion(...)"),
        (0.5, 1.0, "%attn_norm.1 = f32[8] fusion(...)"),
        (1.0, 2.0, "%attn_full.2 = bf16[8]{0} custom-call(...)"),
        (2.0, 2.5, "%dot.3 = f32[8] convolution(...)"),
        (2.5, 3.0, "%copy.60 = f32[8] copy(...)"),
        # the trips' loop, 3.0-7.0: 0.2 of its own between its body's events
        (3.0, 7.0, "%while.9 = (s32[], f32[8]) while(...)"),
        (3.1, 3.6, "%gather.4 = bf16[8] fusion(...)"),
        (3.6, 4.6, "%moe_gmm.5 = bf16[8] custom-call(...)"),
        (4.6, 4.9, "%silu.6 = bf16[8] fusion(...)"),
        (4.9, 6.9, "%while.11 = (s32[], f32[8]) while(...)"),
        (5.0, 5.4, "%add.7 = f32[8] fusion(...)"),
        (5.5, 5.9, "%add.7 = f32[8] fusion(...)"),
        (7.0, 8.0, "%attn_mla.8 = bf16[8] custom-call(...)"),
        (8.0, 10.0, "%while.42 = (s32[], f32[8]) while(...)"),
        (8.1, 9.9, "%fusion.627 = f32[8] fusion(...)"),
    ]
    return [(at + s, at + e, n) for s, e, n in ops]


def traced(ops, modules, name="jit_fused(77)"):
    planes = [("/device:TPU:0", [
        ("XLA Modules", [(s, e, name) for s, e in modules]
         + [(30.0, 31.0, "jit_convert_element_type(3)")]),
        ("XLA Ops", ops + [(30.0, 31.0, "%convert.1 = f32[8] convert(...)")])])]
    return trace.reduce_planes(planes, window_s=40.0)


@pytest.fixture()
def tr():
    return traced(batch(0.0) + batch(10.0), [(0.0, 10.0), (10.0, 20.0)])


def ctx_of(tr, programs=LIVE, monkeypatch=None):
    ctx = {"trace": tr, "window_s": tr.window_s}
    if monkeypatch is not None:
        monkeypatch.setattr(scopes, "recorded", lambda: programs)
    return ctx


# -- self time -----------------------------------------------------------------

def test_a_loop_has_the_self_time_of_its_gaps_and_self_seconds_add_up(tr):
    events = tr.devices[0].ops
    timed = scopes.self_times(events)
    by_name = {}
    for (sec, _parent), ev in zip(timed, events):
        name = scopes.instruction_of(ev[2])
        by_name[name] = by_name.get(name, 0.0) + sec
    assert by_name["while.9"] == pytest.approx(2 * 0.2)     # 4.0 less 0.5+1.0+0.3+2.0
    assert by_name["while.11"] == pytest.approx(2 * 1.2)    # 2.0 less two adds of 0.4
    assert by_name["while.42"] == pytest.approx(2 * 0.2)
    assert by_name["add.7"] == pytest.approx(2 * 0.8)
    assert sum(sec for sec, _ in timed) == pytest.approx(tr.busy_s)
    assert tr.busy_s == pytest.approx(21.0)
    # the old reduction counted the loops' seconds with their bodies'
    assert sum(sec for _n, sec in tr.top_ops(100)) > tr.busy_s + 10.0


def test_an_event_knows_the_event_that_holds_it(tr):
    events = tr.devices[0].ops
    timed = scopes.self_times(events)
    holder = {scopes.instruction_of(events[i][2]):
              scopes.instruction_of(events[p][2]) if p >= 0 else None
              for i, (_s, p) in enumerate(timed)}
    assert holder["add.7"] == "while.11" and holder["while.11"] == "while.9"
    assert holder["moe_gmm.5"] == "while.9" and holder["attn_mla.8"] is None


def test_events_that_overlap_without_nesting_still_add_up_to_busy():
    ops = [(0.0, 5.0, "%a.1"), (3.0, 8.0, "%b.2"), (9.0, 10.0, "%c.3")]
    timed = scopes.self_times(ops)
    assert [sec for sec, _ in timed] == pytest.approx([3.0, 5.0, 1.0])
    assert sum(sec for sec, _ in timed) == pytest.approx(
        trace.total(trace.merge((s, e) for s, e, _ in ops)))


# -- which program, which scope --------------------------------------------------

def test_rows_carry_path_instruction_and_whether_in_a_loop(tr):
    rows = scopes.rows_of(tr, LIVE)
    got = {(r.path, r.instruction): (r.nested, r.seconds) for r in rows}
    assert got[(f"{STAGE}/layer1/moe/experts", "moe_gmm.5")] == (True, pytest.approx(2.0))
    assert got[(f"{STAGE}/layer0/attn/core", "attn_full.2")] == (False, pytest.approx(2.0))
    assert got[("", "copy.60")] == (False, pytest.approx(1.0))
    assert got[(scopes.OTHER, "convert.1")] == (False, pytest.approx(1.0))
    assert sum(r.seconds for r in rows) == pytest.approx(tr.busy_s)


def test_an_instruction_the_map_lacks_is_an_error_that_names_it(tr):
    lacking = [Program("DNNModel", "jit_fused",
                       {k: v for k, v in MAP.items() if k != "moe_gmm.5"})]
    with pytest.raises(LookupError, match=r"'moe_gmm\.5' of jit_fused\(77\)"):
        scopes.rows_of(tr, lacking)
    with pytest.raises(LookupError, match="no live program's HLO module is named"):
        scopes.rows_of(tr, [Program("DNNModel", "jit_other", MAP)])


def test_two_programs_under_one_name_are_told_apart_by_what_resolves(tr):
    warm_up = Program("DNNModel", "jit_fused", {"fusion.1": f"{STAGE}/embed"})
    rows = scopes.rows_of(tr, [warm_up, LIVE[0]])
    assert sum(r.seconds for r in rows if r.path.startswith(STAGE)) \
        == pytest.approx(19.0)
    # a second bucket of the same program, every name alike: no ambiguity
    scopes.rows_of(tr, [LIVE[0], Program("DNNModel", "jit_fused", dict(MAP))])
    other = dict(MAP, **{"dot.3": f"{STAGE}/layer0/mlp"})
    with pytest.raises(LookupError, match="ambiguous"):
        scopes.rows_of(tr, [LIVE[0], Program("DNNModel", "jit_fused", other)])


def test_an_executable_from_another_trees_cache_is_an_error_and_no_zero(tr):
    bare = [Program("DNNModel", "jit_fused", {k: "" for k in MAP})]
    with pytest.raises(LookupError, match="compile cache that another tree filled"):
        scopes.rows_of(tr, bare)


# -- the readers -------------------------------------------------------------------

READINGS = {          # two batches of `batch()`
    "expert_layer_ms.score": 4000.0,             # while.9 and all it holds
    "expert_outside_gmm_ms.score": 3000.0,       # less moe_gmm.5
    "attn_ms.score": (0.5 + 1.0 + 0.5 + 1.0) * 1e3 / 2,    # two layers have one
    "attn_outside_kernel_ms.score": (0.5 + 0.5) * 1e3 / 2,
    "lm_head_ms.score": 2000.0,
    "device_scoped_pct.score": 100.0 * 19.0 / 21.0,
    "device_scoped_pct.tag": 100.0 * 19.0 / 21.0,
    "device_scoped_pct.featurize": 100.0 * 19.0 / 21.0,
}


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_each_reader_returns_the_hand_computed_value(metric, tr, monkeypatch):
    reader = spec.bench_module("layer_metrics", metric)
    assert reader.read(ctx_of(tr, LIVE, monkeypatch)) == pytest.approx(READINGS[metric])


def test_the_loop_readers_split_a_direction_at_its_loop(monkeypatch):
    ops = [(0.0, 1.0, "%fusion.1"),
           (1.0, 4.0, "%while.6"), (1.1, 2.0, "%step.1"), (2.0, 3.9, "%step.1"),
           (4.0, 5.0, "%rev.1"),
           (5.0, 8.0, "%while.7"), (5.0, 7.8, "%step.2"),
           (8.0, 8.5, "%rev.1"), (8.5, 9.0, "%concat.3")]
    ctx = ctx_of(traced(ops, [(0.0, 9.0)]), LIVE, monkeypatch)
    loop = spec.bench_module("layer_metrics", "lstm_loop_ms.tag").read(ctx)
    around = spec.bench_module("layer_metrics", "lstm_around_loop_ms.tag").read(ctx)
    assert loop == pytest.approx(6000.0)         # both loops, their own gaps too
    assert around == pytest.approx(2000.0)       # two reverses and the join


@pytest.mark.parametrize("metric", sorted(READINGS) + ["lstm_loop_ms.tag",
                                                       "lstm_around_loop_ms.tag"])
def test_each_reader_reads_nothing_from_a_commit_without_scopes(metric, tr, monkeypatch):
    from mmlspark_tpu.obs import scopes as program

    monkeypatch.delattr(program, "programs")       # as on a commit before it
    assert scopes.recorded() is None
    assert spec.bench_module("layer_metrics", metric).read(ctx_of(tr)) is None


def test_a_part_no_event_lies_under_is_nothing_to_read(tr, monkeypatch):
    with pytest.raises(LookupError, match="no device event under"):
        spec.bench_module("layer_metrics", "lstm_loop_ms.tag").read(
            ctx_of(tr, LIVE, monkeypatch))


def test_the_table_is_printed_once_and_sums_to_busy(tr, monkeypatch, capsys):
    ctx = ctx_of(tr, LIVE, monkeypatch)
    scopes.by_scope(ctx)
    scopes.by_scope(ctx)
    err = capsys.readouterr().err
    assert err.count("device seconds by scope") == 1
    assert "busy 21.000000 s, scopes sum to 21.000000 s" in err
    assert f"{STAGE}/layer1/moe" in err and scopes.NO_SCOPE in err and scopes.OTHER in err
    paths = scopes.by_scope(ctx)
    assert scopes.under(paths, r"(^|/)layer\d+/moe(/|$)") == pytest.approx(8.0)
    assert sum(paths.values()) == pytest.approx(tr.busy_s)


def test_the_error_is_kept_and_every_reader_of_the_run_raises_it(tr, monkeypatch):
    asked = []
    monkeypatch.setattr(scopes, "recorded", lambda: asked.append(1) or [])
    ctx = ctx_of(tr)
    for _ in range(3):
        with pytest.raises(LookupError):
            scopes.of(ctx)
    assert len(asked) == 1


def test_every_new_reader_is_an_entry_of_the_benchmark():
    bench = spec.load_json(spec.ROOT + "/BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for metric in list(READINGS) + ["lstm_loop_ms.tag", "lstm_around_loop_ms.tag"]:
        assert entries[metric]["source"] == "device_trace"
        cells = {"score": ["kexaone.score", "xing4.score"], "tag": ["bilstm.tag"],
                 "featurize": ["resnet50.featurize"]}[metric.rsplit(".", 1)[1]]
        assert entries[metric]["workloads"] == cells
    for metric in ("ingest_fill_ms.tag", "ingest_h2d_ms.tag",
                   "call_outside_transform_pct.tag"):
        twin = entries[metric.replace(".tag", ".featurize")]
        assert entries[metric]["workloads"] == ["bilstm.tag"]
        assert {k: entries[metric][k] for k in ("unit", "better", "source", "layer")} \
            == {k: twin[k] for k in ("unit", "better", "source", "layer")}
        assert spec.bench_module("layer_metrics", metric).read \
            is not None
    # the clock join does not fit every run of the token cell (PERF.md section 7)
    assert not {"device_idle_named_pct.tag", "trace_clock_slack_ms.tag"} & set(entries)


def test_the_live_programs_map_is_what_is_read():
    """A tiny fused call on the CPU: the events are named after the live
    program's own instructions, and the readers find them under its stage."""
    import numpy as np

    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.core.pipeline import PipelineModel
    from mmlspark_tpu.models import attention
    from mmlspark_tpu.models.dnn_model import DNNModel
    from mmlspark_tpu.obs import scopes as program

    ids = np.random.default_rng(0).integers(1, 64, (8, 16), dtype=np.int32)
    col = np.empty(8, dtype=object)
    for i in range(8):
        col[i] = ids[i]
    model = attention.bilstm_tagger(16, 64, 8, 12, 5)
    fused = PipelineModel([DNNModel(inputCol="tokens", outputCol="tags", batchSize=4)
                           .set_model(model)]).fuse()
    fused.transform(DataFrame.from_dict({"tokens": col}, num_partitions=1))
    mine = max((p for p in program.programs() if p.module == "jit_fused"),
               key=lambda p: sum("bilstm" in v for v in p.scopes.values()))
    names = [n for n, path in mine.scopes.items() if path] \
        + [n for n, path in mine.scopes.items() if not path][:3]
    ops = [(0.1 * k, 0.1 * k + 0.1, f"%{n} = f32[4] fusion(...)")
           for k, n in enumerate(names)]
    tr = traced(ops, [(0.0, 0.1 * len(ops))], "jit_fused(5)")
    ctx = {"trace": tr, "window_s": 40.0}
    paths = scopes.by_scope(ctx)
    assert any(p.startswith("DNNModel/bilstm/fwd") for p in paths)
    pct = spec.bench_module("layer_metrics", "device_scoped_pct.tag").read(ctx)
    assert pct == pytest.approx(100.0 * (len(ops) - 3) * 0.1 / (len(ops) * 0.1 + 1.0))
