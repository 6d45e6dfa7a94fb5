"""Pipeline-parallel mesh execution tests (parallel/pipeplan.py + wiring).

Covers:
  - the pipeline view: ``split_segments`` re-cuts a fused chain at clean
    d2d boundaries into chainable sub-segments (host stages, single-stage
    and stitched segments pass through), and ``chainable``/
    ``chainable_runs`` enforce the handoff contract;
  - plan derivation: disjoint pipe-axis sub-meshes preserving non-pipe
    axes, predict_ms-balanced contiguous stage grouping (equal-count
    while uncalibrated), and ``build_pipe_plan``'s serial-stay gates;
  - the cost model's pipelined clock: ``predict_pipelined_ms`` /
    ``choose_pipe_depth`` calibration gates (None while cold — plans
    from an uncalibrated model are bitwise-identical to serial);
  - the bitwise contract: knob off / pipe_depth=1 / no pipe axis all run
    the exact serial path (no ``pipeline`` stats key, byte-identical
    metrics exposition), and the pipelined stream over a forced
    4-device ``pipe=2`` mesh matches the serial fused chain BITWISE;
  - the Tuner's journaled ``pipe_depth`` knob with one-step rollback
    restoring the serial path bitwise;
  - stage quarantine: ``set_pipe_stages``/``note_stage_wedged`` eject a
    wedged stage's whole sub-mesh, and the ``pipe.stage_wedge`` chaos
    point drives a depth N-1 re-plan that drops no in-flight request;
  - the fleet cache fingerprint: a pipelined executable can never
    warm-load onto a different pipe layout (clean counted miss), while
    non-pipe fingerprints stay byte-identical.
"""

import os

import numpy as np
import pytest

import jax

from mmlspark_tpu.core import faults
from mmlspark_tpu.core.costmodel import SegmentCostModel
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.device_stage import CompileCache
from mmlspark_tpu.core.fusion import FusedPipelineModel, HostStage
from mmlspark_tpu.core.pipeline import PipelineModel
from mmlspark_tpu.core.schema import ImageSchema
from mmlspark_tpu.core.tune import KnobSet, Tuner
from mmlspark_tpu.image.featurizer import ImageFeaturizer
from mmlspark_tpu.image.stages import ImageTransformer
from mmlspark_tpu.models.dnn_model import DNNModel
from mmlspark_tpu.models.module import (Conv2D, Dense, FunctionModel,
                                        GlobalAvgPool, Sequential, relu)
from mmlspark_tpu.obs.bridge import _fusion_families
from mmlspark_tpu.parallel import pipeplan
from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
from mmlspark_tpu.parallel.pipeplan import (PipeStageSharding,
                                            PipeSupervision, balance_stages,
                                            build_pipe_plan, chainable,
                                            chainable_runs, pipe_submeshes,
                                            split_segments)
from mmlspark_tpu.serving.fleet.cache import (PersistentCompileCache,
                                              content_key, env_fingerprint)
from mmlspark_tpu.serving.supervisor import (HEALTHY, QUARANTINED,
                                             ReplicaSupervisor)

#: seeded chaos lane (docs/faults.md): MMLSPARK_CHAOS_SEED replays the
#: -m faults classes under a different but deterministic fault schedule
CHAOS_SEED = int(os.environ.get("MMLSPARK_CHAOS_SEED", "0"))

PEAKS = {"flops": 1e9, "bytes_per_s": 1e9, "peak_source": "test"}


def _make_chain(rows=16, partitions=2, deep=False):
    """Fused image chain (ImageTransformer -> CNN featurizer -> DNN head
    [-> second DNN head with ``deep=True``]): splits at the d2d
    boundaries into 2 (3 with ``deep``) chainable sub-segments.
    Returns (fused model, DataFrame)."""
    size = 16
    mod = Sequential([("conv", Conv2D(4, (3, 3))), ("act", relu()),
                      ("pool", GlobalAvgPool()), ("head", Dense(4))],
                     name="pipecnn")
    params, _ = mod.init(jax.random.PRNGKey(0), (size, size, 3))
    backbone = FunctionModel(mod, params, (size, size, 3),
                             layer_names=["head", "pool"], name="pipecnn")
    head = Sequential([("d1", Dense(8)), ("a", relu()), ("d2", Dense(3))],
                      name="pipehead")
    hp, _ = head.init(jax.random.PRNGKey(1), (4,))
    dnn = DNNModel(inputCol="features", outputCol="emb", batchSize=8)
    dnn.set_model(FunctionModel(head, hp, (4,), name="pipehead"))
    stages = [ImageTransformer().resize(size, size),
              ImageFeaturizer(scaleFactor=1 / 255., batchSize=8)
              .set_model(backbone), dnn]
    if deep:
        head2 = Sequential([("d3", Dense(5))], name="pipehead2")
        hp2, _ = head2.init(jax.random.PRNGKey(2), (3,))
        dnn2 = DNNModel(inputCol="emb", outputCol="emb2", batchSize=8)
        dnn2.set_model(FunctionModel(head2, hp2, (3,), name="pipehead2"))
        stages.append(dnn2)
    rng = np.random.default_rng(4)
    obj = np.empty(rows, dtype=object)
    for i in range(rows):
        obj[i] = ImageSchema.make(
            rng.integers(0, 256, (20, 20, 3), dtype=np.uint8), f"img{i}")
    df = DataFrame.from_dict({"image": obj}, num_partitions=partitions)
    pm = PipelineModel(stages)
    return FusedPipelineModel(pm.stages, cache=CompileCache()), df


def _col(out, name="emb"):
    return np.stack([np.asarray(v) for v in out.column(name)])


def _pipe_mesh(n=4, pipe=2):
    return make_mesh(MeshSpec(data=n // pipe, pipe=pipe),
                     device_list=jax.devices()[:n])


def _pipe_metric_lines(fused):
    return [f.name for f in _fusion_families(fused.fusion_stats())
            if f.name.startswith("mmlspark_pipe_")]


# -- the pipeline view + handoff contract ------------------------------------


class TestSplitAndChainable:
    def test_fused_chain_splits_at_d2d_boundaries(self):
        fused, df = _make_chain()
        fused.transform(df)
        nodes = fused._last_plan
        assert [type(n).__name__ for n in nodes] == ["Segment"]
        view = split_segments(nodes)
        assert [n.label for n in view] == [
            "ImageTransformer+ImageFeaturizer", "DNNModel"]
        assert chainable(view[0], view[1])
        runs = chainable_runs(view)
        assert len(runs) == 1 and [j for j, _ in runs[0]] == [0, 1]
        # the original plan fuses everything: no runs before the re-cut
        assert chainable_runs(nodes) == []

    def test_deep_chain_splits_into_three(self):
        fused, df = _make_chain(deep=True)
        fused.transform(df)
        view = split_segments(fused._last_plan)
        assert [n.label for n in view] == [
            "ImageTransformer+ImageFeaturizer", "DNNModel", "DNNModel"]
        assert len(chainable_runs(view)[0]) == 3

    def test_host_and_single_stage_nodes_pass_through(self):
        fused, df = _make_chain()
        fused.transform(df)
        seg = fused._last_plan[0]
        host = HostStage(ImageTransformer())
        view = split_segments([host, seg])
        assert view[0] is host
        single = view[2]
        assert split_segments([single]) == [single]

    def test_prepare_headed_stage_cannot_head_a_subsegment(self):
        # ImageTransformer's DeviceFn carries a host ``prepare`` (raw
        # image staging): the cut before it is illegal, so it stays
        # glued to whatever precedes it — here the segment head
        fused, df = _make_chain()
        fused.transform(df)
        seg = fused._last_plan[0]
        assert seg.dfns[0].prepare is not None
        view = split_segments([seg])
        assert view[0].label == "ImageTransformer+ImageFeaturizer"

    def test_serial_view_is_bitwise_identical(self):
        fused, df = _make_chain()
        want = _col(fused.transform(df))
        fused2, df2 = _make_chain()
        fused2.transform(df2)  # build the plan
        # running the re-cut view serially (what a pipelined stream
        # degrades to per-partition) matches the fused chain bitwise
        view = split_segments(fused2._last_plan)
        assert len(view) == 2
        got = df2
        from mmlspark_tpu.parallel.ingest import IngestStats
        for node in view:
            got = fused2._make_executor(node).run(got, IngestStats())
        assert np.array_equal(_col(got), want)


class TestSubmeshesAndBalance:
    def test_submeshes_partition_the_pipe_axis(self):
        mesh = _pipe_mesh(4, pipe=2)
        subs = pipe_submeshes(mesh, 2)
        assert len(subs) == 2
        ids = [sorted(d.id for d in np.asarray(s.devices).flat)
               for s in subs]
        assert ids[0] and ids[1] and not (set(ids[0]) & set(ids[1]))
        assert sorted(ids[0] + ids[1]) == \
            sorted(d.id for d in np.asarray(mesh.devices).flat)
        for s in subs:
            assert dict(s.shape)["data"] == 2 and dict(s.shape)["pipe"] == 1

    def test_submeshes_none_without_pipe_axis(self):
        assert pipe_submeshes(make_mesh(
            MeshSpec(data=4), device_list=jax.devices()[:4]), 2) is None
        assert pipe_submeshes(_pipe_mesh(4, pipe=2), 1) is None
        assert pipe_submeshes(_pipe_mesh(4, pipe=2), 3) is None

    def test_balance_equal_count_while_uncalibrated(self):
        assert balance_stages([None, None, None], 2) == [2, 1]
        assert balance_stages([1.0, None], 2) == [1, 1]

    def test_balance_minimizes_the_clock(self):
        assert balance_stages([4.0, 1.0, 1.0], 2) == [1, 2]
        assert balance_stages([1.0, 1.0, 4.0], 2) == [2, 1]
        assert balance_stages([1.0] * 4, 5) == [1, 1, 1, 1]

    def test_build_pipe_plan_serial_gates(self):
        fused, df = _make_chain()
        fused.transform(df)
        nodes = fused._last_plan
        assert build_pipe_plan(nodes, None, 2) is None
        assert build_pipe_plan(
            nodes, make_mesh(MeshSpec(data=4),
                             device_list=jax.devices()[:4]), 2) is None
        assert build_pipe_plan(nodes, _pipe_mesh(), 1) is None
        pplan = build_pipe_plan(nodes, _pipe_mesh(), 2)
        assert pplan is not None and pplan.depth == 2
        assert (pplan.first, pplan.last) == (0, 2)
        assert [st.labels for st in pplan.stages] == [
            ("ImageTransformer+ImageFeaturizer",), ("DNNModel",)]
        assert pplan.nodes is not None and len(pplan.nodes) == 2

    def test_stage_cache_keys_are_disjoint(self):
        mesh = _pipe_mesh(4, pipe=2)
        subs = pipe_submeshes(mesh, 2)
        a = PipeStageSharding(None, subs[0], 0, 2)
        b = PipeStageSharding(None, subs[1], 1, 2)
        assert a.cache_key() != b.cache_key()
        assert a.shape_prefix() == "pipe=s0of2;"
        # replicated default placement: GSPMD degenerates to the original
        # program, and donation MUST stay off (the staged input is the
        # upstream stage's output buffer, still read at drain)
        kw = a.jit_kwargs()
        assert "donate_argnums" not in kw
        assert "in_shardings" in kw and "out_shardings" in kw


# -- the cost model's pipelined clock ----------------------------------------


class _Timing:
    def __init__(self, compute_ms, rows=8):
        self.queue_s = 0.0
        self.h2d_s = 1e-4
        self.dispatch_s = 1e-4
        self.compute_s = compute_ms / 1e3
        self.readback_s = 1e-4
        self.bytes_in = 1024
        self.rows = rows
        self.padded_rows = rows
        self.mega_k = 1


def _calibrated_model(labels_ms, handoff=True):
    model = SegmentCostModel(peaks=PEAKS, min_obs=2)
    for label, ms in labels_ms.items():
        for _ in range(3):
            model.observe_batch(label, _Timing(ms))
    if handoff:
        model.observe_collective(pipeplan.PIPE_HANDOFF_OP, 1024, 1e-4)
        model.observe_collective(pipeplan.PIPE_HANDOFF_OP, 4096, 2e-4)
    return model


class TestPipelinedClock:
    def test_uncalibrated_predicts_nothing(self):
        model = SegmentCostModel(peaks=PEAKS, min_obs=2)
        assert model.predict_pipelined_ms(["a", "b"], 8) is None
        assert model.choose_pipe_depth(["a", "b"], 8, 2) is None

    def test_unfitted_handoff_gates_the_prediction(self):
        model = _calibrated_model({"a": 10.0, "b": 10.0}, handoff=False)
        assert model.predict_pipelined_ms(
            ["a", "b"], 8, handoff_bytes=1024) is None
        assert model.predict_pipelined_ms(["a", "b"], 8) is not None

    def test_gpipe_clock_shape(self):
        model = _calibrated_model({"a": 10.0, "b": 10.0})
        a = model.predict_ms("a", batch=8)
        serial = 8 * (a + model.predict_ms("b", batch=8))
        piped = model.predict_pipelined_ms(["a", "b"], 8, microbatches=8)
        # (M + S - 1) * clock vs M * sum: near-2x at equal stage costs
        assert piped < serial * 0.65

    def test_choose_pipe_depth(self):
        model = _calibrated_model({"a": 10.0, "b": 10.0})
        assert model.choose_pipe_depth(["a", "b"], 8, 2) == 2
        assert model.choose_pipe_depth(["a", "b"], 8, 1) is None
        assert model.choose_pipe_depth(["a"], 8, 2) is None
        # one dominant stage: the clock never drops below it, so the
        # fill/drain overhead can't pay for itself
        skew = _calibrated_model({"a": 100.0, "b": 0.05})
        assert skew.choose_pipe_depth(["a", "b"], 8, 2) is None


# -- bitwise contract --------------------------------------------------------


class TestColdStartParity:
    def test_mesh_without_knob_stays_serial(self):
        fused, df = _make_chain()
        want = _col(fused.transform(df))
        fused2, df2 = _make_chain()
        fused2.set_mesh(_pipe_mesh())
        got = _col(fused2.transform(df2))
        stats = fused2.fusion_stats()
        assert "pipeline" not in stats
        assert _pipe_metric_lines(fused2) == []
        assert np.array_equal(want, got)

    def test_pipe_depth_one_clears_the_knob(self):
        fused, df = _make_chain()
        want = _col(fused.transform(df))
        fused2, df2 = _make_chain()
        fused2.set_mesh(_pipe_mesh())
        fused2.set_tuning(pipe_depth=2)
        fused2.set_tuning(pipe_depth=1)
        assert fused2._pipe_depth is None
        got = _col(fused2.transform(df2))
        assert "pipeline" not in fused2.fusion_stats()
        assert np.array_equal(want, got)

    def test_knob_without_pipe_axis_stays_serial(self):
        fused, df = _make_chain()
        want = _col(fused.transform(df))
        fused2, df2 = _make_chain()
        fused2.set_mesh(make_mesh(MeshSpec(data=4),
                                  device_list=jax.devices()[:4]))
        fused2.set_tuning(pipe_depth=2)
        got = _col(fused2.transform(df2))
        assert "pipeline" not in fused2.fusion_stats()
        assert np.array_equal(want, got)


class TestPipelinedParity:
    def test_pipelined_bitwise_equals_serial(self):
        fused, df = _make_chain()
        want_emb = _col(fused.transform(df))
        want_feat = _col(fused.transform(df), "features")
        fused2, df2 = _make_chain()
        fused2.set_mesh(_pipe_mesh())
        fused2.set_tuning(pipe_depth=2)
        out = fused2.transform(df2)
        assert np.array_equal(_col(out), want_emb)
        assert np.array_equal(_col(out, "features"), want_feat)
        # the resized image never came back from stage 0's sub-mesh
        assert fused2.fusion_stats()["host_emit"][
            "ImageTransformer+ImageFeaturizer"]["cols"] == ["image"]
        pipe = fused2.fusion_stats()["pipeline"]
        assert pipe["depth"] == 2 and pipe["replans"] == 0
        assert pipe["serial_fallback_partitions"] == 0
        assert pipe["micro_batches"] >= 2
        assert pipe["handoff_bytes"] > 0
        devs = [set(st["devices"]) for st in pipe["stages"]]
        assert devs[0] and devs[1] and not (devs[0] & devs[1])
        assert 0.0 < pipe["bubble_ratio"] < 1.0
        for st in pipe["stages"]:
            assert 0.0 <= st["busy_ratio"] <= 1.0

    def test_a_handed_through_column_crosses_the_handoff(self):
        # stage 0 emits ``y`` from the host rows it staged, so ``y`` is no
        # output of its program: the next stage reads the staged input
        from mmlspark_tpu.stages.basic import UDFTransformer

        class HandOn(UDFTransformer):
            def device_fn(self, schema):
                dfn = super().device_fn(schema)
                dfn.passthrough = {"y": "x"}
                return dfn

        def chain():
            head = Sequential([("d1", Dense(8)), ("a", relu()),
                               ("d2", Dense(3))], name="pipehead")
            hp, _ = head.init(jax.random.PRNGKey(1), (4,))
            dnn = DNNModel(inputCol="y", outputCol="emb", batchSize=8)
            dnn.set_model(FunctionModel(head, hp, (4,), name="pipehead"))
            hand = HandOn(inputCol="x", outputCol="y",
                          vectorizedUdf=lambda col: col,
                          deviceUdf=lambda x: x)
            return FusedPipelineModel([hand, dnn], cache=CompileCache())

        rng = np.random.default_rng(6)
        rows = np.empty(20, dtype=object)
        for i in range(20):
            rows[i] = rng.normal(size=4).astype(np.float32)
        df = DataFrame.from_dict({"x": rows}, num_partitions=2)
        want = chain().transform(df)
        fused = chain()
        fused.set_mesh(_pipe_mesh())
        fused.set_tuning(pipe_depth=2)
        got = fused.transform(df)
        stats = fused.fusion_stats()
        assert stats["pipeline"]["depth"] == 2
        assert stats["pipeline"]["serial_fallback_partitions"] == 0
        assert stats["host_emit"]["HandOn"] == {"cols": ["y"],
                                                "bytes": 20 * 4 * 4}
        for name in ("y", "emb"):
            assert np.array_equal(_col(got, name), _col(want, name))

    def test_deep_chain_three_stages(self):
        fused, df = _make_chain(deep=True)
        want = _col(fused.transform(df), "emb2")
        fused2, df2 = _make_chain(deep=True)
        fused2.set_mesh(make_mesh(MeshSpec(pipe=3),
                                  device_list=jax.devices()[:3]))
        fused2.set_tuning(pipe_depth=3)
        got = _col(fused2.transform(df2), "emb2")
        assert np.array_equal(want, got)
        pipe = fused2.fusion_stats()["pipeline"]
        assert pipe["depth"] == 3
        assert [len(st["segments"]) for st in pipe["stages"]] == [1, 1, 1]

    def test_pipe_metric_families_only_when_active(self):
        fused, df = _make_chain()
        fused.set_mesh(_pipe_mesh())
        fused.set_tuning(pipe_depth=2)
        fused.transform(df)
        names = _pipe_metric_lines(fused)
        assert names == [
            "mmlspark_pipe_depth", "mmlspark_pipe_bubble_ratio",
            "mmlspark_pipe_stage_busy_ratio",
            "mmlspark_pipe_handoff_bytes_total",
            "mmlspark_pipe_stage_requeues_total"]
        fams = {f.name: f for f in _fusion_families(fused.fusion_stats())}
        assert [s.labels.get("stage") for s in
                fams["mmlspark_pipe_stage_busy_ratio"].samples] == ["0", "1"]
        # knob back off: the families vanish with the stats key
        fused.set_tuning(pipe_depth=1)
        fused.transform(df)
        assert _pipe_metric_lines(fused) == []


# -- the Tuner knob ----------------------------------------------------------


class _ForcedDepthModel(SegmentCostModel):
    """Always proposes depth 2 — pins the Tuner-side plumbing under test
    (choose_pipe_depth's decision surface has its own tests above)."""

    def choose_pipe_depth(self, chain_labels, batch, max_depth,
                          microbatches=8, handoff_bytes=0.0,
                          op="pipe_handoff", margin=0.95):
        return 2 if max_depth >= 2 and len(chain_labels) >= 2 else None


def _depth_tuner(**tuner_kw):
    fused, df = _make_chain()
    fused.transform(df)
    fused.set_mesh(_pipe_mesh())
    model = _ForcedDepthModel(peaks=PEAKS, min_obs=2)
    return fused, Tuner(fused=fused, model=model, **tuner_kw), df


class TestTunerKnob:
    def test_knobset_round_trip(self):
        k = KnobSet(pipe_depth=2)
        assert not k.is_default()
        assert k.to_dict()["pipe_depth"] == 2
        assert KnobSet.from_dict(k.to_dict()).pipe_depth == 2
        assert KnobSet.from_dict(KnobSet().to_dict()).is_default()

    def test_propose_carries_pipe_depth(self):
        fused, t, df = _depth_tuner()
        assert t.propose().pipe_depth == 2
        # no pipe axis -> no proposal, whatever the chooser says
        fused.set_mesh(make_mesh(MeshSpec(data=4),
                                 device_list=jax.devices()[:4]))
        assert t.propose().pipe_depth is None

    def test_apply_journals_and_pipelines(self):
        fused, t, df = _depth_tuner()
        result = t.tune(lambda: 100.0, steps=1, warmup=0)
        assert result["rollbacks"] == 0
        assert fused._pipe_depth == 2
        applied = [e for e in t.journal if e["action"] == "apply"]
        assert applied and applied[-1]["knobs"]["pipe_depth"] == 2
        fused.transform(df)
        assert fused.fusion_stats()["pipeline"]["depth"] == 2

    def test_rollback_restores_serial_bitwise(self):
        fused, t, df = _depth_tuner()
        want = _col(fused.transform(df))
        t.tolerance = 0.05
        with faults.FaultInjector(seed=3).plan(
                faults.TUNER_MEASURE, at=(2,), delay_s=0.2, exc=None):
            result = t.tune(lambda: 100.0, steps=3, warmup=0)
        assert t.rollbacks >= 1
        assert result["steps"][1]["accepted"] is False
        assert any(e["action"].startswith("rollback") for e in t.journal)
        # one-step rollback: the knob cleared, the serial path is bitwise
        assert fused._pipe_depth is None
        assert np.array_equal(_col(fused.transform(df)), want)
        assert "pipeline" not in fused.fusion_stats()


# -- stage quarantine + chaos ------------------------------------------------


class TestStageQuarantine:
    def test_wedge_ejects_the_stage_submesh(self):
        sup = ReplicaSupervisor(4, quarantine_s=60.0)
        sup.set_pipe_stages([[0, 2], [1, 3]])
        assert sup.pipe_stage(1) == (1, 3)
        sup.note_stage_wedged(1)
        rows = {r["replica"]: r for r in sup.describe()}
        assert rows[1]["state"] == QUARANTINED
        assert rows[3]["state"] == QUARANTINED
        assert rows[1]["last_reason"] == "pipe_stage:1"
        assert rows[0]["state"] == HEALTHY
        assert rows[2]["state"] == HEALTHY


@pytest.mark.faults
class TestWedgeChaos:
    def test_full_wedge_degrades_to_serial_bitwise(self):
        fused, df = _make_chain()
        want = _col(fused.transform(df))
        fused2, df2 = _make_chain()
        mesh = _pipe_mesh()
        sup = ReplicaSupervisor(4, quarantine_s=60.0)
        PipeSupervision(fused2, mesh, supervisor=sup)
        fused2.set_tuning(pipe_depth=2)
        with faults.FaultInjector(seed=CHAOS_SEED).plan(
                faults.PIPE_STAGE_WEDGE, every=1,
                message="chaos: stage wedged") as inj:
            got = _col(fused2.transform(df2))
        assert inj.fired(faults.PIPE_STAGE_WEDGE)
        # depth 2 - 1 = serial on the survivors; nothing dropped
        assert np.array_equal(want, got)
        assert "pipeline" not in fused2.fusion_stats()
        sview = fused2._pipe_supervision.describe()
        assert sview["replans"] == 1 and sview["depth"] == 1
        rows = {r["replica"]: r for r in sup.describe()}
        wedged = [i for i, r in rows.items()
                  if r["state"] == QUARANTINED]
        assert len(wedged) == 2  # exactly one stage's sub-mesh
        assert all(rows[i]["last_reason"].startswith("pipe_stage:")
                   for i in wedged)

    def test_mid_stream_wedge_replans_depth_two(self):
        fused, df = _make_chain(deep=True)
        want = _col(fused.transform(df), "emb2")
        fused2, df2 = _make_chain(deep=True)
        mesh = make_mesh(MeshSpec(pipe=3), device_list=jax.devices()[:3])
        PipeSupervision(fused2, mesh)
        fused2.set_tuning(pipe_depth=3)
        with faults.FaultInjector(seed=CHAOS_SEED).plan(
                faults.PIPE_STAGE_WEDGE, at=(5,),
                message="chaos: stage wedged"):
            got = _col(fused2.transform(df2), "emb2")
        assert np.array_equal(want, got)
        # the 2 surviving devices re-plan at depth 3 - 1 = 2 and the
        # re-run pipeline (not a serial fallback) carries the replan tally
        pipe = fused2.fusion_stats()["pipeline"]
        assert pipe["depth"] == 2 and pipe["replans"] == 1
        assert sum(st["requeues"] for st in pipe["stages"]) >= 0
        assert fused2._pipe_supervision.describe()["replans"] == 1


# -- fleet cache fingerprint -------------------------------------------------


class TestPipeFingerprint:
    def test_non_pipe_fingerprint_unchanged(self):
        fp = env_fingerprint(make_mesh(MeshSpec(data=4),
                                       device_list=jax.devices()[:4]))
        assert sorted(fp) == ["backend", "devices", "format", "jax",
                              "mesh"]
        assert "pipe_submesh" not in env_fingerprint()

    def test_pipe_fingerprint_carries_submesh_shape(self):
        fp = env_fingerprint(_pipe_mesh())
        assert fp["pipe_submesh"] == \
            "data=2;fsdp=1;tensor=1;seq=1;expert=1;pipe=2"
        other = env_fingerprint(make_mesh(MeshSpec(pipe=4),
                                          device_list=jax.devices()[:4]))
        assert fp["pipe_submesh"] != other["pipe_submesh"]
        assert content_key(("seg", 8), fp) != content_key(("seg", 8), other)

    def test_warm_load_on_other_pipe_layout_is_a_counted_miss(self,
                                                              tmp_path):
        t1 = PersistentCompileCache(str(tmp_path), mesh=_pipe_mesh())
        t1.store(("seg", 8), None, cost={"compute_ms": 1.0},
                 label="seg", shape="b8")
        t2 = PersistentCompileCache(
            str(tmp_path),
            mesh=make_mesh(MeshSpec(pipe=4), device_list=jax.devices()[:4]))
        assert t2.load(("seg", 8), label="seg", shape="b8") is None
        # clean counted miss: the entry was never even found
        assert t2.misses == 1 and t2.costs_only == 0
        # same layout: the entry is found again (cost-only tier here —
        # ``costs_only`` proves the content address matched)
        t3 = PersistentCompileCache(str(tmp_path), mesh=_pipe_mesh())
        assert t3.load(("seg", 8), label="seg", shape="b8") is None
        assert t3.costs_only == 1
