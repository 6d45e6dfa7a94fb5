"""Pod-scale sharded execution tests (parallel/shardplan.py + wiring).

Covers:
  - candidate derivation from the stage graph: batch-dim data parallelism
    by default, feature-dim candidates only where every DeviceFn DECLARES
    its shardable dims (``DeviceFn.shard_dims``);
  - the bitwise-identity contract: no mesh / mesh-without-knob / 1-shard
    candidates all run the exact single-device path (outputs bitwise
    equal, no sharding section in fusion_stats);
  - sharded execution parity on the 8-virtual-device CPU mesh: the fused
    image chain data-sharded via the planner knob matches the unsharded
    output, with the spec recorded in fusion_stats + roofline;
  - the collective cost term: measured all-reduce/all-gather probes
    calibrate ``collective_ms``, ``choose_sharding`` stays None until BOTH
    the segment and the collectives are calibrated, serialization
    round-trips the probe points;
  - the Tuner knob: ``sharding`` proposed/journaled/applied like every
    other knob, with one-step rollback on an injected measurement
    regression (FaultInjector TUNER_MEASURE seam) restoring the unsharded
    path bitwise;
  - mesh-aware supervision: shard-group quarantine on wedge/failure
    (ReplicaSupervisor.set_shard_groups), MeshSupervision re-planning onto
    the surviving submesh with output parity, and the ``mesh.chip_wedge``
    chaos point degrading the sharded path to the host fallback — never to
    a wrong answer;
  - the persistent compile cache's mesh fingerprint: a sharded ``.mmlc``
    executable can never warm-load onto a different mesh shape.
"""

import os

import numpy as np
import pytest

import jax

from mmlspark_tpu.core import faults
from mmlspark_tpu.core.costmodel import SegmentCostModel, bucket_of_shape
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.device_stage import CompileCache
from mmlspark_tpu.core.fusion import FusedPipelineModel
from mmlspark_tpu.core.pipeline import PipelineModel
from mmlspark_tpu.core.schema import ImageSchema
from mmlspark_tpu.core.tune import KnobSet, Tuner
from mmlspark_tpu.image.featurizer import ImageFeaturizer
from mmlspark_tpu.image.stages import ImageTransformer
from mmlspark_tpu.models.module import (Conv2D, Dense, FunctionModel,
                                        GlobalAvgPool, Sequential, relu)
from mmlspark_tpu.parallel import shardplan
from mmlspark_tpu.parallel.ingest import BatchTiming
from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
from mmlspark_tpu.serving.supervisor import (HEALTHY, QUARANTINED,
                                             ReplicaSupervisor)

PEAKS = {"flops": 1e9, "bytes_per_s": 1e9, "peak_source": "test"}


def _timing(compute_ms=2.0, rows=8, padded=8):
    return BatchTiming(compute_s=compute_ms / 1e3, h2d_s=5e-4, rows=rows,
                       padded_rows=padded)


def _make_chain(rows=24, partitions=2, seed=0, size=16, batch=8,
                min_obs=2):
    """Tiny fused image chain (ImageTransformer -> CNN featurizer): the
    same flagship shape the bench measures, scaled down for test speed.
    Returns (fused, cost model, df)."""
    mod = Sequential([("conv", Conv2D(4, (3, 3))), ("act", relu()),
                      ("pool", GlobalAvgPool()), ("head", Dense(4))],
                     name="shardcnn")
    params, _ = mod.init(jax.random.PRNGKey(seed), (size, size, 3))
    backbone = FunctionModel(mod, params, (size, size, 3),
                             layer_names=["head", "pool"], name="shardcnn")
    rng = np.random.default_rng(seed)
    obj = np.empty(rows, dtype=object)
    for i in range(rows):
        obj[i] = ImageSchema.make(
            rng.integers(0, 256, (20, 20, 3), dtype=np.uint8), f"img{i}")
    df = DataFrame.from_dict({"image": obj}, num_partitions=partitions)
    pm = PipelineModel([
        ImageTransformer().resize(size, size),
        ImageFeaturizer(scaleFactor=1 / 255., batchSize=batch)
        .set_model(backbone)])
    model = SegmentCostModel(peaks=PEAKS, min_obs=min_obs)
    fused = FusedPipelineModel(pm.stages, cache=CompileCache(),
                               cost_model=model)
    return fused, model, df


def _segment(fused):
    """The single fused Segment node of a just-transformed chain."""
    return next(n for n in fused._last_plan if hasattr(n, "dfns"))


def _features(out):
    return np.stack([np.asarray(v) for v in out.column("features")])


class _FakeDfn:
    def __init__(self, in_cols, out_cols, shard_dims=None):
        self.in_cols = tuple(in_cols)
        self.out_cols = tuple(out_cols)
        self.shard_dims = shard_dims


class _FakeSegment:
    label = "Fake"

    def __init__(self, dfns, external):
        self.dfns = list(dfns)
        self.external_in_cols = list(external)


# -- candidate derivation ----------------------------------------------------


class TestCandidates:
    def test_data_candidate_by_default(self, mesh8):
        seg = _FakeSegment([_FakeDfn(["x"], ["y"])], ["x"])
        cands = shardplan.candidates(seg, mesh8)
        assert [c.name for c in cands] == [shardplan.SPEC_DATA]
        c = cands[0]
        assert c.axis == "data" and c.shards == 8
        assert dict(c.in_dims) == {"x": 0} and c.out_dim == 0
        assert c.collective == "all_gather"

    def test_one_device_mesh_has_no_candidates(self):
        mesh1 = make_mesh(MeshSpec(data=1),
                          device_list=jax.devices()[:1])
        seg = _FakeSegment([_FakeDfn(["x"], ["y"])], ["x"])
        assert shardplan.candidates(seg, mesh1) == []
        assert shardplan.sharding_for(seg, mesh1, "data") is None

    def test_feature_candidate_requires_declarations(self):
        mesh = make_mesh(MeshSpec(data=4, tensor=2))
        undeclared = _FakeSegment([_FakeDfn(["x"], ["y"])], ["x"])
        names = [c.name for c in shardplan.candidates(undeclared, mesh)]
        assert names == [shardplan.SPEC_DATA]
        declared = _FakeSegment(
            [_FakeDfn(["x"], ["y"], shard_dims={"x": 1}),
             _FakeDfn(["y"], ["z"])],  # internal input: no declaration
            ["x"])
        cands = {c.name: c for c in shardplan.candidates(declared, mesh)}
        assert set(cands) == {shardplan.SPEC_DATA, shardplan.SPEC_FEATURE}
        feat = cands[shardplan.SPEC_FEATURE]
        assert feat.axis == "tensor" and feat.shards == 2
        assert dict(feat.in_dims) == {"x": 1} and feat.out_dim is None
        assert feat.collective == "all_reduce"

    def test_sharding_for_none_paths(self, mesh8):
        seg = _FakeSegment([_FakeDfn(["x"], ["y"])], ["x"])
        assert shardplan.sharding_for(seg, None, "data") is None
        assert shardplan.sharding_for(seg, mesh8, "") is None
        assert shardplan.sharding_for(seg, mesh8, None) is None
        assert shardplan.sharding_for(seg, mesh8, "feature") is None

    def test_real_segment_derives_data_candidate(self, mesh8):
        fused, _, df = _make_chain()
        fused.transform(df)
        seg = _segment(fused)
        cands = shardplan.candidates(seg, mesh8)
        assert [c.name for c in cands] == [shardplan.SPEC_DATA]
        tc = shardplan.tuner_candidates(seg, mesh8)
        assert tc == [{"name": "data", "shards": 8, "op": "all_gather",
                       "collective_bytes": 0.0}]


# -- SegmentSharding keys / donation -----------------------------------------


class TestSegmentSharding:
    def _sharding(self, mesh8):
        seg = _FakeSegment([_FakeDfn(["x"], ["y"])], ["x"])
        sh = shardplan.sharding_for(seg, mesh8, "data")
        assert sh is not None
        return sh

    def test_cache_key_and_shape_prefix(self, mesh8):
        sh = self._sharding(mesh8)
        assert sh.cache_key() == ("spec", "data", "data", 8)
        prefix = sh.shape_prefix()
        assert prefix == "spec=data8;"
        # a sharded cost record must never fold into the single-device
        # analytic table: the prefixed shape key parses as no bucket
        assert bucket_of_shape(prefix + "f32[16,24,24,3]") is None

    def test_donation_gated_off_on_cpu(self, mesh8, monkeypatch):
        monkeypatch.delenv("MMLSPARK_SHARD_DONATE", raising=False)
        sh = self._sharding(mesh8)
        assert shardplan.donation_supported(mesh8) is False
        assert "donate_argnums" not in sh.jit_kwargs()
        monkeypatch.setenv("MMLSPARK_SHARD_DONATE", "1")
        assert shardplan.donation_supported(mesh8) is True
        assert sh.jit_kwargs()["donate_argnums"] == (1,)

    def test_jit_kwargs_mega_shape(self, mesh8):
        sh = self._sharding(mesh8)
        kw = sh.jit_kwargs(mega_k=3)
        params_sh, cols = kw["in_shardings"]
        assert isinstance(cols, tuple) and len(cols) == 3
        assert all(set(c) == {"x"} for c in cols)

    def test_mesh_topology_strings(self, mesh8):
        assert shardplan.mesh_topology(None) == "none"
        topo = shardplan.mesh_topology(mesh8)
        assert topo.startswith("data=8,") and ";kind=" in topo


# -- collective probes + cost model ------------------------------------------


class TestCollectiveModel:
    def test_fit_and_predict(self):
        m = SegmentCostModel(peaks=PEAKS)
        assert m.collective_ms("all_gather", 1024) is None
        assert m.collective_calibrated() is False
        m.observe_collective("all_gather", 1024, 1e-6)
        m.observe_collective("all_gather", 4096, 4e-6)
        assert m.collective_calibrated("all_gather") is True
        ms = m.collective_ms("all_gather", 2048)
        assert ms == pytest.approx(2e-3, rel=0.2)

    def test_measure_collectives_feeds_model(self, mesh8):
        m = SegmentCostModel(peaks=PEAKS)
        recs = shardplan.measure_collectives(
            mesh8, sizes=(1 << 12, 1 << 14), repeats=1, model=m)
        assert {r["op"] for r in recs} == {"all_reduce", "all_gather"}
        assert all(r["seconds"] >= 0 for r in recs)
        assert m.collective_calibrated() is True
        assert m.collective_ms("all_reduce", 1 << 13) is not None

    def test_serialization_roundtrips_collectives(self):
        m = SegmentCostModel(peaks=PEAKS)
        m.observe_collective("all_reduce", 1024, 1e-6)
        m.observe_collective("all_reduce", 2048, 2e-6)
        m2 = SegmentCostModel.from_dict(m.to_dict())
        assert m2.collective_calibrated("all_reduce") is True
        assert m2.collective_ms("all_reduce", 2048) == \
            pytest.approx(m.collective_ms("all_reduce", 2048))

    def test_choose_sharding_uncalibrated_is_none(self):
        cands = [{"name": "data", "shards": 8, "op": "all_gather",
                  "collective_bytes": 0.0}]
        m = SegmentCostModel(peaks=PEAKS, min_obs=2)
        assert m.choose_sharding("Seg", 16, cands) is None  # nothing
        for b in (2, 16):
            for _ in range(3):
                m.observe_batch("Seg", _timing(compute_ms=0.25 * b,
                                               rows=b, padded=b))
        # segment calibrated, collectives not: still None (cold-start
        # bitwise contract — an unpriced collective must not look free)
        assert m.collective_calibrated() is False
        assert m.choose_sharding("Seg", 16, cands) is None

    def test_choose_sharding_picks_cheaper_candidate(self):
        m = SegmentCostModel(peaks=PEAKS, min_obs=2)
        for b in (2, 16):
            for _ in range(3):
                m.observe_batch("Seg", _timing(compute_ms=0.25 * b,
                                               rows=b, padded=b))
        m.observe_collective("all_gather", 1024, 1e-8)
        m.observe_collective("all_gather", 4096, 4e-8)
        cands = [{"name": "data", "shards": 8, "op": "all_gather",
                  "collective_bytes": 1024.0}]
        # sharded: predict at ceil(16/8)=2 rows (~0.5ms) + ~1e-5ms
        # collective, vs ~4ms unsharded — a clear winner
        assert m.choose_sharding("Seg", 16, cands) == "data"
        # an unpriced op (no probes) keeps the candidate unviable
        bad = [{"name": "data", "shards": 8, "op": "all_reduce",
                "collective_bytes": 1024.0}]
        assert m.predict_sharded_ms("Seg", 16, 8, collective_bytes=1024.0,
                                    op="all_reduce") is None
        assert m.choose_sharding("Seg", 16, bad) is None


# -- execution parity --------------------------------------------------------


class TestExecutionParity:
    def test_mesh_only_is_bitwise_identical(self, mesh8):
        fused, _, df = _make_chain()
        want = _features(fused.transform(df))
        fused.set_mesh(mesh8)  # mesh set, knob never tuned: unsharded
        got = _features(fused.transform(df))
        assert np.array_equal(want, got)
        assert "sharding" not in fused.fusion_stats()

    def test_sharded_transform_parity(self, mesh8):
        fused, _, df = _make_chain(rows=23, partitions=2)
        want = _features(fused.transform(df))
        label = _segment(fused).label
        fused.set_mesh(mesh8)
        fused.set_tuning(sharding={label: "data"})
        got = _features(fused.transform(df))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        stats = fused.fusion_stats()
        assert stats["fallbacks"] == []
        seg = stats["sharding"]["segments"][label]
        assert seg["spec"] == "data" and seg["shards"] == 8
        assert stats["sharding"]["mesh"].startswith("data=8,")
        roof = stats["roofline"][label]
        assert roof["spec"] == "data" and roof["shards"] == 8
        # this container's device is not in the peaks table: the sharded
        # record carries no bound either (scaling is in TestPerf below)
        assert roof["peak_source"] == "unknown"

    def test_knob_cleared_restores_bitwise_path(self, mesh8):
        fused, _, df = _make_chain()
        want = _features(fused.transform(df))
        label = _segment(fused).label
        fused.set_mesh(mesh8)
        fused.set_tuning(sharding={label: "data"})
        fused.transform(df)
        fused.set_tuning(sharding={label: ""})  # cleared: back to PR 13
        got = _features(fused.transform(df))
        assert np.array_equal(want, got)

    def test_odd_buckets_pad_to_shard_multiple(self, mesh8):
        # an 11-row bucket is not divisible by 8 shards: the executor must
        # round the pad target up to a shard multiple and still match
        fused, _, df = _make_chain(rows=22, partitions=2)
        want = _features(fused.transform(df))
        label = _segment(fused).label
        fused.set_mesh(mesh8)
        fused.set_tuning(buckets={label: [11]},
                         sharding={label: "data"})
        got = _features(fused.transform(df))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        assert fused.fusion_stats()["fallbacks"] == []

    def test_chip_wedge_injection_falls_back_correct(self, mesh8):
        fused, _, df = _make_chain()
        want = _features(fused.transform(df))
        label = _segment(fused).label
        fused.set_mesh(mesh8)
        fused.set_tuning(sharding={label: "data"})
        with faults.FaultInjector(seed=11).plan(
                faults.MESH_CHIP_WEDGE, every=1,
                exc=RuntimeError("chip wedged")):
            got = _features(fused.transform(df))
        # a wedged chip degrades the partition to the host path — the
        # answer stays right and the fallback is accounted
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        fb = fused.fusion_stats()["fallbacks"]
        assert fb and any("mesh stage failure" in f for f in fb)


# -- Tuner knob + rollback ---------------------------------------------------


class _ForcedSpecModel(SegmentCostModel):
    """Cost model that always proposes data sharding for calibrated
    segments — pins the Tuner-side plumbing under test (the real
    choose_sharding decision surface has its own tests above)."""

    def choose_sharding(self, segment, batch, candidates, margin=0.95):
        for cand in candidates:
            if cand["name"] == "data":
                return "data"
        return None


def _calibrated_tuner(mesh8, rows=24):
    fused, _, df = _make_chain(rows=rows)
    fused.transform(df)
    label = _segment(fused).label
    fused.set_mesh(mesh8)
    model = _ForcedSpecModel(peaks=PEAKS, min_obs=2)
    for _ in range(3):
        model.observe_batch(label, _timing(compute_ms=2.0, rows=8,
                                           padded=8))
    return fused, model, df, label


class TestTunerKnob:
    def test_propose_carries_sharding_knob(self, mesh8):
        fused, model, df, label = _calibrated_tuner(mesh8)
        t = Tuner(fused=fused, model=model)
        knobs = t.propose()
        assert knobs.sharding == {label: "data"}
        assert not knobs.is_default()
        d = knobs.to_dict()
        assert d["sharding"] == {label: "data"}
        assert KnobSet.from_dict(d).sharding == {label: "data"}

    def test_apply_reaches_fused_and_journals(self, mesh8):
        fused, model, df, label = _calibrated_tuner(mesh8)
        t = Tuner(fused=fused, model=model)
        result = t.tune(lambda: 100.0, steps=1, warmup=0)
        assert result["rollbacks"] == 0
        assert fused._sharding_overrides == {label: "data"}
        applied = [e for e in t.journal if e["action"] == "apply"]
        assert applied and \
            applied[-1]["knobs"]["sharding"] == {label: "data"}
        # the applied knob executes sharded — and correctly
        want = _features(fused.transform(df))
        fused.set_tuning(sharding={})
        np.testing.assert_allclose(_features(fused.transform(df)), want,
                                   rtol=1e-5, atol=1e-6)

    def test_rollback_on_injected_regression_unshards(self, mesh8):
        fused, model, df, label = _calibrated_tuner(mesh8)
        want = _features(fused.transform(df))
        t = Tuner(fused=fused, model=model, tolerance=0.05)
        with faults.FaultInjector(seed=3).plan(
                faults.TUNER_MEASURE, at=(2,), delay_s=0.2, exc=None):
            result = t.tune(lambda: 100.0, steps=3, warmup=0)
        assert t.rollbacks == 1
        assert result["steps"][1]["accepted"] is False
        assert KnobSet.from_dict(result["final_knobs"]).is_default()
        assert any(e["action"].startswith("rollback") for e in t.journal)
        # rollback cleared the sharding override: bitwise PR 13 path again
        assert fused._sharding_overrides == {}
        assert np.array_equal(_features(fused.transform(df)), want)


# -- mesh-aware supervision --------------------------------------------------


class TestShardGroupQuarantine:
    def test_wedge_quarantines_whole_group(self):
        sup = ReplicaSupervisor(4, quarantine_s=60.0)
        sup.set_shard_groups([[0, 1], [2, 3]])
        sup.note_wedged(0)
        rows = {r["replica"]: r for r in sup.describe()}
        assert rows[0]["state"] == QUARANTINED
        assert rows[0]["last_reason"] == "wedged"
        assert rows[1]["state"] == QUARANTINED
        assert rows[1]["last_reason"] == "shard_group:wedged"
        assert rows[2]["state"] == HEALTHY
        assert rows[3]["state"] == HEALTHY

    def test_failure_cascade_quarantines_group(self):
        sup = ReplicaSupervisor(4, max_failures=1, quarantine_s=60.0)
        sup.set_shard_groups([[0, 1, 2]])
        sup.note_failure(1, reason="boom")
        rows = {r["replica"]: r for r in sup.describe()}
        assert rows[1]["state"] == QUARANTINED
        assert rows[0]["last_reason"] == "shard_group:boom"
        assert rows[2]["last_reason"] == "shard_group:boom"
        assert rows[3]["state"] == HEALTHY

    def test_cleared_groups_restore_per_replica(self):
        sup = ReplicaSupervisor(2, quarantine_s=60.0)
        sup.set_shard_groups([[0, 1]])
        sup.set_shard_groups(())
        assert sup.shard_group(0) == (0,)
        sup.note_wedged(0)
        rows = {r["replica"]: r for r in sup.describe()}
        assert rows[0]["state"] == QUARANTINED
        assert rows[1]["state"] == HEALTHY


class TestMeshSupervision:
    def test_groups_follow_data_axis(self, mesh8):
        groups = shardplan.shard_groups(mesh8)
        assert groups == [[i] for i in range(8)]
        mesh = make_mesh(MeshSpec(data=4, tensor=2))
        groups = shardplan.shard_groups(mesh)
        assert len(groups) == 4 and all(len(g) == 2 for g in groups)
        flat = sorted(i for g in groups for i in g)
        assert flat == list(range(8))
        some = groups[1][0]
        assert shardplan.group_of(mesh, some) == groups[1]
        with pytest.raises(ValueError):
            shardplan.group_of(mesh, 99)

    def test_submesh_excluding(self, mesh8):
        devs = list(np.asarray(mesh8.devices).flat)
        sub = shardplan.submesh_excluding(mesh8, devs[:2])
        assert dict(sub.shape)["data"] == 6
        assert shardplan.submesh_excluding(mesh8, devs) is None

    def test_on_wedge_replans_and_stays_correct(self, mesh8):
        fused, _, df = _make_chain()
        want = _features(fused.transform(df))
        label = _segment(fused).label
        sup = ReplicaSupervisor(8, quarantine_s=60.0)
        ms = shardplan.MeshSupervision(fused, mesh8, supervisor=sup)
        assert fused.shard_mesh is mesh8
        fused.set_tuning(sharding={label: "data"})
        np.testing.assert_allclose(_features(fused.transform(df)), want,
                                   rtol=1e-5, atol=1e-6)
        sub = ms.on_wedge(0)
        assert dict(sub.shape)["data"] == 7
        assert ms.replans == 1 and fused.shard_mesh is sub
        rows = {r["replica"]: r for r in sup.describe()}
        assert rows[0]["state"] == QUARANTINED
        # re-planned onto the submesh: still sharded, still right
        got = _features(fused.transform(df))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        seg = fused.fusion_stats()["sharding"]["segments"][label]
        assert seg["shards"] == 7
        # idempotent per group: a second wedge of the same chip is a no-op
        assert ms.on_wedge(0) is sub
        assert ms.replans == 1
        assert ms.describe()["failed_devices"] == 1


# -- persistent cache fingerprint --------------------------------------------


class TestMeshFingerprint:
    def test_fingerprint_carries_topology(self, mesh8):
        from mmlspark_tpu.serving.fleet.cache import env_fingerprint

        fp = env_fingerprint(mesh=mesh8)
        assert fp["mesh"].startswith("data=8,")
        assert env_fingerprint()["mesh"] == "none"

    def test_mesh_mismatch_is_a_clean_miss(self, mesh8, tmp_path):
        from mmlspark_tpu.serving.fleet.cache import (PersistentCompileCache,
                                                      content_key)

        sharded = PersistentCompileCache(str(tmp_path), mesh=mesh8)
        single = PersistentCompileCache(str(tmp_path))
        key = ("seg", "f32[16,24,24,3]")
        # different digests: a sharded executable and a single-device one
        # can never collide in the store...
        assert content_key(key, sharded._fp) != content_key(key, single._fp)
        # ...so whatever the sharded process stored, the single-device
        # process misses cleanly (recompile, never a wrong-mesh warm load)
        sharded.store(key, lambda x: x, cost={"flops": 1.0}, label="seg")
        assert single.load(key, label="seg") is None
        assert single.misses == 1 and single.load_errors == 0
        sub = make_mesh(MeshSpec(data=4),
                        device_list=list(np.asarray(
                            mesh8.devices).flat)[:4])
        other = PersistentCompileCache(str(tmp_path), mesh=sub)
        assert other.load(key, label="seg") is None
        assert other.misses == 1


# -- roofline / metrics labels -----------------------------------------------


class TestShardedAttribution:
    PER_SEG = {"seg": {"n_batches": 2, "rows": 32, "wall_s": 0.2,
                       "queue_s": 0.01, "h2d_s": 0.12, "compute_s": 0.02,
                       "dispatch_s": 0.001, "readback_s": 0.002}}
    COSTS = {"seg": {"spec=data8;f32[16]": {
        "flops": 1e6, "bytes_accessed": 2e6, "output_bytes": 4096.0}}}

    def test_sharded_bound_scales_and_attributes_collective(self):
        from mmlspark_tpu.obs import perf

        m = SegmentCostModel(peaks=PEAKS)
        m.observe_collective("all_gather", 1024, 1e-6)
        m.observe_collective("all_gather", 4096, 4e-6)
        shard = {"seg": {"spec": "data", "shards": 8,
                         "collective": "all_gather"}}
        out = perf.attribute_segments(self.PER_SEG, self.COSTS,
                                      peaks=PEAKS, sharding=shard,
                                      cost_model=m)
        rec = out["seg"]
        assert rec["spec"] == "data" and rec["shards"] == 8
        assert rec["peak_source"] == "testx8"
        # bound = max(1e6, 2e6) / (1e9 * 8) = 0.25ms (vs 2ms single-chip)
        assert rec["bound_ms_per_batch"] == pytest.approx(0.25)
        assert rec["collective_ms_per_batch"] == \
            pytest.approx(m.collective_ms("all_gather", 4096.0), rel=1e-6)

    def test_unsharded_report_byte_identical(self):
        from mmlspark_tpu.obs import perf

        base = perf.attribute_segments(self.PER_SEG, self.COSTS,
                                       peaks=PEAKS)
        off = perf.attribute_segments(self.PER_SEG, self.COSTS,
                                      peaks=PEAKS, sharding=None,
                                      cost_model=SegmentCostModel())
        assert base == off
        assert "spec" not in base["seg"]
        assert base["seg"]["bound_ms_per_batch"] == pytest.approx(2.0)

    def test_segment_families_carry_spec_labels(self):
        from mmlspark_tpu.obs import perf

        fusion = {"roofline": {
            "sharded": {"roofline_ratio": 0.5, "bottleneck": "compute",
                        "spec": "data", "shards": 8,
                        "collective_ms_per_batch": 0.01},
            "plain": {"roofline_ratio": 0.4, "bottleneck": "h2d"}}}
        fams = {f.name: f for f in perf.segment_families(fusion)}
        ratio = fams["mmlspark_segment_roofline_ratio"]
        by_seg = {s.labels["segment"]: s.labels
                  for s in ratio.samples}
        assert by_seg["sharded"]["sharded"] == "1"
        assert by_seg["sharded"]["spec"] == "data"
        assert "sharded" not in by_seg["plain"]
        coll = fams["mmlspark_segment_collective_ms_per_batch"]
        assert coll.samples and \
            coll.samples[0].labels["segment"] == "sharded"

    def test_device_peaks_scaling(self, monkeypatch):
        from mmlspark_tpu.obs import perf

        one = {**perf.peaks_for_kind("TPU v5 lite"), "peak_source": "table"}
        four = perf._scale_peaks(one, 4)
        assert four["flops"] == pytest.approx(one["flops"] * 4)
        assert four["bytes_per_s"] == pytest.approx(one["bytes_per_s"] * 4)
        assert four["peak_source"] == f"{one['peak_source']}x4"
        assert four["data_shards"] == 4
        assert "data_shards" not in one
