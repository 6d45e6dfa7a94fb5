"""Cost-model auto-tuning tests (core/costmodel.py + core/tune.py + wiring).

Covers:
  - the SegmentCostModel: analytical roofline prediction from harvested
    costs, measured EWMA refinement, interpolation, confidence/calibration
    gates, serialization round-trip;
  - degradation paths: cost_analysis absent (not an executable) leaves
    the model analytical-free but measured-capable; an UNCALIBRATED model
    produces bitwise-identical plans, bucket sequences, and fused outputs
    (the cold-start contract);
  - knob decisions: choose_buckets kills measured pad-waste (None until
    calibrated), fuse_decision compares predicted device vs measured host;
  - the bounded CompileCache: LRU eviction + eviction counter + costs()
    consistency under eviction;
  - padding-waste stats through IngestStats + the
    mmlspark_batch_pad_ratio{bucket=} gauge;
  - AdaptiveBatchController knob exposure + model seeding, and the
    executor's live set_inflight;
  - the Tuner: measure->refit->apply loop, journaled decisions, one-step
    rollback on an injected regression (FaultInjector TUNER_MEASURE seam),
    serving integration (serve_pipeline(autotune=True): tuner section in
    /_mmlspark/stats, mmlspark_tuner_* families, replies bitwise-identical
    to a static server while uncalibrated).
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

import jax

from mmlspark_tpu.core import faults
from mmlspark_tpu.core.costmodel import SegmentCostModel, bucket_of_shape
from mmlspark_tpu.core.device_stage import CompileCache
from mmlspark_tpu.core.fusion import FusedPipelineModel, Segment, plan
from mmlspark_tpu.core.tune import KnobSet, Tuner
from mmlspark_tpu.parallel.ingest import BatchTiming, IngestStats

PEAKS = {"flops": 1e9, "bytes_per_s": 1e9, "peak_source": "test"}


def timing(compute_ms=2.0, h2d_ms=0.5, rows=8, padded=8, **kw):
    return BatchTiming(compute_s=compute_ms / 1e3, h2d_s=h2d_ms / 1e3,
                       rows=rows, padded_rows=padded, **kw)


def calibrated_model(segment="Seg", buckets=(8, 16), min_obs=2,
                     ms_per_row=0.25):
    """A model with trusted measured records at the given buckets."""
    m = SegmentCostModel(peaks=PEAKS, min_obs=min_obs)
    for b in buckets:
        for _ in range(min_obs + 1):
            m.observe_batch(segment, timing(compute_ms=ms_per_row * b,
                                            rows=b, padded=b))
    return m


# -- cost model --------------------------------------------------------------


class TestSegmentCostModel:
    def test_bucket_of_shape(self):
        assert bucket_of_shape("img=64x32x32x3:uint8;x=64x4:float32") == 64
        assert bucket_of_shape("a=8:float32") == 8
        assert bucket_of_shape("garbage") is None

    def test_analytical_prediction_from_costs(self):
        m = SegmentCostModel(peaks=PEAKS)
        m.ingest_costs({"Seg": {"x=16x4:float32": {
            "flops": 2e6, "bytes_accessed": 1e6, "compile_s": 0.1}}})
        pred = m.predict("Seg", batch=16)
        # roofline: max(2e6/1e9, 1e6/1e9) s = 2 ms
        assert pred["source"] == "analytic"
        assert pred["ms"] == pytest.approx(2.0)
        assert 0 < pred["confidence"] < 0.5
        assert not m.calibrated("Seg")

    def test_measured_refinement_beats_analytic(self):
        m = SegmentCostModel(peaks=PEAKS, min_obs=2)
        m.ingest_costs({"Seg": {"x=8x4:float32": {"flops": 1e3}}})
        for _ in range(3):
            m.observe_batch("Seg", timing(compute_ms=4.0, rows=8, padded=8))
        pred = m.predict("Seg", batch=8)
        assert pred["source"] == "measured"
        assert pred["ms"] == pytest.approx(4.5, rel=0.01)  # + h2d 0.5
        assert m.calibrated("Seg")
        assert m.confidence("Seg") >= 0.5

    def test_interpolation_between_buckets(self):
        m = calibrated_model(buckets=(8, 16), ms_per_row=0.25)
        p8 = m.predict("Seg", batch=8)["ms"]
        p16 = m.predict("Seg", batch=16)["ms"]
        p12 = m.predict("Seg", batch=12)
        assert p12["source"] == "interpolated"
        assert min(p8, p16) <= p12["ms"] <= max(p8, p16)

    def test_unknown_segment_predicts_none(self):
        m = SegmentCostModel(peaks=PEAKS)
        assert m.predict_ms("Nope", batch=8) is None
        assert m.confidence("Nope") == 0.0

    def test_serialization_round_trip(self):
        m = calibrated_model()
        m.ingest_costs({"Seg": {"x=8x4:float32": {
            "flops": 1e6, "compile_s": 0.2}}})
        m.observe_host("StageA", 0.004, 8)
        m2 = SegmentCostModel.from_dict(m.to_dict(), peaks=PEAKS)
        assert m2.calibrated("Seg")
        assert m2.predict("Seg", batch=8)["ms"] == \
            pytest.approx(m.predict("Seg", batch=8)["ms"])
        assert m2.host_ms_per_row("StageA") == m.host_ms_per_row("StageA")
        assert m2.choose_buckets("Seg", 16) == m.choose_buckets("Seg", 16)

    def test_choose_buckets_requires_calibration(self):
        m = SegmentCostModel(peaks=PEAKS)
        m.ingest_costs({"Seg": {"x=16x4:float32": {"flops": 1e6}}})
        assert m.choose_buckets("Seg", 16) is None

    def test_choose_buckets_kills_pad_waste(self):
        # every observed batch has 11 real rows padded to 16: the chosen
        # set must contain a bucket that fits 11 exactly (cost at 11 <
        # cost at 16 by interpolation/extrapolation)
        m = SegmentCostModel(peaks=PEAKS, min_obs=2)
        for b, ms in ((8, 2.0), (16, 4.0)):
            for _ in range(3):
                m.observe_batch("Seg", timing(compute_ms=ms, rows=11 if
                                              b == 16 else b, padded=b))
        chosen = m.choose_buckets("Seg", 16)
        assert chosen is not None
        assert any(11 <= c < 16 for c in chosen)
        assert chosen[-1] == 16  # cap always present

    def test_fuse_decision_needs_both_sides(self):
        m = calibrated_model(segment="A+B")
        assert m.fuse_decision("A+B") is None  # no host measurements
        for _ in range(4):
            m.observe_host("A", 0.004, 8)   # 0.5 ms/row
            m.observe_host("B", 0.004, 8)
        # device: 0.25 ms/row + h2d ~0.0625 < host 1.0 ms/row -> fuse
        assert m.fuse_decision("A+B") is True
        slow = calibrated_model(segment="A+B", ms_per_row=3.0)
        for _ in range(4):
            slow.observe_host("A", 0.0004, 8)
            slow.observe_host("B", 0.0004, 8)
        assert slow.fuse_decision("A+B") is False

    def test_prediction_error_table(self):
        m = calibrated_model(buckets=(8,))
        m.ingest_costs({"Seg": {"x=8x4:float32": {
            "flops": 1e6, "bytes_accessed": 1e6}}})
        err = m.prediction_error()
        rec = err["Seg"]["8"]
        assert rec["analytic_ms"] == pytest.approx(1.0)
        assert rec["measured_ms"] == pytest.approx(2.5, rel=0.01)
        assert rec["error_ratio"] == pytest.approx(2.5, rel=0.01)


# -- degradation paths -------------------------------------------------------


class _NoCost:
    """Compiled-executable stand-in without cost_analysis."""

    def __call__(self, *a):
        return a


class TestDegradation:
    def test_cost_absent_still_measures(self):
        cache = CompileCache()
        cache.get(("k1",), lambda: _NoCost(), label="Seg", shape="x=8:f32")
        m = SegmentCostModel(peaks=PEAKS, min_obs=2)
        m.ingest_costs(cache.costs())  # only compile_s present — no crash
        assert m.predict("Seg", batch=8) is None  # compile_s alone is no
        # roofline bound, but measured data still calibrates the model
        for _ in range(3):
            m.observe_batch("Seg", timing())
        assert m.predict("Seg", batch=8)["source"] == "measured"

    def test_uncalibrated_model_plans_identically(self, small_chain):
        fused, _, df = small_chain
        nodes_default = plan(fused.stages, df.schema.copy())
        nodes_model = plan(fused.stages, df.schema.copy(),
                           cost_model=SegmentCostModel(peaks=PEAKS))
        assert [type(n).__name__ for n in nodes_default] == \
            [type(n).__name__ for n in nodes_model]
        assert [n.label for n in nodes_default] == \
            [n.label for n in nodes_model]

    def test_uncalibrated_model_bitwise_outputs_and_buckets(
            self, small_chain):
        fused, model, df = small_chain
        plain = FusedPipelineModel(fused.stages, cache=CompileCache())
        out_plain = plain.transform(df).collect()
        out_model = fused.transform(df).collect()
        assert set(out_plain) == set(out_model)
        for col in out_plain:
            for a, b in zip(out_plain[col], out_model[col]):
                av, bv = np.asarray(a), np.asarray(b)
                if av.dtype == object or bv.dtype == object:
                    continue  # image structs compared via feature cols
                assert av.dtype == bv.dtype
                assert np.array_equal(av, bv)
        # identical bucket sequence: same padding histogram per segment
        pads_plain = {k: s.summary().get("padding")
                      for k, s in plain._seg_stats.items()}
        pads_model = {k: s.summary().get("padding")
                      for k, s in fused._seg_stats.items()}
        assert pads_plain == pads_model

    def test_fuse_decision_exception_falls_back(self, small_chain):
        fused, _, df = small_chain

        class Broken:
            def fuse_decision(self, label):
                raise RuntimeError("boom")

        nodes = plan(fused.stages, df.schema.copy(), cost_model=Broken())
        assert [type(n).__name__ for n in nodes] == \
            [type(n).__name__
             for n in plan(fused.stages, df.schema.copy())]


# -- chain fixture -----------------------------------------------------------


@pytest.fixture(scope="module")
def chain_parts():
    from mmlspark_tpu.core.pipeline import PipelineModel
    from mmlspark_tpu.core.schema import ImageSchema
    from mmlspark_tpu.image.featurizer import ImageFeaturizer
    from mmlspark_tpu.image.stages import ImageTransformer
    from mmlspark_tpu.models.module import (Dense, FunctionModel,
                                            GlobalAvgPool, Sequential)

    size = 12
    mod = Sequential([("pool", GlobalAvgPool()), ("head", Dense(3))],
                     name="tinycnn")
    params, _ = mod.init(jax.random.PRNGKey(0), (size, size, 3))
    backbone = FunctionModel(mod, params, (size, size, 3),
                             layer_names=["head", "pool"], name="tinycnn")
    pm = PipelineModel([
        ImageTransformer().resize(size, size).flip(1),
        ImageFeaturizer(scaleFactor=1 / 255., batchSize=16)
        .set_model(backbone)])

    def make_df(rows=22, parts=2, seed=0):
        rng = np.random.default_rng(seed)
        obj = np.empty(rows, dtype=object)
        for i in range(rows):
            obj[i] = ImageSchema.make(
                rng.integers(0, 256, (16, 16, 3), dtype=np.uint8),
                f"img{i}")
        from mmlspark_tpu.core.dataframe import DataFrame

        return DataFrame.from_dict({"image": obj}, num_partitions=parts)

    return pm, make_df


@pytest.fixture()
def small_chain(chain_parts):
    """(fused model with attached cost model, the model, a 2x11-row df).

    ``compile_horizon`` is pinned high so the bucket chooser's compile-
    amortization charge (measured compile seconds on a LOADED ci host can
    exceed the tiny chain's pad-waste saving — a correct trade-off, but a
    nondeterministic one) never vetoes the pad-waste decision under test."""
    pm, make_df = chain_parts
    model = SegmentCostModel(peaks=PEAKS, min_obs=2,
                             compile_horizon=100_000)
    fused = FusedPipelineModel(pm.stages, cache=CompileCache(),
                               cost_model=model)
    return fused, model, make_df()


# -- CompileCache LRU --------------------------------------------------------


class TestCompileCacheLRU:
    def test_capacity_bound_and_eviction_counter(self):
        cache = CompileCache(capacity=2)
        for i in range(4):
            cache.get((i,), lambda i=i: f"exe{i}", label="S",
                      shape=f"x={i}:f32")
        s = cache.stats()
        assert s["entries"] == 2
        assert s["capacity"] == 2
        assert s["evictions"] == 2

    def test_lru_order_hit_refreshes(self):
        cache = CompileCache(capacity=2)
        cache.get(("a",), lambda: "A")
        cache.get(("b",), lambda: "B")
        cache.get(("a",), lambda: "A2")     # hit refreshes "a"
        cache.get(("c",), lambda: "C")      # evicts LRU = "b"
        assert cache.get(("a",), lambda: "NEW") == "A"   # still cached
        assert cache.get(("b",), lambda: "REBUILT") == "REBUILT"

    def test_costs_dropped_with_evicted_entry(self):
        cache = CompileCache(capacity=1)
        cache.get(("a",), lambda: "A", label="S", shape="x=8:f32")
        assert "x=8:f32" in cache.costs()["S"]
        cache.get(("b",), lambda: "B", label="S", shape="x=16:f32")
        costs = cache.costs()
        assert list(costs["S"]) == ["x=16:f32"]
        assert cache.stats()["evictions"] == 1

    def test_set_capacity_shrinks(self):
        cache = CompileCache(capacity=8)
        for i in range(5):
            cache.get((i,), lambda i=i: i)
        cache.set_capacity(2)
        assert cache.entries == 2
        assert cache.stats()["evictions"] == 3
        with pytest.raises(ValueError):
            cache.set_capacity(0)

    def test_clear_resets_eviction_counter(self):
        cache = CompileCache(capacity=1)
        cache.get(("a",), lambda: "A")
        cache.get(("b",), lambda: "B")
        assert cache.stats()["evictions"] == 1
        cache.clear()
        assert cache.stats()["evictions"] == 0


# -- padding stats -----------------------------------------------------------


class TestPadStats:
    def test_summary_padding_section(self):
        st = IngestStats()
        st.record(timing(rows=11, padded=16))
        st.record(timing(rows=16, padded=16))
        st.record(timing(rows=3, padded=8))
        s = st.summary()
        assert s["padding"]["16"] == {
            "batches": 2, "rows": 27, "padded": 32,
            "pad_ratio": pytest.approx(1 - 27 / 32, abs=1e-4)}
        assert s["pad_ratio"] == pytest.approx(1 - 30 / 40, abs=1e-4)

    def test_merge_folds_padding(self):
        a, b = IngestStats(), IngestStats()
        a.record(timing(rows=4, padded=8))
        b.record(timing(rows=6, padded=8))
        a.merge(b)
        assert a.summary()["padding"]["8"]["rows"] == 10

    def test_unpadded_batches_report_nothing(self):
        st = IngestStats()
        st.record(BatchTiming(rows=5))
        assert "padding" not in st.summary()

    def test_minibatcher_buckets_and_stats(self):
        from mmlspark_tpu.parallel.batching import Minibatcher

        st = IngestStats()
        mb = Minibatcher(batch_size=16, buckets=(11, 16), stats=st)
        part = {"x": np.arange(22, dtype=np.float32).reshape(22, 1)}
        sizes = [b.size for b in mb.batches(part, ["x"])]
        assert sizes == [16, 11]  # short batch lands on the tuned bucket
        assert st.summary()["padding"]["11"]["rows"] == 6

    def test_bridge_pad_ratio_gauge(self):
        from mmlspark_tpu.obs.bridge import _ingest_families

        st = IngestStats()
        st.record(timing(rows=11, padded=16))
        fams = {f.name: f for f in _ingest_families(st.summary())}
        fam = fams["mmlspark_batch_pad_ratio"]
        assert fam.samples[0].labels == {"bucket": "16"}
        assert fam.samples[0].value == pytest.approx(1 - 11 / 16)
        assert "mmlspark_batch_pad_rows_total" in fams


# -- controller + executor knobs ---------------------------------------------


class TestControllerKnobs:
    def test_state_exposes_knobs(self):
        from mmlspark_tpu.serving.executor import AdaptiveBatchController

        c = AdaptiveBatchController(alpha=0.3, min_wait_ms=1.0,
                                    max_wait_ms=20.0)
        s = c.state()
        assert s["alpha"] == 0.3
        assert s["min_wait_ms"] == 1.0
        assert s["max_wait_ms"] == 20.0
        assert s["seeded"] is False

    def test_seed_compute_ms(self):
        from mmlspark_tpu.serving.executor import AdaptiveBatchController

        c = AdaptiveBatchController(alpha=0.5, max_wait_ms=50.0)
        c.seed_compute_ms(8.0)
        s = c.state()
        assert s["seeded"] is True
        assert s["compute_ewma_ms"] == pytest.approx(8.0)
        # a later measurement blends instead of being overwritten
        c.observe(0.004, 0.0, 4, 0)
        assert 4.0 < c.state()["compute_ewma_ms"] < 8.0

    def test_server_controller_knobs_plumbed(self):
        from mmlspark_tpu.serving import ServingServer

        srv = ServingServer(lambda df: df, port=0, async_exec=True,
                            batch_alpha=0.25, batch_min_wait_ms=0.5,
                            batch_max_wait_ms=12.0)
        with srv:
            state = srv._controller.state()
            assert state["alpha"] == 0.25
            assert state["min_wait_ms"] == 0.5
            assert state["max_wait_ms"] == 12.0
            status, _, body, _ = srv._handle_control(
                "/_mmlspark/stats", b"", {})
            stats = json.loads(body)
            assert stats["async"]["controller"]["alpha"] == 0.25

    def test_set_inflight_grow_and_shrink(self):
        from mmlspark_tpu.serving.executor import (PipelinedExecutor,
                                                   ReplicaSet)

        class FakeServer:
            name = "t"
            _stop = threading.Event()
            _wake = threading.Event()

        ex = PipelinedExecutor(FakeServer(), ReplicaSet(lambda df: df),
                               inflight=2)
        # grow: +2 permits immediately available
        ex.set_inflight(4)
        assert ex.inflight == 4
        got = [ex._slots.acquire(blocking=False) for _ in range(4)]
        assert all(got)
        assert not ex._slots.acquire(blocking=False)
        # shrink while all 4 are held: releases are consumed, not returned
        ex.set_inflight(2)
        ex._release_slot()
        ex._release_slot()
        assert not ex._slots.acquire(blocking=False)
        ex._release_slot()  # third release: shrink debt paid, permit real
        assert ex._slots.acquire(blocking=False)


# -- Tuner -------------------------------------------------------------------


class _FakeFused:
    """Minimal FusedPipelineModel stand-in for Tuner unit tests."""

    def __init__(self, label="Seg", batch_size=16):
        self._cache = CompileCache()
        self._seg_stats = {}
        self.applied = []

        class Node:
            def __init__(self, lab, bs):
                self.label = lab
                self._bs = bs

            def batch_size(self):
                return self._bs

        self._last_plan = [Node(label, batch_size)]

    def set_tuning(self, buckets=None, fuse=None, cost_model=None):
        self.applied.append({"buckets": dict(buckets or {}),
                             "fuse": dict(fuse or {})})


class TestTuner:
    def test_uncalibrated_proposes_default(self):
        t = Tuner(fused=_FakeFused(), model=SegmentCostModel(peaks=PEAKS))
        assert t.propose().is_default()

    def test_calibrated_proposes_knobs(self):
        model = calibrated_model(buckets=(8, 16))
        t = Tuner(fused=_FakeFused(), model=model)
        knobs = t.propose()
        assert not knobs.is_default()
        assert knobs.window_seed_ms is not None
        assert knobs.inflight is not None and knobs.inflight >= 1

    def test_tune_accepts_improvement_and_journals(self):
        model = calibrated_model()
        fused = _FakeFused()
        t = Tuner(fused=fused, model=model)
        result = t.tune(lambda: 100.0, steps=1, warmup=0)
        assert result["rollbacks"] == 0
        assert result["steps"][-1]["accepted"] is True
        assert fused.applied  # knobs reached the fused model
        actions = [e["action"] for e in t.journal]
        assert "baseline" in actions and "apply" in actions

    def test_rollback_on_injected_regression(self):
        model = calibrated_model()
        fused = _FakeFused()
        t = Tuner(fused=fused, model=model, tolerance=0.05)
        # FaultInjector arms the tuner.measure seam: the SECOND measurement
        # (post-apply) stalls, reading as a >5% e2e regression
        with faults.FaultInjector(seed=3).plan(
                faults.TUNER_MEASURE, at=(2,), delay_s=0.2, exc=None):
            result = t.tune(lambda: 100.0, steps=3, warmup=0)
        assert result["steps"][1]["accepted"] is False
        assert t.rollbacks == 1
        assert len(result["steps"]) == 2  # loop stopped at the rollback
        # knobs rolled back to the pre-apply (default) set
        assert KnobSet.from_dict(result["final_knobs"]).is_default()
        assert any(e["action"].startswith("rollback") for e in t.journal)

    def test_stats_and_serialization(self):
        model = calibrated_model()
        t = Tuner(fused=_FakeFused(), model=model, every=7)
        t.tune(lambda: 50.0, steps=1, warmup=0)
        s = t.stats()
        assert s["calibrated"] is True
        assert s["applies"] >= 1
        assert s["default_knobs"] == {}
        assert "Seg" in s["model"]["confidence"]
        t2 = Tuner.from_dict(t.to_dict(), fused=_FakeFused())
        assert t2.every == 7
        assert t2.knobs.to_dict() == t.knobs.to_dict()
        assert t2.model.calibrated("Seg")

    def test_on_epoch_applies_every_n(self):
        model = calibrated_model()
        fused = _FakeFused()
        t = Tuner(fused=fused, model=model, every=3)
        for _ in range(6):
            t.on_epoch(0.002)
        assert t.epochs == 6
        assert t.applies >= 1

    def test_refit_folds_incrementally(self):
        fused = _FakeFused()
        st = IngestStats()
        fused._seg_stats["Seg"] = st
        model = SegmentCostModel(peaks=PEAKS, min_obs=2)
        t = Tuner(fused=fused, model=model)
        for _ in range(3):
            st.record(timing())
        t.refit()
        n0 = model.predict("Seg", batch=8)["observed_batches"]
        t.refit()  # same records must not double-count
        assert model.predict("Seg", batch=8)["observed_batches"] == n0


# -- end-to-end through the fused chain + serving ----------------------------


class TestAutotuneEndToEnd:
    def test_tune_removes_pad_waste_bitwise(self, small_chain):
        fused, model, df = small_chain
        base = fused.transform(df).collect()
        fused.transform(df)
        tuner = Tuner(fused=fused, model=model)
        tuner.refit()
        assert model.calibrated()
        knobs = tuner.propose()
        label = next(iter(fused._seg_stats))
        assert label in knobs.buckets
        assert any(b <= 11 for b in knobs.buckets[label])
        tuner.apply(knobs)
        tuned = fused.transform(df).collect()
        feat = next(c for c in base if c != "image")
        for a, b in zip(base[feat], tuned[feat]):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        pad = fused._seg_stats[label].summary()["padding"]
        assert all(rec["pad_ratio"] == 0.0 for rec in pad.values())
        assert fused.fusion_stats()["tuning"]["buckets"][label] == \
            list(knobs.buckets[label])

    def test_serving_autotune_stats_and_metrics(self, chain_parts):
        pm, make_df = chain_parts
        from mmlspark_tpu.serving import ServingServer

        model = SegmentCostModel(peaks=PEAKS, min_obs=2)
        fused = FusedPipelineModel(pm.stages, cache=CompileCache(),
                                   cost_model=model)
        tuner = Tuner(fused=fused, model=model, every=2)

        def transform(df):
            return df.with_column("reply", lambda p: [int(len(p["id"]))]
                                  * len(p["id"]))

        srv = ServingServer(transform, port=0, max_wait_ms=0.0,
                            tuner=tuner)
        with srv:
            for _ in range(5):
                req = urllib.request.Request(srv.address, data=b"{}",
                                             method="POST")
                with urllib.request.urlopen(req, timeout=10) as r:
                    r.read()
            status, _, body, _ = srv._handle_control(
                "/_mmlspark/stats", b"", {})
            stats = json.loads(body)
            assert "tuner" in stats
            assert stats["tuner"]["epochs"] >= 5
            status, _, body, _ = srv._handle_control(
                "/_mmlspark/metrics", b"", {})
            text = body.decode()
            assert "mmlspark_tuner_epochs_total" in text
            assert "mmlspark_tuner_calibrated" in text

    def test_serving_replies_bitwise_identical_uncalibrated(
            self, chain_parts):
        # acceptance: with an UNCALIBRATED model, serving replies match a
        # static server byte-for-byte over the same request sequence
        pm, make_df = chain_parts
        import base64

        from mmlspark_tpu.core.schema import ImageSchema
        from mmlspark_tpu.serving import serve_pipeline
        from mmlspark_tpu.stages import UDFTransformer

        rng = np.random.default_rng(5)
        bodies = [json.dumps({"img_b64": base64.b64encode(
            rng.integers(0, 256, (16, 16, 3), dtype=np.uint8).tobytes())
            .decode("ascii")}).encode() for _ in range(4)]

        def make_server(autotune):
            def decode_rows(col):
                out = np.empty(len(col), dtype=object)
                for i, v in enumerate(col):
                    raw = np.frombuffer(
                        base64.b64decode(v["img_b64"]),
                        dtype=np.uint8).reshape(16, 16, 3)
                    out[i] = ImageSchema.make(raw, f"r{i}")
                return out

            decode = UDFTransformer(inputCol="data", outputCol="image",
                                    vectorizedUdf=decode_rows)
            fused = FusedPipelineModel(
                pm.stages, cache=CompileCache(),
                cost_model=SegmentCostModel(peaks=PEAKS, min_obs=2))
            in_cols = {"data", "image", "id", "value", "headers",
                       "origin"}

            class Chain:
                def transform(self, df):
                    out = fused.transform(decode.transform(df))
                    feat = next(c for c in out.schema.names
                                if c not in in_cols)
                    return out.with_column(
                        "reply",
                        lambda p, _c=feat: [np.asarray(v).tolist()
                                            for v in p[_c]])

                def set_tuning(self, **kw):
                    fused.set_tuning(**kw)

                cost_model = property(lambda self: fused.cost_model)
                _seg_stats = property(lambda self: fused._seg_stats)
                _cache = property(lambda self: fused._cache)
                _last_plan = property(lambda self: fused._last_plan)

                def fusion_stats(self):
                    return fused.fusion_stats()

                def has_param(self, name):
                    return False

            # tune_every high: the tuner never fires during the sequence,
            # so the model stays uncalibrated = knobs stay default
            return serve_pipeline(Chain(), "data", parse="json", port=0,
                                  max_wait_ms=0.0, autotune=autotune,
                                  tune_every=10_000)

        def collect(server):
            replies = []
            with server:
                for body in bodies:
                    req = urllib.request.Request(server.address, data=body,
                                                 method="POST")
                    with urllib.request.urlopen(req, timeout=30) as r:
                        replies.append((r.status, r.read()))
            return replies

        assert collect(make_server(False)) == collect(make_server(True))
