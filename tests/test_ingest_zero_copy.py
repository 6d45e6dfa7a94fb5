"""Single-copy ingress-to-device: deposit staging, spanning views, mega-K.

Covers the three coordinated pieces of the slot-staging path:

  - ``deposit_frame`` / ``decode_frame(out=...)``: wire payloads land in
    caller-provided staging buffers; hostile frames (truncated, misaligned
    dtype/shape, read-only or non-contiguous destinations) raise
    ``FrameError`` BEFORE any slot byte is written.
  - ``rows_to_batch``: the strided-view fast path across rows of ONE frame
    and across rows spanning MULTIPLE pipelined frames of one connection
    buffer; zero-copy vs copied batches are counted in ``IngestStats``.
  - slot deposit through the fused executor: bitwise parity against the
    allocating path across wire x fused x async-exec modes, and the
    deposits/copies counters that make "exactly one host copy" auditable.
  - AOT mega-dispatch: K>1 parity, K=1 uncalibrated bitwise identity, the
    Tuner's journaled/rollback-able K knob, and the serving watchdog's
    K-scaled budget.
"""

import json
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.fusion import CompileCache, FusedPipelineModel
from mmlspark_tpu.core.pipeline import PipelineModel
from mmlspark_tpu.core.schema import ImageSchema
from mmlspark_tpu.io.binary import (FRAME_CONTENT_TYPE, FrameError,
                                    decode_frame, deposit_frame,
                                    encode_frame)
from mmlspark_tpu.parallel.ingest import IngestStats, SlotPool, rows_to_batch

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _post(address, body, headers=None, timeout=15):
    req = urllib.request.Request(address, data=body, method="POST",
                                 headers=dict(headers or {}))
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _image_chain():
    """The flagship image chain (ImageTransformer -> tiny CNN featurizer)."""
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
    return PipelineModel([
        ImageTransformer().resize(size, size).flip(1),
        ImageFeaturizer(scaleFactor=1 / 255., batchSize=16)
        .set_model(backbone)])


def _image_df(rows=22, parts=2, seed=0):
    rng = np.random.default_rng(seed)
    obj = np.empty(rows, dtype=object)
    for i in range(rows):
        obj[i] = ImageSchema.make(
            rng.integers(0, 256, (16, 16, 3), dtype=np.uint8), f"img{i}")
    return DataFrame.from_dict({"image": obj}, num_partitions=parts)


def _feature_matrix(df_out):
    pdf = df_out.to_pandas()
    col = next(c for c in pdf.columns if c != "image")
    return np.stack([np.asarray(v) for v in pdf[col].to_list()])


# ---------------------------------------------------------------------------
# deposit_frame: the socket-to-slot primitive
# ---------------------------------------------------------------------------


class TestDepositFrame:
    COLS = {"img": np.arange(2 * 4 * 4 * 3, dtype=np.uint8)
            .reshape(2, 4, 4, 3),
            "y": np.array([1.5, -2.0], dtype=np.float32)}

    def _slots(self):
        return {"img": np.zeros((2, 4, 4, 3), np.uint8),
                "y": np.zeros((2,), np.float32)}

    def test_deposit_bitwise_matches_decode(self):
        buf = encode_frame(self.COLS)
        out = self._slots()
        got = deposit_frame(buf, out)
        dec = decode_frame(buf)
        for name in self.COLS:
            np.testing.assert_array_equal(got[name], dec[name])
            assert got[name] is out[name]  # landed in MY buffer

    def test_decode_frame_out_kwarg_delegates(self):
        buf = encode_frame(self.COLS)
        out = self._slots()
        got = decode_frame(buf, out=out)
        np.testing.assert_array_equal(got["img"], self.COLS["img"])

    @pytest.mark.parametrize("mutate", [
        lambda b: b[: len(b) // 2],            # truncated payload
        lambda b: b"XXXX" + b[4:],             # bad magic
        lambda b: b[:-1],                      # short by one byte
    ])
    def test_hostile_frames_raise_before_any_slot_write(self, mutate):
        buf = encode_frame(self.COLS)
        out = self._slots()
        for a in out.values():
            a.fill(7)  # sentinel: any write would disturb it
        before = {k: v.copy() for k, v in out.items()}
        with pytest.raises(FrameError):
            deposit_frame(bytes(mutate(bytearray(buf))), out)
        for k in out:
            np.testing.assert_array_equal(out[k], before[k])

    @pytest.mark.parametrize("bad", [
        {"img": "wrong_dtype"}, {"img": "wrong_shape"},
        {"img": "readonly"}, {"img": "noncontig"}, {"img": "missing"},
    ])
    def test_bad_destinations_raise_before_any_slot_write(self, bad):
        buf = encode_frame(self.COLS)
        out = self._slots()
        kind = bad["img"]
        if kind == "wrong_dtype":
            out["img"] = np.zeros((2, 4, 4, 3), np.float32)
        elif kind == "wrong_shape":
            out["img"] = np.zeros((2, 4, 4), np.uint8)
        elif kind == "readonly":
            ro = np.zeros((2, 4, 4, 3), np.uint8)
            ro.setflags(write=False)
            out["img"] = ro
        elif kind == "noncontig":
            out["img"] = np.zeros((2, 4, 4, 6), np.uint8)[..., ::2]
        elif kind == "missing":
            del out["img"]
        out["y"].fill(9)
        before_y = out["y"].copy()
        with pytest.raises(FrameError):
            deposit_frame(buf, out)
        # the OTHER column's slot is untouched: validation is all-or-nothing
        np.testing.assert_array_equal(out["y"], before_y)


# ---------------------------------------------------------------------------
# rows_to_batch: spanning views and the slot-fill mode
# ---------------------------------------------------------------------------


class TestRowsToBatchSpanning:
    def test_rows_of_one_frame_stay_zero_copy(self):
        batch = np.arange(3 * 8 * 8, dtype=np.uint8).reshape(3, 8, 8)
        rows = list(decode_frame(encode_frame({"x": batch}))["x"])
        st = IngestStats()
        out = rows_to_batch(rows, stats=st)
        np.testing.assert_array_equal(out, batch)
        assert out.base is not None  # a view, not a copy
        assert st.zero_copy_batches == 1 and st.copied_batches == 0

    def test_rows_spanning_pipelined_frames_share_one_view(self):
        """Pipelined requests on one connection land back-to-back in one
        recv buffer; equal-shape single-row frames decode to views at a
        CONSTANT stride (the frame length) over the same base — the
        spanning fast path stitches them without a copy."""
        rng = np.random.default_rng(3)
        imgs = [rng.integers(0, 256, (8, 8, 3), dtype=np.uint8)
                for _ in range(4)]
        frames = [encode_frame({"img": im}) for im in imgs]
        flen = len(frames[0])
        assert all(len(f) == flen for f in frames)
        wire = b"".join(frames)  # one connection buffer
        rows = [decode_frame(wire[i * flen:(i + 1) * flen])["img"]
                for i in range(len(frames))]
        # slicing a bytes keeps the copies rooted per-slice; use a
        # memoryview so every row's base chain ends at the SAME buffer
        mv = memoryview(wire)
        rows = [decode_frame(mv[i * flen:(i + 1) * flen])["img"]
                for i in range(len(frames))]
        st = IngestStats()
        out = rows_to_batch(rows, stats=st)
        np.testing.assert_array_equal(out, np.stack(imgs))
        assert out.base is not None, "spanning view expected, got a copy"
        assert st.zero_copy_batches == 1

    def test_rows_from_unrelated_buffers_are_copied_and_counted(self):
        rng = np.random.default_rng(4)
        imgs = [rng.integers(0, 256, (8, 8, 3), dtype=np.uint8)
                for _ in range(3)]
        rows = [decode_frame(encode_frame({"img": im}))["img"]
                for im in imgs]  # three separate wire buffers
        st = IngestStats()
        out = rows_to_batch(rows, stats=st)
        np.testing.assert_array_equal(out, np.stack(imgs))
        assert st.copied_batches == 1 and st.zero_copy_batches == 0

    def test_out_mode_fills_slot_without_allocation(self):
        rng = np.random.default_rng(5)
        rows = [rng.integers(0, 256, (6, 6), dtype=np.uint8)
                for _ in range(3)]
        slot = np.zeros((8, 6, 6), np.uint8)
        st = IngestStats()
        got = rows_to_batch(rows, out=slot, stats=st)
        assert got.base is slot or got is slot
        np.testing.assert_array_equal(got, np.stack(rows))
        assert st.copied_batches == 1  # the one accounted host copy

    def test_out_mode_validates_shape_and_dtype(self):
        rows = [np.zeros((4, 4), np.uint8)] * 2
        with pytest.raises(ValueError):
            rows_to_batch(rows, out=np.zeros((8, 4, 4), np.float32))
        with pytest.raises(ValueError):
            rows_to_batch(rows, out=np.zeros((1, 4, 4), np.uint8))


class TestSlotPool:
    def test_acquire_release_cycle_and_stats(self):
        pool = SlotPool(buffers_per_bucket=2)
        spec = {"x": ((8, 4), np.float32)}
        a = pool.acquire(spec)
        b = pool.acquire(spec)
        assert a is not None and b is not None
        # both buffers leased: the next acquire times out to the fallback
        assert pool.acquire(spec, timeout=0.05) is None
        a.release()
        c = pool.acquire(spec, timeout=1.0)
        assert c is not None
        b.release()
        c.release()
        assert pool.stats()["buckets"] == 1

    def test_oversized_spec_falls_back(self):
        pool = SlotPool(max_slot_bytes=64)
        assert pool.acquire({"x": ((1024, 1024), np.float32)}) is None

    def test_overlap_accounting_records_fill_transfer_intersection(self):
        pool = SlotPool()
        st = IngestStats()
        lease = pool.acquire({"x": ((4, 4), np.float32)}, stats=st)
        lease.fill_begin()
        lease.fill_end()
        lease.transfer_begin()
        lease.transfer_end()
        s = st.summary()
        assert s["slot_fill_s"] >= 0 and s["slot_transfer_s"] >= 0
        assert 0.0 <= s["slot_overlap_ratio"] <= 1.0

    def test_overlap_counts_only_own_bucket_fills(self):
        """A transfer's overlap is measured against ITS bucket's sibling
        fills — fills from unrelated leases elsewhere in the shared pool
        must not inflate slot_overlap_ratio."""
        pool = SlotPool()
        a = pool.acquire({"x": ((4, 4), np.float32)})
        b = pool.acquire({"y": ((4, 4), np.float32)})
        pool._note_fill(a._held, (1.0, 2.0))
        assert pool._overlap(b._held, 0.0, 10.0) == 0.0
        assert pool._overlap(a._held, 0.0, 10.0) == pytest.approx(1.0)
        a.release()
        b.release()

    def test_abandoned_lease_is_finalized_back_to_pool(self):
        """A lease dropped without release() (any abort path the explicit
        cleanup misses) returns its buffers via the weakref finalizer —
        the never-replenished pool must not shrink permanently."""
        import gc

        pool = SlotPool(buffers_per_bucket=1)
        spec = {"x": ((4, 4), np.float32)}
        lease = pool.acquire(spec)
        assert lease is not None
        del lease
        gc.collect()
        again = pool.acquire(spec, timeout=0.5)
        assert again is not None
        again.release()

    def test_total_bytes_cap_evicts_lru_free_buckets(self):
        # bucket A: 2 x 64B; bucket B: 2 x 128B — together over the cap,
        # so inserting B evicts the fully-free A instead of growing
        pool = SlotPool(buffers_per_bucket=2, max_total_bytes=300)
        a = pool.acquire({"x": ((4, 4), np.float32)})
        a.release()
        b = pool.acquire({"x": ((8, 4), np.float32)})
        assert b is not None
        s = pool.stats()
        assert s["buckets"] == 1 and s["bytes"] == 256
        assert s["evictions"] == 1
        b.release()

    def test_leased_buckets_are_never_evicted(self):
        """When in-use buckets pin the pool at the byte cap, a new shape
        falls back to the copy path (None) instead of yanking live
        buffers or growing without bound."""
        pool = SlotPool(buffers_per_bucket=2, max_total_bytes=300)
        a = pool.acquire({"x": ((4, 4), np.float32)})
        assert pool.acquire({"x": ((8, 4), np.float32)},
                            timeout=0.05) is None
        s = pool.stats()
        assert s["buckets"] == 1 and s["evictions"] == 0
        a.release()

    def test_multi_column_spec_over_cap_falls_back(self):
        """A spec whose buckets jointly exceed the cap returns None (copy
        fallback) instead of evicting its own sibling buckets in a
        build/evict livelock."""
        pool = SlotPool(buffers_per_bucket=2, max_total_bytes=300)
        spec = {"x": ((4, 4), np.float32),   # 128B
                "y": ((8, 4), np.float32)}   # 256B -> jointly over cap
        assert pool.acquire(spec, timeout=0.2) is None


class TestLeaseReleaseOnAbort:
    def test_prefetcher_close_releases_queued_leases(self):
        """DevicePrefetcher.close() must hand queued batches' SlotPool
        leases back: an early abort (fault, fallback, watchdog kill) that
        drops queued items otherwise removes buffers from the shared pool
        forever, and every later acquire for that shape eats the full
        acquire timeout before falling back."""
        import time

        from mmlspark_tpu.parallel.batching import Batch, DevicePrefetcher

        pool = SlotPool(buffers_per_bucket=2)
        spec = {"x": ((4, 4), np.float32)}

        def batches():
            while True:
                lease = pool.acquire(spec, timeout=1.0)
                if lease is None:
                    return
                yield Batch({"x": lease.arrays["x"]},
                            np.ones(4, dtype=bool), 4, staging=lease)

        pf = DevicePrefetcher(batches(), depth=2)
        deadline = time.monotonic() + 2.0
        while pf._q.qsize() < 2 and time.monotonic() < deadline:
            time.sleep(0.01)  # let the producer queue both leased batches
        pf.close()
        pf._thread.join(timeout=5.0)
        assert not pf._thread.is_alive()
        a = pool.acquire(spec, timeout=1.0)
        b = pool.acquire(spec, timeout=1.0)
        assert a is not None and b is not None  # nothing leaked
        a.release()
        b.release()


# ---------------------------------------------------------------------------
# Deposit path through the fused executor: parity + counters
# ---------------------------------------------------------------------------


class TestFusedDepositParity:
    def test_transform_bitwise_parity_and_counters(self):
        pm = _image_chain()
        df = _image_df()
        copy = FusedPipelineModel(pm.stages, cache=CompileCache(),
                                  slot_staging=False)
        dep = FusedPipelineModel(pm.stages, cache=CompileCache())
        ref = _feature_matrix(copy.transform(df))
        got = _feature_matrix(dep.transform(df))
        np.testing.assert_array_equal(got, ref)
        s_copy = copy.last_ingest_stats.summary()
        s_dep = dep.last_ingest_stats.summary()
        assert "slot_deposits" not in s_copy
        assert s_dep["slot_deposits"] > 0
        assert s_dep.get("fallback_copies", 0) == 0

    def test_async_submit_bitwise_parity(self):
        pm = _image_chain()
        df = _image_df(rows=20, parts=1, seed=2)
        copy = FusedPipelineModel(pm.stages, cache=CompileCache(),
                                  slot_staging=False)
        dep = FusedPipelineModel(pm.stages, cache=CompileCache())
        ref = _feature_matrix(copy.transform_submit(df)())
        got = _feature_matrix(dep.transform_submit(df)())
        np.testing.assert_array_equal(got, ref)
        assert dep.last_ingest_stats.summary()["slot_deposits"] > 0

    def test_slot_contention_falls_back_with_accounted_copy(self):
        pm = _image_chain()
        df = _image_df(rows=10, parts=1, seed=3)
        dep = FusedPipelineModel(pm.stages, cache=CompileCache())
        _ = dep.transform(df)  # warm the pool with THIS df's buckets
        pool = dep._get_slot_pool()
        # lease every buffer of every bucket so the transform's acquire
        # must time out into the accounted copy fallback
        held = []
        specs = [{key[0]: (key[1], np.dtype(key[2]))}
                 for key in list(pool._buckets)]
        for spec in specs:
            while True:
                lease = pool.acquire(spec, timeout=0.01)
                if lease is None:
                    break
                held.append(lease)
        pool._timeout = 0.01  # keep the fallback fast under test
        ref = _feature_matrix(
            FusedPipelineModel(pm.stages, cache=CompileCache(),
                               slot_staging=False).transform(df))
        got = _feature_matrix(dep.transform(df))
        np.testing.assert_array_equal(got, ref)
        s = dep.last_ingest_stats.summary()
        assert s.get("fallback_copies", 0) > 0  # accounted, not silent
        for lease in held:
            lease.release()


# ---------------------------------------------------------------------------
# AOT mega-dispatch
# ---------------------------------------------------------------------------


class TestMegaDispatch:
    def _label(self, fused):
        _ = fused.transform(_image_df(rows=4, parts=1))
        return next(iter(fused.fusion_stats()["per_segment"]))

    @pytest.mark.parametrize("k", [2, 3])
    def test_k_step_parity(self, k):
        pm = _image_chain()
        df = _image_df(rows=48, parts=1, seed=1)
        base = FusedPipelineModel(pm.stages, cache=CompileCache())
        ref = _feature_matrix(base.transform_submit(df)())
        mega = FusedPipelineModel(pm.stages, cache=CompileCache())
        label = self._label(mega)
        mega.set_tuning(mega_k={label: k})
        assert mega.mega_k_max == k
        got = _feature_matrix(mega.transform_submit(df)())
        np.testing.assert_array_equal(got, ref)
        # one builder, two programs: the K-step one is keyed apart from the
        # per-batch one, whose key says nothing of K
        assert not any(("mega", k) in key
                       for key in base.compile_cache._entries)
        assert any(key[2] == ("mega", k)
                   for key in mega.compile_cache._entries)

    def test_k1_uncalibrated_is_bitwise_identical(self):
        """K=1 + no deposit-eligible frames == the pre-slot-staging path:
        same bytes out, batch for batch."""
        pm = _image_chain()
        df = _image_df(rows=22, parts=2, seed=0)
        plain = FusedPipelineModel(pm.stages, cache=CompileCache(),
                                   slot_staging=False)
        ref = _feature_matrix(plain.transform_submit(df)())
        again = _feature_matrix(
            FusedPipelineModel(pm.stages, cache=CompileCache(),
                               slot_staging=False).transform_submit(df)())
        np.testing.assert_array_equal(ref, again)
        assert plain.mega_k_max == 1
        assert "tuning" not in plain.fusion_stats()

    def test_partial_group_dispatches_singly(self):
        """Row count chosen so the last group is SHORTER than K: the
        leftover batches ride the normal per-batch step and outputs still
        match."""
        pm = _image_chain()
        df = _image_df(rows=42, parts=1, seed=6)  # 3 batches of 16: 2+1
        base = FusedPipelineModel(pm.stages, cache=CompileCache())
        ref = _feature_matrix(base.transform_submit(df)())
        mega = FusedPipelineModel(pm.stages, cache=CompileCache())
        label = self._label(mega)
        mega.set_tuning(mega_k={label: 2})
        got = _feature_matrix(mega.transform_submit(df)())
        np.testing.assert_array_equal(got, ref)

    def test_mega_stages_in_sliding_groups_of_k(self):
        """The K>1 submit path must NOT stage the whole partition before
        dispatching (unbounded device memory): groups of K stage, dispatch,
        and drop — at the first mega dispatch only K items may have been
        pulled from the staging iterator."""
        from mmlspark_tpu.core.fusion import SegmentExecutor
        from mmlspark_tpu.parallel.ingest import BatchTiming

        ex = object.__new__(SegmentExecutor)
        pulled = [0]
        dispatch_pulls = []

        def staged_items():
            for _ in range(6):
                pulled[0] += 1
                yield ({"x": np.zeros((4, 2), np.float32)}, 4), \
                    BatchTiming(rows=4)

        def mega(group):
            dispatch_pulls.append(pulled[0])
            return [(np.zeros(1),)] * len(group)

        ex._make_mega_step = lambda params, state, k: mega
        handles = []
        ex._dispatch_mega(staged_items(), None, {"ext": ["x"]}, None, 2,
                          handles)
        assert dispatch_pulls == [2, 4, 6]  # eager staging would be [6,...]
        assert len(handles) == 6
        assert all(t.mega_k == 2 for _h, t in handles)


class TestMegaKnob:
    def test_cost_model_chooses_k_from_dispatch_ratio(self):
        from mmlspark_tpu.core.costmodel import SegmentCostModel
        from mmlspark_tpu.parallel.ingest import BatchTiming

        model = SegmentCostModel(peaks={"flops": 1e9, "bytes_per_s": 1e9,
                                        "peak_source": "test"}, min_obs=2)
        # dispatch dominates: 5ms fixed vs 1ms device work per batch
        for _ in range(4):
            model.observe_batch("seg", BatchTiming(
                h2d_s=0.0004, dispatch_s=0.005, compute_s=0.0005,
                readback_s=0.0001, rows=16, padded_rows=16))
        k = model.choose_mega_k("seg")
        assert k is not None and k > 1
        # dispatch negligible: stay at 1
        cheap = SegmentCostModel(peaks={"flops": 1e9, "bytes_per_s": 1e9,
                                        "peak_source": "test"}, min_obs=2)
        for _ in range(4):
            cheap.observe_batch("seg", BatchTiming(
                h2d_s=0.004, dispatch_s=0.0001, compute_s=0.005,
                readback_s=0.001, rows=16, padded_rows=16))
        assert cheap.choose_mega_k("seg") == 1
        # uncalibrated: None
        assert SegmentCostModel().choose_mega_k("other") is None

    def test_choose_mega_k_stable_under_amortized_timings(self):
        """Once K>1 is active, recorded dispatch_s is the per-batch SHARE
        of one mega dispatch. choose_mega_k must de-amortize via the
        mega_k tag — otherwise the tuner sees cheap dispatch, proposes
        K=1, the cost reappears, and K oscillates every cycle."""
        from mmlspark_tpu.core.costmodel import SegmentCostModel
        from mmlspark_tpu.parallel.ingest import BatchTiming

        model = SegmentCostModel(peaks={"flops": 1e9, "bytes_per_s": 1e9,
                                        "peak_source": "test"}, min_obs=2)
        for _ in range(4):
            model.observe_batch("seg", BatchTiming(
                h2d_s=0.0004, dispatch_s=0.005, compute_s=0.0005,
                readback_s=0.0001, rows=16, padded_rows=16))
        k = model.choose_mega_k("seg")
        assert k is not None and k > 1
        # mega active: per-batch dispatch share = fixed cost / K, tagged
        for _ in range(16):
            model.observe_batch("seg", BatchTiming(
                h2d_s=0.0004, dispatch_s=0.005 / k, compute_s=0.0005,
                readback_s=0.0001, rows=16, padded_rows=16, mega_k=k))
        assert model.choose_mega_k("seg") == k
        # the de-amortized EWMA survives serialization
        restored = SegmentCostModel.from_dict(
            model.to_dict(), peaks={"flops": 1e9, "bytes_per_s": 1e9,
                                    "peak_source": "test"})
        assert restored.choose_mega_k("seg") == k

    def test_knobset_round_trips_and_rollback(self):
        from mmlspark_tpu.core.tune import KnobSet

        k = KnobSet(mega_k={"seg": 4})
        assert not k.is_default()
        assert KnobSet.from_dict(k.to_dict()).mega_k == {"seg": 4}
        assert KnobSet().is_default()

    def test_tuner_apply_and_rollback_drive_mega_k(self):
        from mmlspark_tpu.core.tune import KnobSet, Tuner

        pm = _image_chain()
        fused = FusedPipelineModel(pm.stages, cache=CompileCache())
        _ = fused.transform(_image_df(rows=4, parts=1))
        label = next(iter(fused.fusion_stats()["per_segment"]))
        tuner = Tuner(fused=fused)
        tuner.apply(KnobSet(mega_k={label: 3}))
        assert fused.mega_k_max == 3
        assert tuner.rollback("test")
        assert fused.mega_k_max == 1  # previous (default) set re-applied

    def test_watchdog_budget_scales_with_k_batches(self):
        from mmlspark_tpu.serving.supervisor import DispatchWatchdog

        wd = DispatchWatchdog(k=2.0, min_budget_s=0.0)
        assert wd.budget_s(16) is None  # unarmed
        wd.observe(1.0)
        b1 = wd.budget_s(16)
        b4 = wd.budget_s(16, batches=4)
        assert b1 == pytest.approx(2.0)
        assert b4 == pytest.approx(8.0)  # EWMA fallback scales by K
        # the cost-model path prices rows directly: no K scaling
        wd2 = DispatchWatchdog(k=2.0, min_budget_s=0.0,
                               predict_ms_fn=lambda rows: 100.0)
        assert wd2.budget_s(16, batches=4) == pytest.approx(0.2)


# ---------------------------------------------------------------------------
# Serving e2e: binary wire -> fused chain -> exactly one host copy
# ---------------------------------------------------------------------------


def _serve_frame_image_chain(slot_staging=True, mega_k=None,
                             async_exec=False, http_mode="thread"):
    """serve_pipeline over the fused image chain fed by BINARY frames:
    each request body is one single-column frame carrying a (16,16,3)
    uint8 image. Returns (started server, fused model)."""
    from mmlspark_tpu.serving import serve_pipeline
    from mmlspark_tpu.stages import UDFTransformer

    pm = _image_chain()
    fused = FusedPipelineModel(pm.stages, cache=CompileCache(),
                               slot_staging=slot_staging)
    if mega_k:
        _ = fused.transform(_image_df(rows=4, parts=1))
        label = next(iter(fused.fusion_stats()["per_segment"]))
        fused.set_tuning(mega_k={label: int(mega_k)})
    in_cols = {"data", "image", "id", "value", "headers", "origin"}

    def decode_rows(col):
        out = np.empty(len(col), dtype=object)
        for i, v in enumerate(col):
            out[i] = ImageSchema.make(np.asarray(v, dtype=np.uint8),
                                      f"req{i}")
        return out

    decode = UDFTransformer(inputCol="data", outputCol="image",
                            vectorizedUdf=decode_rows)

    class _Chain:
        def transform(self, df):
            out = fused.transform(decode.transform(df))
            feat = next((c for c in out.schema.names
                         if c not in in_cols), None)
            if feat is not None and "reply" not in out.schema:
                out = out.with_column(
                    "reply",
                    lambda p, _c=feat: [
                        None if v is None else np.asarray(v).tolist()
                        for v in p[_c]])
            return out

        def set_tuning(self, **kw):
            fused.set_tuning(**kw)

        cost_model = property(lambda self: fused.cost_model)
        last_ingest_stats = property(lambda self: fused.last_ingest_stats)
        mega_k_max = property(lambda self: fused.mega_k_max)
        _seg_stats = property(lambda self: fused._seg_stats)
        _cache = property(lambda self: fused._cache)
        _last_plan = property(lambda self: fused._last_plan)

        def fusion_stats(self):
            return fused.fusion_stats()

        def has_param(self, name):
            return False

    srv = serve_pipeline(_Chain(), "data", parse="json", port=0,
                         max_wait_ms=0.0, http_mode=http_mode,
                         async_exec=async_exec)
    return srv.start(), fused


def _frame_body(seed=11):
    rng = np.random.default_rng(seed)
    img = rng.integers(0, 256, (16, 16, 3), dtype=np.uint8)
    return encode_frame({"img": img})


class TestServingSingleCopyE2E:
    def test_binary_wire_reaches_device_with_one_host_copy(self):
        srv, fused = _serve_frame_image_chain()
        try:
            body = _frame_body()
            for _ in range(4):
                status, reply = _post(srv.address, body,
                                      {"Content-Type": FRAME_CONTENT_TYPE})
                assert status == 200, reply
        finally:
            srv.stop()
        s = fused.last_ingest_stats.summary()
        # every batch deposited: exactly ONE host copy (the slot fill);
        # zero accounted fallback copies
        assert s["slot_deposits"] > 0
        assert s.get("fallback_copies", 0) == 0

    def test_deposit_vs_copy_reply_parity_across_modes(self):
        body = _frame_body(seed=12)
        replies = {}
        for staging in (False, True):
            for async_exec in (False, True):
                srv, _ = _serve_frame_image_chain(
                    slot_staging=staging, async_exec=async_exec)
                try:
                    status, reply = _post(
                        srv.address, body,
                        {"Content-Type": FRAME_CONTENT_TYPE})
                finally:
                    srv.stop()
                assert status == 200, reply
                replies[(staging, async_exec)] = reply
        assert len(set(replies.values())) == 1, replies

    def test_mega_k_serving_reply_parity(self):
        body = _frame_body(seed=13)
        srv, _ = _serve_frame_image_chain(mega_k=None)
        try:
            _, ref = _post(srv.address, body,
                           {"Content-Type": FRAME_CONTENT_TYPE})
        finally:
            srv.stop()
        srv, fused = _serve_frame_image_chain(mega_k=2)
        try:
            status, got = _post(srv.address, body,
                                {"Content-Type": FRAME_CONTENT_TYPE})
        finally:
            srv.stop()
        assert status == 200 and got == ref
        assert fused.mega_k_max == 2
