"""Sparse end-to-end tests (docs/sparse.md): CSR through the DeviceFn
contract, Pallas sparse kernels, and the nnz-predicted layout knob.

Covers:
  - the CSR wire format (io/binary.py): encode/decode round-trip of the
    four sub-columns, dense passthrough as a byte-identical no-op, and
    all-or-nothing rejection of hostile triples (out-of-bounds or
    negative indices, non-monotone indptr, nnz mismatches, a missing
    sibling, row-count disagreement) with ``FrameError``;
  - the Pallas sparse kernels (gbdt/pallas_sparse.py): CSR feature
    gather bitwise-equal between the XLA path, the Pallas interpret-mode
    path, and a densified reference (including out-of-range used-feature
    clamping), the MXU sparse histogram within the ``hist.csr``
    declared tolerance, and both variants present in the kernel
    registry;
  - fused CSR execution parity: with the layout knob OFF, sparse rows
    fall back to the host path bitwise; with the knob ON the segment
    stages CSR triples (``csr_batches`` accounted, no densify), matches
    the f64 host scorer within the declared tolerance, and matches the
    fault-forced densify fallback BITWISE — layout never changes the
    answer, only the staging;
  - cold-start parity: an uncalibrated cost model proposes no layout,
    and the untouched knob leaves outputs, fallbacks, cache keys, stats
    keys, and the metrics exposition byte-for-byte free of any sparse
    machinery;
  - the layout knob lifecycle: ``observe_nnz`` -> ``choose_layout``
    calibration gate, Tuner proposal, journaled apply, and one-step
    rollback restoring the knob-off output bitwise;
  - row-split CSR sharding (parallel/shardplan.py): ``split_csr_rows``
    reconstruction parity with ragged per-shard nnz on the forced
    multi-device CPU mesh, the fitted ragged all-gather cost term, the
    ``csr_row`` candidate gated on sparse-capable DeviceFns, and the
    CSR-staging x sharding exclusion;
  - seeded chaos (``sparse.stage``): an injected staging fault degrades
    to the ACCOUNTED densify fallback with bitwise-identical output,
    under the CI chaos-seed matrix.
"""

import os

import numpy as np
import pytest

from mmlspark_tpu.core import faults, kernels
from mmlspark_tpu.core.costmodel import SegmentCostModel
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.device_stage import CompileCache
from mmlspark_tpu.core.fusion import FusedPipelineModel
from mmlspark_tpu.core.pipeline import PipelineModel
from mmlspark_tpu.core.tune import KnobSet, Tuner
from mmlspark_tpu.gbdt import pallas_sparse
from mmlspark_tpu.gbdt.sparse import rows_to_csr
from mmlspark_tpu.gbdt.stages import LightGBMRegressor
from mmlspark_tpu.io.binary import (CSR_SUFFIXES, FrameError,
                                    decode_csr_columns, decode_frame,
                                    encode_csr_columns, encode_frame,
                                    validate_csr_triple)
from mmlspark_tpu.obs.bridge import _ingest_families
from mmlspark_tpu.parallel import shardplan
from mmlspark_tpu.parallel.ingest import BatchTiming

#: seed matrix knob for the CI chaos lane (tools/ci/run_ci.sh chaos stage)
CHAOS_SEED = int(os.environ.get("MMLSPARK_CHAOS_SEED", "0"))

N_ROWS, N_FEATURES, DENSITY = 200, 32, 0.15

#: fused CSR staging runs the f32 on-device forest against the f64 host
#: scorer — reduction order is identical (forest.csr is an exact
#: variant), so the only drift is the widened host accumulate
CSR_VS_HOST_ATOL = 1e-6


def _sparse_matrix(n=N_ROWS, width=N_FEATURES, density=DENSITY, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, width)).astype(np.float32)
    X[rng.random((n, width)) >= density] = 0.0
    return X


def _csr_of(X):
    indptr = [0]
    indices, values = [], []
    for row in X:
        nz = np.flatnonzero(row)
        indices.extend(nz.tolist())
        values.extend(row[nz].tolist())
        indptr.append(len(indices))
    return (np.asarray(indptr, dtype=np.int32),
            np.asarray(indices, dtype=np.int32),
            np.asarray(values, dtype=np.float32))


def _sparse_rows(X):
    out = np.empty(len(X), dtype=object)
    for i, row in enumerate(X):
        nz = np.flatnonzero(row)
        out[i] = {"indices": nz.astype(np.int64),
                  "values": row[nz].astype(np.float64),
                  "size": X.shape[1]}
    return out


def _dense_rows(X):
    out = np.empty(len(X), dtype=object)
    for i, row in enumerate(X):
        out[i] = row
    return out


@pytest.fixture(scope="module")
def gbdt():
    """Trained regressor + dense/sparse views of the same rows + the
    host-path predictions (the parity reference for every fused run)."""
    X = _sparse_matrix()
    rng = np.random.default_rng(1)
    y = X[:, 0] * 2 + X[:, 3] - X[:, 7] + rng.normal(
        scale=0.1, size=len(X))
    df_fit = DataFrame.from_dict(
        {"features": _dense_rows(X), "label": y}, num_partitions=1)
    model = LightGBMRegressor(numIterations=10, numLeaves=7,
                              featuresCol="features",
                              labelCol="label").fit(df_fit)
    pred = model.get("predictionCol")
    df_sp = DataFrame.from_dict({"features": _sparse_rows(X)},
                                num_partitions=1)
    df_dense = DataFrame.from_dict({"features": _dense_rows(X)},
                                   num_partitions=1)
    host = np.asarray(model.transform(df_sp).column(pred), float)
    return {"model": model, "pred": pred, "X": X, "df_sparse": df_sp,
            "df_dense": df_dense, "host": host}


def _fused(gbdt, **kwargs):
    pm = PipelineModel([gbdt["model"]])
    return FusedPipelineModel(pm.stages, cache=CompileCache(), **kwargs)


def _segment_label(fused):
    return [nd.label for nd in fused._last_plan if hasattr(nd, "dfns")][0]


def _seg_summary(fused):
    st = fused.fusion_stats()
    return next(iter(st["per_segment"].values()), {})


# -- CSR wire format ---------------------------------------------------------


class TestCSRWire:
    def _triple(self, seed=0):
        return _csr_of(_sparse_matrix(n=16, width=12, seed=seed))

    def test_round_trip_through_binary_frame(self):
        indptr, indices, values = self._triple()
        cols = encode_csr_columns("feat", indptr, indices, values, 12)
        assert sorted(cols) == sorted(
            f"feat{s}" for s in CSR_SUFFIXES)
        cols["label"] = np.arange(16, dtype=np.float64)
        decoded = decode_csr_columns(decode_frame(encode_frame(cols)))
        assert set(decoded) == {"feat", "label"}
        np.testing.assert_array_equal(decoded["label"],
                                      cols["label"])
        for i, row in enumerate(decoded["feat"]):
            lo, hi = int(indptr[i]), int(indptr[i + 1])
            assert row["size"] == 12
            np.testing.assert_array_equal(row["indices"], indices[lo:hi])
            np.testing.assert_array_equal(row["values"], values[lo:hi])

    def test_dense_passthrough_is_a_no_op(self):
        cols = {"a": np.arange(6, dtype=np.float32),
                "b": np.arange(6, dtype=np.int32)}
        out = decode_csr_columns(cols)
        assert out is cols or all(out[k] is cols[k] for k in cols)

    def test_hostile_triples_rejected(self):
        indptr, indices, values = self._triple()
        cases = {
            "oob index": dict(indices=np.where(indices == indices.max(),
                                               99, indices)),
            "negative index": dict(indices=np.where(
                indices == indices.max(), -1, indices)),
            "non-monotone indptr": dict(
                indptr=np.concatenate([indptr[:3][::-1], indptr[3:]])),
            "indptr not closing on nnz": dict(
                indptr=np.concatenate([indptr[:-1],
                                       [indptr[-1] + 3]])),
            "indices/values length mismatch": dict(
                values=values[:-1]),
            "unanchored indptr": dict(indptr=indptr + 1),
            "bad width": dict(width=0),
            "rank-2 part": dict(values=values.reshape(1, -1)),
        }
        for name, bad in cases.items():
            kw = dict(indptr=indptr, indices=indices, values=values,
                      width=12)
            kw.update(bad)
            with pytest.raises(FrameError):
                validate_csr_triple("feat", kw["indptr"], kw["indices"],
                                    np.asarray(kw["values"]), kw["width"])

    def test_row_count_disagreement_rejected(self):
        indptr, indices, values = self._triple()
        with pytest.raises(FrameError):
            validate_csr_triple("feat", indptr, indices, values, 12,
                                rows=15)

    def test_decode_is_all_or_nothing(self):
        # one valid triple + one hostile sibling set: decode must reject
        # the WHOLE frame before materializing anything
        indptr, indices, values = self._triple()
        cols = encode_csr_columns("good", indptr, indices, values, 12)
        bad = encode_csr_columns("bad", indptr, indices, values, 12)
        bad["bad:indices"] = np.where(indices == indices.max(), 99,
                                      indices).astype(np.int32)
        cols.update(bad)
        with pytest.raises(FrameError):
            decode_csr_columns(cols)

    def test_missing_sibling_rejected(self):
        indptr, indices, values = self._triple()
        cols = encode_csr_columns("feat", indptr, indices, values, 12)
        for drop in (":indices", ":values", ":width"):
            partial = {k: v for k, v in cols.items()
                       if not k.endswith(drop)}
            with pytest.raises(FrameError, match="sibling"):
                decode_csr_columns(partial)


# -- Pallas sparse kernels ---------------------------------------------------


class TestSparseKernels:
    def _gather_case(self, seed=3, n=24, width=40, n_used=9):
        X = _sparse_matrix(n=n, width=width, density=0.2, seed=seed)
        indptr, indices, values = _csr_of(X)
        used = np.sort(np.random.default_rng(seed).choice(
            width, size=n_used, replace=False)).astype(np.int32)
        return X, indptr, indices, values, used

    def test_xla_gather_matches_densified_reference(self):
        X, indptr, indices, values, used = self._gather_case()
        got = np.asarray(pallas_sparse.csr_gather_xla(
            indptr, indices, values, X.shape[1], used))
        np.testing.assert_array_equal(got, X[:, used])

    def test_pallas_gather_bitwise_vs_xla(self):
        X, indptr, indices, values, used = self._gather_case(seed=4)
        ref = np.asarray(pallas_sparse.csr_gather_xla(
            indptr, indices, values, X.shape[1], used))
        got = np.asarray(pallas_sparse.csr_gather_pallas(
            indptr, indices, values, X.shape[1], used, interpret=True))
        np.testing.assert_array_equal(got, ref)

    def test_pallas_gather_tiles_rows_and_features(self, monkeypatch):
        # more rows than one row tile and more used features than one
        # 128-lane feature tile: every (row tile, feature tile) block
        # accumulates its own chunks and lands in the right place
        monkeypatch.setattr(pallas_sparse, "_GATHER_TILE_N", 16)
        X, indptr, indices, values, used = self._gather_case(
            seed=6, n=40, width=400, n_used=150)
        got = np.asarray(pallas_sparse.csr_gather_pallas(
            indptr, indices, values, X.shape[1], used, interpret=True))
        np.testing.assert_array_equal(got, X[:, used])

    def test_gather_clamps_out_of_range_used_features(self):
        # a model trained on MORE features than the rows carry queries
        # columns past ``width``: clamped to the last real column (the
        # remap keeps such ids in range), never an OOB read
        X, indptr, indices, values, _ = self._gather_case(seed=5)
        used = np.asarray([0, X.shape[1] - 1, X.shape[1], X.shape[1] + 7],
                          dtype=np.int32)
        ref = X[:, np.minimum(used, X.shape[1] - 1)]
        for fn in (pallas_sparse.csr_gather_xla,
                   lambda *a: pallas_sparse.csr_gather_pallas(
                       *a, interpret=True)):
            got = np.asarray(fn(indptr, indices, values, X.shape[1],
                                used))
            np.testing.assert_array_equal(got, ref)

    def test_sparse_histogram_within_declared_tolerance(self):
        rng = np.random.default_rng(7)
        nnz, total_bins = 400, 96
        flat_bins = rng.integers(0, total_bins, size=nnz,
                                 dtype=np.int32)
        stats = rng.normal(size=(3, nnz)).astype(np.float32)
        stats[2] = 1.0  # count channel: exact below 2^24
        ref = np.zeros((3, total_bins), dtype=np.float64)
        for c in range(3):
            np.add.at(ref[c], flat_bins, stats[c].astype(np.float64))
        got = np.asarray(pallas_sparse.sparse_histogram_mxu(
            flat_bins, stats, total_bins, interpret=True))
        tol = {v.id: v for v in
               kernels.variants_for("hist")}["hist.csr"].tolerance
        assert tol is not None
        np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)
        np.testing.assert_array_equal(got[2],
                                      ref[2].astype(np.float32))

    def test_variants_registered(self):
        hist = {v.id: v for v in kernels.variants_for("hist")}
        forest = {v.id: v for v in kernels.variants_for("forest")}
        assert hist["hist.csr"].params.get("layout") == "csr"
        assert forest["forest.csr"].params.get("csr_gather") == "pallas"
        # forest traversal is an exact gather: bitwise contract
        assert forest["forest.csr"].tolerance is None


# -- fused CSR execution -----------------------------------------------------


class TestFusedSparseParity:
    def test_knob_off_sparse_rows_fall_back_bitwise(self, gbdt):
        fused = _fused(gbdt)
        out = np.asarray(
            fused.transform(gbdt["df_sparse"]).column(gbdt["pred"]),
            float)
        np.testing.assert_array_equal(out, gbdt["host"])
        st = fused.fusion_stats()
        assert any("sparse" in f for f in st["fallbacks"])

    def test_knob_on_stages_csr_within_tolerance(self, gbdt):
        fused = _fused(gbdt)
        fused.transform(gbdt["df_sparse"])
        fused.set_tuning(layout={_segment_label(fused): "csr"})
        out = np.asarray(
            fused.transform(gbdt["df_sparse"]).column(gbdt["pred"]),
            float)
        st = fused.fusion_stats()
        assert st["fallbacks"] == []
        seg = _seg_summary(fused)
        assert seg.get("csr_batches", 0) >= 1
        assert "densifies" not in seg
        assert seg["csr_nnz_bytes"] < seg["csr_dense_bytes"]
        assert np.max(np.abs(out - gbdt["host"])) <= CSR_VS_HOST_ATOL

    def test_csr_cache_key_and_dense_program_coexist(self, gbdt):
        fused = _fused(gbdt)
        fused.transform(gbdt["df_dense"])
        fused.set_tuning(layout={_segment_label(fused): "csr"})
        fused.transform(gbdt["df_sparse"])
        shapes = [s for shapes in fused._cache.costs().values()
                  for s in shapes]
        assert any(s.startswith("layout=csr;") for s in shapes)
        assert any(not s.startswith("layout=csr;") for s in shapes)

    def test_dense_rows_unaffected_by_layout_knob(self, gbdt):
        fused = _fused(gbdt)
        ref = np.asarray(
            fused.transform(gbdt["df_dense"]).column(gbdt["pred"]),
            float)
        fused.set_tuning(layout={_segment_label(fused): "csr"})
        got = np.asarray(
            fused.transform(gbdt["df_dense"]).column(gbdt["pred"]),
            float)
        np.testing.assert_array_equal(got, ref)

    def test_roofline_carries_layout_and_nnz_bound(self, gbdt):
        cm = SegmentCostModel(min_obs=1)
        fused = _fused(gbdt, cost_model=cm)
        fused.transform(gbdt["df_sparse"])  # feeds observe_nnz
        fused.set_tuning(layout={_segment_label(fused): "csr"})
        fused.transform(gbdt["df_sparse"])
        st = fused.fusion_stats()
        label = _segment_label(fused)
        assert st["tuning"]["layout"] == {label: "csr"}
        rec = st["roofline"][label]
        assert rec["layout"] == "csr"
        assert rec["nnz_bytes_per_batch"] > 0
        # the nnz prediction must price well under the dense staging
        assert rec["nnz_bytes_per_batch"] < \
            cm.dense_bytes(label, N_ROWS)


# -- cold-start parity -------------------------------------------------------


class TestColdStartParity:
    def test_uncalibrated_model_proposes_no_layout(self, gbdt):
        fused = _fused(gbdt)
        tuner = Tuner(fused)
        fused.transform(gbdt["df_sparse"])
        knobs = tuner.propose()
        assert knobs.layout == {}

    def test_untuned_run_carries_no_sparse_machinery(self, gbdt):
        fused = _fused(gbdt)
        out = np.asarray(
            fused.transform(gbdt["df_sparse"]).column(gbdt["pred"]),
            float)
        np.testing.assert_array_equal(out, gbdt["host"])
        st = fused.fusion_stats()
        assert "layout" not in st.get("tuning", {})
        seg = _seg_summary(fused)
        assert "csr_batches" not in seg and "csr_nnz_bytes" not in seg
        shapes = [s for shapes in fused._cache.costs().values()
                  for s in shapes]
        assert not any("layout=" in s for s in shapes)

    def test_exposition_free_of_sparse_families_when_unused(self, gbdt):
        fused = _fused(gbdt)
        fused.transform(gbdt["df_dense"])
        names = {f.name for f in _ingest_families(_seg_summary(fused))}
        assert not any("densif" in n or "csr" in n for n in names)

    def test_exposition_gains_sparse_families_with_knob_on(self, gbdt):
        fused = _fused(gbdt)
        fused.transform(gbdt["df_sparse"])
        fused.set_tuning(layout={_segment_label(fused): "csr"})
        fused.transform(gbdt["df_sparse"])
        names = {f.name for f in _ingest_families(_seg_summary(fused))}
        assert "mmlspark_ingest_csr_batches_total" in names
        assert "mmlspark_ingest_csr_bytes_total" in names

    def test_knobset_default_and_serialization(self):
        assert KnobSet().is_default()
        knobs = KnobSet(layout={"Seg": "csr"})
        assert not knobs.is_default()
        assert KnobSet.from_dict(knobs.to_dict()).layout == \
            {"Seg": "csr"}
        assert "layout" not in KnobSet().to_dict()


# -- the layout knob lifecycle -----------------------------------------------


class TestLayoutKnob:
    def test_choose_layout_gates_on_calibration(self):
        cm = SegmentCostModel(min_obs=2)
        # density observations alone never flip the knob: the segment
        # cost itself must be calibrated first (cold start is inert)
        timing = BatchTiming(compute_s=2e-3, h2d_s=5e-4, rows=128,
                             padded_rows=128)
        cm.observe_nnz("Seg", rows=100, nnz=300, width=64)
        cm.observe_nnz("Seg", rows=100, nnz=300, width=64)
        assert cm.choose_layout("Seg") is None
        for _ in range(3):
            cm.observe_batch("Seg", timing)
        assert cm.choose_layout("Seg") == "csr"
        # near-dense rows: CSR per-row bytes (8/nnz + indptr) cannot
        # undercut width x f32 by the margin — keep densify
        dense = SegmentCostModel(min_obs=1)
        dense.observe_nnz("Seg", rows=100, nnz=100 * 60, width=64)
        dense.observe_batch("Seg", timing)
        dense.observe_batch("Seg", timing)
        assert dense.choose_layout("Seg") is None

    def test_nnz_term_serializes(self):
        cm = SegmentCostModel(min_obs=1)
        cm.observe_nnz("Seg", rows=10, nnz=30, width=64)
        clone = SegmentCostModel.from_dict(cm.to_dict())
        assert clone.nnz_bytes("Seg", 10) == cm.nnz_bytes("Seg", 10)
        assert clone.dense_bytes("Seg", 10) == cm.dense_bytes("Seg", 10)

    def test_tuner_proposes_layout_once_calibrated(self, gbdt):
        cm = SegmentCostModel(min_obs=2)
        fused = _fused(gbdt, cost_model=cm)
        tuner = Tuner(fused, model=cm)
        # sparse traffic feeds the density EWMA (the knob-off runs fall
        # back to host, which is exactly the cold-start contract)...
        for _ in range(2):
            fused.transform(gbdt["df_sparse"])
        # ...while dense traffic on the same segment calibrates the
        # per-batch cost term; refit after EVERY transform — the live
        # stats object is replaced per run
        for _ in range(4):
            fused.transform(gbdt["df_dense"])
            tuner.refit()
        label = _segment_label(fused)
        assert cm.choose_layout(label) == "csr"
        knobs = tuner.propose()
        assert knobs.layout == {label: "csr"}

    def test_apply_journal_rollback_bitwise(self, gbdt):
        fused = _fused(gbdt)
        off = np.asarray(
            fused.transform(gbdt["df_sparse"]).column(gbdt["pred"]),
            float)
        label = _segment_label(fused)
        tuner = Tuner(fused)
        tuner.apply(KnobSet(layout={label: "csr"}))
        assert [e["action"] for e in tuner.journal] == ["apply"]
        assert tuner.journal[0]["knobs"]["layout"] == {label: "csr"}
        on = np.asarray(
            fused.transform(gbdt["df_sparse"]).column(gbdt["pred"]),
            float)
        assert _seg_summary(fused).get("csr_batches", 0) >= 1
        assert np.max(np.abs(on - gbdt["host"])) <= CSR_VS_HOST_ATOL
        assert tuner.rollback()
        actions = [e["action"] for e in tuner.journal]
        assert actions[0] == "apply" and \
            actions[1].startswith("rollback")
        back = np.asarray(
            fused.transform(gbdt["df_sparse"]).column(gbdt["pred"]),
            float)
        np.testing.assert_array_equal(back, off)
        np.testing.assert_array_equal(back, gbdt["host"])


# -- row-split CSR sharding --------------------------------------------------


class _SparseDfn:
    def __init__(self, in_cols, sparse=True):
        self.in_cols = tuple(in_cols)
        self.out_cols = ("y",)
        self.shard_dims = None
        self.sparse_cols = tuple(in_cols) if sparse else ()
        self.sparse_fn = (lambda *a: None) if sparse else None


class _FakeSegment:
    label = "Fake"

    def __init__(self, dfns, external):
        self.dfns = list(dfns)
        self.external_in_cols = list(external)


class TestShardedCSR:
    def test_split_reconstructs_ragged_shards(self):
        X = _sparse_matrix(n=50, width=20, seed=11)
        X[7] = 0.0  # empty rows make genuinely ragged shards
        X[8] = 0.0
        indptr, indices, values = _csr_of(X)
        shards = shardplan.split_csr_rows(indptr, indices, values, 4)
        assert len(shards) == 4
        rows = 0
        for ip, ix, vals in shards:
            assert ip[0] == 0 and len(ix) == len(vals) == int(ip[-1])
            lo = rows
            rows += len(ip) - 1
            base = int(indptr[lo])
            np.testing.assert_array_equal(
                ip, (indptr[lo:rows + 1] - base).astype(np.int32))
            np.testing.assert_array_equal(
                ix, indices[base:int(indptr[rows])])
            np.testing.assert_array_equal(
                vals, values[base:int(indptr[rows])])
        assert rows == len(X)

    def test_sharded_predict_matches_unsharded(self, gbdt):
        import jax
        assert len(jax.devices()) >= 4  # conftest forces the CPU mesh
        X = gbdt["X"]
        indptr, indices, values = _csr_of(X)
        ens = gbdt["model"]._ensemble()
        full = pallas_sparse.csr_gather_xla(
            indptr, indices, values, X.shape[1],
            pallas_sparse.used_features(ens))
        parts = []
        for dev, (ip, ix, vals) in zip(
                jax.devices()[:4],
                shardplan.split_csr_rows(indptr, indices, values, 4)):
            with jax.default_device(dev):
                parts.append(np.asarray(pallas_sparse.csr_gather_xla(
                    ip, ix, vals, X.shape[1],
                    pallas_sparse.used_features(ens))))
        np.testing.assert_array_equal(np.concatenate(parts),
                                      np.asarray(full))

    def test_ragged_allgather_term(self):
        # the fitted term pads every shard to the max nnz (SPMD): cost
        # follows the WORST shard, not the mean
        even = shardplan.ragged_allgather_bytes([100, 100, 100, 100])
        ragged = shardplan.ragged_allgather_bytes([10, 10, 10, 370])
        assert ragged > even
        assert even == 4 * 100 * 8.0 + 4 * 4.0
        assert shardplan.ragged_allgather_bytes(
            [100], rows_per_shard=[25]) == 100 * 8.0 + (25 + 1) * 4.0

    def test_csr_row_candidate_gated_on_sparse_capability(self, ):
        from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
        import jax
        mesh = make_mesh(MeshSpec(data=4), device_list=jax.devices()[:4])
        seg = _FakeSegment([_SparseDfn(["x"])], ["x"])
        names = [c.name for c in shardplan.candidates(seg, mesh)]
        assert shardplan.SPEC_CSR_ROW in names
        plain = _FakeSegment([_SparseDfn(["x"], sparse=False)], ["x"])
        names = [c.name for c in shardplan.candidates(plain, mesh)]
        assert shardplan.SPEC_CSR_ROW not in names

    def test_csr_staging_excluded_under_sharding(self, gbdt):
        # CSR wire staging and mesh sharding compose through the
        # csr_row partition spec (priced host-side), NOT through
        # per-shard CSR slot staging: once a segment actually shards,
        # _csr_capable returns nothing and sparse rows keep the
        # knob-off host fallback — never a per-shard CSR triple
        import jax
        from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
        fused = _fused(gbdt)
        fused.transform(gbdt["df_sparse"])
        label = _segment_label(fused)
        fused.set_mesh(make_mesh(MeshSpec(data=4),
                                 device_list=jax.devices()[:4]))
        fused.set_tuning(layout={label: "csr"},
                         sharding={label: shardplan.SPEC_DATA})
        out = np.asarray(
            fused.transform(gbdt["df_sparse"]).column(gbdt["pred"]),
            float)
        st = fused.fusion_stats()
        assert "csr_batches" not in _seg_summary(fused)
        assert any("sparse" in f for f in st["fallbacks"])
        np.testing.assert_array_equal(out, gbdt["host"])


# -- seeded chaos: the sparse.stage fault point ------------------------------


@pytest.mark.faults
class TestSparseChaos:
    def test_staging_fault_degrades_to_accounted_densify(self, gbdt):
        fused = _fused(gbdt)
        fused.transform(gbdt["df_sparse"])
        fused.set_tuning(layout={_segment_label(fused): "csr"})
        csr_out = np.asarray(
            fused.transform(gbdt["df_sparse"]).column(gbdt["pred"]),
            float)
        with faults.FaultInjector(seed=CHAOS_SEED).plan(
                faults.SPARSE_STAGE, every=1) as inj:
            faulted = np.asarray(
                fused.transform(gbdt["df_sparse"]).column(gbdt["pred"]),
                float)
            assert len(inj.fired(faults.SPARSE_STAGE)) >= 1
        # the fallback DENSIFIES (accounted, never silent) and the
        # answer is bitwise what the CSR staging produced
        np.testing.assert_array_equal(faulted, csr_out)
        seg = _seg_summary(fused)
        assert seg["densifies"] >= 1
        assert seg["densified_bytes"] > seg["densify_nnz_bytes"]

    def test_fault_schedule_replays_under_seed(self, gbdt):
        fused = _fused(gbdt)
        fused.transform(gbdt["df_sparse"])
        fused.set_tuning(layout={_segment_label(fused): "csr"})
        counts = []
        for _ in range(2):
            with faults.FaultInjector(seed=CHAOS_SEED).plan(
                    faults.SPARSE_STAGE, p=0.5) as inj:
                fused.transform(gbdt["df_sparse"])
                counts.append(len(inj.fired(faults.SPARSE_STAGE)))
        assert counts[0] == counts[1]

    def test_host_sparse_path_unaffected_by_fault(self, gbdt):
        # knob off: the fault point is never reached — sparse rows ride
        # the host fallback regardless of the injector
        fused = _fused(gbdt)
        with faults.FaultInjector(seed=CHAOS_SEED).plan(
                faults.SPARSE_STAGE, every=1) as inj:
            out = np.asarray(
                fused.transform(gbdt["df_sparse"]).column(gbdt["pred"]),
                float)
            assert inj.fired(faults.SPARSE_STAGE) == []
        np.testing.assert_array_equal(out, gbdt["host"])


# -- host CSR builder interop ------------------------------------------------


class TestRowsToCsrInterop:
    def test_wire_decode_feeds_rows_to_csr(self, gbdt):
        # the decoded wire rows are exactly what the host scorer's
        # rows_to_csr consumes: wire -> decode -> CSR is lossless
        X = gbdt["X"]
        indptr, indices, values = _csr_of(X)
        cols = encode_csr_columns("features", indptr, indices, values,
                                  X.shape[1])
        cols["row_id"] = np.arange(len(X), dtype=np.int64)
        rows = decode_csr_columns(
            decode_frame(encode_frame(cols)))["features"]
        ip2, ix2, v2, width = rows_to_csr(rows, filter_zeros=False)
        assert width == X.shape[1]
        np.testing.assert_array_equal(ip2, indptr)
        np.testing.assert_array_equal(ix2, indices)
        np.testing.assert_array_equal(np.asarray(v2, dtype=np.float32),
                                      values)
