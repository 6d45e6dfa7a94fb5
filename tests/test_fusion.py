"""Device-resident pipeline fusion (core/fusion.py).

The load-bearing contract: ``PipelineModel.fuse()`` output is BITWISE
identical to the unfused stage-by-stage chain — same values, same dtypes,
same nulls — across image chains, featurize->GBDT, featurize->DNN, split
segments, and every fallback path. Plus: compile-cache reuse, bucketing,
profiler annotation, and the serving round trip.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.device_stage import CompileCache, compile_cache
from mmlspark_tpu.core.fusion import FusedPipelineModel, HostStage, Segment, plan
from mmlspark_tpu.core.pipeline import PipelineModel
from mmlspark_tpu.core.schema import ImageSchema
from mmlspark_tpu.featurize.assemble import FastVectorAssembler
from mmlspark_tpu.gbdt.stages import LightGBMClassifier, LightGBMRegressor
from mmlspark_tpu.image.featurizer import ImageFeaturizer
from mmlspark_tpu.image.stages import ImageTransformer, ResizeImageTransformer
from mmlspark_tpu.models.dnn_model import DNNModel
from mmlspark_tpu.models.module import (BatchNorm, Conv2D, Dense, FunctionModel,
                                        GlobalAvgPool, Sequential, relu)
from mmlspark_tpu.stages.basic import UDFTransformer


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------


def toy_cnn(size=16, c=3):
    mod = Sequential([("conv", Conv2D(8, (3, 3))), ("bn", BatchNorm()),
                      ("act", relu()), ("pool", GlobalAvgPool()),
                      ("head", Dense(4))], name="toycnn")
    params, _ = mod.init(jax.random.PRNGKey(0), (size, size, c))
    return FunctionModel(mod, params, (size, size, c),
                         layer_names=["head", "pool"], name="toycnn")


def toy_mlp(d_in=4):
    mod = Sequential([("d1", Dense(8)), ("act", relu()), ("d2", Dense(3))],
                     name="toymlp")
    params, _ = mod.init(jax.random.PRNGKey(1), (d_in,))
    return FunctionModel(mod, params, (d_in,), layer_names=["d2", "d1"],
                         name="toymlp")


def image_df(n=23, seed=3, parts=2, null_at=None):
    rng = np.random.default_rng(seed)
    rows = np.empty(n, dtype=object)
    for i in range(n):
        rows[i] = ImageSchema.make(
            rng.integers(0, 256, (20 + i % 3, 24, 3), dtype=np.uint8),
            f"img{i}")
    if null_at is not None:
        rows[null_at] = None
    return DataFrame.from_dict({"image": rows, "idx": np.arange(float(n))},
                               num_partitions=parts)


def tabular_df(n=120, seed=5, parts=3, dtype=np.float32):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=n).astype(dtype)
    b = rng.normal(size=(n, 3)).astype(dtype)
    y = (a + b[:, 0] > 0).astype(np.float64)
    return DataFrame.from_dict(
        {"a": a, "b": [b[i] for i in range(n)], "label": y},
        num_partitions=parts)


def assert_bitwise(ref_df, got_df):
    """Exact equality: columns, row counts, values AND dtypes."""
    assert ref_df.columns == got_df.columns
    rc, gc = ref_df.collect(), got_df.collect()
    for name in ref_df.columns:
        a, b = rc[name], gc[name]
        assert len(a) == len(b), f"{name}: {len(a)} vs {len(b)} rows"
        if a.dtype != object and b.dtype != object:
            assert a.dtype == b.dtype, f"{name}: {a.dtype} vs {b.dtype}"
            np.testing.assert_array_equal(a, b, err_msg=name)
            continue
        for i, (x, y) in enumerate(zip(a, b)):
            if x is None or y is None:
                assert x is None and y is None, f"{name} row {i} null mismatch"
            elif ImageSchema.is_image(x) or ImageSchema.is_image(y):
                dx, dy = ImageSchema.to_array(x), ImageSchema.to_array(y)
                assert dx.dtype == dy.dtype, f"{name} row {i} image dtype"
                np.testing.assert_array_equal(dx, dy, err_msg=f"{name} row {i}")
                assert x["origin"] == y["origin"], f"{name} row {i} origin"
            elif isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
                x, y = np.asarray(x), np.asarray(y)
                assert x.dtype == y.dtype, \
                    f"{name} row {i}: {x.dtype} vs {y.dtype}"
                np.testing.assert_array_equal(x, y, err_msg=f"{name} row {i}")
            else:
                assert x == y, f"{name} row {i}: {x!r} != {y!r}"


def fused_of(pm, cache=None):
    return FusedPipelineModel(pm.stages, cache=cache or CompileCache())


# --------------------------------------------------------------------------
# bitwise parity across representative pipelines
# --------------------------------------------------------------------------


class TestBitwiseParity:
    def test_image_chain(self):
        df = image_df()
        pm = PipelineModel([
            ImageTransformer().resize(16, 16).flip(1).threshold(100.0, 255.0),
            ImageFeaturizer(scaleFactor=1 / 255., batchSize=8)
            .set_model(toy_cnn())])
        fused = fused_of(pm)
        assert_bitwise(pm.transform(df), fused.transform(df))
        stats = fused.fusion_stats()
        assert stats["n_fused_segments"] == 1
        assert stats["fallbacks"] == []
        seg = stats["segments"][0]
        assert seg["stages"] == ["ImageTransformer", "ImageFeaturizer"]

    def test_image_chain_with_null_and_dropna(self):
        df = image_df(null_at=7)
        pm = PipelineModel([
            ImageTransformer().resize(16, 16).flip(1),
            ImageFeaturizer(scaleFactor=1 / 255., batchSize=8, dropNa=True)
            .set_model(toy_cnn())])
        fused = fused_of(pm)
        ref, got = pm.transform(df), fused.transform(df)
        assert ref.count() == got.count() == 22  # the null row dropped
        assert_bitwise(ref, got)

    def test_resize_stage_heads_a_segment(self):
        df = image_df(n=11)
        pm = PipelineModel([
            ResizeImageTransformer(height=16, width=16, nChannels=3),
            ImageFeaturizer(scaleFactor=1 / 255., batchSize=8)
            .set_model(toy_cnn())])
        fused = fused_of(pm)
        assert_bitwise(pm.transform(df), fused.transform(df))
        assert fused.fusion_stats()["n_fused_segments"] == 1

    def test_featurize_gbdt_classifier(self):
        df = tabular_df()
        asm = FastVectorAssembler(inputCols=["a", "b"])
        model = LightGBMClassifier(labelCol="label", numIterations=8,
                                   numLeaves=7).fit(asm.transform(df))
        pm = PipelineModel([asm, model])
        fused = fused_of(pm)
        assert_bitwise(pm.transform(df), fused.transform(df))
        assert fused.fusion_stats()["fallbacks"] == []

    def test_featurize_gbdt_regressor(self):
        df = tabular_df(seed=6)
        asm = FastVectorAssembler(inputCols=["a", "b"])
        model = LightGBMRegressor(labelCol="label", numIterations=5) \
            .fit(asm.transform(df))
        pm = PipelineModel([asm, model])
        assert_bitwise(pm.transform(df), fused_of(pm).transform(df))

    def test_featurize_dnn(self):
        df = tabular_df(seed=7)
        asm = FastVectorAssembler(inputCols=["a", "b"])
        dnn = DNNModel(inputCol="features", outputCol="emb", batchSize=16)
        dnn.set_model(toy_mlp())
        pm = PipelineModel([asm, dnn])
        fused = fused_of(pm)
        assert_bitwise(pm.transform(df), fused.transform(df))
        seg = fused.fusion_stats()["segments"][0]
        assert seg["stages"] == ["FastVectorAssembler", "DNNModel"]

    def test_dnn_null_rows_propagate(self):
        rng = np.random.default_rng(9)
        rows = np.empty(20, dtype=object)
        for i in range(20):
            rows[i] = rng.normal(size=4).astype(np.float32)
        rows[3] = None
        df = DataFrame.from_dict({"x": rows}, num_partitions=2)
        dnn = DNNModel(inputCol="x", outputCol="emb", batchSize=8)
        dnn.set_model(toy_mlp())
        pm = PipelineModel([dnn])
        ref, got = pm.transform(df), fused_of(pm).transform(df)
        assert got.collect()["emb"][3] is None
        assert_bitwise(ref, got)

    def test_udf_device_mirror_fuses(self):
        rng = np.random.default_rng(11)
        rows = np.empty(30, dtype=object)
        for i in range(30):
            rows[i] = rng.normal(size=4).astype(np.float32)
        df = DataFrame.from_dict({"x": rows}, num_partitions=2)

        def host_double(col):
            out = np.empty(len(col), dtype=object)
            for i, v in enumerate(col):
                out[i] = v * np.float32(2.0)
            return out

        udf = UDFTransformer(inputCol="x", outputCol="x2",
                             vectorizedUdf=host_double,
                             deviceUdf=lambda x: x * np.float32(2.0))
        dnn = DNNModel(inputCol="x2", outputCol="emb", batchSize=8)
        dnn.set_model(toy_mlp())
        pm = PipelineModel([udf, dnn])
        fused = fused_of(pm)
        assert_bitwise(pm.transform(df), fused.transform(df))
        seg = fused.fusion_stats()["segments"][0]
        assert seg["stages"] == ["UDFTransformer", "DNNModel"]

    def test_transform_fused_kwarg(self):
        df = tabular_df(seed=8)
        asm = FastVectorAssembler(inputCols=["a", "b"])
        dnn = DNNModel(inputCol="features", outputCol="emb", batchSize=16)
        dnn.set_model(toy_mlp())
        pm = PipelineModel([asm, dnn])
        assert_bitwise(pm.transform(df), pm.transform(df, fused=True))
        assert pm.fuse() is pm.fuse()  # cached runner


# --------------------------------------------------------------------------
# the head resize, made for the column in one native call
# --------------------------------------------------------------------------


def typed_image_df(dtype, n=23, parts=2, ragged=True, null_at=None, seed=9):
    rng = np.random.default_rng(seed)
    rows = np.empty(n, dtype=object)
    for i in range(n):
        shape = (20 + (i % 3 if ragged else 0), 24 - (i % 2 if ragged else 0), 3)
        img = rng.integers(0, 256, shape, dtype=np.uint8) if dtype == np.uint8 \
            else (rng.normal(size=shape) * 60).astype(np.float32)
        rows[i] = ImageSchema.make(img, f"img{i}")
    if null_at is not None:
        rows[null_at] = None
    return DataFrame.from_dict({"image": rows}, num_partitions=parts)


def resize_head_chain(head, drop_na=False):
    return PipelineModel([
        head, ImageFeaturizer(scaleFactor=1 / 255., batchSize=8, dropNa=drop_na)
        .set_model(toy_cnn())])


class TestColumnResize:
    @pytest.mark.parametrize("dtype", [np.uint8, np.float32])
    @pytest.mark.parametrize("ragged", [False, True], ids=["uniform", "ragged"])
    @pytest.mark.parametrize("head", [
        lambda: ImageTransformer().resize(16, 16),
        lambda: ImageTransformer().resize(16, 16).blur(3, 3).flip(1),
        lambda: ResizeImageTransformer(height=16, width=16),
    ], ids=["resize", "resize-blur-flip", "ResizeImageTransformer"])
    def test_fused_equals_unfused_bitwise(self, head, ragged, dtype):
        df = typed_image_df(dtype, ragged=ragged)
        pm = resize_head_chain(head())
        fused = fused_of(pm)
        assert_bitwise(pm.transform(df), fused.transform(df))
        assert fused.fusion_stats()["fallbacks"] == []

    def test_a_null_row_is_dropped_before_the_column_call(self):
        df = typed_image_df(np.uint8, null_at=5)
        pm = resize_head_chain(ImageTransformer().resize(16, 16), drop_na=True)
        fused = fused_of(pm)
        ref, got = pm.transform(df), fused.transform(df)
        assert ref.count() == got.count() == 22
        assert_bitwise(ref, got)

    def test_without_the_library_both_take_the_numpy_rows(self, monkeypatch):
        from mmlspark_tpu import native_loader

        monkeypatch.setattr(native_loader, "load", lambda: None)
        df = typed_image_df(np.uint8)
        pm = resize_head_chain(ImageTransformer().resize(16, 16))
        fused = fused_of(pm)
        assert_bitwise(pm.transform(df), fused.transform(df))
        assert fused.fusion_stats()["fallbacks"] == []

    @pytest.mark.parametrize("stage", [
        ImageTransformer().resize(16, 16),
        ResizeImageTransformer(height=16, width=16),
        ImageFeaturizer(scaleFactor=1 / 255.).set_model(toy_cnn()),
    ], ids=lambda s: type(s).__name__)
    def test_prepared_rows_fill_a_slot_in_one_copy(self, stage):
        # what `prepare` hands on are views of one array, side by side: the
        # slot filler's _spanning_view takes them, so a batch is one memcpy
        from mmlspark_tpu.core.fusion import SegmentExecutor
        from mmlspark_tpu.parallel.ingest import (IngestStats, _spanning_view,
                                                  rows_to_batch)

        df = typed_image_df(np.uint8, n=12, parts=1)
        dfn = stage.device_fn(df.schema)
        ctx = {}
        rows = dfn.prepare({"image": df.collect()["image"]}, ctx)["image"]
        assert ctx["span_attrs"] == {"resized_rows": 12, "resize_threads": 1}
        deposit = SegmentExecutor._deposit_rows(rows)
        assert deposit is not None
        view = _spanning_view(deposit, deposit[0].shape)
        assert view is not None and view.shape == (12, 16, 16, 3)
        assert all(np.shares_memory(view[i], deposit[i]) for i in range(12))
        stats = IngestStats()
        batch = rows_to_batch(deposit[2:9], stats=stats)
        assert stats.zero_copy_batches == 1 and stats.copied_batches == 0
        assert np.shares_memory(batch, deposit[2])

    def test_a_presized_column_is_handed_on_untouched(self):
        block = np.random.default_rng(4).integers(0, 256, (6, 16, 16, 3),
                                                  dtype=np.uint8)
        col = np.empty(6, dtype=object)
        for i in range(6):
            col[i] = ImageSchema.make(block[i], f"img{i}")
        dfn = ImageTransformer().resize(16, 16).device_fn(None)
        ctx = {}
        rows = dfn.prepare({"image": col}, ctx)["image"]
        assert all(np.shares_memory(rows[i], block[i]) for i in range(6))
        assert ctx["span_attrs"] == {"resized_rows": 6, "resize_threads": 0}


# --------------------------------------------------------------------------
# a handed-through column is emitted from the host rows that were shipped
# --------------------------------------------------------------------------


def presized_image_df(n=23, parts=2, size=16, seed=12):
    # rows already at the resize's target: resize_rows hands them on
    rng = np.random.default_rng(seed)
    rows = np.empty(n, dtype=object)
    for i in range(n):
        rows[i] = ImageSchema.make(
            rng.integers(0, 256, (size, size, 3), dtype=np.uint8), f"img{i}")
    return DataFrame.from_dict({"image": rows}, num_partitions=parts)


def host_emit_of(fused):
    """(columns, bytes) the last transform emitted from host rows, over
    every fused segment."""
    sections = fused.fusion_stats()["host_emit"].values()
    return (sorted(c for s in sections for c in s["cols"]),
            sum(s["bytes"] for s in sections))


IMAGE_BYTES = 16 * 16 * 3  # a resized uint8 row


class TestHostEmit:
    @pytest.mark.parametrize("case", [
        "resize", "presized", "null-row", "short-last-batch", "dropna",
        "ResizeImageTransformer", "renamed-column"])
    def test_image_column_is_bitwise_the_unfused_path(self, case):
        df = {"presized": presized_image_df,
              "null-row": lambda: typed_image_df(np.uint8, null_at=5),
              "dropna": lambda: typed_image_df(np.uint8, null_at=5),
              "short-last-batch": lambda: typed_image_df(np.uint8, n=21,
                                                         parts=1),
              }.get(case, lambda: typed_image_df(np.uint8))()
        col = "resized" if case == "renamed-column" else "image"
        head = ResizeImageTransformer(height=16, width=16) \
            if case == "ResizeImageTransformer" \
            else ImageTransformer(outputCol=col).resize(16, 16)
        pm = PipelineModel([
            head, ImageFeaturizer(inputCol=col, scaleFactor=1 / 255.,
                                  batchSize=8, dropNa=case == "dropna")
            .set_model(toy_cnn())])
        fused = fused_of(pm)
        ref, got = pm.transform(df), fused.transform(df)
        assert_bitwise(ref, got)
        stats = fused.fusion_stats()
        assert stats["fallbacks"] == []
        # the plan names the column before anything runs
        assert stats["segments"][0]["host_emit"] == [col]
        n_valid = sum(r is not None for r in df.collect()["image"])
        assert host_emit_of(fused) == ([col], n_valid * IMAGE_BYTES)
        if case == "null-row":
            assert got.collect()["image"][5] is None
        if case == "dropna":
            assert got.count() == 22

    @pytest.mark.parametrize("head", [
        lambda: [ImageTransformer().resize(18, 18).crop(1, 1, 16, 16)],
        lambda: [ImageTransformer().resize(16, 16).flip(1)],
        # a later in-segment stage that rewrites the column wins
        lambda: [ImageTransformer().resize(16, 16), ImageTransformer().flip(0)],
        # a handed-through column that an earlier stage wrote is a device
        # value, not the staged input
        lambda: [ImageTransformer().resize(16, 16).flip(1), ImageTransformer()],
    ], ids=["crop", "flip", "later-writer", "internal-input"])
    def test_a_column_the_device_wrote_is_read_back(self, head):
        df = typed_image_df(np.uint8)
        pm = PipelineModel(head() + [
            ImageFeaturizer(scaleFactor=1 / 255., batchSize=8)
            .set_model(toy_cnn())])
        fused = fused_of(pm)
        assert_bitwise(pm.transform(df), fused.transform(df))
        stats = fused.fusion_stats()
        assert stats["n_fused_segments"] == 1 and stats["fallbacks"] == []
        assert "host_emit" not in stats["segments"][0]
        assert host_emit_of(fused) == ([], 0)

    def test_a_float64_image_column_still_falls_back_to_the_host(self):
        rng = np.random.default_rng(13)
        rows = np.empty(9, dtype=object)
        for i in range(9):
            rows[i] = ImageSchema.make(rng.normal(size=(16, 16, 3)), f"img{i}")
        df = DataFrame.from_dict({"image": rows})
        pm = resize_head_chain(ImageTransformer().resize(16, 16))
        fused = fused_of(pm)
        assert_bitwise(pm.transform(df), fused.transform(df))
        assert any("dtype gate" in f for f in fused.fusion_stats()["fallbacks"])
        assert host_emit_of(fused) == ([], 0)

    def test_a_narrowed_column_is_read_back(self):
        # int64 rows narrow to int32 on the wire: the host rows are not the
        # bytes the device holds, so the declaration alone does not engage
        from mmlspark_tpu.core.device_stage import DeviceFn
        from mmlspark_tpu.core.fusion import SegmentExecutor
        from mmlspark_tpu.parallel.ingest import IngestStats

        class HandOn(ImageTransformer):
            def device_fn(self, schema):
                return DeviceFn(key=("HandOn",), in_cols=("x",),
                                out_cols=("y",), heavy=True,
                                fn=lambda p, env: {"y": env["x"]},
                                passthrough={"y": "x"})

        seg = Segment()
        stage = HandOn()
        seg.add(stage, stage.device_fn(None))
        assert seg.describe()["host_emit"] == ["y"]
        for dtype, engaged in [(np.int64, False), (np.int32, True)]:
            rows = np.empty(5, dtype=object)
            for i in range(5):
                rows[i] = np.arange(4, dtype=dtype) + i
            ex = SegmentExecutor(seg, CompileCache())
            state = ex._prep_partition({"x": rows})
            assert state.get("host_cols", {}) == ({"y": "x"} if engaged
                                                  else {})
            assert state["keys"] == ([] if engaged else ["y"])
            # a program left with no output at all still runs and emits
            out = ex.run(DataFrame.from_dict({"x": rows}), IngestStats())
            assert ex.fallbacks == []
            assert ex.host_emit == ({"y": 5 * 4 * 4} if engaged else {})
            for got, want in zip(out.collect()["y"], rows):
                np.testing.assert_array_equal(got, want.astype(np.int32))

    def test_output_rows_are_not_views_of_a_slot_buffer(self):
        pm = resize_head_chain(ImageTransformer().resize(16, 16))
        fused = fused_of(pm)
        first = fused.transform(typed_image_df(np.uint8, seed=1)).collect()
        label = next(iter(fused.fusion_stats()["per_segment"]))
        assert fused.fusion_stats()["per_segment"][label]["slot_deposits"] > 0
        pool = fused._get_slot_pool()
        slots = [buf for b in pool._buckets.values() for buf in b.bufs]
        assert slots and not any(
            np.shares_memory(row["data"], buf)
            for row in first["image"] for buf in slots)
        held = [row["data"].copy() for row in first["image"]]
        # the next call refills the same slots with other pixels
        fused.transform(typed_image_df(np.uint8, seed=2))
        for row, was in zip(first["image"], held):
            np.testing.assert_array_equal(row["data"], was)

    def test_the_program_has_one_output_fewer(self):
        df = typed_image_df(np.uint8, ragged=False)
        programs = {}
        for name, head in [("handed-on", ImageTransformer().resize(16, 16)),
                           ("flipped",
                            ImageTransformer().resize(16, 16).flip(1))]:
            cache = CompileCache()
            fused = fused_of(resize_head_chain(head), cache=cache)
            fused.transform(df)
            programs[name] = (cache, fused)
        outs = {name: {fn.out_tree.num_leaves
                       for fn in cache._entries.values()}
                for name, (cache, _) in programs.items()}
        assert outs == {"handed-on": {1}, "flipped": {2}}
        cache, fused = programs["handed-on"]
        # keyed apart from a program of the same segment that returns it
        assert all(("host_emit", ("image",)) in key for key in cache._entries)
        assert host_emit_of(fused) == (["image"], 23 * IMAGE_BYTES)

    def test_a_false_declaration_fails_the_build(self):
        class Liar(ImageTransformer):
            def device_fn(self, schema):
                dfn = super().device_fn(schema)
                dfn.passthrough = {"image": "image"}
                return dfn

        pm = resize_head_chain(Liar().resize(16, 16).flip(1))
        with pytest.raises(ValueError, match="passthrough"):
            fused_of(pm).transform(typed_image_df(np.uint8))

    def test_a_stage_that_hands_nothing_through_reads_zero(self):
        rng = np.random.default_rng(14)
        rows = np.empty(20, dtype=object)
        for i in range(20):
            rows[i] = rng.normal(size=4).astype(np.float32)
        df = DataFrame.from_dict({"x": rows}, num_partitions=2)
        dnn = DNNModel(inputCol="x", outputCol="emb", batchSize=8)
        dnn.set_model(toy_mlp())
        fused = fused_of(PipelineModel([dnn]))
        fused.transform(df)
        stats = fused.fusion_stats()
        assert stats["host_emit"] == {"DNNModel": {"cols": [], "bytes": 0}}
        assert "host_emit" not in stats["segments"][0]

    def test_the_served_path_emits_from_the_host_too(self):
        df = typed_image_df(np.uint8, null_at=3)
        pm = resize_head_chain(ImageTransformer().resize(16, 16))
        cache = CompileCache()
        fused = fused_of(pm, cache=cache)
        assert_bitwise(pm.transform(df), fused.transform_submit(df)())
        assert fused.fusion_stats()["fallbacks"] == []
        assert host_emit_of(fused) == (["image"], 22 * IMAGE_BYTES)
        assert {fn.out_tree.num_leaves
                for fn in cache._entries.values()} == {1}

    def test_the_emit_span_says_what_it_did_not_read_back(self):
        from mmlspark_tpu.obs.trace import default_tracer

        df = typed_image_df(np.uint8, parts=1)
        fused = fused_of(resize_head_chain(ImageTransformer().resize(16, 16)))
        fused.transform(df)
        emit = [s for s in default_tracer().spans() if s["name"] == "emit"][-1]
        assert emit["attrs"] == {"rows": 23, "host_cols": 1,
                                 "host_bytes": 23 * IMAGE_BYTES,
                                 "joined_bytes": 0}


# --------------------------------------------------------------------------
# a writer with no finalize of its own is emitted from its fetched batches
# --------------------------------------------------------------------------


def emit_joined(ex, state, collected, host):
    """The joined form: what ``emit`` gave when it first joined every
    output's fetched batches into one array (the plain reference of
    ``_emit_columns``; nothing of it is shared with the code under test
    but the stages' own finalize functions)."""
    full = {k: np.concatenate(v, axis=0) for k, v in collected.items()}
    full.update(host)
    by_writer = {}
    for k, i in state["readback"]:
        by_writer.setdefault(i, {})[k] = full[k]
    n, n_valid, valid = state["n"], state["n_valid"], state["valid"]
    out_part = dict(state["part"])
    for i, dfn in enumerate(ex.segment.dfns):
        if i not in by_writer:
            continue
        if dfn.finalize is not None:
            cols = dfn.finalize(by_writer[i], state["ctx"])
        else:
            cols = {}
            for name, arr in by_writer[i].items():
                cols[name] = arr
                if arr.ndim > 1:
                    cols[name] = np.empty(len(arr), dtype=object)
                    for j in range(len(arr)):
                        cols[name][j] = arr[j]
        for c in dfn.out_cols:
            if c in cols and n_valid == n:
                out_part[c] = cols[c]
            elif c in cols:
                out_part[c] = np.empty(n, dtype=object)
                out_part[c][np.flatnonzero(valid)] = cols[c]
    if any(d.drop_invalid for d in ex.segment.dfns) and n_valid < n:
        out_part = {k: v[valid] for k, v in out_part.items()}
    return out_part


def device_stage(out_fn, out_cols=("y",), **kwargs):
    """A one-off device stage over the float32 column ``x``; every
    DeviceFn field comes through ``kwargs``."""
    from mmlspark_tpu.core.device_stage import DeviceFn

    class Probe(ImageTransformer):
        def device_fn(self, schema):
            return DeviceFn(key=("Probe", out_cols), in_cols=("x",),
                            out_cols=out_cols, heavy=True,
                            fn=lambda p, env: out_fn(env["x"]), **kwargs)

    seg = Segment()
    seg.add(Probe(), Probe().device_fn(None))
    return seg


def rows_finalize(outs, ctx):
    # a finalize of the stage's own: it takes ONE whole-partition array
    # (it reverses each row, so the test sees that it ran)
    y = outs["y"]
    assert isinstance(y, np.ndarray) and y.ndim == 2 and y.flags.writeable
    col = np.empty(len(y), dtype=object)
    for i in range(len(y)):
        col[i] = y[i, ::-1].astype(np.float64)
    return {"y": col}


def plan_segment(pm, df):
    nodes = fused_of(pm)._plan_for(df.schema)
    assert [type(n).__name__ for n in nodes] == ["Segment"]
    return nodes[0]


def dnn_segment(df, **params):
    dnn = DNNModel(inputCol="x", batchSize=8, **params)
    dnn.set_model(toy_mlp())
    return plan_segment(PipelineModel([dnn]), df)


def null_rows(df, *at):
    col = df.partitions[0]["x"]
    for i in at:
        col[i] = None
    return df


# case -> (the frame, its segment, the columns whose rows are views of the
# batch they came back in, the bytes emit still joins a partition)
EMIT_CASES = {
    "one-batch": lambda: (
        df := vector_df((7,)), dnn_segment(df, outputCol="emb"), ["emb"], 0),
    "short-last-batch": lambda: (
        df := vector_df((13,)), dnn_segment(df, outputCol="emb"), ["emb"], 0),
    "nulls-scattered": lambda: (
        df := null_rows(vector_df((21,)), 0, 9, 20),
        dnn_segment(df, outputCol="emb"), ["emb"], 0),
    "drop-invalid": lambda: (
        df := typed_image_df(np.uint8, n=21, parts=1, null_at=9),
        plan_segment(resize_head_chain(ImageTransformer().resize(16, 16),
                                       drop_na=True), df), ["features"], 0),
    "two-columns": lambda: (
        df := vector_df((13,)),
        dnn_segment(df, fetchDict={"hidden": "d1", "out": "d2"}),
        ["hidden", "out"], 0),
    "one-d-output": lambda: (
        vector_df((13,)), device_stage(lambda x: {"y": x.sum(axis=1)}),
        [], 13 * 4),
    "own-finalize": lambda: (
        vector_df((13,)),
        device_stage(lambda x: {"y": x * 2}, finalize=rows_finalize),
        [], 13 * 4 * 4),
    "own-finalize-then-default": lambda: (
        df := tabular_df(n=13, parts=1),
        plan_segment(PipelineModel([
            FastVectorAssembler(inputCols=["a", "b"]),
            DNNModel(inputCol="features", outputCol="emb", batchSize=8)
            .set_model(toy_mlp())]), df), ["emb"], 13 * 4 * 4),
}


class TestEmitFromBatches:
    @pytest.mark.parametrize("path", ["run", "submit"])
    @pytest.mark.parametrize("case", sorted(EMIT_CASES))
    def test_the_partition_is_bitwise_the_joined_form(self, case, path,
                                                      monkeypatch):
        from mmlspark_tpu.core.fusion import SegmentExecutor
        from mmlspark_tpu.obs.trace import (Tracer, root_span,
                                            set_default_tracer)
        from mmlspark_tpu.parallel.ingest import IngestStats

        df, seg, view_cols, joined_bytes = EMIT_CASES[case]()
        emits = []
        emit = SegmentExecutor._emit_columns

        def spy(self, state, collected, obs, host):
            emits.append((state, collected,
                          emit_joined(self, state, collected, host)))
            return emit(self, state, collected, obs, host)

        monkeypatch.setattr(SegmentExecutor, "_emit_columns", spy)
        tracer = Tracer(service="batch")
        old = set_default_tracer(tracer)
        try:
            ex = SegmentExecutor(seg, CompileCache())
            with root_span("call"):     # the binding the spans go under
                out = ex.run(df, IngestStats()) if path == "run" \
                    else ex.submit_run(df, IngestStats())()
        finally:
            set_default_tracer(old)
        assert ex.fallbacks == [] and len(emits) == 1
        state, collected, want = emits[0]
        got = out.partitions[0]
        assert_bitwise(DataFrame([want]), out)
        assert all(want[c].shape == got[c].shape for c in want)
        # rows of a writer with no finalize of its own: views of the batch
        # they were fetched in, read-only as the batch is
        batch = seg.batch_size()
        for name in view_cols:
            assert len(collected[name]) == -(-state["n_valid"] // batch)
            rows = [r for r in got[name] if r is not None]
            assert len(rows) == state["n_valid"]
            for i, row in enumerate(rows):
                assert np.shares_memory(row, collected[name][i // batch])
                assert not row.flags.writeable
            with pytest.raises(ValueError, match="read-only"):
                rows[0][...] = 0
        span = [s for s in tracer.spans() if s["name"] == "emit"]
        assert [s["attrs"]["joined_bytes"] for s in span] == [joined_bytes]
        assert ex.joined_bytes == joined_bytes
        if case == "one-d-output":      # still one numeric array
            assert got["y"].dtype == np.float32 and got["y"].shape == (13,)
        if case == "nulls-scattered":
            assert [i for i, r in enumerate(got["emb"]) if r is None] \
                == [0, 9, 20]
        if case == "drop-invalid":
            assert state["n"] == 21 and len(got["features"]) == 20

# --------------------------------------------------------------------------
# planning: splits, demotion, terminal stages
# --------------------------------------------------------------------------


class TestPlanning:
    def test_host_stage_splits_segment(self):
        df = tabular_df(seed=12)
        asm = FastVectorAssembler(inputCols=["a", "b"])

        def host_sum(col):
            return np.asarray([float(v.sum()) for v in col], dtype=np.float64)

        udf = UDFTransformer(inputCol="features", outputCol="fsum",
                             vectorizedUdf=host_sum)  # no device mirror
        dnn = DNNModel(inputCol="features", outputCol="emb", batchSize=16)
        dnn.set_model(toy_mlp())
        pm = PipelineModel([asm, udf, dnn])
        fused = fused_of(pm)
        nodes = fused._plan_for(df.schema)
        kinds = [type(n).__name__ for n in nodes]
        # the host-only UDF splits; the lone assembler run is demoted to
        # host (no heavy stage to amortize a device round trip)
        assert kinds == ["HostStage", "HostStage", "Segment"]
        assert_bitwise(pm.transform(df), fused.transform(df))

    def test_light_only_segment_demoted(self):
        df = tabular_df(seed=13)
        asm = FastVectorAssembler(inputCols=["a", "b"])
        nodes = plan([asm], df.schema.copy())
        assert all(isinstance(n, HostStage) for n in nodes)

    def test_gbdt_is_terminal(self):
        df = tabular_df(seed=14)
        asm = FastVectorAssembler(inputCols=["a", "b"])
        model = LightGBMRegressor(labelCol="label", numIterations=3) \
            .fit(asm.transform(df))
        dnn = DNNModel(inputCol="features", outputCol="emb", batchSize=16)
        dnn.set_model(toy_mlp())
        nodes = plan([asm, model, dnn], df.schema.copy())
        segs = [n for n in nodes if isinstance(n, Segment)]
        # GBDT finalizes on host (f64 objective math) => ends its segment
        assert [s.describe()["stages"] for s in segs] == \
            [["FastVectorAssembler", "LightGBMRegressionModel"], ["DNNModel"]]

    def test_image_host_prefix_op_starts_new_segment(self):
        # a mid-chain resize cannot replay on device-resident input: the
        # planner must split rather than silently lose exactness
        df = image_df(n=9)
        t1 = ImageTransformer().resize(16, 16).flip(1)
        t2 = ImageTransformer().resize(8, 8)  # host-prep op, internal input
        feat = ImageFeaturizer(scaleFactor=1 / 255., batchSize=8) \
            .set_model(toy_cnn(size=8))
        pm = PipelineModel([t1, t2, feat])
        fused = fused_of(pm)
        nodes = fused._plan_for(df.schema)
        # t2's host-prep resize cannot consume t1's device output: t1 is cut
        # off (and, alone, demoted to host); t2 heads the fused segment
        assert [type(n).__name__ for n in nodes] == ["HostStage", "Segment"]
        assert nodes[1].describe()["stages"] == \
            ["ImageTransformer", "ImageFeaturizer"]
        assert_bitwise(pm.transform(df), fused.transform(df))


# --------------------------------------------------------------------------
# fallbacks: anything the bitwise contract cannot hold for -> host path
# --------------------------------------------------------------------------


class TestFallbacks:
    def test_f64_inputs_fall_back(self):
        df = tabular_df(seed=15, dtype=np.float64)
        asm = FastVectorAssembler(inputCols=["a", "b"])
        model = LightGBMRegressor(labelCol="label", numIterations=4) \
            .fit(asm.transform(df))
        pm = PipelineModel([asm, model])
        fused = fused_of(pm)
        assert_bitwise(pm.transform(df), fused.transform(df))
        assert any("dtype gate" in f for f in fused.fusion_stats()["fallbacks"])

    def test_sparse_rows_fall_back(self):
        rng = np.random.default_rng(16)
        n = 40
        dense = rng.normal(size=(n, 4)).astype(np.float64)
        y = (dense[:, 0] > 0).astype(np.float64)
        feats = np.empty(n, dtype=object)
        for i in range(n):
            feats[i] = {"indices": np.array([0, 2]),
                        "values": dense[i, [0, 2]], "size": 4}
        df_fit = DataFrame.from_dict(
            {"features": [dense[i] for i in range(n)], "label": y})
        model = LightGBMClassifier(labelCol="label", numIterations=4,
                                   numLeaves=5).fit(df_fit)
        df = DataFrame.from_dict({"features": feats}, num_partitions=2)
        pm = PipelineModel([model])
        fused = fused_of(pm)
        assert_bitwise(pm.transform(df), fused.transform(df))
        assert any("sparse" in f for f in fused.fusion_stats()["fallbacks"])

    def test_ragged_rows_fall_back(self):
        rng = np.random.default_rng(17)
        rows = np.empty(12, dtype=object)
        for i in range(12):
            rows[i] = rng.normal(size=4 if i % 2 else 5).astype(np.float32)
        df = DataFrame.from_dict({"x": rows})
        dnn = DNNModel(inputCol="x", outputCol="emb", batchSize=8)
        dnn.set_model(toy_mlp())
        pm = PipelineModel([dnn])
        fused = fused_of(pm)
        with pytest.raises(ValueError):
            pm.transform(df).collect()  # unfused raises on ragged rows too
        with pytest.raises(ValueError):
            fused.transform(df).collect()

    def test_shape_mismatch_falls_back_to_host(self):
        # featurizer fed 8x8 device batches but backbone wants 16x16: the
        # trace gate fires and the segment reruns on host (bitwise anyway)
        df = image_df(n=9)
        t1 = ImageTransformer().resize(8, 8).flip(1)
        feat = ImageFeaturizer(scaleFactor=1 / 255., batchSize=8) \
            .set_model(toy_cnn(size=16))
        pm = PipelineModel([t1, feat])
        fused = fused_of(pm)
        assert_bitwise(pm.transform(df), fused.transform(df))
        assert len(fused.fusion_stats()["fallbacks"]) > 0


# --------------------------------------------------------------------------
# one ring a call, fed by a stream of partitions
# --------------------------------------------------------------------------


def vector_df(sizes, seed=30, ragged_at=None):
    """float32 rows of width 4 in partitions of the given sizes (0 = an
    empty partition); ``ragged_at``: that partition gets rows of two widths,
    which no stack can hold."""
    rng = np.random.default_rng(seed)
    parts = []
    for k, n in enumerate(sizes):
        col = np.empty(n, dtype=object)
        for i in range(n):
            wide = 5 if k == ragged_at and i % 2 else 4
            col[i] = rng.normal(size=wide).astype(np.float32)
        parts.append({"x": col, "idx": np.arange(float(n)) + 100 * k})
    return DataFrame(parts)


def dnn_chain(batch=8):
    dnn = DNNModel(inputCol="x", outputCol="emb", batchSize=batch)
    dnn.set_model(toy_mlp())
    return PipelineModel([dnn])


def live_threads(*names):
    return [t.name for t in threading.enumerate()
            if t.name.startswith(names) and t.is_alive()]


def wait_gone(*names, seconds=5.0):
    end = time.time() + seconds
    while live_threads(*names) and time.time() < end:
        time.sleep(0.01)
    return live_threads(*names)


RING_THREADS = ("partition-prep", "device-prefetch", "slot-fill")


class TestPartitionStream:
    @pytest.mark.parametrize("sizes", [(19,), (16, 7), (9, 0, 17, 1, 12)],
                             ids=["1", "2", "5-uneven-empty"])
    def test_run_submit_and_unfused_agree_bitwise(self, sizes):
        # batches of 8: ragged last batches, a partition of one row, an
        # empty one; every partition's rows come back in its place
        pm, df = dnn_chain(), vector_df(sizes)
        fused = fused_of(pm)
        ref = pm.transform(df)
        got = fused.transform(df)
        assert_bitwise(ref, got)
        assert [len(p["idx"]) for p in got.partitions] == list(sizes)
        assert fused.fusion_stats()["fallbacks"] == []
        assert_bitwise(ref, fused.transform_submit(df)())
        assert fused.fusion_stats()["fallbacks"] == []

    def test_image_chain_of_five_partitions(self):
        pm = PipelineModel([
            ImageTransformer().resize(16, 16),
            ImageFeaturizer(scaleFactor=1 / 255., batchSize=4)
            .set_model(toy_cnn())])
        df = image_df(n=23, parts=5, null_at=7)
        fused = fused_of(pm)
        assert_bitwise(pm.transform(df), fused.transform(df))
        assert_bitwise(pm.transform(df), fused.transform_submit(df)())

    @pytest.mark.parametrize("sizes,ahead", [((16, 16), 1), ((19,), 0),
                                             ((9, 0, 17, 1, 12), 4)])
    def test_one_ring_a_call_and_the_partitions_ahead_counted(self, sizes,
                                                              ahead):
        fused = fused_of(dnn_chain())
        fused.transform(vector_df(sizes))
        summary = fused.last_ingest_stats.summary()
        assert summary["rings"] == 1
        assert summary["partitions"] == len(sizes)
        assert summary["partitions_ahead"] == ahead
        per_seg = fused.fusion_stats()["per_segment"]["DNNModel"]
        assert (per_seg["rings"], per_seg["partitions"],
                per_seg["partitions_ahead"]) == (1, len(sizes), ahead)
        assert summary["n_batches"] == sum(-(-n // 8) for n in sizes)

    def test_a_partition_with_nothing_to_ship_builds_no_ring(self):
        fused = fused_of(dnn_chain())
        out = fused.transform(vector_df((0,)))
        assert out.count() == 0
        summary = fused.last_ingest_stats.summary()
        assert "rings" not in summary and summary["partitions"] == 1

    def test_a_fallback_at_prepare_takes_its_partition_alone(self):
        # the middle partition's rows are of two widths: no stack holds
        # them, and the unfused path refuses them too; so that one
        # partition goes to the host path, which here is made to answer
        from mmlspark_tpu.core.fusion import SegmentExecutor

        pm, df = dnn_chain(), vector_df((9, 6, 12), ragged_at=1)
        fused = fused_of(pm)
        seen = []

        def host(self, part, schema, obs=None):
            seen.append(len(part["x"]))
            out = dict(part)
            out["emb"] = np.array([None] * len(part["x"]), dtype=object)
            return [out]

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(SegmentExecutor, "_host_partition", host)
            got = fused.transform(df)
        assert seen == [6]
        assert [len(p["idx"]) for p in got.partitions] == [9, 6, 12]
        stats = fused.fusion_stats()
        assert len(stats["fallbacks"]) == 1 and "ragged" in stats["fallbacks"][0]
        # the neighbours took the device path, and their rows are the
        # unfused path's, in order
        summary = fused.last_ingest_stats.summary()
        assert (summary["rings"], summary["n_batches"]) == (1, 2 + 2)
        ok = DataFrame([df.partitions[0], df.partitions[2]])
        ref = pm.transform(ok).partitions
        for want, have in zip(ref, (got.partitions[0], got.partitions[2])):
            assert_bitwise(DataFrame([want]), DataFrame([have]))
        assert all(v is None for v in got.partitions[1]["emb"])

    @pytest.mark.parametrize("submit", [False, True], ids=["run", "submit"])
    def test_a_build_that_refuses_demotes_its_partition_alone(self, submit):
        # the middle partition's 9 rows pad to a bucket of 16, a program of
        # its own, and that build refuses: the partition reruns on the
        # host, its neighbours (buckets of 32) stay on the device
        from mmlspark_tpu.core.device_stage import FusionUnsupported
        from mmlspark_tpu.core.fusion import SegmentExecutor

        pm, df = dnn_chain(batch=32), vector_df((64, 9, 20))
        fused = fused_of(pm)
        build = SegmentExecutor._build

        def refusing(self, params_dev, x, keys, **kw):
            if len(x["x"]) == 16:
                raise FusionUnsupported("no program for a bucket of 16")
            return build(self, params_dev, x, keys, **kw)

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(SegmentExecutor, "_build", refusing)
            got = fused.transform_submit(df)() if submit \
                else fused.transform(df)
        assert_bitwise(pm.transform(df), got)
        stats = fused.fusion_stats()
        assert stats["fallbacks"] == ["DNNModel: no program for a bucket of 16"]
        assert sum(stats["devices"].values()) == 3    # 2 + 1 batches
        assert not wait_gone(*RING_THREADS)

    def test_an_error_on_the_look_ahead_thread_reaches_the_caller(
            self, monkeypatch):
        from mmlspark_tpu.image import stages as image_stages

        resize = image_stages._resize_column
        where = []

        def failing(imgs, height, width, ctx=None):
            where.append(threading.current_thread().name)
            if len(where) == 2:
                raise RuntimeError("the second partition's resize broke")
            return resize(imgs, height, width, ctx=ctx)

        monkeypatch.setattr(image_stages, "_resize_column", failing)
        fused = fused_of(PipelineModel([
            ImageTransformer().resize(16, 16),
            ImageFeaturizer(scaleFactor=1 / 255., batchSize=4)
            .set_model(toy_cnn())]))
        with pytest.raises(RuntimeError, match="resize broke"):
            fused.transform(image_df(n=24, parts=3))
        assert where[1] == "partition-prep" and where[0] != where[1]
        assert not wait_gone(*RING_THREADS)

    def test_the_look_ahead_holds_one_prepared_partition(self, monkeypatch):
        # count partitions that are prepared and not yet taken by the
        # batch stream: never more than one, whatever the timing
        from mmlspark_tpu.core.fusion import SegmentExecutor

        lock = threading.Lock()
        waiting, most = [0], [0]
        prep = SegmentExecutor._prep_partition
        fill = SegmentExecutor._fill_ahead

        def prepared(self, part, stats=None, obs=None, ahead=False):
            state = prep(self, part, stats, obs, ahead)
            if ahead:
                with lock:
                    waiting[0] += 1
                    most[0] = max(most[0], waiting[0])
            return state

        def taken(self, state, stats, obs=None):
            with lock:
                waiting[0] = max(0, waiting[0] - 1)
            return fill(self, state, stats, obs)

        monkeypatch.setattr(SegmentExecutor, "_prep_partition", prepared)
        monkeypatch.setattr(SegmentExecutor, "_fill_ahead", taken)
        pm, df = dnn_chain(batch=4), vector_df((8,) * 6)
        fused = fused_of(pm)
        assert_bitwise(pm.transform(df), fused.transform(df))
        assert most[0] == 1
        assert fused.last_ingest_stats.summary()["partitions_ahead"] == 5


# --------------------------------------------------------------------------
# compile cache + bucketing
# --------------------------------------------------------------------------


class TestCompileCache:
    def test_executables_reused_across_calls(self):
        df = tabular_df(seed=18)
        asm = FastVectorAssembler(inputCols=["a", "b"])
        dnn = DNNModel(inputCol="features", outputCol="emb", batchSize=16)
        dnn.set_model(toy_mlp())
        cache = CompileCache()
        fused = fused_of(PipelineModel([asm, dnn]), cache=cache)
        fused.transform(df)  # warmup: compiles
        warm = cache.stats()
        assert warm["misses"] >= 1
        for _ in range(3):
            fused.transform(df)
        stats = cache.stats()
        assert stats["misses"] == warm["misses"]  # no recompiles
        post = ((stats["hits"] - warm["hits"])
                / max((stats["hits"] - warm["hits"])
                      + (stats["misses"] - warm["misses"]), 1))
        assert post >= 0.9  # acceptance: hit rate after warmup
        assert stats["compile_time_s"] > 0

    def test_bucketed_shapes_bound_compiles(self):
        # ragged partition tails pad to power-of-two buckets: many partition
        # sizes, O(log batch) compiled shapes
        rng = np.random.default_rng(19)
        cache = CompileCache()
        dnn = DNNModel(inputCol="x", outputCol="emb", batchSize=16)
        dnn.set_model(toy_mlp())
        fused = fused_of(PipelineModel([dnn]), cache=cache)
        for n in (5, 9, 16, 23, 31, 37):
            rows = np.empty(n, dtype=object)
            for i in range(n):
                rows[i] = rng.normal(size=4).astype(np.float32)
            fused.transform(DataFrame.from_dict({"x": rows}))
        # buckets: 8, 16 (and full 16-batches) => at most 3 distinct shapes
        assert cache.entries <= 3

    def test_global_cache_shared(self):
        assert compile_cache() is compile_cache()

    def test_the_k1_program_keeps_the_name_the_benchmark_matches(self):
        """Two contracts with readers outside the package. The benchmark's
        device-trace metrics find the fused program by its module's name
        (``MODULE_PATTERN`` in benchmarks/layer_metrics; the trace shows
        ``<module>(<fingerprint>)``). JAX's persistent cache, the fleet's
        tier and the cost model read the K=1 CompileCache key and shape
        key, which carry nothing of the K-step program's."""
        import pathlib
        import re

        cache = CompileCache()
        fused = fused_of(PipelineModel([
            ImageTransformer().resize(16, 16).flip(1),
            ImageFeaturizer(scaleFactor=1 / 255., batchSize=8)
            .set_model(toy_cnn())]), cache=cache)
        fused.transform(image_df())
        assert fused.fusion_stats()["fallbacks"] == []
        (seg,) = fused._last_plan
        names = {re.match(r"HloModule ([^\s,]+)", fn.as_text()).group(1)
                 for fn in cache._entries.values()}
        assert names == {"jit_fused"}
        readers = sorted((pathlib.Path(__file__).parent.parent / "benchmarks"
                          / "layer_metrics").glob("*_roofline_pct.*.py"))
        assert readers
        for reader in readers:
            pattern = re.search(r'^MODULE_PATTERN = r"(.+)"$',
                                reader.read_text(), re.M).group(1)
            assert re.search(pattern, "jit_fused(1234567890)"), reader.name
        for key in cache._entries:
            assert key[0] == seg.key and len(key) == 3, key
            assert key[2] == ("device", jax.devices()[0].id)
        shapes = [shape for label, shape in cache._costs if label == seg.label]
        assert shapes and all(shape.startswith("image=") for shape in shapes)

# --------------------------------------------------------------------------
# observability: profiler annotations + stats surfaces
# --------------------------------------------------------------------------


class TestObservability:
    def test_annotate_named_per_segment(self, monkeypatch):
        from mmlspark_tpu.core import fusion as fusion_mod

        seen = []
        import contextlib

        @contextlib.contextmanager
        def recording_annotate(name):
            seen.append(name)
            yield

        monkeypatch.setattr(fusion_mod.profiling, "annotate",
                            recording_annotate)
        df = tabular_df(seed=20)
        asm = FastVectorAssembler(inputCols=["a", "b"])
        dnn = DNNModel(inputCol="features", outputCol="emb", batchSize=16)
        dnn.set_model(toy_mlp())
        fused = fused_of(PipelineModel([asm, dnn]))
        fused.transform(df)
        assert any(s == "fused:FastVectorAssembler+DNNModel" for s in seen)

    def test_ingest_stats_surface(self):
        df = tabular_df(seed=21)
        asm = FastVectorAssembler(inputCols=["a", "b"])
        dnn = DNNModel(inputCol="features", outputCol="emb", batchSize=16)
        dnn.set_model(toy_mlp())
        fused = fused_of(PipelineModel([asm, dnn]))
        assert fused.last_ingest_stats is None
        fused.transform(df)
        summary = fused.last_ingest_stats.summary()
        assert summary["rows"] == df.count()
        assert summary["bytes"] > 0
        per_seg = fused.fusion_stats()["per_segment"]
        assert list(per_seg) == ["FastVectorAssembler+DNNModel"]

    def test_fused_model_not_registered_and_saves_plain(self, tmp_path):
        from mmlspark_tpu.core.pipeline import (PipelineStage,
                                                registered_stages)

        assert "FusedPipelineModel" not in registered_stages()
        dnn = DNNModel(inputCol="x", outputCol="emb", batchSize=8)
        dnn.set_model(toy_mlp())
        fused = fused_of(PipelineModel([dnn]))
        path = str(tmp_path / "fused_pm")
        fused.save(path)
        loaded = PipelineStage.load(path)
        assert type(loaded) is PipelineModel  # fusion is not persisted
        rng = np.random.default_rng(22)
        rows = np.empty(6, dtype=object)
        for i in range(6):
            rows[i] = rng.normal(size=4).astype(np.float32)
        df = DataFrame.from_dict({"x": rows})
        assert_bitwise(loaded.transform(df), fused.transform(df))


# --------------------------------------------------------------------------
# serving round trip
# --------------------------------------------------------------------------


class TestServingFused:
    def test_round_trip_and_stats(self):
        from mmlspark_tpu.serving.server import serve_pipeline

        dnn = DNNModel(inputCol="x", outputCol="reply", batchSize=8)
        dnn.set_model(toy_mlp())
        pm = PipelineModel([dnn])
        server = serve_pipeline(pm, input_col="x", reply_col="reply",
                                parse="json", port=0, fused=True)
        with server:
            body = json.dumps([0.5, -1.0, 2.0, 0.25]).encode("utf-8")
            req = urllib.request.Request(server.address, data=body,
                                         method="POST")
            with urllib.request.urlopen(req, timeout=30) as resp:
                got = np.asarray(json.loads(resp.read()), dtype=np.float32)
            # oracle: the unfused chain on the same parsed payload
            x = np.empty(1, dtype=object)
            x[0] = np.asarray([0.5, -1.0, 2.0, 0.25], dtype=np.float64)
            ref = pm.transform(DataFrame.from_dict({"x": x})) \
                .collect()["reply"][0]
            np.testing.assert_array_equal(ref, got)
            stats_url = server.address.rstrip("/") + "/_mmlspark/stats"
            with urllib.request.urlopen(stats_url, timeout=10) as resp:
                stats = json.loads(resp.read())
        assert "fusion" in stats
        assert stats["fusion"]["n_fused_segments"] == 1
        # the reply's row was a view of the batch it came back in
        assert stats["fusion"]["joined_bytes"] == {"DNNModel": 0}
        assert stats["fusion"]["compile_cache"]["hits"] >= 1
