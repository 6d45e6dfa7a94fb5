"""Spans of the batch path (obs/trace.py default recorder, core/fusion.py,
parallel/ingest.py, gbdt/booster.py): one tree a call, one span per batch
per phase at the finest, clocked by the reads that fill BatchTiming."""

import numpy as np
import pytest

import jax

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.device_stage import CompileCache
from mmlspark_tpu.core.fusion import FusedPipelineModel
from mmlspark_tpu.core.schema import ImageSchema
from mmlspark_tpu.image.featurizer import ImageFeaturizer
from mmlspark_tpu.image.stages import ImageTransformer
from mmlspark_tpu.models.module import (BatchNorm, Conv2D, Dense, FunctionModel,
                                        GlobalAvgPool, Sequential, relu)
from mmlspark_tpu.obs import trace as obs_trace
from mmlspark_tpu.obs.trace import Tracer, batch_context, set_default_tracer
from mmlspark_tpu.stages.basic import UDFTransformer

SEGMENT = "segment:ImageTransformer+ImageFeaturizer"

# span name -> its parent's name, for one fused image transform
TREE = {
    "transform": None,
    SEGMENT: "transform",
    "put_params": SEGMENT,
    "partition": SEGMENT,
    "overlay": SEGMENT,
    "prepare": "partition",
    "prepare:ImageTransformer": "prepare",
    "stack": "prepare",
    "fill": "partition",
    "h2d": "partition",
    "queue": "partition",
    "dispatch": "partition",
    "in_flight": "partition",
    "compute_wait": "partition",
    "readback": "partition",
    "emit": "partition",
    "finalize:ImageTransformer": "emit",
    "finalize:ImageFeaturizer": "emit",
}
# spans of one call: 2 partitions of 2 batches. One ring carries both, two
# deep, so the caller's work for them interleaves and a ``partition`` span
# is a stretch of it: 0 [prepare .. drain 0], 1 [dispatch 2], 0 [drain 1,
# emit], 1 [dispatch 3 .. emit]
COUNTS = {"transform": 1, SEGMENT: 1, "put_params": 1, "overlay": 1,
          "partition": 4, "prepare": 2, "prepare:ImageTransformer": 2,
          "stack": 2, "emit": 2, "finalize:ImageTransformer": 2,
          "finalize:ImageFeaturizer": 2, "fill": 4, "h2d": 4, "queue": 4,
          "dispatch": 4, "in_flight": 4, "compute_wait": 4, "readback": 4}


def toy_cnn(size=16, c=3):
    mod = Sequential([("conv", Conv2D(8, (3, 3))), ("bn", BatchNorm()),
                      ("act", relu()), ("pool", GlobalAvgPool()),
                      ("head", Dense(4))], name="toycnn")
    params, _ = mod.init(jax.random.PRNGKey(0), (size, size, c))
    return FunctionModel(mod, params, (size, size, c),
                         layer_names=["head", "pool"], name="toycnn")


def image_df(n=32, parts=2, seed=3):
    rng = np.random.default_rng(seed)
    rows = np.empty(n, dtype=object)
    for i in range(n):
        rows[i] = ImageSchema.make(
            rng.integers(0, 256, (20, 24, 3), dtype=np.uint8), f"img{i}")
    return DataFrame.from_dict({"image": rows}, num_partitions=parts)


def image_chain(batch=8, cache=None, **kwargs):
    return FusedPipelineModel(
        [ImageTransformer().resize(16, 16),
         ImageFeaturizer(scaleFactor=1 / 255., batchSize=batch)
         .set_model(toy_cnn())], cache=cache or CompileCache(), **kwargs)


def features(df):
    return np.stack([np.asarray(v) for v in df.column("features")])


@pytest.fixture()
def recorder():
    """A fresh default recorder for the test; the old one comes back."""
    mine = Tracer(service="batch")
    old = set_default_tracer(mine)
    yield mine
    set_default_tracer(old)


@pytest.fixture()
def warm_call(recorder):
    """(model, spans of one warm call of 2 partitions x 2 batches)."""
    fused = image_chain()
    df = image_df()
    fused.transform(df)
    recorder.clear()
    fused.transform(df)
    return fused, recorder.spans()


class TestTree:
    @pytest.mark.parametrize("name", sorted(TREE))
    def test_names_and_parent_links(self, warm_call, name):
        _fused, spans = warm_call
        by_id = {s["span_id"]: s for s in spans}
        mine = [s for s in spans if s["name"] == name]
        assert len(mine) == COUNTS[name]
        for s in mine:
            parent = by_id.get(s["parent_id"])
            assert (parent["name"] if parent else None) == TREE[name]

    def test_nothing_else_is_recorded(self, warm_call):
        _fused, spans = warm_call
        assert {s["name"] for s in spans} == set(TREE)
        assert len(spans) == sum(COUNTS.values())

    def test_one_trace_id_a_call(self, recorder):
        fused, df = image_chain(), image_df()
        fused.transform(df)
        fused.transform(df)
        roots = [s for s in recorder.spans() if s["name"] == "transform"]
        assert len(roots) == 2 and roots[0]["trace_id"] != roots[1]["trace_id"]
        for root in roots:
            assert root["parent_id"] is None
            assert root["attrs"] == {"rows": 32, "partitions": 2, "segments": 1}
        by_trace = {}
        for s in recorder.spans():
            by_trace.setdefault(s["trace_id"], []).append(s)
        assert sorted(map(len, by_trace.values()))[0] == sum(COUNTS.values())

    def test_children_lie_inside_their_parents_on_one_thread(self, warm_call):
        _fused, spans = warm_call
        by_id = {s["span_id"]: s for s in spans}
        for s in spans:
            parent = by_id.get(s["parent_id"])
            if parent is None or s["thread"] != parent["thread"]:
                continue
            assert s["t0"] >= parent["t0"] - 1e-3
            assert s["t0"] + s["dur_s"] <= parent["t0"] + parent["dur_s"] + 1e-3

    def test_batch_ordinals_run_through_the_call(self, warm_call):
        _fused, spans = warm_call
        for name in ("fill", "h2d", "queue", "dispatch", "in_flight",
                     "compute_wait", "readback"):
            got = sorted(s["attrs"]["batch"] for s in spans
                         if s["name"] == name)
            assert got == [0, 1, 2, 3], name

    def test_spans_on_the_calling_thread_nest(self, warm_call):
        # what the benchmark's self time and idle attribution rest on: two
        # spans of the calling thread overlap only if one is the other's
        # ancestor, and a child lies inside its parent there
        _fused, spans = warm_call
        by_id = {s["span_id"]: s for s in spans}
        root = next(s for s in spans if s["name"] == "transform")
        mine = [s for s in spans if s["thread"] == root["thread"]]

        def ancestors(s):
            while s["parent_id"] in by_id:
                s = by_id[s["parent_id"]]
                yield s["span_id"]

        for i, a in enumerate(mine):
            for b in mine[i + 1:]:
                lo = max(a["t0"], b["t0"])
                hi = min(a["t0"] + a["dur_s"], b["t0"] + b["dur_s"])
                if hi - lo > 1e-4:
                    assert a["span_id"] in ancestors(b) \
                        or b["span_id"] in ancestors(a), (a["name"], b["name"])
        stretches = [s for s in mine if s["name"] == "partition"]
        assert [s["attrs"]["part"] for s in
                sorted(stretches, key=lambda s: s["t0"])] == [0, 1, 0, 1]

    def test_emit_says_what_it_joined(self, warm_call):
        # the image column comes from the staged host rows and the
        # featurizer has no finalize of its own: its rows are views of the
        # batches that came back, nothing is joined
        fused, spans = warm_call
        emits = [s for s in spans if s["name"] == "emit"]
        assert [s["attrs"] for s in emits] == [
            {"rows": 16, "host_cols": 1, "host_bytes": 16 * 16 * 16 * 3,
             "joined_bytes": 0}] * 2
        assert fused.fusion_stats()["joined_bytes"] == {
            "ImageTransformer+ImageFeaturizer": 0}

    def test_one_dispatch_flight_and_wait_a_batch_across_partitions(
            self, recorder):
        # 40 rows in 3 partitions of 14, 13, 13: 2 batches each, the second
        # ragged; the clock join counts these spans against the programs
        fused, df = image_chain(), image_df(n=40, parts=3)
        fused.transform(df)
        recorder.clear()
        fused.transform(df)
        spans = recorder.spans()
        by_id = {s["span_id"]: s for s in spans}
        for name in ("dispatch", "in_flight", "compute_wait"):
            mine = sorted((s for s in spans if s["name"] == name),
                          key=lambda s: s["attrs"]["batch"])
            assert [s["attrs"]["batch"] for s in mine] == list(range(6)), name
            assert [by_id[s["parent_id"]]["attrs"]["part"] for s in mine] \
                == [0, 0, 1, 1, 2, 2], name
        assert len(fused.last_ingest_stats.records) == 6


class TestNoSpanPerRow:
    def test_spans_a_call_do_not_grow_with_rows(self, recorder):
        counts = {}
        for rows, batch in ((32, 8), (128, 32)):       # 4 batches each
            fused, df = image_chain(batch), image_df(rows)
            fused.transform(df)
            recorder.clear()
            fused.transform(df)
            counts[rows] = len(recorder.spans())
        assert counts[32] == counts[128] == sum(COUNTS.values())


class TestThreads:
    def test_producer_and_filler_thread_names(self, warm_call):
        _fused, spans = warm_call
        root = next(s for s in spans if s["name"] == "transform")
        for name, thread in (("h2d", "device-prefetch"), ("fill", "slot-fill"),
                             ("in_flight", "device-watch")):
            mine = [s for s in spans if s["name"] == name]
            assert {s["thread"] for s in mine} == {thread}
            assert {s["trace_id"] for s in mine} == {root["trace_id"]}
            assert all(s["attrs"].get("bytes", 1) > 0 for s in mine)
        # the first partition is prepared on the calling thread, the second
        # by the look-ahead, beside the first one's batches
        prepares = sorted((s for s in spans if s["name"] == "prepare"),
                          key=lambda s: s["attrs"]["ahead"])
        assert [s["attrs"]["ahead"] for s in prepares] == [0, 1]
        assert [s["thread"] for s in prepares] \
            == [root["thread"], "partition-prep"]
        by_id = {s["span_id"]: s for s in spans}
        for s in spans:
            if s["name"] in ("prepare:ImageTransformer", "stack"):
                assert s["thread"] == by_id[s["parent_id"]]["thread"]
        caller = {s["thread"] for s in spans
                  if s["name"] not in ("h2d", "fill", "in_flight", "prepare",
                                       "prepare:ImageTransformer", "stack")}
        assert caller == {root["thread"]}

    def test_in_flight_runs_from_the_dispatch_to_before_the_drain(self, warm_call):
        _fused, spans = warm_call
        for batch in range(4):
            one = {s["name"]: s for s in spans
                   if s["attrs"].get("batch") == batch}
            flight, wait = one["in_flight"], one["compute_wait"]
            dispatched = one["dispatch"]["t0"] + one["dispatch"]["dur_s"]
            assert flight["t0"] >= dispatched - 1e-3
            # the watcher sees the batch ready no later than the drain does
            assert flight["t0"] + flight["dur_s"] \
                <= wait["t0"] + wait["dur_s"] + 5e-3

    def test_without_a_filler_the_producer_fills(self, recorder):
        fused = image_chain(slot_staging=False)
        fused.transform(image_df())
        fills = [s for s in recorder.spans() if s["name"] == "fill"]
        assert fills and {s["thread"] for s in fills} == {"device-prefetch"}


class TestHostSpans:
    def test_a_fallback_partition_yields_host_spans(self, recorder):
        # the featurizer is fed 8x8 batches but its backbone wants 16x16:
        # the build refuses, and every partition reruns on the host
        fused = FusedPipelineModel(
            [ImageTransformer().resize(8, 8),
             ImageFeaturizer(scaleFactor=1 / 255., batchSize=8)
             .set_model(toy_cnn(size=16))], cache=CompileCache())
        fused.transform(image_df())
        assert len(fused.fusion_stats()["fallbacks"]) == 2
        spans = recorder.spans()
        by_id = {s["span_id"]: s for s in spans}
        for stage in ("ImageTransformer", "ImageFeaturizer"):
            host = [s for s in spans if s["name"] == f"host:{stage}"]
            assert len(host) == 2
            assert all(s["attrs"] == {"rows": 16} for s in host)
            assert {by_id[s["parent_id"]]["name"] for s in host} == {SEGMENT}
        # what ran before the fault is still on record, the partition too
        for name in ("prepare", "compile"):
            assert sum(s["name"] == name for s in spans) == 2, name
        assert {s["attrs"]["part"] for s in spans
                if s["name"] == "partition"} == {0, 1}

    def test_a_host_stage_of_the_plan_is_a_span(self, recorder):
        fused = FusedPipelineModel(
            [UDFTransformer(inputCol="image", outputCol="copy",
                            udf=lambda v: v),
             ImageTransformer().resize(16, 16),
             ImageFeaturizer(scaleFactor=1 / 255., batchSize=8)
             .set_model(toy_cnn())], cache=CompileCache())
        fused.transform(image_df())
        spans = recorder.spans()
        root = next(s for s in spans if s["name"] == "transform")
        host = [s for s in spans if s["name"] == "host:UDFTransformer"]
        assert len(host) == 1 and host[0]["parent_id"] == root["span_id"]
        assert host[0]["attrs"] == {"rows": 32}


class TestPrepareSpanSaysHowTheColumnWasResized:
    @staticmethod
    def prepares(recorder):
        return [s["attrs"] for s in recorder.spans()
                if s["name"] == "prepare:ImageTransformer"]

    def test_the_column_call_took_every_row(self, warm_call):
        attrs = [s["attrs"] for s in warm_call[1]
                 if s["name"] == "prepare:ImageTransformer"]
        assert attrs == [{"rows": 16, "resized_rows": 16, "resize_threads": 1}] * 2

    def test_threads_come_with_the_rows(self, recorder, monkeypatch):
        import os

        monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0, 1, 2, 3})
        image_chain(batch=128).transform(image_df(n=200, parts=1))
        assert self.prepares(recorder) == [
            {"rows": 200, "resized_rows": 200, "resize_threads": 3}]

    def test_a_null_row_never_reaches_the_hook(self, recorder):
        df = image_df(n=16, parts=1)
        col = df.collect()["image"].copy()
        col[3] = None
        fused = FusedPipelineModel(
            [ImageTransformer().resize(16, 16),
             ImageFeaturizer(scaleFactor=1 / 255., batchSize=8, dropNa=True)
             .set_model(toy_cnn())], cache=CompileCache())
        fused.transform(DataFrame.from_dict({"image": col}, num_partitions=1))
        assert self.prepares(recorder) == [
            {"rows": 15, "resized_rows": 15, "resize_threads": 1}]

    def test_the_per_row_path_reads_zero(self, recorder, monkeypatch):
        from mmlspark_tpu import native_loader

        monkeypatch.setattr(native_loader, "load", lambda: None)
        fused = image_chain()
        fused.transform(image_df())
        assert self.prepares(recorder) == [
            {"rows": 16, "resized_rows": 0, "resize_threads": 0}] * 2
        assert fused.fusion_stats()["fallbacks"] == []

    def test_presized_rows_are_counted_and_nothing_is_computed(self, recorder):
        rng = np.random.default_rng(5)
        block = rng.integers(0, 256, (16, 16, 16, 3), dtype=np.uint8)
        col = np.empty(16, dtype=object)
        for i in range(16):
            col[i] = ImageSchema.make(block[i], f"img{i}")
        image_chain().transform(DataFrame.from_dict({"image": col},
                                                    num_partitions=1))
        assert self.prepares(recorder) == [
            {"rows": 16, "resized_rows": 16, "resize_threads": 0}]

    def test_a_stage_with_no_resize_adds_nothing(self, recorder):
        fused = FusedPipelineModel(
            [ImageTransformer().flip(1),
             ImageFeaturizer(scaleFactor=1 / 255., batchSize=8)
             .set_model(toy_cnn(size=20, c=3))], cache=CompileCache())
        rng = np.random.default_rng(6)
        col = np.empty(8, dtype=object)
        for i in range(8):
            col[i] = ImageSchema.make(
                rng.integers(0, 256, (20, 20, 3), dtype=np.uint8), "")
        fused.transform(DataFrame.from_dict({"image": col}, num_partitions=1))
        assert self.prepares(recorder) == [{"rows": 8}]


class TestCompileSpan:
    def test_one_on_a_cache_miss_and_none_warm(self, recorder):
        fused, df = image_chain(), image_df()
        fused.transform(df)
        cold = recorder.spans()
        compiles = [s for s in cold if s["name"] == "compile"]
        assert len(compiles) == 1
        parent = next(s for s in cold
                      if s["span_id"] == compiles[0]["parent_id"])
        assert parent["name"] == "dispatch" and parent["attrs"]["batch"] == 0
        assert compiles[0]["attrs"]["label"] == "ImageTransformer+ImageFeaturizer"
        assert "image=8x16x16x3" in compiles[0]["attrs"]["shape"]
        assert compiles[0]["dur_s"] <= parent["dur_s"]
        recorder.clear()
        fused.transform(df)
        assert not [s for s in recorder.spans() if s["name"] == "compile"]


class TestClockedOnce:
    @pytest.mark.parametrize("span,field", [
        ("queue", "queue_s"), ("dispatch", "dispatch_s"),
        ("compute_wait", "compute_s"), ("readback", "readback_s"),
        ("h2d", "h2d_s")])
    def test_span_durations_are_the_batch_timings(self, warm_call, span, field):
        fused, spans = warm_call
        records = fused.last_ingest_stats.records
        mine = sorted((s for s in spans if s["name"] == span),
                      key=lambda s: s["attrs"]["batch"])
        assert len(mine) == len(records) == 4
        for s, rec in zip(mine, records):
            assert s["dur_s"] == getattr(rec, field)     # exactly: one clock


class TestOffSwitch:
    def test_off_records_nothing_and_changes_no_bit(self, recorder):
        fused, df = image_chain(), image_df()
        on = features(fused.transform(df))
        n_on = len(recorder.spans())
        assert n_on > 0
        assert set_default_tracer(None) is recorder
        assert obs_trace.default_tracer() is None
        off = features(fused.transform(df))
        assert len(recorder.spans()) == n_on
        assert on.dtype == off.dtype and np.array_equal(on, off)

    def test_off_leaves_the_counters_on(self, recorder):
        set_default_tracer(None)
        fused = image_chain()
        fused.transform(image_df())
        records = fused.last_ingest_stats.records
        assert len(records) == 4 and all(r.h2d_s > 0 for r in records)


class TestServingBindingWins:
    def test_transform_under_a_bound_batch(self, recorder):
        mine = Tracer(service="worker")
        req = mine.ingress()
        fused, df = image_chain(), image_df()
        with batch_context(mine, [req]):
            fused.transform(df)
        assert recorder.spans() == []
        spans = mine.spans()
        names = {s["name"] for s in spans}
        # no root of the batch path: the request's ingress span is the root
        assert "transform" not in names
        assert set(TREE) - {"transform"} <= names
        seg = next(s for s in spans if s["name"] == SEGMENT)
        assert seg["parent_id"] == req.span_id
        assert {s["trace_id"] for s in spans} == {req.trace_id}

    def test_the_served_split_gets_the_same_tree(self, recorder):
        mine = Tracer(service="worker")
        req = mine.ingress()
        fused, df = image_chain(), image_df()
        want = features(fused.transform(df))
        recorder.clear()
        with batch_context(mine, [req]):
            resolve = fused.transform_submit(df)
        got = features(resolve())          # on any thread, with no binding
        assert np.array_equal(want, got) and recorder.spans() == []
        spans = mine.spans()
        by_id = {s["span_id"]: s for s in spans}
        counts = {}
        for s in spans:
            counts[s["name"]] = counts.get(s["name"], 0) + 1
            if s["name"] != SEGMENT:
                assert by_id[s["parent_id"]]["name"] == TREE[s["name"]]
        want_counts = {k: v for k, v in COUNTS.items()
                       if k not in ("transform", "queue", "in_flight")}
        # the split has no ring: no queue, and a partition's work is not
        # interleaved with another's, so one span a partition
        want_counts["partition"] = 2
        assert counts == want_counts
        records = fused.last_ingest_stats.records
        for name, field in (("dispatch", "dispatch_s"),
                            ("compute_wait", "compute_s"),
                            ("readback", "readback_s")):
            mine_ = sorted((s for s in spans if s["name"] == name),
                           key=lambda s: s["attrs"]["batch"])
            assert [s["dur_s"] for s in mine_] \
                == [getattr(r, field) for r in records]

    def test_two_sampled_requests_each_see_the_tree(self, recorder):
        mine = Tracer(service="worker")
        a, b = mine.ingress(), mine.ingress()
        fused, df = image_chain(), image_df()
        fused.transform(df)
        recorder.clear()
        with batch_context(mine, [a, b]):
            fused.transform(df)
        for req in (a, b):
            spans = mine.spans(req.trace_id)
            assert len(spans) == sum(COUNTS.values()) - 1


class TestGbdtFitSpans:
    def test_a_scan_fit_records_its_phases_under_fit(self, recorder,
                                                     monkeypatch):
        from mmlspark_tpu.gbdt.booster import TrainParams, train

        monkeypatch.setenv("MMLSPARK_TPU_SCAN_TRAIN", "1")
        monkeypatch.setenv("MMLSPARK_TPU_PALLAS_INTERPRET", "1")
        rng = np.random.default_rng(0)
        X = rng.normal(size=(600, 4))
        y = (X[:, 0] + X[:, 1] > 0).astype(float)
        booster = train(TrainParams(objective="binary", num_iterations=2,
                                    num_leaves=4, min_data_in_leaf=5,
                                    max_bin=15, seed=0), X, y)
        assert len(booster.trees) == 2
        spans = recorder.spans()
        by_id = {s["span_id"]: s for s in spans}
        parents = {s["name"]: (by_id[s["parent_id"]]["name"]
                               if s["parent_id"] else None) for s in spans}
        assert parents == {"fit": None, "gbdt:bin_fit": "fit",
                           "gbdt:bins": "fit", "gbdt:scan": "fit",
                           "gbdt:scan_chunk": "gbdt:scan",
                           "gbdt:fetch": "gbdt:scan_chunk",
                           "gbdt:trees": "fit"}
        root = next(s for s in spans if s["name"] == "fit")
        assert root["attrs"] == {"rows": 600, "features": 4, "iterations": 2}
        assert len({s["trace_id"] for s in spans}) == 1
        for s in spans:
            assert s["t0"] >= root["t0"] - 1e-3
            assert s["t0"] + s["dur_s"] <= root["t0"] + root["dur_s"] + 1e-3
        chunk = next(s for s in spans if s["name"] == "gbdt:scan_chunk")
        assert chunk["attrs"]["iterations"] == 2

    def test_a_native_fit_is_one_root_span(self, recorder):
        from mmlspark_tpu.gbdt.booster import TrainParams, train

        rng = np.random.default_rng(0)
        X = rng.normal(size=(200, 3))
        y = (X[:, 0] > 0).astype(float)
        train(TrainParams(objective="binary", num_iterations=2, num_leaves=4,
                          min_data_in_leaf=5, max_bin=15, seed=0), X, y)
        assert [s["name"] for s in recorder.spans()][-1] == "fit"
