"""Tests for the unified ingest layer (parallel/ingest.py): PreprocessSpec,
TransferRing, IngestStats, the uint8 wire format through DNNModel /
ImageFeaturizer, and the satellite bugfix regressions that ride this PR."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.parallel.ingest import (
    IngestStats, PreprocessSpec, TransferRing,
)


def tiny_mlp(din=4, dhid=8, dout=3, seed=0):
    import jax

    from mmlspark_tpu.models import Dense, FunctionModel, Sequential, relu

    module = Sequential([
        ("dense1", Dense(dhid)),
        ("relu1", relu()),
        ("dense2", Dense(dout)),
    ], name="mlp")
    params, _ = module.init(jax.random.PRNGKey(seed), (din,))
    return FunctionModel(module, params, (din,),
                         layer_names=["dense2", "relu1", "dense1"])


class TestPreprocessSpec:
    def test_host_device_parity(self):
        spec = PreprocessSpec(scale=1.0 / 255, offset=-0.5)
        x = np.random.default_rng(0).integers(0, 256, (4, 6, 6, 3),
                                              dtype=np.uint8)
        host = spec.apply_host(x)
        dev = np.asarray(spec.apply_device(x))
        assert host.dtype == np.float32
        np.testing.assert_array_equal(host, dev)

    def test_transpose_matches_legacy_host_layout(self):
        # the legacy NCHW host path: astype(f32) * scale, then per-row
        # img.transpose(2, 0, 1)
        spec = PreprocessSpec(scale=2.0, transpose=(2, 0, 1))
        x = np.random.default_rng(1).integers(0, 256, (3, 5, 7, 2),
                                              dtype=np.uint8)
        legacy = np.stack([(r.astype(np.float32) * np.float32(2.0)
                            ).transpose(2, 0, 1) for r in x])
        np.testing.assert_array_equal(spec.apply_host(x), legacy)
        np.testing.assert_array_equal(np.asarray(spec.apply_device(x)), legacy)

    def test_identity_and_hashable(self):
        assert PreprocessSpec().is_identity
        assert not PreprocessSpec(scale=0.5).is_identity
        # jit-cache keys hash the spec
        assert hash(PreprocessSpec(scale=0.5)) == hash(PreprocessSpec(scale=0.5))
        assert PreprocessSpec(transpose=[2, 0, 1]) == \
            PreprocessSpec(transpose=(2, 0, 1))

    def test_identity_still_casts(self):
        x = np.arange(8, dtype=np.uint8).reshape(2, 4)
        assert PreprocessSpec().apply_host(x).dtype == np.float32


class TestTransferRing:
    def _run(self, n=7, depth=2, **kw):
        stats = IngestStats()
        ring = TransferRing((np.full((4, 3), i, dtype=np.float32)
                             for i in range(n)),
                            step=lambda x: x * 2.0,
                            fetch=lambda y: np.asarray(y),
                            depth=depth, stats=stats, **kw)
        return list(ring), stats

    def test_order_and_results(self):
        outs, stats = self._run(n=7, depth=3)
        assert len(outs) == 7
        for i, o in enumerate(outs):
            np.testing.assert_array_equal(o, np.full((4, 3), 2.0 * i))

    def test_depth_variants_agree(self):
        base, _ = self._run(n=5, depth=1)
        for depth in (2, 4, 16):
            outs, _ = self._run(n=5, depth=depth)
            for a, b in zip(base, outs):
                np.testing.assert_array_equal(a, b)

    def test_stats_populated(self):
        outs, stats = self._run(n=6, depth=2)
        s = stats.summary()
        assert s["n_batches"] == 6
        assert s["rows"] == 6 * 4
        assert s["bytes"] == 6 * 4 * 3 * 4  # f32 batches
        assert s["wall_s"] > 0
        for f in ("queue", "h2d", "dispatch", "compute", "readback"):
            assert s[f + "_s"] >= 0.0
            assert s[f + "_ms_per_batch"] >= 0.0
        assert s["overlap_ratio"] is None or s["overlap_ratio"] > 0

    def test_empty_iterator(self):
        outs, stats = self._run(n=0)
        assert outs == []
        assert stats.summary() == {"n_batches": 0, "rings": 1}

    def test_put_runs_on_prefetch_thread(self):
        names = []

        def put(x):
            names.append(threading.current_thread().name)
            return x

        list(TransferRing(iter([1, 2, 3]), put=put, depth=2))
        assert names and all(n == "device-prefetch" for n in names)

    def test_producer_exception_propagates(self):
        def bad():
            yield 1
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="boom"):
            list(TransferRing(bad(), depth=2))

    def test_close_mid_stream_releases_producer(self):
        produced = []

        def slow():
            for i in range(100):
                produced.append(i)
                time.sleep(0.005)
                yield i

        ring = TransferRing(slow(), depth=2)
        it = iter(ring)
        next(it)
        ring.close()
        it.close()
        # the producer thread must terminate instead of spinning the full
        # 100-item iterator (or blocking on the bounded queue forever)
        ring._prefetch._thread.join(timeout=5)
        assert not ring._prefetch._thread.is_alive()
        assert len(produced) < 100

    def test_ring_with_jit_step(self):
        import jax

        f = jax.jit(lambda x: x.astype(np.float32) * (1.0 / 255))
        stats = IngestStats()
        batches = [np.random.default_rng(i).integers(0, 256, (8, 5),
                                                     dtype=np.uint8)
                   for i in range(4)]
        ring = TransferRing(iter(batches), put=jax.device_put, step=f,
                            fetch=lambda y: np.asarray(y), depth=2,
                            stats=stats)
        outs = list(ring)
        for b, o in zip(batches, outs):
            np.testing.assert_allclose(o, b.astype(np.float32) / 255,
                                       rtol=1e-6)
        assert stats.summary()["bytes"] == sum(b.nbytes for b in batches)


class TestDNNModelIngest:
    def _df(self, n=11, din=4, parts=2, dtype=np.float32, seed=1):
        rng = np.random.default_rng(seed)
        if np.issubdtype(dtype, np.integer):
            rows = [rng.integers(0, 256, size=din).astype(dtype)
                    for _ in range(n)]
        else:
            rows = [rng.normal(size=din).astype(dtype) for _ in range(n)]
        return DataFrame.from_dict({"feats": rows}, num_partitions=parts), rows

    def test_uint8_wire_with_spec_matches_host_preprocess(self):
        from mmlspark_tpu.models import DNNModel

        m = tiny_mlp()
        df, rows = self._df(dtype=np.uint8)
        spec = PreprocessSpec(scale=1.0 / 255)
        dev = (DNNModel(inputCol="feats", outputCol="out", batchSize=4)
               .set_model(m).set_preprocess(spec))
        got = np.stack(list(dev.transform(df).column("out")))
        # host oracle: preprocess on host, plain forward
        host_in = spec.apply_host(np.stack(rows))
        ref = np.asarray(m.apply(host_in))
        np.testing.assert_allclose(got, ref, atol=1e-5)

    def test_ring_depth_param_parity(self):
        from mmlspark_tpu.models import DNNModel

        m = tiny_mlp()
        df, rows = self._df(n=13)
        base = None
        for depth in (1, 2, 5):
            stage = DNNModel(inputCol="feats", outputCol="out", batchSize=4,
                             ringDepth=depth).set_model(m)
            got = np.stack(list(stage.transform(df).column("out")))
            if base is None:
                base = got
            else:
                np.testing.assert_allclose(got, base, atol=1e-6)

    def test_donation_noop_on_cpu(self):
        """donateInputs=True on CPU: donation is a no-op there, results and
        buffers must be unaffected (the donated executable still runs)."""
        from mmlspark_tpu.models import DNNModel

        m = tiny_mlp()
        df, rows = self._df(n=9)
        plain = (DNNModel(inputCol="feats", outputCol="out", batchSize=4,
                          donateInputs=False).set_model(m))
        ref = np.stack(list(plain.transform(df).column("out")))
        donated = (DNNModel(inputCol="feats", outputCol="out", batchSize=4,
                            donateInputs=True).set_model(m))
        got = np.stack(list(donated.transform(df).column("out")))
        np.testing.assert_allclose(got, ref, atol=1e-6)

    def test_ingest_stats_surface(self):
        from mmlspark_tpu.models import DNNModel

        m = tiny_mlp()
        df, _ = self._df(n=10)
        stage = DNNModel(inputCol="feats", outputCol="out",
                         batchSize=4).set_model(m)
        assert stage.last_ingest_stats is None
        stage.transform(df)
        s = stage.last_ingest_stats.summary()
        assert s["n_batches"] >= 3  # 10 rows / batch 4, both partitions
        assert s["rows"] == 10
        assert s["bytes"] > 0
        for f in ("queue_s", "h2d_s", "compute_s", "readback_s"):
            assert s[f] >= 0.0

    def test_sharding_indivisible_batch_stays_uncommitted(self, mesh8):
        """A batch not divisible by the mesh's data axis must eval as an
        uncommitted host array (committing would conflict with replicated
        params inside jit) and still produce correct rows."""
        from mmlspark_tpu.models import DNNModel
        from mmlspark_tpu.parallel.mesh import MeshContext

        m = tiny_mlp(din=6)
        rng = np.random.default_rng(0)
        rows = [rng.normal(size=6).astype(np.float32) for _ in range(5)]
        df = DataFrame.from_dict({"feats": rows})
        single = DNNModel(inputCol="feats", outputCol="out", batchSize=3,
                          useMesh=False).set_model(m)
        ref = np.stack(list(single.transform(df).column("out")))
        MeshContext.set(mesh8)
        try:
            # batchSize=3: batches of 3 and 2, neither divisible by 8
            sharded = DNNModel(inputCol="feats", outputCol="out",
                               batchSize=3).set_model(m)
            got = np.stack(list(sharded.transform(df).column("out")))
        finally:
            MeshContext.reset()
        np.testing.assert_allclose(got, ref, atol=1e-5)

    def test_preprocess_with_feed_fetch_dicts(self):
        """The spec composes with the feedDict/fetchDict surface as long as
        the model stays single-input (multi-output is fine: ONE forward)."""
        from mmlspark_tpu.models import DNNModel

        m = tiny_mlp()
        df, rows = self._df(n=4)
        stage = (DNNModel(batchSize=2).set_model(m)
                 .set_feed_dict({"ARGUMENT_0": "feats"})
                 .set_fetch_dict({"out": "OUTPUT_0", "h": "relu1"})
                 .set_preprocess(PreprocessSpec(scale=0.5)))
        out = stage.transform(df)
        ref = np.asarray(m.apply(np.stack(rows) * np.float32(0.5)))
        np.testing.assert_allclose(np.stack(list(out.column("out"))), ref,
                                   atol=1e-5)
        assert out.column("h")[0].shape == (8,)


class TestImageFeaturizerWire:
    def _image_df(self, n=5, h=20, w=14, seed=0):
        from mmlspark_tpu.core.schema import ImageSchema

        rng = np.random.default_rng(seed)
        col = np.empty(n, dtype=object)
        for i in range(n):
            col[i] = ImageSchema.make(
                rng.integers(0, 256, (h, w, 3), dtype=np.uint8))
        return DataFrame([{"image": col}])

    def test_uint8_wire_matches_float32_host_path(self):
        """Acceptance: uint8-wire output == legacy float32 host-preprocess
        output within atol=1e-5 on CPU."""
        from mmlspark_tpu.models import resnet

        from mmlspark_tpu.image import ImageFeaturizer

        m = resnet(18, num_classes=10, image_size=16, width=8)
        df = self._image_df()
        kw = dict(inputCol="image", outputCol="features", batchSize=4,
                  scaleFactor=1.0 / 255)
        wire = (ImageFeaturizer(**kw).set_model(m).set_cut_output_layers(1))
        legacy = (ImageFeaturizer(hostPreprocess=True, **kw)
                  .set_model(m).set_cut_output_layers(1))
        got = np.stack(list(wire.transform(df).column("features")))
        ref = np.stack(list(legacy.transform(df).column("features")))
        assert got.shape == ref.shape == (5, 64)
        np.testing.assert_allclose(got, ref, atol=1e-5)

    def test_featurizer_exposes_ingest_stats(self):
        from mmlspark_tpu.models import resnet

        from mmlspark_tpu.image import ImageFeaturizer

        m = resnet(18, num_classes=10, image_size=16, width=8)
        feat = (ImageFeaturizer(inputCol="image", outputCol="f", batchSize=4)
                .set_model(m))
        assert feat.last_ingest_stats is None
        feat.transform(self._image_df(n=3))
        s = feat.last_ingest_stats.summary()
        assert s["n_batches"] >= 1 and s["rows"] == 3
        # wire bytes: 3 uint8 images of 16*16*3 padded to one bucket-of-4
        # batch -> 4 * 16*16*3 bytes (1/4 of the float32 wire)
        assert s["bytes"] == 4 * 16 * 16 * 3

    def test_wire_bytes_quarter_of_float32(self):
        """The uint8 wire ships exactly 1/4 the bytes of the legacy path."""
        from mmlspark_tpu.models import resnet

        from mmlspark_tpu.image import ImageFeaturizer

        m = resnet(18, num_classes=10, image_size=16, width=8)
        df = self._image_df(n=4)
        kw = dict(inputCol="image", outputCol="f", batchSize=4)
        wire = ImageFeaturizer(**kw).set_model(m)
        wire.transform(df)
        legacy = ImageFeaturizer(hostPreprocess=True, **kw).set_model(m)
        legacy.transform(df)
        b_wire = wire.last_ingest_stats.summary()["bytes"]
        b_legacy = legacy.last_ingest_stats.summary()["bytes"]
        assert b_wire * 4 == b_legacy


class TestGbdtRingScoring:
    def test_chunked_predict_rides_ring(self):
        """Chunked GEMM scoring through the transfer ring matches the
        single-dispatch path and records ingest stats."""
        from mmlspark_tpu.gbdt import LightGBMRegressor

        rng = np.random.default_rng(0)
        X = rng.normal(size=(300, 4))
        y = X[:, 0] * 2 + X[:, 1] - X[:, 2] * 0.5
        df = DataFrame.from_dict({"features": [X[i] for i in range(300)],
                                  "label": y})
        model = LightGBMRegressor(numIterations=8, numLeaves=7,
                                  minDataInLeaf=5).fit(df)
        ref = np.asarray(model.transform(df).column("prediction"),
                         dtype=np.float64)
        ens = model._ensemble()
        if ens.cat_host_fallback or ens._gemm is None:
            pytest.skip("host-fallback ensemble has no device chunk path")
        old_chunk = ens._gemm_row_chunk
        try:
            ens._gemm_row_chunk = 64  # force chunking (300 rows -> 5 chunks)
            got = np.asarray(model.transform(df).column("prediction"),
                             dtype=np.float64)
        finally:
            ens._gemm_row_chunk = old_chunk
        np.testing.assert_allclose(got, ref, atol=1e-6)
        s = ens.last_ingest_stats.summary()
        assert s["n_batches"] == 5
        assert s["rows"] == 300


class TestServingIngestSurface:
    def test_stats_endpoint_reports_ingest(self):
        """serve_pipeline over a DNNModel: /_mmlspark/stats carries the
        device-ingest decomposition next to the latency percentiles."""
        from mmlspark_tpu.models import DNNModel
        from mmlspark_tpu.serving import serve_pipeline

        m = tiny_mlp()
        stage = DNNModel(inputCol="features", outputCol="reply",
                         batchSize=4).set_model(m)
        server = serve_pipeline(stage, input_col="features", port=0)
        with server:
            payload = json.dumps({"data": [1.0, 2.0, 3.0, 4.0]}).encode()
            req = urllib.request.Request(server.address, data=payload,
                                         method="POST")
            with urllib.request.urlopen(req, timeout=15) as resp:
                resp.read()
            with urllib.request.urlopen(
                    f"http://{server.host}:{server.port}/_mmlspark/stats",
                    timeout=15) as resp:
                remote = json.loads(resp.read())
        assert "ingest" in remote
        assert remote["ingest"]["n_batches"] >= 1
        for f in ("queue_s", "h2d_s", "compute_s", "readback_s"):
            assert f in remote["ingest"]


class TestBatcherCloseRaceRegressions:
    """Close-vs-producer races in parallel/batching.py (a review finding of
    an earlier round)."""

    def test_dynamic_batcher_sentinel_never_leaks_as_data(self):
        from mmlspark_tpu.parallel.batching import DynamicBufferedBatcher

        # Force the race deterministically: fill the queue, then inject the
        # DONE mid-queue the way a racing producer put would leave it
        b = DynamicBufferedBatcher(iter([]), max_buffer=10)
        b._thread.join(timeout=5)
        while not b._q.empty():
            b._q.get_nowait()
        b._q.put(1)
        b._q.put(2)
        b._q.put(b._DONE)
        b._q.put(3)  # a racing put landing AFTER the sentinel
        got = [item for batch in b for item in batch]
        assert got == [1, 2]  # post-sentinel item abandoned, sentinel hidden

    def test_dynamic_batcher_close_unblocks_consumer(self):
        from mmlspark_tpu.parallel.batching import DynamicBufferedBatcher

        def slow():
            yield 1
            time.sleep(30)
            yield 2

        b = DynamicBufferedBatcher(slow(), max_buffer=2)
        consumed = []
        done = threading.Event()

        def consume():
            for batch in b:
                consumed.append(batch)
                b.close()  # external close mid-iteration
            done.set()

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        assert done.wait(timeout=10), "consumer stranded after close()"
        assert all(b._DONE not in batch for batch in consumed)

    def test_device_prefetcher_close_unblocks_consumer(self):
        from mmlspark_tpu.parallel.batching import DevicePrefetcher

        def hang():
            yield 1
            time.sleep(30)
            yield 2

        p = DevicePrefetcher(hang(), depth=1)
        it = iter(p)
        assert next(it) == 1
        # close from another thread while the consumer is about to block
        closer = threading.Timer(0.2, p.close)
        closer.start()
        rest = list(it)  # must return promptly instead of hanging forever
        assert rest == []


class TestVwNativeFallbackRegression:
    def test_vw_train_pass_none_falls_back_to_scan(self, monkeypatch):
        """A vanished .so between the _native_pass_ok probe and the call must
        fall through to the jax scan engine (not TypeError under python -O)."""
        from mmlspark_tpu import native_loader
        from mmlspark_tpu.vw import learner as L

        monkeypatch.setattr(L, "_native_pass_ok", lambda cfg: True)
        monkeypatch.setattr(native_loader, "vw_train_pass",
                            lambda *a, **k: None)
        cfg = L.LearnerConfig(num_bits=8, num_passes=2, loss_function="squared")
        rng = np.random.default_rng(0)
        rows = [{"indices": np.array([i % 5]), "values": np.array([1.0]),
                 "size": 256} for i in range(20)]
        ds = L.SparseDataset.from_rows(rows, rng.normal(size=20), num_bits=8)
        w, stats = L.train_linear(cfg, ds)
        assert w.shape == (256,)
        assert np.isfinite(w).all()
        assert len(stats) == 2  # scan engine ran both passes
        assert not np.allclose(w, 0.0)  # it actually trained


class TestParseReadableModelRegression:
    def test_oob_index_raises(self):
        from mmlspark_tpu.vw import parse_readable_model

        text = "bits:4\n3:0.5\n200:1.0\n"
        with pytest.raises(ValueError, match="outside the 4-bit"):
            parse_readable_model(text)

    def test_missing_bits_header_warns(self):
        from mmlspark_tpu.vw import parse_readable_model

        with pytest.warns(UserWarning, match="no bits header"):
            bits, w = parse_readable_model("7:0.25\n")
        assert bits == 18 and w[7] == 0.25

    def test_clean_dump_no_warning(self):
        import warnings as W

        from mmlspark_tpu.vw import parse_readable_model

        with W.catch_warnings():
            W.simplefilter("error")
            bits, w = parse_readable_model("bits:10\n7:0.25\n")
        assert bits == 10 and w[7] == 0.25


class TestRenderCommentRegression:
    def test_quoted_hash_preserved(self):
        import importlib.util
        from pathlib import Path

        spec = importlib.util.spec_from_file_location(
            "render", Path(__file__).parent.parent / "tools/k8s/render.py")
        render = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(render)
        text = ('image: "repo/app#sha256"\n'
                "tag: v1.0   # trailing comment\n"
                "token: 'a#b'\n"
                "plain: a#b\n")
        vals = {}
        for line in text.splitlines():
            line = render._strip_comment(line)
            if not line:
                continue
            k, _, v = line.partition(":")
            vals[k] = render._coerce(v.strip())
        assert vals["image"] == "repo/app#sha256"
        assert vals["tag"] == "v1.0"
        assert vals["token"] == "a#b"
        assert vals["plain"] == "a#b"  # no preceding whitespace: not a comment
