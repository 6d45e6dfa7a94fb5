"""Native C++ runtime tests: build, load, and parity with the numpy/jax paths."""

import os

import numpy as np
import pytest

from mmlspark_tpu import native_loader as NL
from mmlspark_tpu.ops import image as imops
from mmlspark_tpu.ops.hashing import hash_string


@pytest.fixture(scope="module")
def native():
    if not NL.available():
        pytest.skip("native toolchain unavailable")
    return NL


def frozen_bilinear(img, oh, ow):
    """The bilinear formula of the C++ kernel, transcribed to float64 numpy
    and frozen here (operand order as in mml_resize_bilinear_u8 before the
    column kernel replaced it): top/bot interpolate rows y0/y1 horizontally,
    v blends them; uint8 rounds half to even, then clamps."""
    h, w, _ = img.shape
    src = img.astype(np.float64)

    def taps(n_out, n_in):
        f = (np.arange(n_out, dtype=np.float64) + 0.5) * n_in / n_out - 0.5
        i0 = np.floor(f).astype(np.int64)
        wt = f - i0
        low, high = i0 < 0, i0 > n_in - 1
        i0 = np.where(low, 0, np.where(high, n_in - 1, i0))
        wt = np.clip(np.where(low | high, 0.0, wt), 0.0, 1.0)
        return i0, np.minimum(i0 + 1, n_in - 1), wt

    y0, y1, wy = taps(oh, h)
    x0, x1, wx = taps(ow, w)
    wx, wy = wx[None, :, None], wy[:, None, None]
    top = src[y0][:, x0] * (1 - wx) + src[y0][:, x1] * wx
    bot = src[y1][:, x0] * (1 - wx) + src[y1][:, x1] * wx
    v = top * (1 - wy) + bot * wy
    if img.dtype == np.uint8:
        return np.clip(np.rint(v), 0, 255).astype(np.uint8)
    return v.astype(np.float32)


def _image(rng, h, w, c, dtype):
    if dtype == np.uint8:
        return rng.integers(0, 256, (h, w, c), dtype=np.uint8)
    return (rng.normal(size=(h, w, c)) * 50).astype(np.float32)


def assert_same_bits(a, b):
    assert a.shape == b.shape and a.dtype == b.dtype
    assert a.tobytes() == b.tobytes()


# (source h, w) -> (output h, w): up, down, both at once, non-integer
# ratios, ratios whose taps land on exact ties, and sides of one pixel
RESIZE_SHAPES = [((37, 23), (16, 16)), ((12, 18), (24, 9)), ((20, 24), (16, 16)),
                 ((64, 64), (32, 32)), ((16, 16), (64, 48)), ((7, 5), (23, 31)),
                 ((1, 9), (4, 4)), ((9, 1), (5, 3)), ((1, 1), (3, 2)),
                 ((33, 47), (1, 1)), ((256, 256), (224, 224))]


class TestResizeRows:
    """The column kernel (mml_resize_bilinear_rows): bitwise the frozen
    float64 formula, whatever the batching and the thread count."""

    @pytest.mark.parametrize("dtype", [np.uint8, np.float32])
    @pytest.mark.parametrize("c", [1, 3, 4])
    @pytest.mark.parametrize("src,dst", RESIZE_SHAPES)
    def test_bitwise_the_frozen_formula(self, native, src, dst, c, dtype):
        img = _image(np.random.default_rng(sum(src) + c), *src, c, dtype)
        got = native.resize_bilinear_rows([img], *dst)
        assert got.shape == (1, *dst, c)
        assert_same_bits(got[0], frozen_bilinear(img, *dst))
        assert_same_bits(native.resize_bilinear(img, *dst), got[0])

    def test_uint8_ties_round_half_to_even(self, native):
        # 2x2 -> 1x1 is the mean of four pixels: 0.5 -> 0, 1.5 -> 2, 2.5 -> 2
        for px, want in (([0, 0, 1, 1], 0), ([1, 1, 2, 2], 2), ([2, 2, 3, 3], 2)):
            img = np.array(px, dtype=np.uint8).reshape(2, 2, 1)
            assert native.resize_bilinear(img, 1, 1)[0, 0, 0] == want

    @pytest.mark.parametrize("dtype", [np.uint8, np.float32])
    def test_column_call_equals_per_row_calls(self, native, dtype):
        rng = np.random.default_rng(11)
        rows = [_image(rng, 20, 24, 3, dtype) for _ in range(40)]
        out = native.resize_bilinear_rows(rows, 16, 16)
        assert out.shape == (40, 16, 16, 3) and out.flags["C_CONTIGUOUS"]
        for i, r in enumerate(rows):
            assert_same_bits(out[i], native.resize_bilinear(r, 16, 16))

    @pytest.mark.parametrize("threads", [2, 3, 7, 64])
    def test_one_thread_equals_many(self, native, threads):
        rng = np.random.default_rng(12)
        rows = [_image(rng, 9 + i % 5, 30 - i % 7, 3, np.uint8)
                for i in range(150)]
        one = native.resize_bilinear_rows(rows, 12, 10, threads=1)
        many = native.resize_bilinear_rows(rows, 12, 10, threads=threads)
        assert_same_bits(one, many)

    def test_ragged_sources_in_one_column(self, native):
        rng = np.random.default_rng(13)
        shapes = [(20, 24), (21, 24), (8, 8), (16, 16), (50, 3), (1, 40), (20, 24)]
        rows = [_image(rng, h, w, 3, np.uint8) for h, w in shapes]
        out = native.resize_bilinear_rows(rows, 16, 16, threads=2)
        for i, r in enumerate(rows):
            assert_same_bits(out[i], frozen_bilinear(r, 16, 16))
        assert_same_bits(out[3], rows[3])  # already 16x16: the row as it is

    def test_a_presized_float_row_keeps_its_infinities(self, native):
        # a 0-weight tap times inf would be NaN; a presized row is copied
        rng = np.random.default_rng(14)
        keep = _image(rng, 8, 8, 1, np.float32)
        keep[2, 3, 0] = np.inf
        out = native.resize_bilinear_rows([keep, _image(rng, 5, 6, 1, np.float32)],
                                          8, 8)
        assert_same_bits(out[0], keep)

    def test_rejects_what_it_cannot_resize(self, native):
        u8 = np.zeros((4, 4, 3), np.uint8)
        for rows in ([u8, u8.astype(np.float32)], [u8, u8[:, :, :1]],
                     [u8.astype(np.float64)], [u8[:, :, 0]], [u8[:0]], []):
            with pytest.raises(ValueError):
                native.resize_bilinear_rows(rows, 2, 2)


class TestOpsResizeRows:
    """ops.image.resize_rows: the column entry and its eligibility rules."""

    def test_equals_resize_row_by_row(self, native):
        rng = np.random.default_rng(21)
        rows = [_image(rng, 20 + i % 3, 24, 3, np.uint8) for i in range(130)]
        out = imops.resize_rows(rows, 16, 16)
        assert out.shape == (130, 16, 16, 3)
        for i, r in enumerate(rows):
            assert_same_bits(out[i], imops.resize(r, 16, 16))

    def test_two_dimensional_rows_stay_two_dimensional(self, native):
        rng = np.random.default_rng(22)
        rows = [rng.integers(0, 256, (9, 7), dtype=np.uint8) for _ in range(3)]
        out = imops.resize_rows(rows, 4, 5)
        assert out.shape == (3, 4, 5)
        for i, r in enumerate(rows):
            assert_same_bits(out[i], imops.resize(r, 4, 5))

    def test_a_presized_column_comes_back_as_the_same_objects(self, native):
        block = np.zeros((5, 16, 16, 3), np.uint8)
        rows = [block[i] for i in range(5)]
        out = imops.resize_rows(rows, 16, 16)
        assert out is rows and all(a is b for a, b in zip(out, rows))

    @pytest.mark.parametrize("rows", [
        [], [None], [np.zeros((4, 4, 3), np.uint8), None],
        [np.zeros((4, 4, 3), np.float64)],
        [np.zeros((4, 4, 3), np.uint8), np.zeros((4, 4, 3), np.float32)],
        [np.zeros((4, 4, 3), np.uint8), np.zeros((4, 4, 1), np.uint8)],
        [np.zeros((4, 4, 3), np.uint8), np.zeros((4, 4), np.uint8)],
        [np.zeros((4, 4, 3), np.uint8), [[1, 2], [3, 4]]],
        [np.zeros((4, 0, 3), np.uint8)],
    ], ids=["empty", "none", "a-none-row", "float64", "mixed-dtype",
            "mixed-channels", "mixed-rank", "not-an-array", "zero-width"])
    def test_ineligible_columns_are_left_to_the_caller(self, native, rows):
        assert imops.resize_rows(rows, 2, 2) is None

    def test_without_the_library_it_is_none(self, monkeypatch):
        monkeypatch.setattr(NL, "load", lambda: None)
        assert imops.resize_rows([np.zeros((4, 4, 3), np.uint8)], 2, 2) is None

    def test_threads_follow_the_rows_and_the_cores(self, monkeypatch):
        monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(32)))
        assert [imops.resize_threads(n) for n in (0, 1, 63, 64, 200, 2048, 10**6)] \
            == [1, 1, 1, 1, 3, 16, 16]
        monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0, 1})
        assert imops.resize_threads(2048) == 2


class TestNative:
    def test_builds_and_loads(self, native):
        assert native.load() is not None

    def test_try_load_foreign_so_returns_none(self, monkeypatch):
        # a loadable .so lacking the mml_version symbol (foreign file at
        # the cache path) must return None — triggering the rebuild flow —
        # not raise AttributeError out of load()
        import ctypes.util

        libm = ctypes.util.find_library("m")
        if libm is None:
            pytest.skip("libm not found")
        monkeypatch.setattr(NL, "_SO_PATH", libm)
        assert NL._try_load() is None

    def test_murmur_batch_matches_python(self, native):
        strings = ["hello", "world", "", "mmlspark_tpu", "日本語テキスト"]
        got = native.murmur3_batch(strings, seed=42)
        want = [hash_string(s, 42) for s in strings]
        np.testing.assert_array_equal(got, want)

    def test_resize_u8_matches_numpy(self, native):
        rng = np.random.default_rng(0)
        img = rng.integers(0, 256, (37, 23, 3), dtype=np.uint8)
        got = native.resize_bilinear(img, 16, 16)
        want = imops.resize(img, 16, 16)
        # rounding at exact .5 boundaries may differ by 1
        assert np.abs(got.astype(int) - want.astype(int)).max() <= 1

    def test_resize_f32_matches_numpy(self, native):
        rng = np.random.default_rng(1)
        img = rng.normal(size=(12, 18, 3)).astype(np.float32)
        got = native.resize_bilinear(img, 24, 9)
        want = imops.resize(img, 24, 9)
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_unroll_matches_numpy(self, native):
        rng = np.random.default_rng(2)
        img = rng.integers(0, 256, (6, 5, 3), dtype=np.uint8)
        got = native.unroll_chw(img)
        want = imops.unroll_chw(img)
        np.testing.assert_array_equal(got, want)

    def test_histogram_matches_jax(self, native):
        from mmlspark_tpu.gbdt import histogram as H
        rng = np.random.default_rng(3)
        n, f, b = 500, 6, 32
        bins = rng.integers(0, b, (n, f)).astype(np.int32)
        grad = rng.normal(size=n).astype(np.float32)
        hess = rng.uniform(0.1, 1, n).astype(np.float32)
        mask = rng.random(n) < 0.8
        got = native.histogram(bins, grad, hess, mask, b)
        # the JAX engine takes the canonical feature-major [F, N] layout
        # (histogram.compute_histogram docstring); the C++ path keeps the
        # row-major host layout it was built for
        want = np.asarray(H.compute_histogram(
            np.ascontiguousarray(bins.T), grad, hess, mask, b))
        np.testing.assert_allclose(got, want, atol=1e-3)

    def test_forest_predict_matches_host(self, native):
        from mmlspark_tpu.gbdt import TrainParams
        from mmlspark_tpu.gbdt import booster as B
        from mmlspark_tpu.gbdt.predict import DeviceEnsemble, predict_ensemble
        rng = np.random.default_rng(4)
        X = rng.normal(size=(200, 5))
        y = (X[:, 0] > 0).astype(np.float64)
        booster = B.train(TrainParams(objective="binary", num_iterations=8,
                                      num_leaves=7, min_data_in_leaf=5), X, y)
        ens = DeviceEnsemble(booster.trees, 1)
        got = native.forest_predict(
            X.astype(np.float32), ens.feature, ens.threshold, ens.default_left,
            ens.left, ens.right, ens.value, ens.class_of_tree, 1)
        want = predict_ensemble(booster.trees, X, 1)
        np.testing.assert_allclose(got, want, atol=1e-4)


def test_packaged_native_source_in_sync():
    """The wheel ships mmlspark_tpu/native_src/ as package data; in the repo
    it is a symlink to the canonical native/src/ tree (single source of
    truth), materialized as a real file at wheel-build time."""
    import mmlspark_tpu

    pkg = os.path.join(os.path.dirname(mmlspark_tpu.__file__),
                       "native_src", "mmlspark_native.cpp")
    repo = os.path.join(os.path.dirname(os.path.dirname(mmlspark_tpu.__file__)),
                        "native", "src", "mmlspark_native.cpp")
    if not os.path.exists(repo):
        pytest.skip("installed layout: only the packaged copy exists")
    with open(pkg, "rb") as a, open(repo, "rb") as b:
        assert a.read() == b.read(), \
            "native_src/ drifted from native/src/ — re-copy the source"
