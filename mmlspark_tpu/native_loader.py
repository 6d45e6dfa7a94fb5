"""NativeLoader: build/discover/load the C++ runtime, with graceful fallback.

Reference: core/env/NativeLoader.java:28-140 — extracts .so files from jar
resources and System.load()s them on each executor. Here: the .so is built
from in-repo C++ source (native/src/) on first use (g++ is in the image),
cached under native/build/, and loaded via ctypes. Every consumer falls back
to the numpy implementation when the library is unavailable, so the Python
surface never hard-depends on the toolchain.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

log = logging.getLogger("mmlspark_tpu.native")

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_PKG_DIR)
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")


_ABI_VERSION = 6


def _host_tag() -> str:
    """Short CPU-identity tag for the cache filename: the build uses
    -march=native, so a cached .so is only valid on a CPU with the same
    feature set — a shared cache dir (NFS home, baked image) must rebuild
    on a different host instead of dying with SIGILL mid-call."""
    import hashlib
    import platform

    ident = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    ident += line
                    break
    except OSError:
        pass
    return hashlib.md5(ident.encode()).hexdigest()[:8]


def _source_path() -> Optional[str]:
    # dev checkout first; the wheel ships the same source as package data
    # (native_src/ is a symlink to native/src/ in the repo; wheel builds
    # materialize it as a real file)
    candidates = [os.path.join(_NATIVE_DIR, "src", "mmlspark_native.cpp"),
                  os.path.join(_PKG_DIR, "native_src", "mmlspark_native.cpp")]
    return next((c for c in candidates if os.path.exists(c)), None)


def _source_tag() -> str:
    """Content hash of the C++ source: what is loaded is always built from
    the source that is present — an ignored build dir copied along with a
    checkout (or left by an older commit) can never be picked up stale."""
    import hashlib

    src = _source_path()
    if src is None:
        return "nosrc"
    with open(src, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:12]


def _so_path() -> str:
    """Repo build dir when the repo layout is present (dev checkout); else a
    user cache dir (pip-installed: site-packages may be read-only). The
    source hash AND a host-CPU tag are part of the filename so co-installed
    package versions (or hosts with different CPU features — the build is
    -march=native) sharing a cache dir never load each other's build."""
    name = f"libmmlspark_native.{_source_tag()}.{_host_tag()}.so"
    if os.path.isdir(_NATIVE_DIR):
        return os.path.join(_NATIVE_DIR, "build", name)
    cache = os.environ.get("XDG_CACHE_HOME",
                           os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(cache, "mmlspark_tpu", name)


_SO_PATH = _so_path()

_lib = None
_lib_lock = threading.Lock()
_build_attempted = False


def _build() -> bool:
    src = _source_path()
    if src is None:
        return False
    os.makedirs(os.path.dirname(_SO_PATH), exist_ok=True)
    # build to a temp path + atomic rename: concurrent processes (e.g. the
    # two-OS-process tests) may race the build — a reader must never dlopen
    # a half-written .so, and a process that mmapped the old file must not
    # have its inode rewritten under it (rename unlinks, not overwrites)
    tmp = f"{_SO_PATH}.tmp.{os.getpid()}"
    # -ffp-contract=off: no FMA contraction — the predict paths are
    # documented (and test-gated) bit-equal to the numpy references, and
    # contraction changes their rounding by 1 ulp
    # (native/Makefile carries the same flags)
    cmd = ["g++", "-O3", "-march=native", "-ffp-contract=off",
           "-funroll-loops", "-fPIC", "-shared", "-std=c++17", "-pthread",
           "-o", tmp, src]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO_PATH)
        return True
    except Exception as e:  # toolchain missing / compile error -> fallback
        log.warning("native build failed (%s); using numpy fallbacks", e)
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False


def _try_load() -> Optional[ctypes.CDLL]:
    """dlopen + ABI check; None on any failure (caller decides rebuild)."""
    try:
        lib = ctypes.CDLL(_SO_PATH)
    except OSError as e:
        log.warning("native load failed (%s)", e)
        return None
    try:
        lib.mml_version.restype = ctypes.c_int32
        got = lib.mml_version()
    except (OSError, AttributeError) as e:
        # loadable .so without the symbol (foreign or truncated-but-
        # linkable file) must trigger the rebuild path, not crash load()
        log.warning("native ABI probe failed (%s)", e)
        return None
    if got != _ABI_VERSION:
        log.warning("native ABI v%s != expected v%s", got, _ABI_VERSION)
        return None
    return lib


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable.

    ANY first-load failure — absent, corrupted/half-written, or a stale
    ABI from older source — gets exactly one rebuild attempt (dlopen
    failures must rebuild too: build-on-absent alone left a corrupt file
    permanently wedging the process into numpy fallbacks)."""
    global _lib, _build_attempted
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        lib = _try_load() if os.path.exists(_SO_PATH) else None
        if lib is None:
            if _build_attempted:
                return None
            _build_attempted = True
            try:
                os.remove(_SO_PATH)
            except OSError:
                pass
            if not _build():
                return None
            lib = _try_load()
            if lib is None:
                log.warning("native library unusable after rebuild; using "
                            "numpy fallbacks")
                return None
        _declare(lib)
        _lib = lib
    return _lib


def available() -> bool:
    return load() is not None


def _declare(lib: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    f32p = ctypes.POINTER(ctypes.c_float)
    f64p = ctypes.POINTER(ctypes.c_double)

    lib.mml_version.restype = ctypes.c_int32
    lib.mml_murmur3_32.restype = ctypes.c_uint32
    lib.mml_murmur3_32.argtypes = [u8p, ctypes.c_int32, ctypes.c_uint32]
    lib.mml_murmur3_batch.argtypes = [u8p, i64p, ctypes.c_int64,
                                      ctypes.c_uint32, u32p]
    lib.mml_resize_bilinear_rows.restype = None
    lib.mml_resize_bilinear_rows.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), i32p, i32p, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_void_p, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32]
    lib.mml_unroll_chw_f64.argtypes = [u8p, ctypes.c_int32, ctypes.c_int32,
                                       ctypes.c_int32, f64p, ctypes.c_int32]
    lib.mml_histogram.argtypes = [i32p, f32p, f32p, u8p, ctypes.c_int64,
                                  ctypes.c_int32, ctypes.c_int32, f32p]
    lib.mml_forest_predict.argtypes = [f32p, ctypes.c_int64, ctypes.c_int32,
                                       i32p, f32p, u8p, i32p, i32p, f32p,
                                       ctypes.c_int32, ctypes.c_int32, i32p,
                                       ctypes.c_int32, f64p]
    lib.mml_csr_forest_predict.argtypes = [
        i64p, i64p, f64p, ctypes.c_int64,
        i32p, f64p, i32p, i32p, f64p,
        i64p, f64p, i32p, ctypes.c_int32, ctypes.c_int32, f64p]
    lib.mml_forest_predict_f64.argtypes = [
        f64p, ctypes.c_int64, ctypes.c_int32,
        i32p, f64p, u8p, i32p, i32p, f64p,
        ctypes.c_int32, ctypes.c_int32, i32p, ctypes.c_int32, f64p]
    lib.mml_bin_column_f64.argtypes = [f64p, ctypes.c_int64, f64p,
                                       ctypes.c_int32, i32p]
    lib.mml_bin_matrix_f64_u8.argtypes = [f64p, ctypes.c_int64,
                                          ctypes.c_int32, f64p, i64p, u8p]
    lib.mml_bin_matrix_f64_i32.argtypes = [f64p, ctypes.c_int64,
                                           ctypes.c_int32, f64p, i64p, i32p]
    lib.mml_vw_train_pass.argtypes = [
        i32p, f32p, f32p, f32p, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_float, ctypes.c_float, ctypes.c_float,
        ctypes.c_float, ctypes.c_float, ctypes.c_int32,
        f32p, f32p, f32p, f64p]
    lib.mml_gbdt_grow_tree.restype = ctypes.c_int32
    lib.mml_gbdt_grow_tree.argtypes = [
        u8p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        f32p, f32p, u8p, u8p,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_double, ctypes.c_double,
        ctypes.c_double, ctypes.c_double, ctypes.c_double, ctypes.c_double,
        i32p, i32p, u8p, i32p, i32p, f64p, f32p, i32p, f64p, i32p]


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


# ---------------------------------------------------------------------------
# Typed wrappers (None lib -> caller should use its numpy fallback)
# ---------------------------------------------------------------------------


def murmur3_batch(strings: List[str], seed: int = 0) -> Optional[np.ndarray]:
    lib = load()
    if lib is None:
        return None
    encoded = [s.encode("utf-8") for s in strings]
    offsets = np.zeros(len(encoded) + 1, dtype=np.int64)
    np.cumsum([len(e) for e in encoded], out=offsets[1:])
    buf = np.frombuffer(b"".join(encoded), dtype=np.uint8) if encoded else \
        np.empty(0, dtype=np.uint8)
    buf = np.ascontiguousarray(buf)
    out = np.zeros(len(encoded), dtype=np.uint32)
    lib.mml_murmur3_batch(_ptr(buf, ctypes.c_uint8), _ptr(offsets, ctypes.c_int64),
                          len(encoded), seed & 0xFFFFFFFF,
                          _ptr(out, ctypes.c_uint32))
    return out.astype(np.int64)


def resize_bilinear_rows(rows: Sequence[np.ndarray], oh: int, ow: int,
                         threads: int = 1) -> Optional[np.ndarray]:
    """Bilinear resize of HWC rows into ONE ``[n, oh, ow, c]`` array, in one
    native call. The rows share a channel count and a pixel type (uint8 or
    float32; the caller checks) and may differ in height and width.
    ``threads`` workers share the rows inside the call, which holds no GIL."""
    lib = load()
    if lib is None:
        return None
    rows = [np.ascontiguousarray(r) for r in rows]  # kept alive over the call
    n = len(rows)
    shape, dt = (rows[0].shape, rows[0].dtype) if n else ((), None)
    if len(shape) != 3 or dt not in (np.uint8, np.float32) \
            or min(oh, ow) < 1 or any(
                r.ndim != 3 or r.dtype != dt or r.shape[2] != shape[2]
                or 0 in r.shape for r in rows):
        raise ValueError("resize_bilinear_rows: non-empty HWC rows of one "
                         "channel count, all uint8 or all float32")
    c = shape[2]
    srcs = (ctypes.c_void_p * n)(*[r.ctypes.data for r in rows])
    hs = np.array([r.shape[0] for r in rows], dtype=np.int32)
    ws = np.array([r.shape[1] for r in rows], dtype=np.int32)
    dst = np.empty((n, oh, ow, c), dtype=dt)
    lib.mml_resize_bilinear_rows(
        srcs, _ptr(hs, ctypes.c_int32), _ptr(ws, ctypes.c_int32), n, c,
        int(dt == np.float32), dst.ctypes.data, oh, ow,
        max(1, min(int(threads), n)))
    return dst


def resize_bilinear(img: np.ndarray, oh: int, ow: int) -> Optional[np.ndarray]:
    """One image through the column kernel (n = 1): same code, same bits."""
    img = np.asarray(img)
    if img.ndim == 2:
        img = img[:, :, None]
    if img.dtype != np.uint8:
        img = img.astype(np.float32, copy=False)
    out = resize_bilinear_rows([img], oh, ow)
    return None if out is None else out[0]


def unroll_chw(img: np.ndarray, normalize: bool = False) -> Optional[np.ndarray]:
    lib = load()
    if lib is None or img.dtype != np.uint8:
        return None
    img = np.ascontiguousarray(img)
    if img.ndim == 2:
        img = img[:, :, None]
    h, w, c = img.shape
    out = np.empty(c * h * w, dtype=np.float64)
    lib.mml_unroll_chw_f64(_ptr(img, ctypes.c_uint8), h, w, c,
                           _ptr(out, ctypes.c_double), int(normalize))
    return out


def histogram(bins: np.ndarray, grad: np.ndarray, hess: np.ndarray,
              mask: np.ndarray, num_bins: int) -> Optional[np.ndarray]:
    lib = load()
    if lib is None:
        return None
    bins = np.ascontiguousarray(bins, dtype=np.int32)
    grad = np.ascontiguousarray(grad, dtype=np.float32)
    hess = np.ascontiguousarray(hess, dtype=np.float32)
    mask8 = np.ascontiguousarray(mask, dtype=np.uint8)
    n, f = bins.shape
    out = np.zeros((f, num_bins, 3), dtype=np.float32)
    lib.mml_histogram(_ptr(bins, ctypes.c_int32), _ptr(grad, ctypes.c_float),
                      _ptr(hess, ctypes.c_float), _ptr(mask8, ctypes.c_uint8),
                      n, f, num_bins, _ptr(out, ctypes.c_float))
    return out


def forest_predict(X: np.ndarray, feature: np.ndarray, threshold: np.ndarray,
                   default_left: np.ndarray, left: np.ndarray,
                   right: np.ndarray, value: np.ndarray,
                   class_of_tree: np.ndarray, num_class: int
                   ) -> Optional[np.ndarray]:
    lib = load()
    if lib is None:
        return None
    X = np.ascontiguousarray(X, dtype=np.float32)
    n, num_feat = X.shape
    t, m = feature.shape
    feature = np.ascontiguousarray(feature, dtype=np.int32)
    threshold = np.ascontiguousarray(threshold, dtype=np.float32)
    dl = np.ascontiguousarray(default_left, dtype=np.uint8)
    left = np.ascontiguousarray(left, dtype=np.int32)
    right = np.ascontiguousarray(right, dtype=np.int32)
    value = np.ascontiguousarray(value, dtype=np.float32)
    cot = np.ascontiguousarray(class_of_tree, dtype=np.int32)
    out = np.zeros((n, num_class), dtype=np.float64)
    lib.mml_forest_predict(
        _ptr(X, ctypes.c_float), n, num_feat, _ptr(feature, ctypes.c_int32),
        _ptr(threshold, ctypes.c_float), _ptr(dl, ctypes.c_uint8),
        _ptr(left, ctypes.c_int32), _ptr(right, ctypes.c_int32),
        _ptr(value, ctypes.c_float), t, m, _ptr(cot, ctypes.c_int32),
        num_class, _ptr(out, ctypes.c_double))
    return out


def csr_forest_predict(indptr: np.ndarray, indices: np.ndarray,
                       values: np.ndarray, feature: np.ndarray,
                       threshold: np.ndarray, left: np.ndarray,
                       right: np.ndarray, value: np.ndarray,
                       tree_offset: np.ndarray, shrinkage: np.ndarray,
                       class_of_tree: np.ndarray, num_class: int
                       ) -> Optional[np.ndarray]:
    """Flattened-forest traversal over CSR rows (numeric splits only; the
    caller keeps categorical forests on the numpy path). Node arrays are
    the per-tree arrays concatenated; ``tree_offset`` is the [T+1] node
    base of each tree; left/right stay tree-local ids."""
    lib = load()
    if lib is None:
        return None
    indptr = np.ascontiguousarray(indptr, dtype=np.int64)
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    values = np.ascontiguousarray(values, dtype=np.float64)
    feature = np.ascontiguousarray(feature, dtype=np.int32)
    threshold = np.ascontiguousarray(threshold, dtype=np.float64)
    left = np.ascontiguousarray(left, dtype=np.int32)
    right = np.ascontiguousarray(right, dtype=np.int32)
    value = np.ascontiguousarray(value, dtype=np.float64)
    tree_offset = np.ascontiguousarray(tree_offset, dtype=np.int64)
    shrinkage = np.ascontiguousarray(shrinkage, dtype=np.float64)
    cot = np.ascontiguousarray(class_of_tree, dtype=np.int32)
    n = len(indptr) - 1
    n_trees = len(shrinkage)
    out = np.zeros((n, num_class), dtype=np.float64)
    lib.mml_csr_forest_predict(
        _ptr(indptr, ctypes.c_int64), _ptr(indices, ctypes.c_int64),
        _ptr(values, ctypes.c_double), n,
        _ptr(feature, ctypes.c_int32), _ptr(threshold, ctypes.c_double),
        _ptr(left, ctypes.c_int32), _ptr(right, ctypes.c_int32),
        _ptr(value, ctypes.c_double),
        _ptr(tree_offset, ctypes.c_int64), _ptr(shrinkage, ctypes.c_double),
        _ptr(cot, ctypes.c_int32), n_trees, num_class,
        _ptr(out, ctypes.c_double))
    return out


def bin_column(vals: np.ndarray, edges: np.ndarray) -> Optional[np.ndarray]:
    """Numeric-column quantile binning: lower_bound(edges)+1, NaN -> 0."""
    lib = load()
    if lib is None:
        return None
    vals = np.ascontiguousarray(vals, dtype=np.float64)
    edges = np.ascontiguousarray(edges, dtype=np.float64)
    out = np.empty(len(vals), dtype=np.int32)
    lib.mml_bin_column_f64(_ptr(vals, ctypes.c_double), len(vals),
                           _ptr(edges, ctypes.c_double), len(edges),
                           _ptr(out, ctypes.c_int32))
    return out


_LOSS_IDS = {"squared": 0, "logistic": 1, "hinge": 2, "quantile": 3}


def vw_train_pass(indices: np.ndarray, values: np.ndarray,
                  labels: np.ndarray, weights: np.ndarray,
                  w: np.ndarray, g2: np.ndarray, t: float, *,
                  loss: str, tau: float, lr: float, power_t: float,
                  initial_t: float, l2: float, adaptive: bool):
    """One sequential learning pass IN PLACE over ``w``/``g2`` (padded
    sparse examples). Returns (new_t, loss_sum) or None when unavailable.
    Mirrors vw/learner.make_scan_pass's f32 update exactly."""
    lib = load()
    if lib is None or loss not in _LOSS_IDS:
        return None
    indices = np.ascontiguousarray(indices, dtype=np.int32)
    values = np.ascontiguousarray(values, dtype=np.float32)
    labels = np.ascontiguousarray(labels, dtype=np.float32)
    weights = np.ascontiguousarray(weights, dtype=np.float32)
    if w.dtype != np.float32 or g2.dtype != np.float32:
        # in-place C++ update needs f32 buffers; a bare assert here would
        # vanish under `python -O` and hand the kernel mistyped pointers —
        # degrade to the scan engine instead (the None contract above)
        return None
    n, k = indices.shape
    t_box = np.array([t], dtype=np.float32)
    loss_out = np.zeros(1, dtype=np.float64)
    lib.mml_vw_train_pass(
        _ptr(indices, ctypes.c_int32), _ptr(values, ctypes.c_float),
        _ptr(labels, ctypes.c_float), _ptr(weights, ctypes.c_float),
        n, k, _LOSS_IDS[loss], float(tau), float(lr), float(power_t),
        float(initial_t), float(l2), int(adaptive),
        _ptr(w, ctypes.c_float), _ptr(g2, ctypes.c_float),
        _ptr(t_box, ctypes.c_float), _ptr(loss_out, ctypes.c_double))
    return float(t_box[0]), float(loss_out[0])


def bin_matrix(X: np.ndarray, edges_list, dtype=np.int32
               ) -> Optional[np.ndarray]:
    """Row-major [N, F] floats -> feature-major [F, N] bins in ONE blocked
    pass (numeric features only; NaN -> bin 0)."""
    lib = load()
    if lib is None or dtype not in (np.uint8, np.int32):
        return None
    X = np.ascontiguousarray(X, dtype=np.float64)
    n, num_f = X.shape
    offsets = np.zeros(num_f + 1, dtype=np.int64)
    np.cumsum([len(e) for e in edges_list], out=offsets[1:])
    flat = (np.concatenate([np.asarray(e, dtype=np.float64)
                            for e in edges_list])
            if offsets[-1] else np.empty(0, dtype=np.float64))
    flat = np.ascontiguousarray(flat)
    out = np.empty((num_f, n), dtype=dtype)
    if dtype == np.uint8:
        lib.mml_bin_matrix_f64_u8(
            _ptr(X, ctypes.c_double), n, num_f, _ptr(flat, ctypes.c_double),
            _ptr(offsets, ctypes.c_int64), _ptr(out, ctypes.c_uint8))
    else:
        lib.mml_bin_matrix_f64_i32(
            _ptr(X, ctypes.c_double), n, num_f, _ptr(flat, ctypes.c_double),
            _ptr(offsets, ctypes.c_int64), _ptr(out, ctypes.c_int32))
    return out


def gbdt_grow_tree(bins_fm: np.ndarray, grad: np.ndarray, hess: np.ndarray,
                   row_mask: Optional[np.ndarray],
                   feature_mask: Optional[np.ndarray], *,
                   num_bins: int, num_leaves: int, max_depth: int,
                   min_data_in_leaf: float, min_sum_hessian: float,
                   min_gain_to_split: float, lambda_l1: float,
                   lambda_l2: float, max_delta_step: float):
    """Grow one leaf-wise tree on the host (LightGBM serial learner
    equivalent; numeric splits only). Returns a dict of flat node arrays
    (length = node count) + ``leaf_of_row`` [N], or None when the native
    library is unavailable.

    ``bins_fm``: [F, N] uint8 feature-major bins (0 = missing)."""
    lib = load()
    if lib is None or num_bins > 256:
        return None
    bins_fm = np.ascontiguousarray(bins_fm, dtype=np.uint8)
    num_f, n = bins_fm.shape
    grad = np.ascontiguousarray(grad, dtype=np.float32)
    hess = np.ascontiguousarray(hess, dtype=np.float32)
    rm = (np.ascontiguousarray(row_mask, dtype=np.uint8)
          if row_mask is not None else None)
    fm = (np.ascontiguousarray(feature_mask, dtype=np.uint8)
          if feature_mask is not None else None)
    cap = 2 * num_leaves - 1
    feature = np.empty(cap, dtype=np.int32)
    tbin = np.empty(cap, dtype=np.int32)
    dleft = np.empty(cap, dtype=np.uint8)
    left = np.empty(cap, dtype=np.int32)
    right = np.empty(cap, dtype=np.int32)
    value = np.empty(cap, dtype=np.float64)
    gain = np.empty(cap, dtype=np.float32)
    count = np.empty(cap, dtype=np.int32)
    weight = np.empty(cap, dtype=np.float64)
    leaf_of_row = np.empty(n, dtype=np.int32)
    null_u8 = ctypes.POINTER(ctypes.c_uint8)()
    n_nodes = lib.mml_gbdt_grow_tree(
        _ptr(bins_fm, ctypes.c_uint8), n, num_f, num_bins,
        _ptr(grad, ctypes.c_float), _ptr(hess, ctypes.c_float),
        _ptr(rm, ctypes.c_uint8) if rm is not None else null_u8,
        _ptr(fm, ctypes.c_uint8) if fm is not None else null_u8,
        num_leaves, max_depth, float(min_data_in_leaf),
        float(min_sum_hessian), float(min_gain_to_split),
        float(lambda_l1), float(lambda_l2), float(max_delta_step),
        _ptr(feature, ctypes.c_int32), _ptr(tbin, ctypes.c_int32),
        _ptr(dleft, ctypes.c_uint8), _ptr(left, ctypes.c_int32),
        _ptr(right, ctypes.c_int32), _ptr(value, ctypes.c_double),
        _ptr(gain, ctypes.c_float), _ptr(count, ctypes.c_int32),
        _ptr(weight, ctypes.c_double), _ptr(leaf_of_row, ctypes.c_int32))
    m = int(n_nodes)
    return {"feature": feature[:m], "threshold_bin": tbin[:m],
            "default_left": dleft[:m].astype(bool), "left": left[:m],
            "right": right[:m], "value": value[:m], "gain": gain[:m],
            "count": count[:m], "weight": weight[:m],
            "leaf_of_row": leaf_of_row}


def forest_predict_f64(X: np.ndarray, feature: np.ndarray,
                       threshold: np.ndarray, default_left: np.ndarray,
                       left: np.ndarray, right: np.ndarray,
                       value: np.ndarray, class_of_tree: np.ndarray,
                       num_class: int) -> Optional[np.ndarray]:
    """f64 dense forest traversal — bit-equal to the Python host path
    (predict.predict_single_tree) for numeric splits; ``value`` must be
    pre-scaled by shrinkage. Node arrays are [T, m] padded SoA."""
    lib = load()
    if lib is None:
        return None
    X = np.ascontiguousarray(X, dtype=np.float64)
    n, num_feat = X.shape
    t, m = feature.shape
    feature = np.ascontiguousarray(feature, dtype=np.int32)
    threshold = np.ascontiguousarray(threshold, dtype=np.float64)
    dl = np.ascontiguousarray(default_left, dtype=np.uint8)
    left = np.ascontiguousarray(left, dtype=np.int32)
    right = np.ascontiguousarray(right, dtype=np.int32)
    value = np.ascontiguousarray(value, dtype=np.float64)
    cot = np.ascontiguousarray(class_of_tree, dtype=np.int32)
    out = np.zeros((n, num_class), dtype=np.float64)
    lib.mml_forest_predict_f64(
        _ptr(X, ctypes.c_double), n, num_feat,
        _ptr(feature, ctypes.c_int32), _ptr(threshold, ctypes.c_double),
        _ptr(dl, ctypes.c_uint8), _ptr(left, ctypes.c_int32),
        _ptr(right, ctypes.c_int32), _ptr(value, ctypes.c_double),
        t, m, _ptr(cot, ctypes.c_int32), num_class,
        _ptr(out, ctypes.c_double))
    return out
