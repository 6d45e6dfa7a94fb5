"""Tree-ensemble prediction: vectorized host path + jitted device kernels.

LGBM_BoosterPredictForMat/PredictForMatSingle parity (driven by the reference's
scoring UDFs, lightgbm/LightGBMBooster.scala:21-148). Two device strategies:

- **GEMM forest** (default for numerical forests): tree traversal
  reformulated as matrix algebra on the MXU — the TPU-first design, since
  per-node gathers serialize badly on TPU (measured ~20k rows/s for the
  gather loop at 200k x 50 trees). Per row: comparison signs s_i = ±1 for
  every internal node of every tree (one [N, I] gather + compare), then
  ONE matmul against the ±1/0 path matrix C[i, l] (+1 left-ancestor, -1
  right-ancestor, 0 non-ancestor): a leaf l is reached iff (S @ C)[l]
  equals its path length. Leaf values arrive via a second matmul. All
  products are ±1/0 — exact in bf16 with f32 accumulation; the value
  matmul runs f32. Rows are chunked so [N, I]/[N, L] activations stay
  bounded.
- **Gather loop** (fallback): bounded per-depth gathers over the padded
  node SoA — used for categorical forests (set membership is not a sign
  comparison; small models use host traversal outright).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .tree import Tree


def predict_single_tree(tree: Tree, X: np.ndarray) -> np.ndarray:
    """Host path: [N,F] raw floats -> [N] contributions (incl. shrinkage).

    Categorical SET nodes (tree.cat_sets): LightGBM semantics — the value
    is truncated to int and tested for set membership; members go left,
    everything else (incl. NaN and unseen categories) goes right."""
    n = X.shape[0]
    node = np.zeros(n, dtype=np.int64)
    has_cat = tree.cat_sets is not None
    active = tree.feature[node] != -1
    while active.any():
        cur = node[active]
        f = tree.feature[cur]
        x = X[active, f]
        miss = np.isnan(x)
        go_left = np.where(miss, tree.default_left[cur], x <= tree.threshold[cur])
        if has_cat:
            for nid in np.unique(cur):
                cset = tree.cat_sets[nid]
                if cset is None:
                    continue
                sel = cur == nid
                xv = x[sel]
                ok = ~np.isnan(xv)
                member = np.zeros(len(xv), dtype=bool)
                member[ok] = np.isin(xv[ok].astype(np.int64), cset)
                go_left[sel] = member
        node[active] = np.where(go_left, tree.left[cur], tree.right[cur])
        active = tree.feature[node] != -1
    return tree.value[node] * tree.shrinkage


_FOREST_MEMO: dict = {}


def memoize_forest(tree_groups, tag: str, build):
    """Identity-memoized per-forest arrays for the native predict paths.

    Key: the first Tree object's id + ``tag`` (layout variant). A cache
    hit must prove the forest is the SAME sequence of Tree objects, not
    just the same head: boosters continued from one init_model share their
    prefix trees (Booster.trees copies the list but not the Tree objects),
    so two distinct forests can agree on (id(first), length, shrinkages).
    Validation therefore holds a weakref per tree and requires every
    weakref to resolve to the corresponding tree by identity (weakrefs
    also guard against id reuse after GC; Tree is an eq-dataclass and
    cannot key a WeakKeyDictionary). Per-tree shrinkage is checked too:
    the ONLY in-place Tree mutation in the codebase (dart rescales dropped
    trees' shrinkage between iterations, rf normalizes after training).
    Any new in-place mutation must extend THIS validation — it covers the
    dense and CSR layouts at once, which is why the helper is shared."""
    import weakref

    trees = [t for g in tree_groups for t in g]
    shr = tuple(float(t.shrinkage) for t in trees)
    # first+last+length in the key so prefix-sharing forests (same head,
    # different tails) cache SIMULTANEOUSLY instead of evicting each other
    key = (id(trees[0]), id(trees[-1]), len(trees), tag)
    cached = _FOREST_MEMO.get(key)
    if (cached is not None and len(cached[0]) == len(trees)
            and cached[1] == shr
            and all(r() is t for r, t in zip(cached[0], trees))):
        return cached[2]
    flat = build()
    if len(_FOREST_MEMO) >= 16:
        _FOREST_MEMO.pop(next(iter(_FOREST_MEMO)))
    _FOREST_MEMO[key] = (tuple(weakref.ref(t) for t in trees), shr, flat)
    return flat


def pad_soa(vals, fill, dtype, T: int, m: int) -> np.ndarray:
    """[T, m] padded struct-of-arrays field (shared by the device ensemble
    and the native host layouts)."""
    arr = np.full((T, m), fill, dtype=dtype)
    for i, v in enumerate(vals):
        arr[i, :len(v)] = v
    return arr


def _padded_forest_f64(tree_groups):
    """[T, m] padded SoA (f64 thresholds/values, value pre-scaled by
    shrinkage) for the native host traversal."""

    def build():
        trees = [t for g in tree_groups for t in g]
        m = max(len(t.feature) for t in trees)
        T = len(trees)
        return (pad_soa([t.feature for t in trees], -1, np.int32, T, m),
                pad_soa([t.threshold for t in trees], 0.0, np.float64, T, m),
                pad_soa([t.default_left for t in trees], True, bool, T, m),
                pad_soa([t.left for t in trees], 0, np.int32, T, m),
                pad_soa([t.right for t in trees], 0, np.int32, T, m),
                pad_soa([np.asarray(t.value) * t.shrinkage for t in trees],
                        0.0, np.float64, T, m),
                np.array([k for g in tree_groups for k in range(len(g))],
                         dtype=np.int32))

    return memoize_forest(tree_groups, "dense_f64", build)


def predict_ensemble(tree_groups: List[List[Tree]], X: np.ndarray,
                     num_class: int) -> np.ndarray:
    """[iterations][class] trees -> [N, num_class] raw score deltas.

    Native fast path (numeric forests): one C++ SoA traversal, f64
    end-to-end — bit-equal to the per-tree numpy loop below, which stays
    as the toolchain-free fallback, the categorical path, and the parity
    reference (gated equal in tests). The reference's scoring surface is
    LightGBM's C++ predict (LightGBMBooster.scala:21-148);
    MMLSPARK_TPU_NO_NATIVE_PREDICT=1 disables."""
    import os

    n = X.shape[0]
    trees = [t for g in tree_groups for t in g]
    if (trees and not any(t.cat_sets is not None for t in trees)
            and os.environ.get("MMLSPARK_TPU_NO_NATIVE_PREDICT",
                               "") in ("", "0")):
        from .. import native_loader

        flat = _padded_forest_f64(tree_groups)
        res = native_loader.forest_predict_f64(np.asarray(X), *flat,
                                               num_class)
        if res is not None:
            return res
    out = np.zeros((n, num_class), dtype=np.float64)
    for group in tree_groups:
        for k, tree in enumerate(group):
            out[:, k] += predict_single_tree(tree, X)
    return out


class DeviceEnsemble:
    """All trees padded into one SoA tensor; one jitted traversal for the forest.

    Used by the model stages' transform hot path: predict cost is
    O(depth * N * T) gathers, fully parallel on device.
    """

    def __init__(self, tree_groups: List[List[Tree]], num_class: int):
        trees = [t for g in tree_groups for t in g]
        self.num_class = num_class
        self.last_ingest_stats = None  # set by chunked ring scoring
        self.class_of_tree = np.array(
            [k for g in tree_groups for k in range(len(g))], dtype=np.int32)
        self.num_trees = len(trees)
        if not trees:
            return
        m = max(len(t.feature) for t in trees)
        self.max_depth = 0
        T = self.num_trees
        self.feature = pad_soa([t.feature for t in trees], -1, np.int32, T, m)
        self.threshold = pad_soa([t.threshold for t in trees], 0.0,
                                 np.float32, T, m)
        self.default_left = pad_soa([t.default_left for t in trees], True,
                                    bool, T, m)
        self.left = pad_soa([t.left for t in trees], 0, np.int32, T, m)
        self.right = pad_soa([t.right for t in trees], 0, np.int32, T, m)
        self.value = pad_soa(
            [np.asarray(t.value) * t.shrinkage for t in trees],
            0.0, np.float32, T, m)
        # categorical SET nodes: padded per-node value sets [T, m, S] with
        # NaN fill (== compares false) — built only when the model has any.
        # High-cardinality sets (imported LightGBM models can carry
        # thousands of categories per node) would make both the [T, m, S]
        # tensor and the per-depth-step [N, T, S] gather blow up — those
        # models take the host traversal instead (self.cat_host_fallback).
        self.cat_vals = None
        self.is_cat = None
        self.cat_host_fallback = False
        self._tree_groups = tree_groups
        if any(t.cat_sets is not None for t in trees):
            smax = max((len(s) for t in trees if t.cat_sets is not None
                        for s in t.cat_sets if s is not None), default=1)
            if smax > 256 or self.num_trees * m * smax > 1 << 27:
                self.cat_host_fallback = True
            else:
                cv = np.full((self.num_trees, m, smax), np.nan,
                             dtype=np.float32)
                ic = np.zeros((self.num_trees, m), dtype=bool)
                for i, t in enumerate(trees):
                    if t.cat_sets is None:
                        continue
                    for nid, s in enumerate(t.cat_sets):
                        if s is not None:
                            cv[i, nid, : len(s)] = s
                            ic[i, nid] = True
                self.cat_vals = cv
                self.is_cat = ic
        for t in trees:
            self.max_depth = max(self.max_depth, _tree_depth(t))
        self._jitted = None
        self._jitted_gather = None
        self._gemm = None
        if self.cat_vals is None and not self.cat_host_fallback:
            self._build_gemm(trees)

    def _build_gemm(self, trees):
        """Per-tree padded GEMM layout: comparison-sign x path-matrix
        forest evaluation (module docstring). Host-built once."""
        import os

        T = self.num_trees
        i_max = max(max((int((t.feature >= 0).sum()) for t in trees),
                        default=1), 1)
        l_max = max(max((t.num_leaves for t in trees), default=1), 1)
        if os.environ.get("MMLSPARK_TPU_NO_GEMM_PREDICT", "") not in ("", "0"):
            self._gemm = None
            return
        if T * i_max * l_max > 1 << 27:
            # imported forests can carry thousands of leaves per tree: the
            # [T, I, L] path matrix would be GBs — keep the gather kernel
            self._gemm = None
            return
        # activations scale with rows x T x (I + L) — x_sel/s [N, T, I]
        # (f32 + bf16) and z/reach [N, T, L] (f32 x2); shrink the row chunk
        # so one dispatch stays ~<=1.5 GB (a 1000-tree x 255-leaf imported
        # forest passes the path-matrix guard but costs ~3.6 MB per row)
        per_row = T * (6 * i_max + 8 * l_max)
        budget = 1.5e9
        chunk = int(budget // max(per_row, 1))
        self._gemm_row_chunk = max(256, min(self.GEMM_ROW_CHUNK,
                                            (chunk // 256) * 256))
        feat = np.zeros((T, i_max), dtype=np.int32)
        thr = np.zeros((T, i_max), dtype=np.float32)
        dl = np.zeros((T, i_max), dtype=bool)
        ivalid = np.zeros((T, i_max), dtype=np.float32)
        C = np.zeros((T, i_max, l_max), dtype=np.float32)
        plen = np.full((T, l_max), -1.0, dtype=np.float32)  # pad unreachable
        lval = np.zeros((T, l_max), dtype=np.float32)
        for ti, t in enumerate(trees):
            int_ids = np.nonzero(t.feature >= 0)[0]
            int_index = {int(nid): i for i, nid in enumerate(int_ids)}
            feat[ti, : len(int_ids)] = t.feature[int_ids]
            thr[ti, : len(int_ids)] = t.threshold[int_ids]
            dl[ti, : len(int_ids)] = t.default_left[int_ids]
            ivalid[ti, : len(int_ids)] = 1.0
            li = 0
            stack = [(0, [])]
            while stack:
                nid, path = stack.pop()
                if t.feature[nid] == -1:
                    for ii, sign in path:
                        C[ti, ii, li] = sign
                    plen[ti, li] = float(len(path))
                    lval[ti, li] = float(t.value[nid]) * t.shrinkage
                    li += 1
                else:
                    ii = int_index[int(nid)]
                    stack.append((int(t.left[nid]), path + [(ii, 1.0)]))
                    stack.append((int(t.right[nid]), path + [(ii, -1.0)]))
        self._gemm = (feat, thr, dl, ivalid, C, plen, lval)

    def _compile_gemm(self):
        import jax
        import jax.numpy as jnp

        feat_h, thr_h, dl_h, iv_h, C_h, plen_h, lval_h = self._gemm
        # ±1/0 operands are exact in bf16 (half the MXU passes); CPU XLA
        # has no bf16xbf16->f32 dot, so it keeps f32 (equally exact)
        mm_dtype = (jnp.bfloat16 if jax.default_backend() == "tpu"
                    else jnp.float32)
        feat = jnp.asarray(feat_h)
        thr = jnp.asarray(thr_h)
        dl = jnp.asarray(dl_h)
        iv = jnp.asarray(iv_h)
        Cb = jnp.asarray(C_h, dtype=mm_dtype)
        plen = jnp.asarray(plen_h)
        lval = jnp.asarray(lval_h)
        class_onehot = jax.nn.one_hot(
            jnp.asarray(self.class_of_tree), self.num_class,
            dtype=jnp.float32)

        def fwd(X):
            x_sel = X[:, feat]                       # [N, T, I] gather
            s = jnp.where(jnp.isnan(x_sel),
                          jnp.where(dl[None], 1.0, -1.0),
                          jnp.where(x_sel <= thr[None], 1.0, -1.0))
            s = (s * iv[None]).astype(mm_dtype)      # pad ints contribute 0
            # z[n,t,l] = sum_i s * C: ±1 products are exact in bf16, the
            # f32 accumulation holds small integers exactly
            z = jax.lax.dot_general(
                s, Cb, ((((2,), (1,)), ((1,), (0,)))),
                preferred_element_type=jnp.float32)  # [T, N, L]
            z = jnp.swapaxes(z, 0, 1)                # [N, T, L]
            reach = (z == plen[None]).astype(jnp.float32)
            contrib = jnp.sum(reach * lval[None], axis=2)   # [N, T]
            return contrib @ class_onehot            # [N, K]

        return jax.jit(fwd)

    def _compile(self):
        import jax
        import jax.numpy as jnp

        depth = max(self.max_depth, 1)
        feature = jnp.asarray(self.feature)
        threshold = jnp.asarray(self.threshold)
        default_left = jnp.asarray(self.default_left)
        left = jnp.asarray(self.left)
        right = jnp.asarray(self.right)
        value = jnp.asarray(self.value)
        class_onehot = jax.nn.one_hot(
            jnp.asarray(self.class_of_tree), self.num_class, dtype=jnp.float32)

        cat_vals = (jnp.asarray(self.cat_vals)
                    if self.cat_vals is not None else None)
        is_cat = jnp.asarray(self.is_cat) if self.is_cat is not None else None

        def fwd(X):
            n = X.shape[0]
            t = feature.shape[0]
            node = jnp.zeros((n, t), dtype=jnp.int32)

            t_idx = jnp.arange(t, dtype=jnp.int32)[None, :]

            def body(_, node):
                # advanced-index gathers ([T, m][t, node] -> [N, T]): the
                # take_along_axis(arr[None], node[:, :, None]) form lowered
                # to a broadcast materializing [N, T, m] per field — ~2.4 GB
                # at 200k rows x 50 trees
                f = feature[t_idx, node]
                thr = threshold[t_idx, node]
                dl = default_left[t_idx, node]
                l = left[t_idx, node]
                r = right[t_idx, node]
                x = jnp.take_along_axis(X, jnp.maximum(f, 0), axis=1)
                miss = jnp.isnan(x)
                go_left = jnp.where(miss, dl, x <= thr)
                if cat_vals is not None:
                    # set membership (truncated-int equality; NaN pads and
                    # NaN inputs compare false -> right)
                    sv = cat_vals[t_idx, node]            # [N, T, S]
                    member = jnp.any(
                        jnp.trunc(x)[:, :, None] == sv, axis=-1)
                    icn = is_cat[t_idx, node]             # [N, T]
                    go_left = jnp.where(icn, member, go_left)
                nxt = jnp.where(go_left, l, r)
                return jnp.where(f == -1, node, nxt)

            node = jax.lax.fori_loop(0, depth, body, node)
            leaf_vals = value[t_idx, node]
            return leaf_vals @ class_onehot          # [N, num_class]

        return jax.jit(fwd)

    # max rows per GEMM dispatch; _build_gemm shrinks it when T*(I+L) makes
    # the [N, T, I]/[N, T, L] activations large (see per_row budget there)
    GEMM_ROW_CHUNK = 1 << 16
    _gemm_row_chunk = GEMM_ROW_CHUNK

    def device_forward(self, params=None):
        """The traced forest kernel X[f32] -> [N, num_class] f32 raw scores
        for pipeline fusion, or None when only the host traversal is valid
        (empty/categorical-fallback forests). Returns the SAME jitted
        callable predict_raw dispatches — calling it inside an enclosing
        jit inlines the identical jaxpr, so a fused segment's forest
        arithmetic is bitwise-equal to the standalone path.

        ``params`` (a kernel-variant params dict, see core.kernels) selects
        the traversal implementation: ``{"impl": "gather"}`` forces the
        fori_loop gather kernel even when the GEMM path matrix is built;
        ``{"impl": "gemm"}`` (and None/default) keeps the default routing.
        Both implementations are exact — leaf values reach the output as
        one-hot products with exact-zero padding — so every variant is
        bitwise-equal; the variants differ only in compiled-program cost.
        """
        if self.num_trees == 0 or self.cat_host_fallback:
            return None
        if params and params.get("impl") == "gather":
            if self._jitted_gather is None:
                self._jitted_gather = self._compile()
            return self._jitted_gather
        if self._jitted is None:
            self._jitted = (self._compile_gemm() if self._gemm is not None
                            else self._compile())
        return self._jitted

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        """[N,F] float32 -> [N, num_class] summed tree outputs (device)."""
        if self.num_trees == 0:
            return np.zeros((X.shape[0], self.num_class), dtype=np.float64)
        if self.cat_host_fallback:
            return predict_ensemble(self._tree_groups, np.asarray(X),
                                    self.num_class)
        Xf = np.asarray(X, dtype=np.float32)
        if self._gemm is not None:
            if self._jitted is None:
                self._jitted = self._compile_gemm()
            n = Xf.shape[0]
            row_chunk = self._gemm_row_chunk
            if n <= row_chunk:
                return np.asarray(self._jitted(Xf), dtype=np.float64)
            # chunked scoring rides the shared transfer ring: chunk i+1's
            # pad + H2D overlaps chunk i's forest GEMM instead of the old
            # serial dispatch-readback-dispatch loop
            import jax

            from ..parallel.batching import Batch
            from ..parallel.ingest import IngestStats, TransferRing

            def chunks():
                for r0 in range(0, n, row_chunk):
                    xc = Xf[r0: r0 + row_chunk]
                    m = len(xc)
                    if m < row_chunk:  # pad: one compiled shape
                        xc = np.pad(xc, ((0, row_chunk - m), (0, 0)),
                                    constant_values=np.nan)
                    # analysis: allow D001 -- host validity mask only
                    mask = np.zeros(row_chunk, dtype=bool)
                    mask[:m] = True
                    yield Batch({"x": xc}, mask, m)

            self.last_ingest_stats = IngestStats()
            ring = TransferRing(
                chunks(),
                put=lambda b: (jax.device_put(b.arrays["x"]), b.num_valid),
                step=lambda s: (self._jitted(s[0]), s[1]),
                fetch=lambda h: np.asarray(h[0], dtype=np.float64)[:h[1]],
                depth=2, stats=self.last_ingest_stats)
            outs = list(ring)
            return np.concatenate(outs, axis=0)
        if self._jitted is None:
            self._jitted = self._compile()
        return np.asarray(self._jitted(Xf), dtype=np.float64)


def _tree_depth(tree: Tree) -> int:
    depth = np.zeros(len(tree.feature), dtype=np.int32)
    order = range(len(tree.feature))
    for i in order:  # parents precede children by construction
        if tree.feature[i] != -1:
            depth[tree.left[i]] = depth[i] + 1
            depth[tree.right[i]] = depth[i] + 1
    return int(depth.max()) + 1 if len(depth) else 1
