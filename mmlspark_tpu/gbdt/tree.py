"""Leaf-wise (best-first) tree growth over binned features.

LightGBM's core algorithm (the reference drives it as a black box through
LGBM_BoosterUpdateOneIter, TrainUtils.scala:170-233): grow the leaf with the
largest split gain until ``num_leaves``, computing each split from per-leaf
histograms, with the parent-minus-sibling subtraction trick so each level costs
one scatter pass over the smaller child only.

Two growth paths, identical semantics:

- **Device-fused (default)**: the ENTIRE tree grows inside one jitted
  ``lax.while_loop`` — the best-first heap is an argmax over per-leaf candidate
  gains, node state lives in flat device arrays, and each iteration routes rows
  + scatters the small child's histogram (Pallas MXU kernel on TPU) + derives
  the sibling by subtraction + evaluates both children's splits. One dispatch
  and one host fetch per TREE; the old per-split orchestration cost ~31
  blocking round trips per tree and was dispatch-bound end-to-end.
  Row-sharded (multi-chip) inputs take the same fused path per shard under
  ``shard_map`` with psum'd histograms — replicated split decisions, sharded
  row routing (LightGBM's socket-ring allreduce as one collective stream).
- **Host-orchestrated**: one fused dispatch per split (histogram.py kernels
  with static shapes). The fallback when the per-node histogram buffer would
  exceed the memory budget (MMLSPARK_TPU_FUSED_TREE_BYTES), on CPU (cheap
  in-process dispatch), or when MMLSPARK_TPU_NO_FUSED_TREE=1 forces it.

Trees are stored as flat arrays (SoA) for vectorized prediction: no pointer
chasing, predict is a gather loop over depth (predict_trees in booster.py).
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..parallel.mesh import fetch_global

from . import histogram as H

# Per-node histogram buffer cap for the device-fused grower: [2L-1, F, B, 3] f32.
# Above this, fall back to per-split host orchestration (whose live set is the
# heap frontier only).
_FUSED_TREE_DEFAULT_BUDGET = 2 << 30


def _fused_tree_enabled(max_nodes: int, num_f: int, num_bins: int) -> bool:
    if os.environ.get("MMLSPARK_TPU_NO_FUSED_TREE", "") not in ("", "0"):
        return False
    budget = int(os.environ.get("MMLSPARK_TPU_FUSED_TREE_BYTES",
                                _FUSED_TREE_DEFAULT_BUDGET))
    if max_nodes * num_f * num_bins * 3 * 4 > budget:
        return False
    if os.environ.get("MMLSPARK_TPU_FUSED_TREE", "") not in ("", "0"):
        return True  # forced on (tests exercise the fused path on CPU)
    # default: accelerators only — the fused win is removing per-split
    # dispatch round trips, which in-process CPU dispatch barely pays
    # (measured: TPU 200s -> 27s, CPU 8.3s -> 11.9s on the training bench)
    import jax

    return jax.default_backend() != "cpu"


@dataclasses.dataclass
class Tree:
    """Flat decision tree. Node 0 is the root; feature == -1 marks a leaf."""

    feature: np.ndarray        # i32 [nodes], -1 for leaves
    threshold: np.ndarray      # f64 [nodes], raw-value threshold (<= goes left)
    threshold_bin: np.ndarray  # i32 [nodes]
    default_left: np.ndarray   # bool [nodes], missing direction
    left: np.ndarray           # i32 [nodes]
    right: np.ndarray          # i32 [nodes]
    value: np.ndarray          # f64 [nodes], leaf output (0 for internal)
    gain: np.ndarray           # f32 [nodes], split gain (0 for leaves)
    count: np.ndarray          # i32 [nodes], training rows through the node
    shrinkage: float = 1.0
    weight: Optional[np.ndarray] = None  # f64 [nodes], hessian sums (None: legacy)
    # categorical SET splits (LightGBM num_cat machinery): for a cat split
    # node, membership sends a row LEFT. Two views of the same set:
    #   cat_sets       — per node: sorted int64 category VALUES (raw-float
    #                    predict + LightGBM interchange), None elsewhere
    #   cat_bin_words  — [nodes, CW] u32 bitset over BIN ids (binned
    #                    routing/predict; None for imported models with no
    #                    bin mapper)
    cat_sets: Optional[list] = None
    cat_bin_words: Optional[np.ndarray] = None

    @property
    def num_leaves(self) -> int:
        return int((self.feature == -1).sum())

    def is_cat_node(self, nid: int) -> bool:
        return (self.cat_sets is not None
                and self.cat_sets[nid] is not None) or (
            self.cat_bin_words is not None
            and bool(self.cat_bin_words[nid].any()))

    def to_dict(self) -> dict:
        d = {
            "feature": self.feature.tolist(),
            "threshold": self.threshold.tolist(),
            "threshold_bin": self.threshold_bin.tolist(),
            "default_left": self.default_left.tolist(),
            "left": self.left.tolist(),
            "right": self.right.tolist(),
            "value": self.value.tolist(),
            "gain": self.gain.tolist(),
            "count": self.count.tolist(),
            "shrinkage": self.shrinkage,
        }
        if self.weight is not None:
            d["weight"] = self.weight.tolist()
        if self.cat_sets is not None:
            d["cat_sets"] = [s.tolist() if s is not None else None
                             for s in self.cat_sets]
        if self.cat_bin_words is not None:
            d["cat_bin_words"] = self.cat_bin_words.tolist()
        return d

    @staticmethod
    def from_dict(d: dict) -> "Tree":
        cat_sets = None
        if d.get("cat_sets") is not None:
            cat_sets = [np.asarray(s, dtype=np.int64) if s is not None
                        else None for s in d["cat_sets"]]
        return Tree(
            feature=np.asarray(d["feature"], dtype=np.int32),
            threshold=np.asarray(d["threshold"], dtype=np.float64),
            threshold_bin=np.asarray(d["threshold_bin"], dtype=np.int32),
            default_left=np.asarray(d["default_left"], dtype=bool),
            left=np.asarray(d["left"], dtype=np.int32),
            right=np.asarray(d["right"], dtype=np.int32),
            value=np.asarray(d["value"], dtype=np.float64),
            gain=np.asarray(d["gain"], dtype=np.float32),
            count=np.asarray(d["count"], dtype=np.int32),
            shrinkage=float(d.get("shrinkage", 1.0)),
            weight=(np.asarray(d["weight"], dtype=np.float64)
                    if d.get("weight") is not None else None),
            cat_sets=cat_sets,
            cat_bin_words=(np.asarray(d["cat_bin_words"], dtype=np.uint32)
                           if d.get("cat_bin_words") is not None else None),
        )


@dataclasses.dataclass
class GrowerConfig:
    num_leaves: int = 31
    max_depth: int = -1                 # -1 = unlimited (bounded by num_leaves)
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    max_delta_step: float = 0.0         # clamp |leaf value| (0 = off)
    # categorical set-split controls (LightGBM defaults)
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_threshold: int = 32


class _Node:
    __slots__ = ("id", "depth", "hist", "sums", "split")

    def __init__(self, id, depth, hist, sums, split):
        self.id = id
        self.depth = depth
        self.hist = hist
        self.sums = sums      # np [3]: grad, hess, count
        self.split = split    # SplitInfo (host numpy) or None


def _grow_tree_device_body(bins_fm, grad, hess, row_mask, node_of_row,
                           lambda_l1, lambda_l2, min_sum_hessian,
                           min_gain_to_split, feature_mask, *, num_bins: int,
                           max_nodes: int, min_data_in_leaf: int,
                           max_depth: int, use_mxu: bool,
                           has_feature_mask: bool, psum_axis=None,
                           interpret: bool = False, cat_args=None):
    """Grow one whole tree inside a single jitted ``lax.while_loop``.

    The best-first heap becomes an argmax over ``cand_gain`` (−inf marks
    non-splittable/already-split nodes); ties resolve to the lowest node id.
    NOTE: the host path's heapq breaks exact-gain ties by push order, which
    is small-child-first — NOT always the lower node id — so two candidates
    with bit-identical gains can pop in a different order there. Gains are
    f32 sums of distinct data, so real datasets hit this with probability ~0;
    everywhere else node ids are assigned in split order exactly as the host
    path does, and both paths produce identical trees.

    Returns flat node arrays sized ``max_nodes`` (= 2*num_leaves−1), the
    per-node (grad, hess, count) sums for host-side f64 leaf values, the final
    row→node routing, and ``n_nodes``. One dispatch, one fetch, per tree.

    ``psum_axis``: when set, this body is running per-shard under shard_map
    with rows split over that mesh axis — every histogram/total is psum'd so
    all shards make identical (replicated) split decisions while the row
    routing stays sharded. This is LightGBM's socket-ring data-parallel mode
    as one collective (TrainUtils.scala:383-418).
    """
    import jax
    import jax.numpy as jnp

    from . import pallas_select

    if use_mxu:
        from .pallas_hist import compute_histogram_mxu

        def base_hist(b, g, h, m, nb):
            return compute_histogram_mxu(b, g, h, m, nb, interpret=interpret)
    else:
        base_hist = H.compute_histogram_xla
    if psum_axis is None:
        hist_fn = base_hist
    else:
        def hist_fn(b, g, h, m, nb):
            return jax.lax.psum(base_hist(b, g, h, m, nb), psum_axis)

    # Small-child row compaction: the histogram kernel is row-streaming
    # bound (~2 MXU cycles per row*feature regardless of mask), so scanning
    # all N rows for every split wastes ~(N/|child|)x. Tiered static
    # capacities keep shapes XLA-compilable: pick the smallest tier >= the
    # child's row count, compact its row ids (one cumsum), and histogram
    # only that buffer. Total rows streamed per tree drops from ~2L*N to
    # ~3.5N (measured 8x on the 200k bench). Disabled under psum (a traced
    # switch would diverge across shards and deadlock the collective) and
    # via MMLSPARK_TPU_NO_GATHER_HIST=1 (exact-order parity for tests: the
    # compacted f32 summation order differs by ulps from the full scan).
    # Tier compaction engine: XLA's nonzero(size)+gather is a full-width
    # cumsum + scatter + 3 gathers (~106 ms at 3.2M rows on the chip, per
    # tiered split); the Pallas stream-select kernel does the same
    # compaction as one-hot MXU contractions + offset DMA writes in ~40 ms,
    # preserving row order so histogram summation is bit-identical.
    use_sel = (use_mxu
               and pallas_select.use_select(int(bins_fm.shape[1]),
                                            interpret=interpret))

    gather_caps: Tuple[int, ...] = ()
    if psum_axis is None and os.environ.get(
            "MMLSPARK_TPU_NO_GATHER_HIST", "") in ("", "0"):
        n_rows = int(bins_fm.shape[1])
        caps = []
        # Tier start: with the stream-select kernel the compaction pass
        # streams rows ~5x cheaper
        # than the histogram kernel (~12.5 vs ~59 ms per 1M rows at F=28),
        # so compacting pays for EVERY small child — tiers start at n/2
        # (small children are always <= n/2). The XLA nonzero+gather
        # fallback is only profitable well below n/4 (axis-1 gather ~19 ms
        # per n/2 rows at N=1M), so it keeps the old n/8 start. The select
        # buffer is [cap, 128ch] f32; the n/2 tier is capped to a 4 GB
        # budget (bins + buffers must fit 15.75 GB HBM at 10M rows).
        top_div = 2 if use_sel else 8
        max_tiers = 7 if use_sel else 5
        c = (n_rows // top_div + 511) // 512 * 512
        while c * 132 * 4 > (4 << 30):   # select-buffer HBM budget
            c = (c // 2 + 511) // 512 * 512
        while c >= max(4096, n_rows // 128) and len(caps) < max_tiers:
            caps.append(c)
            c = (c // 2 + 511) // 512 * 512
        if caps:
            gather_caps = tuple(caps)

    def small_child_hist(small_mask, small_cnt):
        """Histogram of the masked rows, streaming only a tier-sized
        compacted buffer when the tiers are enabled."""
        if not gather_caps:
            return hist_fn(bins_fm, grad, hess, small_mask, num_bins)

        def make_branch(cap):
            def br(_):
                valid = jnp.arange(cap, dtype=jnp.int32) < small_cnt
                if use_sel:
                    # safe: the tier switch picks cap >= small_cnt, so the
                    # kernel's offset writes stay inside its slack
                    b_c, g_c, h_c = pallas_select.select_rows(
                        bins_fm, grad, hess, small_mask, cap,
                        interpret=interpret)
                    return base_hist(b_c, g_c, h_c, valid, num_bins)
                idx = jnp.nonzero(small_mask, size=cap, fill_value=0)[0]
                return base_hist(jnp.take(bins_fm, idx, axis=1),
                                 jnp.take(grad, idx), jnp.take(hess, idx),
                                 valid, num_bins)
            return br

        def full(_):
            return hist_fn(bins_fm, grad, hess, small_mask, num_bins)

        # caps are descending; choose the smallest tier that fits (small
        # children are always <= N/2, so tier 0 is a guaranteed fallback)
        branches = [full] + [make_branch(c) for c in gather_caps]
        tidx = jnp.int32(1)
        for i, cap in enumerate(gather_caps[1:], 2):
            tidx = jnp.where(small_cnt <= cap, jnp.int32(i), tidx)
        tidx = jnp.where(small_cnt <= gather_caps[0], tidx, jnp.int32(0))
        return jax.lax.switch(tidx, branches, None)

    fm = feature_mask if has_feature_mask else None
    neg_inf = jnp.float32(-jnp.inf)
    M = max_nodes
    CW = (num_bins + 31) // 32
    num_leaves_target = (max_nodes + 1) // 2
    # cat_args: (cat_mask [F] bool, cat_smooth, cat_l2, max_cat_threshold)
    # — None keeps every compiled graph identical to the numerical-only one
    cat_info = cat_args

    def best(hist):
        return H.find_best_split(hist, lambda_l1, lambda_l2, min_sum_hessian,
                                 min_data_in_leaf, fm, cat_info)

    root_hist = hist_fn(bins_fm, grad, hess, row_mask, num_bins)
    root_sums = H.total_sums(grad, hess, row_mask)
    if psum_axis is not None:
        root_sums = jax.lax.psum(root_sums, psum_axis)
    s0 = best(root_hist)
    # host parity: the root is pushed without the 2*min_data_in_leaf check
    # (find_best_split already enforces per-side constraints), and the
    # max_depth guard can never block depth 0
    root_ok = jnp.isfinite(s0.gain) & (s0.gain > min_gain_to_split)

    f32 = jnp.float32
    state = dict(
        node_of_row=node_of_row,
        feature=jnp.full(M, -1, jnp.int32),
        threshold_bin=jnp.zeros(M, jnp.int32),
        default_left=jnp.ones(M, bool),
        left=jnp.full(M, -1, jnp.int32),
        right=jnp.full(M, -1, jnp.int32),
        gain=jnp.zeros(M, f32),
        sums=jnp.zeros((M, 3), f32).at[0].set(root_sums),
        depth=jnp.zeros(M, jnp.int32),
        hists=jnp.zeros((M,) + root_hist.shape, f32).at[0].set(root_hist),
        cand_gain=jnp.full(M, -jnp.inf, f32).at[0].set(
            jnp.where(root_ok, s0.gain, neg_inf)),
        cand_feature=jnp.zeros(M, jnp.int32).at[0].set(s0.feature),
        cand_bin=jnp.zeros(M, jnp.int32).at[0].set(s0.bin),
        cand_dleft=jnp.zeros(M, bool).at[0].set(s0.default_left),
        cand_lsum=jnp.zeros((M, 3), f32).at[0].set(s0.left_sum),
        cand_rsum=jnp.zeros((M, 3), f32).at[0].set(s0.right_sum),
        n_nodes=jnp.int32(1),
        n_leaves=jnp.int32(1),
    )
    if cat_info is not None:
        state["cat_words"] = jnp.zeros((M, CW), jnp.uint32)
        state["cand_cwords"] = jnp.zeros((M, CW), jnp.uint32) \
            .at[0].set(s0.cat_words)

    def cond(st):
        return (st["n_leaves"] < num_leaves_target) \
            & (jnp.max(st["cand_gain"]) > neg_inf)

    def body(st):
        leaf = jnp.argmax(st["cand_gain"]).astype(jnp.int32)
        f = st["cand_feature"][leaf]
        t = st["cand_bin"][leaf]
        dl = st["cand_dleft"][leaf]
        lsum = st["cand_lsum"][leaf]
        rsum = st["cand_rsum"][leaf]
        lid = st["n_nodes"]
        rid = lid + 1
        dchild = st["depth"][leaf] + 1

        if cat_info is not None:
            node_of_row = H.partition_rows_cat(
                jnp.take(bins_fm, f, axis=0), st["node_of_row"], leaf, t,
                dl, lid, rid, st["cand_cwords"][leaf])
        else:
            node_of_row = H.partition_rows(
                jnp.take(bins_fm, f, axis=0), st["node_of_row"], leaf, t,
                dl, lid, rid)

        small_is_left = lsum[2] <= rsum[2]
        small_id = jnp.where(small_is_left, lid, rid)
        big_id = jnp.where(small_is_left, rid, lid)
        small_mask = row_mask & (node_of_row == small_id)
        # exact int count (the f32 sums channel saturates past 2^24 rows)
        small_cnt = jnp.sum(small_mask, dtype=jnp.int32)
        small_hist = small_child_hist(small_mask, small_cnt)
        big_hist = H.subtract_histogram(st["hists"][leaf], small_hist)
        s_pair = H.find_best_split_pair(
            jnp.stack([small_hist, big_hist]), lambda_l1, lambda_l2,
            min_sum_hessian, min_data_in_leaf, fm, cat_info)
        s_small = jax.tree.map(lambda x: x[0], s_pair)
        s_big = jax.tree.map(lambda x: x[1], s_pair)

        cg = st["cand_gain"].at[leaf].set(neg_inf)
        cf, cb, cd = st["cand_feature"], st["cand_bin"], st["cand_dleft"]
        cl, cr = st["cand_lsum"], st["cand_rsum"]
        cwd = st["cand_cwords"] if cat_info is not None else None

        def push(arrs, nid, s, csum):
            cg, cf, cb, cd, cl, cr, cwd = arrs
            ok = jnp.isfinite(s.gain) & (s.gain > min_gain_to_split)
            ok &= csum[2] >= 2 * min_data_in_leaf
            if max_depth > 0:
                ok &= dchild < max_depth
            if cwd is not None:
                cwd = cwd.at[nid].set(s.cat_words)
            return (cg.at[nid].set(jnp.where(ok, s.gain, neg_inf)),
                    cf.at[nid].set(s.feature), cb.at[nid].set(s.bin),
                    cd.at[nid].set(s.default_left),
                    cl.at[nid].set(s.left_sum), cr.at[nid].set(s.right_sum),
                    cwd)

        small_sums = jnp.where(small_is_left, lsum, rsum)
        big_sums = jnp.where(small_is_left, rsum, lsum)
        arrs = push((cg, cf, cb, cd, cl, cr, cwd), small_id, s_small,
                    small_sums)
        cg, cf, cb, cd, cl, cr, cwd = push(arrs, big_id, s_big, big_sums)

        out = dict(
            node_of_row=node_of_row,
            feature=st["feature"].at[leaf].set(f),
            threshold_bin=st["threshold_bin"].at[leaf].set(t),
            default_left=st["default_left"].at[leaf].set(dl),
            left=st["left"].at[leaf].set(lid),
            right=st["right"].at[leaf].set(rid),
            gain=st["gain"].at[leaf].set(st["cand_gain"][leaf]),
            sums=st["sums"].at[lid].set(lsum).at[rid].set(rsum),
            depth=st["depth"].at[lid].set(dchild).at[rid].set(dchild),
            hists=st["hists"].at[small_id].set(small_hist)
                             .at[big_id].set(big_hist),
            cand_gain=cg, cand_feature=cf, cand_bin=cb, cand_dleft=cd,
            cand_lsum=cl, cand_rsum=cr,
            n_nodes=lid + 2, n_leaves=st["n_leaves"] + 1,
        )
        if cat_info is not None:
            out["cat_words"] = st["cat_words"].at[leaf].set(
                st["cand_cwords"][leaf])
            out["cand_cwords"] = cwd
        return out

    out = jax.lax.while_loop(cond, body, state)
    keys = ["node_of_row", "feature", "threshold_bin", "default_left",
            "left", "right", "gain", "sums", "n_nodes"]
    if cat_info is not None:
        keys.append("cat_words")
    return {k: out[k] for k in keys}


@functools.partial(
    __import__("jax").jit,
    static_argnames=("num_bins", "max_nodes", "min_data_in_leaf", "max_depth",
                     "use_mxu", "has_feature_mask"))
def _grow_tree_device(bins, grad, hess, row_mask, node_of_row,
                      lambda_l1, lambda_l2, min_sum_hessian, min_gain_to_split,
                      feature_mask, cat_args=None, *, num_bins: int,
                      max_nodes: int, min_data_in_leaf: int, max_depth: int,
                      use_mxu: bool, has_feature_mask: bool):
    return _grow_tree_device_body(
        bins, grad, hess, row_mask, node_of_row, lambda_l1, lambda_l2,
        min_sum_hessian, min_gain_to_split, feature_mask, num_bins=num_bins,
        max_nodes=max_nodes, min_data_in_leaf=min_data_in_leaf,
        max_depth=max_depth, use_mxu=use_mxu,
        has_feature_mask=has_feature_mask, cat_args=cat_args)


_SHARDED_GROW_CACHE: Dict[Tuple, Any] = {}


def _grow_tree_device_sharded(bins, grad, hess, row_mask, node_of_row,
                              lambda_l1, lambda_l2, min_sum_hessian,
                              min_gain_to_split, feature_mask, *,
                              num_bins: int, max_nodes: int,
                              min_data_in_leaf: int, max_depth: int,
                              has_feature_mask: bool, cat_args=None):
    """Row-sharded whole-tree growth: the while_loop runs per shard under
    shard_map with psum'd histograms/totals, so every shard takes identical
    split decisions (replicated tree arrays) while ``node_of_row`` stays
    sharded. One dispatch + one collective stream per tree instead of
    one host round trip per split."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import shard_map_compat as shard_map
    from . import pallas_hist

    sh = bins.sharding
    mesh, row_axes = sh.mesh, sh.spec[1]  # bins_fm [F, N]: rows on dim 1
    # interpret mode: CPU tests of the psum'd-Pallas branch production TPU
    # meshes take (shared parser: pallas_hist.interpret_mode)
    interpret = pallas_hist.interpret_mode()
    use_mxu = pallas_hist.use_pallas() or interpret
    has_cat = cat_args is not None
    key = (mesh, row_axes, num_bins, max_nodes, min_data_in_leaf, max_depth,
           has_feature_mask, use_mxu, interpret, has_cat)
    if key not in _SHARDED_GROW_CACHE:
        if len(_SHARDED_GROW_CACHE) >= 16:  # bound compiled-program memory
            _SHARDED_GROW_CACHE.pop(next(iter(_SHARDED_GROW_CACHE)))
        row_spec = P(row_axes)
        rep = P()
        cat_spec = (rep,) * 4 if has_cat else None

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(sh.spec, row_spec, row_spec, row_spec, row_spec,
                      rep, rep, rep, rep, rep, cat_spec),
            out_specs=dict(
                {"node_of_row": row_spec, "feature": rep,
                 "threshold_bin": rep, "default_left": rep, "left": rep,
                 "right": rep, "gain": rep, "sums": rep, "n_nodes": rep},
                **({"cat_words": rep} if has_cat else {})),
            check_vma=False)  # pallas_call can't declare varying-mesh-axes
        def go(b, g, h, m, rows, l1, l2, msh, mgs, fm, ca):
            return _grow_tree_device_body(
                b, g, h, m, rows, l1, l2, msh, mgs, fm, num_bins=num_bins,
                max_nodes=max_nodes, min_data_in_leaf=min_data_in_leaf,
                max_depth=max_depth, use_mxu=use_mxu,
                has_feature_mask=has_feature_mask, psum_axis=row_axes,
                interpret=interpret, cat_args=ca)

        _SHARDED_GROW_CACHE[key] = jax.jit(go)
    return _SHARDED_GROW_CACHE[key](
        bins, grad, hess, row_mask, node_of_row,
        np.float32(lambda_l1), np.float32(lambda_l2),
        np.float32(min_sum_hessian), np.float32(min_gain_to_split),
        feature_mask, cat_args)


def cat_sets_from_words(words: np.ndarray, feature: np.ndarray,
                        bin_mapper) -> Tuple[Optional[list],
                                             Optional[np.ndarray]]:
    """[nodes, CW] u32 bin-bitsets -> (per-node sorted category-VALUE sets,
    the words themselves) — None/None when no node has a set."""
    if words is None or not words.any():
        return None, None
    nn = len(feature)
    sets: list = [None] * nn
    for nid in range(nn):
        w = words[nid]
        if not w.any():
            continue
        bits = np.unpackbits(w.view(np.uint8), bitorder="little")
        bins_in = np.nonzero(bits)[0]            # bin ids (>= 1 by invariant)
        cats = bin_mapper.categories[int(feature[nid])]
        sets[nid] = np.sort(cats[bins_in - 1]).astype(np.int64)
    return sets, words.astype(np.uint32)


def build_thresholds(feature, tbin, cat_sets, bin_mapper) -> np.ndarray:
    """Raw-value thresholds per node: the bin's upper value for numerical
    splits, 0.0 for leaves AND categorical set nodes (their routing is the
    membership set, not a threshold). Single source for the fused-grower
    and whole-run-scan tree builders."""
    return np.array(
        [bin_mapper.bin_upper_value(int(f), int(t))
         if f >= 0 and (cat_sets is None or cat_sets[i] is None) else 0.0
         for i, (f, t) in enumerate(zip(feature, tbin))], dtype=np.float64)


def _grow_tree_fused(bins_dev, grad, hess, row_mask, num_bins: int,
                     config: GrowerConfig, bin_mapper, feature_mask,
                     node_of_row, device_rows: bool = False,
                     row_sharded: bool = False,
                     cat_args=None) -> Tuple[Tree, np.ndarray]:
    """Host wrapper for the one-dispatch-per-tree device grower.

    ``device_rows``: return the row→leaf routing as the device array instead
    of fetching it (the booster's on-device score update wants it resident).
    ``row_sharded``: rows are split over a mesh axis — use the shard_map
    variant with psum'd histograms.
    """
    import jax

    from . import pallas_hist

    fm = feature_mask if feature_mask is not None else np.zeros(0, dtype=bool)
    common = dict(
        num_bins=num_bins, max_nodes=2 * config.num_leaves - 1,
        min_data_in_leaf=config.min_data_in_leaf, max_depth=config.max_depth,
        has_feature_mask=feature_mask is not None)
    if row_sharded:
        dev_out = _grow_tree_device_sharded(
            bins_dev, grad, hess, row_mask, node_of_row,
            config.lambda_l1, config.lambda_l2,
            config.min_sum_hessian_in_leaf, config.min_gain_to_split,
            fm, cat_args=cat_args, **common)
    else:
        dev_out = _grow_tree_device(
            bins_dev, grad, hess, row_mask, node_of_row,
            np.float32(config.lambda_l1), np.float32(config.lambda_l2),
            np.float32(config.min_sum_hessian_in_leaf),
            np.float32(config.min_gain_to_split), fm, cat_args,
            use_mxu=pallas_hist.use_mxu_single_device(bins_dev), **common)
    rows_dev = dev_out.pop("node_of_row")
    out = fetch_global(dev_out)

    nn = int(out["n_nodes"])
    feature = out["feature"][:nn].astype(np.int32)
    tbin = out["threshold_bin"][:nn].astype(np.int32)
    sums = out["sums"][:nn].astype(np.float64)
    # leaf values on host in f64, the same formula + precision lineage as the
    # per-split path (which fetches f32 SplitInfo sums and computes in f64)
    g_thr = np.sign(sums[:, 0]) * np.maximum(
        np.abs(sums[:, 0]) - config.lambda_l1, 0.0)
    value = np.where(feature < 0,
                     -g_thr / (sums[:, 1] + config.lambda_l2), 0.0)
    if config.max_delta_step > 0:
        value = np.clip(value, -config.max_delta_step, config.max_delta_step)
    # host-path parity: values are assigned at child creation only, so an
    # unsplit root keeps 0.0 (it is never anyone's child)
    value[0] = 0.0 if nn == 1 else value[0]
    cat_sets = cat_words_np = None
    if "cat_words" in out:
        cat_sets, cat_words_np = cat_sets_from_words(
            out["cat_words"][:nn], feature, bin_mapper)
    threshold = build_thresholds(feature, tbin, cat_sets, bin_mapper)
    tree = Tree(
        feature=feature,
        threshold=threshold,
        threshold_bin=tbin,
        default_left=out["default_left"][:nn].astype(bool),
        left=out["left"][:nn].astype(np.int32),
        right=out["right"][:nn].astype(np.int32),
        value=value,
        gain=out["gain"][:nn].astype(np.float32),
        count=sums[:, 2].astype(np.int32),
        weight=sums[:, 1],
        cat_sets=cat_sets,
        cat_bin_words=cat_words_np,
    )
    if device_rows:
        return tree, rows_dev
    return tree, np.asarray(fetch_global(rows_dev))


def grow_tree(bins_fm, grad, hess, row_mask, num_bins: int,
              config: GrowerConfig, bin_mapper, feature_mask=None,
              node_of_row=None, device_rows: bool = False,
              cat_args=None) -> Tuple[Tree, np.ndarray]:
    """Grow one tree; returns (tree, leaf_node_of_row).

    ``bins_fm``: [F,N] int (device, FEATURE-MAJOR — the canonical column-store
    layout: minor dim rows avoids XLA lane padding; LightGBM stores features
    column-wise the same way). ``grad``/``hess``: [N] f32 (device).
    ``row_mask``: [N] bool — bagging/goss row subset. ``feature_mask``: [F] bool.
    ``leaf_node_of_row`` maps every (masked-in) row to its final node id, so the
    booster can update scores with one gather instead of re-predicting.
    """
    import jax
    import jax.numpy as jnp

    from . import pallas_hist

    num_f, n = bins_fm.shape
    if node_of_row is None:
        node_of_row = jnp.zeros(n, dtype=jnp.int32)

    # routing, decided ONCE (invariant over the loop): the default on
    # accelerators grows the WHOLE tree in one device dispatch — per-shard
    # under shard_map with psum'd histograms when rows are sharded over a
    # mesh axis, plain when single-device. Fallback (memory budget exceeded
    # or MMLSPARK_TPU_NO_FUSED_TREE=1): host-orchestrated per-split calls,
    # whose compute_histogram dispatch runs the per-shard Pallas kernel +
    # psum for sharded inputs.
    row_sharded = bool(pallas_hist._row_sharded_spec(bins_fm))
    use_mxu = pallas_hist.use_mxu_single_device(bins_fm)

    if _fused_tree_enabled(2 * config.num_leaves - 1, num_f, num_bins):
        return _grow_tree_fused(bins_fm, grad, hess, row_mask, num_bins,
                                config, bin_mapper, feature_mask, node_of_row,
                                device_rows=device_rows,
                                row_sharded=row_sharded, cat_args=cat_args)

    # growable node storage (host lists; frozen to arrays at the end)
    feature = [-1]
    threshold = [0.0]
    threshold_bin = [0]
    default_left = [True]
    left = [-1]
    right = [-1]
    value = [0.0]
    gains = [0.0]
    counts = [0]
    hweights = [0.0]
    cw = (num_bins + 31) // 32
    node_cat_words = [np.zeros(cw, dtype=np.uint32)]

    def eval_node(hist) -> Tuple[Optional[H.SplitInfo], np.ndarray]:
        split = H.find_best_split(
            hist, config.lambda_l1, config.lambda_l2,
            config.min_sum_hessian_in_leaf, config.min_data_in_leaf,
            feature_mask, cat_args)
        return fetch_global(split)

    root_hist = H.compute_histogram(bins_fm, grad, hess, row_mask, num_bins)
    root_sums = np.asarray(fetch_global(
        H.total_sums(grad, hess, row_mask)), dtype=np.float64)
    counts[0] = int(root_sums[2])
    hweights[0] = float(root_sums[1])
    root_split = eval_node(root_hist)

    heap: List[Tuple[float, int, _Node]] = []
    tiebreak = 0

    def push(node: _Node):
        nonlocal tiebreak
        if node.split is not None and np.isfinite(node.split.gain) \
                and node.split.gain > config.min_gain_to_split:
            if config.max_depth > 0 and node.depth >= config.max_depth:
                return
            heapq.heappush(heap, (-float(node.split.gain), tiebreak, node))
            tiebreak += 1

    push(_Node(0, 0, root_hist, root_sums, root_split))
    n_leaves = 1

    while heap and n_leaves < config.num_leaves:
        _, _, node = heapq.heappop(heap)
        s = node.split
        f, t = int(s.feature), int(s.bin)
        lid, rid = len(feature), len(feature) + 1
        words = np.asarray(s.cat_words, dtype=np.uint32)
        is_cat_split = bool(words.any())

        # record the split on the parent
        feature[node.id] = f
        threshold[node.id] = 0.0 if is_cat_split \
            else bin_mapper.bin_upper_value(f, t)
        threshold_bin[node.id] = t
        default_left[node.id] = bool(s.default_left)
        left[node.id] = lid
        right[node.id] = rid
        gains[node.id] = float(s.gain)
        value[node.id] = 0.0
        node_cat_words[node.id] = words

        lsum = np.asarray(s.left_sum, dtype=np.float64)
        rsum = np.asarray(s.right_sum, dtype=np.float64)
        for sums in (lsum, rsum):
            feature.append(-1)
            threshold.append(0.0)
            threshold_bin.append(0)
            default_left.append(True)
            left.append(-1)
            right.append(-1)
            g_thr = np.sign(sums[0]) * max(abs(sums[0]) - config.lambda_l1, 0.0)
            v = float(-g_thr / (sums[1] + config.lambda_l2))
            if config.max_delta_step > 0:
                v = float(np.clip(v, -config.max_delta_step,
                                  config.max_delta_step))
            value.append(v)
            gains.append(0.0)
            counts.append(int(sums[2]))
            hweights.append(float(sums[1]))
            node_cat_words.append(np.zeros(cw, dtype=np.uint32))

        n_leaves += 1
        small_id, big_id = (lid, rid) if lsum[2] <= rsum[2] else (rid, lid)
        small_sums = lsum if small_id == lid else rsum
        big_sums = rsum if small_id == lid else lsum

        if row_sharded:
            # multi-call path: compute_histogram dispatches to the per-shard
            # Pallas kernel + psum (the fused jit's in-graph scatter would
            # lose ~13x and can OOM at large N — pallas_hist.py:30-35)
            node_of_row = H.partition_rows_cat(
                bins_fm[f], node_of_row, node.id,
                np.int32(t), bool(s.default_left), np.int32(lid),
                np.int32(rid), words) if is_cat_split else H.partition_rows(
                bins_fm[f], node_of_row, node.id,
                np.int32(t), bool(s.default_left), np.int32(lid),
                np.int32(rid))
            small_mask = row_mask & (node_of_row == small_id)
            small_hist = H.compute_histogram(bins_fm, grad, hess,
                                             small_mask, num_bins)
            big_hist = H.subtract_histogram(node.hist, small_hist)
            split_small = eval_node(small_hist)
            split_big = eval_node(big_hist)
        else:
            # fused split iteration: route rows + scatter the smaller
            # child's histogram + sibling subtraction + both children's
            # split evals in ONE device dispatch (H.fused_split_step — the
            # loop used to be dispatch-bound at 4-5 round trips per split)
            node_of_row, small_hist, big_hist, split_small, split_big = \
                H.fused_split_step(
                    bins_fm, grad, hess, row_mask, node_of_row, node.hist,
                    np.int32(f), np.int32(t), bool(s.default_left),
                    np.int32(node.id), np.int32(lid), np.int32(rid),
                    np.int32(small_id),
                    config.lambda_l1, config.lambda_l2,
                    config.min_sum_hessian_in_leaf,
                    feature_mask if feature_mask is not None
                    else np.zeros(0, dtype=bool),
                    num_bins=num_bins,
                    min_data_in_leaf=config.min_data_in_leaf,
                    use_mxu=use_mxu,
                    has_feature_mask=feature_mask is not None,
                    cat_words=words if cat_args is not None else None,
                    cat_info=cat_args)
            split_small, split_big = fetch_global((split_small, split_big))

        for cid, chist, csplit, csums in (
                (small_id, small_hist, split_small, small_sums),
                (big_id, big_hist, split_big, big_sums)):
            if csums[2] >= 2 * config.min_data_in_leaf:
                push(_Node(cid, node.depth + 1, chist, csums, csplit))

    words_arr = np.stack(node_cat_words)
    cat_sets, cat_words_np = cat_sets_from_words(
        words_arr, np.asarray(feature, dtype=np.int32), bin_mapper)
    tree = Tree(
        feature=np.asarray(feature, dtype=np.int32),
        threshold=np.asarray(threshold, dtype=np.float64),
        threshold_bin=np.asarray(threshold_bin, dtype=np.int32),
        default_left=np.asarray(default_left, dtype=bool),
        left=np.asarray(left, dtype=np.int32),
        right=np.asarray(right, dtype=np.int32),
        value=np.asarray(value, dtype=np.float64),
        gain=np.asarray(gains, dtype=np.float32),
        count=np.asarray(counts, dtype=np.int32),
        weight=np.asarray(hweights, dtype=np.float64),
        cat_sets=cat_sets,
        cat_bin_words=cat_words_np,
    )
    return tree, np.asarray(fetch_global(node_of_row))


def predict_tree_binned(tree: Tree, bins: np.ndarray) -> np.ndarray:
    """Evaluate one tree on binned features (host reference path for tests)."""
    n = bins.shape[0]
    out = np.zeros(n, dtype=np.float64)
    node = np.zeros(n, dtype=np.int64)
    active = tree.feature[node] != -1
    while active.any():
        cur = node[active]
        f = tree.feature[cur]
        b = bins[active, f]
        t = tree.threshold_bin[cur]
        go_left = np.where(b == 0, tree.default_left[cur], b <= t)
        if tree.cat_bin_words is not None:
            w = tree.cat_bin_words[cur]                     # [A, CW]
            bit = (w[np.arange(len(b)), b >> 5] >> (b & 31).astype(
                np.uint32)) & 1
            go_left = np.where(w.any(axis=1), bit == 1, go_left)
        node[active] = np.where(go_left, tree.left[cur], tree.right[cur])
        active = tree.feature[node] != -1
    out = tree.value[node] * tree.shrinkage
    return out
