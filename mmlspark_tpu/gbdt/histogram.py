"""Histogram accumulation and split finding — the GBDT hot kernels.

LightGBM's C++ core spends its time in exactly two loops (driven from the
reference via LGBM_BoosterUpdateOneIter, lightgbm/TrainUtils.scala:170-233):
binned histogram construction and best-split search. Here both are jitted XLA
kernels over static [N,F] / [F,B] shapes:

  - ``compute_histogram``: masked scatter-add of (grad, hess, count) into
    [F, B, 3]. On TPU the hot path dispatches to the Pallas one-hot-matmul
    kernel in pallas_hist.py (the scatter reformulated as an MXU contraction
    with a VMEM-resident accumulator); elsewhere it falls back to the XLA
    ``at[].add`` scatter below.
  - ``find_best_split``: vectorized gain scan over all (feature, bin) candidates
    with L1/L2 regularization, min-data / min-hessian constraints, and learned
    missing-value default direction — one argmax on device, no per-feature host
    loop.

Data-parallel training: when ``bins``/``grad``/``hess`` are sharded over the mesh
data axis, the scatter-add is a contraction over rows, so GSPMD inserts the
cross-shard psum automatically — the C++ socket-ring allreduce
(TrainUtils.scala:383-418) becomes one XLA collective.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import numpy as np


class SplitInfo(NamedTuple):
    feature: np.ndarray       # i32 scalar
    bin: np.ndarray           # i32 scalar: rows with bin <= this go left
    gain: np.ndarray          # f32 scalar
    default_left: np.ndarray  # bool scalar: where missing (bin 0) goes
    left_sum: np.ndarray      # [3] (grad, hess, count)
    right_sum: np.ndarray     # [3]
    # categorical set split (LightGBM num_cat machinery): bin-space bitset —
    # bin b goes LEFT iff bit b is set. All-zero words = numerical split
    # (bin 0 / missing can never be a member, so it naturally routes right).
    cat_words: np.ndarray = np.zeros(8, dtype=np.uint32)  # [ceil(B/32)] u32


def compute_histogram(bins_fm, grad, hess, row_mask, num_bins: int):
    """[F,N] feature-major int bins + per-row grad/hess + row mask ->
    [F, num_bins, 3] sums.

    Feature-major is the canonical device layout (LightGBM's own column
    store): the minor dim is rows, so no XLA lane padding and contiguous
    per-feature reads. On TPU, dispatches to the Pallas MXU kernel
    (pallas_hist.py): per-shard kernel + psum under shard_map when rows are
    sharded over a mesh axis, plain kernel on single-device inputs. Falls
    back to the XLA scatter for CPU/GPU, traced inputs, and shardings the
    kernel doesn't handle.
    """
    from . import pallas_hist

    out = pallas_hist.dispatch(bins_fm, grad, hess, row_mask, num_bins)
    if out is not None:
        return out
    return compute_histogram_xla(bins_fm, grad, hess, row_mask, num_bins)


@functools.partial(
    __import__("jax").jit, static_argnames=("num_bins",))
def compute_histogram_xla(bins_fm, grad, hess, row_mask, num_bins: int):
    """XLA ``at[].add`` scatter lowering (CPU/GPU fallback + parity reference).
    Takes the canonical feature-major [F, N] layout."""
    import jax.numpy as jnp

    f, n = bins_fm.shape
    m = row_mask.astype(jnp.float32)
    vals = jnp.stack([grad * m, hess * m, m], axis=-1)          # [N, 3]
    vals = jnp.broadcast_to(vals[None, :, :], (f, n, 3))        # [F, N, 3]
    feat_offset = jnp.arange(f, dtype=jnp.int32) * num_bins
    flat_idx = (bins_fm.astype(jnp.int32)
                + feat_offset[:, None]).reshape(-1)             # [F*N]
    hist = jnp.zeros((f * num_bins, 3), dtype=jnp.float32)
    hist = hist.at[flat_idx].add(vals.reshape(-1, 3))
    return hist.reshape(f, num_bins, 3)


def _leaf_objective(G, H, l1, l2):
    """-0.5 * T(G)^2 / (H + l2), T = soft-threshold by l1 (LightGBM's GetLeafGain)."""
    import jax.numpy as jnp

    t = jnp.sign(G) * jnp.maximum(jnp.abs(G) - l1, 0.0)
    return -0.5 * t * t / (H + l2)


def leaf_output(G, H, l1, l2):
    """Optimal leaf value -T(G)/(H + l2) (LightGBM's CalculateSplittedLeafOutput)."""
    import jax.numpy as jnp

    t = jnp.sign(G) * jnp.maximum(jnp.abs(G) - l1, 0.0)
    return -t / (H + l2)


def _cat_best_subset(hist, lambda_l1, lambda_l2, min_sum_hessian,
                     min_data_in_leaf: int, cat_smooth, cat_l2,
                     max_cat_threshold):
    """Per-feature best categorical SET split (LightGBM's sorted-by-
    gradient-statistic category partitioning): categories sorted by
    G/(H + cat_smooth), best prefix of the sorted order goes left.

    Returns (gain [F], words [F, CW] u32 bin-bitsets, left_sum [F, 3]).
    The missing bin (0) is never a member — missing categoricals route
    right, LightGBM's convention for the 'other' bucket."""
    import jax.numpy as jnp

    f, b, _ = hist.shape
    cw = (b + 31) // 32
    vb = hist[:, 1:, :]                                    # [F, B-1, 3]
    cnt = vb[..., 2]
    present = cnt > 0.0
    n_present = jnp.sum(present, axis=1)                   # [F]
    ratio = vb[..., 0] / (vb[..., 1] + cat_smooth)
    ratio = jnp.where(present, ratio, jnp.inf)             # absent: sort last
    order = jnp.argsort(ratio, axis=1)                     # [F, B-1]
    sh = jnp.take_along_axis(vb, order[..., None], axis=1)
    cum = jnp.cumsum(sh, axis=1)                           # [F, B-1, 3]
    total = hist.sum(axis=1)                               # [F, 3] (node totals)
    G, H, C = total[0, 0], total[0, 1], total[0, 2]
    l2c = lambda_l2 + cat_l2
    GL, HL, CL = cum[..., 0], cum[..., 1], cum[..., 2]
    GR, HR, CR = G - GL, H - HL, C - CL
    gain = (_leaf_objective(GL, HL, lambda_l1, l2c)
            + _leaf_objective(GR, HR, lambda_l1, l2c)
            - _leaf_objective(G, H, lambda_l1, l2c)) * -1.0
    k = jnp.arange(1, b, dtype=jnp.int32)[None, :]         # prefix sizes
    ok = ((CL >= min_data_in_leaf) & (CR >= min_data_in_leaf)
          & (HL >= min_sum_hessian) & (HR >= min_sum_hessian)
          & (k <= max_cat_threshold) & (k <= n_present[:, None]))
    gain = jnp.where(ok, gain, -jnp.inf)
    ki = jnp.argmax(gain, axis=1)                          # [F]
    gain_f = jnp.take_along_axis(gain, ki[:, None], axis=1)[:, 0]
    lsum_f = jnp.take_along_axis(cum, ki[:, None, None], axis=1)[:, 0, :]
    # membership back in ORIGINAL bin positions: sorted position <= ki
    member_sorted = (jnp.arange(b - 1)[None, :] <= ki[:, None])
    inv = jnp.argsort(order, axis=1)
    member = jnp.take_along_axis(member_sorted, inv, axis=1)  # [F, B-1]
    member_full = jnp.concatenate(
        [jnp.zeros((f, 1), bool), member], axis=1)         # bin 0 never
    pad = cw * 32 - b
    if pad:
        member_full = jnp.pad(member_full, ((0, 0), (0, pad)))
    bits = member_full.reshape(f, cw, 32).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32)[None, None, :],
                    axis=2, dtype=jnp.uint32)              # [F, CW]
    return gain_f, words, lsum_f


@functools.partial(
    __import__("jax").jit,
    static_argnames=("min_data_in_leaf",))
def find_best_split(hist, lambda_l1, lambda_l2, min_sum_hessian,
                    min_data_in_leaf: int, feature_mask=None, cat_info=None):
    """Best (feature, bin, missing-direction) over a [F,B,3] histogram.

    Threshold semantics: candidate t sends bins 1..t left, bins t+1.. right; the
    missing bin (0) is tried on both sides and the better direction is kept
    (LightGBM's default-direction learning).

    ``cat_info``: optional (cat_mask [F] bool, cat_smooth, cat_l2,
    max_cat_threshold) — features flagged categorical are split by SET
    membership (sorted-gradient-prefix subsets, _cat_best_subset) instead
    of an ordered threshold; the winning split's bitset rides
    SplitInfo.cat_words (all-zero for numerical winners).
    """
    import jax.numpy as jnp

    f, b, _ = hist.shape
    cw = (b + 31) // 32
    miss = hist[:, 0, :]                          # [F,3] missing-bin sums
    cum = jnp.cumsum(hist[:, 1:, :], axis=1)      # [F,B-1,3] cumulative over value bins
    total = cum[:, -1, :] + miss                  # [F,3] node totals (same for all f)
    G, H, C = total[0, 0], total[0, 1], total[0, 2]

    # candidate thresholds t = 1..B-1 (cum index 0..B-2); left-without-missing sums:
    GL0, HL0, CL0 = cum[..., 0], cum[..., 1], cum[..., 2]     # [F,B-1]

    def gains(GL, HL, CL):
        GR, HR, CR = G - GL, H - HL, C - CL
        gain = (_leaf_objective(GL, HL, lambda_l1, lambda_l2)
                + _leaf_objective(GR, HR, lambda_l1, lambda_l2)
                - _leaf_objective(G, H, lambda_l1, lambda_l2)) * -1.0
        ok = ((CL >= min_data_in_leaf) & (CR >= min_data_in_leaf)
              & (HL >= min_sum_hessian) & (HR >= min_sum_hessian))
        return jnp.where(ok, gain, -jnp.inf)

    gain_right = gains(GL0, HL0, CL0)                               # missing -> right
    gain_left = gains(GL0 + miss[:, None, 0], HL0 + miss[:, None, 1],
                      CL0 + miss[:, None, 2])                       # missing -> left
    best_dir_left = gain_left >= gain_right
    gain = jnp.maximum(gain_left, gain_right)                       # [F,B-1]

    if cat_info is None:
        if feature_mask is not None:
            gain = jnp.where(feature_mask[:, None], gain, -jnp.inf)
        flat = jnp.argmax(gain)
        bf = flat // (b - 1)
        bt = flat % (b - 1) + 1                   # threshold bin (1-indexed)
        best_gain = gain.reshape(-1)[flat]
        dleft = best_dir_left.reshape(-1)[flat]
        lsum = cum[bf, bt - 1, :] + jnp.where(dleft, miss[bf], 0.0)
        rsum = total[bf] - lsum
        return SplitInfo(bf.astype(jnp.int32), bt.astype(jnp.int32),
                         best_gain, dleft, lsum, rsum,
                         jnp.zeros(cw, dtype=jnp.uint32))

    cat_mask, cat_smooth, cat_l2, max_cat_threshold = cat_info
    cat_gain, cat_words, cat_lsum = _cat_best_subset(
        hist, lambda_l1, lambda_l2, min_sum_hessian, min_data_in_leaf,
        cat_smooth, cat_l2, max_cat_threshold)
    # per-feature numerical best
    num_ki = jnp.argmax(gain, axis=1)                               # [F]
    num_gain = jnp.take_along_axis(gain, num_ki[:, None], axis=1)[:, 0]
    num_dir = jnp.take_along_axis(best_dir_left, num_ki[:, None],
                                  axis=1)[:, 0]
    num_lsum = (jnp.take_along_axis(cum, num_ki[:, None, None],
                                    axis=1)[:, 0, :]
                + jnp.where(num_dir[:, None], miss, 0.0))
    gain_f = jnp.where(cat_mask, cat_gain, num_gain)
    if feature_mask is not None:
        gain_f = jnp.where(feature_mask, gain_f, -jnp.inf)
    bf = jnp.argmax(gain_f)
    is_cat = cat_mask[bf]
    best_gain = gain_f[bf]
    bt = jnp.where(is_cat, 0, num_ki[bf] + 1)
    dleft = jnp.where(is_cat, False, num_dir[bf])
    lsum = jnp.where(is_cat, cat_lsum[bf], num_lsum[bf])
    rsum = total[bf] - lsum
    words = jnp.where(is_cat, cat_words[bf],
                      jnp.zeros(cw, dtype=jnp.uint32))
    return SplitInfo(bf.astype(jnp.int32), bt.astype(jnp.int32),
                     best_gain, dleft, lsum, rsum, words)


def find_best_split_pair(hist_pair, lambda_l1, lambda_l2, min_sum_hessian,
                         min_data_in_leaf: int, feature_mask=None,
                         cat_info=None):
    """Best splits for TWO sibling histograms stacked [2, F, B, 3] in one
    vectorized evaluation (the per-split while body evaluated each child
    separately — at large N the duplicated cumsum/gain kernels were a
    measurable share of the split cost)."""
    import jax

    def one(h):
        return find_best_split(h, lambda_l1, lambda_l2, min_sum_hessian,
                               min_data_in_leaf, feature_mask, cat_info)

    return jax.vmap(one)(hist_pair)


@functools.partial(
    __import__("jax").jit,
    static_argnames=("num_bins", "min_data_in_leaf", "use_mxu",
                     "has_feature_mask"))
def fused_split_step(bins_fm, grad, hess, row_mask, node_of_row, parent_hist,
                     feature, threshold_bin, default_left, node_id,
                     left_id, right_id, small_id,
                     lambda_l1, lambda_l2, min_sum_hessian,
                     feature_mask, *, num_bins: int, min_data_in_leaf: int,
                     use_mxu: bool, has_feature_mask: bool,
                     cat_words=None, cat_info=None):
    """ONE dispatch for a whole split iteration: route the parent's rows to
    the children, scatter the smaller child's histogram, derive the sibling
    by subtraction, and evaluate both children's best splits.

    grow_tree previously issued 4-5 separate device calls per split (each a
    blocking round trip), which made end-to-end training dispatch-bound.
    Fusing keeps one round trip per split; the host
    fetches only the two SplitInfos.

    ``use_mxu``: lower the histogram through the Pallas MXU kernel (TPU,
    single-device) instead of the XLA scatter.
    """
    import jax.numpy as jnp

    bins_col = jnp.take(bins_fm, feature, axis=0)
    if cat_words is not None:
        node_of_row = partition_rows_cat(bins_col, node_of_row, node_id,
                                         threshold_bin, default_left,
                                         left_id, right_id, cat_words)
    else:
        node_of_row = partition_rows(bins_col, node_of_row, node_id,
                                     threshold_bin, default_left,
                                     left_id, right_id)
    small_mask = row_mask & (node_of_row == small_id)
    if use_mxu:
        from .pallas_hist import compute_histogram_mxu

        small_hist = compute_histogram_mxu(bins_fm, grad, hess, small_mask,
                                           num_bins)
    else:
        small_hist = compute_histogram_xla(bins_fm, grad, hess, small_mask,
                                           num_bins)
    big_hist = subtract_histogram(parent_hist, small_hist)
    fm = feature_mask if has_feature_mask else None
    split_small = find_best_split(small_hist, lambda_l1, lambda_l2,
                                  min_sum_hessian, min_data_in_leaf, fm,
                                  cat_info)
    split_big = find_best_split(big_hist, lambda_l1, lambda_l2,
                                min_sum_hessian, min_data_in_leaf, fm,
                                cat_info)
    return node_of_row, small_hist, big_hist, split_small, split_big


@__import__("jax").jit
def partition_rows(bins_col, node_of_row, node_id, threshold_bin, default_left,
                   left_id, right_id):
    """Route rows of ``node_id`` to children: bin<=t (or missing per default) left."""
    import jax.numpy as jnp

    in_node = node_of_row == node_id
    is_missing = bins_col == 0
    go_left = jnp.where(is_missing, default_left, bins_col <= threshold_bin)
    return jnp.where(in_node, jnp.where(go_left, left_id, right_id), node_of_row)


@__import__("jax").jit
def partition_rows_cat(bins_col, node_of_row, node_id, threshold_bin,
                       default_left, left_id, right_id, cat_words):
    """Cat-aware routing: when ``cat_words`` is non-zero the split is a
    SET — bin b goes left iff bit b is set (bin 0 never is, so missing
    routes right); all-zero words fall back to the threshold rule."""
    import jax.numpy as jnp

    in_node = node_of_row == node_id
    is_cat = jnp.any(cat_words != 0)
    bits = (jnp.take(cat_words, bins_col >> 5)
            >> (bins_col & 31).astype(jnp.uint32)) & 1
    is_missing = bins_col == 0
    go_left = jnp.where(
        is_cat, bits == 1,
        jnp.where(is_missing, default_left, bins_col <= threshold_bin))
    return jnp.where(in_node, jnp.where(go_left, left_id, right_id),
                     node_of_row)


@__import__("jax").jit
def subtract_histogram(parent, child):
    """Sibling histogram by subtraction (LightGBM's halving trick). Grad sums may
    be legitimately negative; only counts/hessians are clamped against tiny
    float cancellation."""
    import jax.numpy as jnp

    diff = parent - child
    return diff.at[..., 1:].set(jnp.maximum(diff[..., 1:], 0.0))


def total_sums(grad, hess, row_mask):
    import jax.numpy as jnp

    m = row_mask.astype(jnp.float32)
    return jnp.stack([jnp.sum(grad * m), jnp.sum(hess * m), jnp.sum(m)])
