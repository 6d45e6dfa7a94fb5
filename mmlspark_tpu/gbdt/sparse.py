"""Sparse/CSR feature path through the GBDT engine.

Reference parity: the reference trains LightGBM directly on sparse vectors —
``generateSparseDataset`` / ``LGBM_DatasetCreateFromCSRSpark``
(lightgbm/TrainUtils.scala:23-66, lightgbm/LightGBMUtils.scala:199-252) — and
predicts single sparse rows via ``PredictForCSRSingle``
(lightgbm/LightGBMBooster.scala:21-148). This module gives the TPU engine the
same capability for TextFeaturizer/VW-width feature spaces (2^18+ columns)
without ever densifying:

  - ``SparseDataset``: CSR (indptr/indices/values) + per-feature
    distinct-value binning over the nonzeros with the implicit zero as its
    own bin, laid out as a FLAT ragged bin space (per-feature offsets,
    ``total_bins = sum_f bins_f`` — LightGBM's num_total_bin layout). Memory
    is O(nnz + total_bins), never O(N * F).
  - histogram: one ``segment_sum`` over the nnz entries' flat bin ids
    (node-masked via a cheap 1-D gather of the row routing); the zero bin of
    every feature is reconstructed by subtraction from the node totals —
    LightGBM's default-bin trick, so absent entries cost nothing.
  - split finding: a single flat cumsum + vectorized gain scan over
    ``total_bins`` candidates with per-feature segment boundaries.
  - ``predict_csr``: depth-stepped traversal where each row resolves the
    split feature's value through its own CSR row (absent -> 0.0).

Trees come out as the ordinary dense ``Tree`` (raw-value thresholds), so
persistence, merge, importances, and the LightGBM text-format interchange
all work unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..parallel.mesh import fetch_global

from .tree import GrowerConfig, Tree

_MAX_SPARSE_BIN = 64  # per-feature cap: count/tf features have few levels


def rows_to_csr(col, num_features: Optional[int] = None,
                filter_zeros: bool = True):
    """Sparse-row column ({"indices","values"[,"size"]}) -> sorted CSR
    (indptr, indices, values, width). The single row-walk shared by training
    (SparseDataset.from_rows) and predict (stages._raw_scores)."""
    from ..parallel.batching import sparse_width

    width = num_features or sparse_width(col)
    indptr = np.zeros(len(col) + 1, dtype=np.int64)
    idx_parts, val_parts = [], []
    for i, v in enumerate(col):
        if v is None:
            indptr[i + 1] = indptr[i]
            continue
        idx = np.asarray(v["indices"], dtype=np.int64)
        val = np.asarray(v["values"], dtype=np.float64)
        keep = idx < width
        if filter_zeros:
            keep &= val != 0.0
        idx, val = idx[keep], val[keep]
        srt = np.argsort(idx, kind="stable")  # CSR contract: sorted rows
        idx_parts.append(idx[srt])
        val_parts.append(val[srt])
        indptr[i + 1] = indptr[i] + len(idx)
    indices = (np.concatenate(idx_parts) if idx_parts
               else np.zeros(0, dtype=np.int64))
    values = (np.concatenate(val_parts) if val_parts
              else np.zeros(0, dtype=np.float64))
    return indptr, indices, values, width


@dataclasses.dataclass
class SparseDataset:
    """CSR dataset with flat ragged binning over the nonzero values."""

    indptr: np.ndarray        # i64 [N+1]
    indices: np.ndarray       # i32 [nnz] feature ids
    values: np.ndarray        # f32 [nnz]
    num_features: int
    # binning (flat ragged layout)
    feat_offset: np.ndarray   # i64 [F+1]: feature f owns flat bins
    #                           [feat_offset[f], feat_offset[f+1])
    thresholds: np.ndarray    # f64 [total_bins]: upper value per flat bin
    zero_local: np.ndarray    # i32 [F]: local bin index holding value 0.0
    bin_of_nnz: np.ndarray    # i32 [nnz]: flat bin id per entry
    row_of_nnz: np.ndarray    # i32 [nnz]

    @property
    def num_rows(self) -> int:
        return len(self.indptr) - 1

    @property
    def total_bins(self) -> int:
        return int(self.feat_offset[-1])

    @staticmethod
    def from_rows(col, num_features: Optional[int] = None,
                  max_bin: int = _MAX_SPARSE_BIN) -> "SparseDataset":
        """Build from a sparse-row column ({"indices","values"[,"size"]})."""
        indptr, indices, values, width = rows_to_csr(col, num_features)
        return SparseDataset.from_csr(indptr, indices, values, width, max_bin)

    @staticmethod
    def from_csr(indptr, indices, values, num_features: int,
                 max_bin: int = _MAX_SPARSE_BIN) -> "SparseDataset":
        indptr = np.asarray(indptr, dtype=np.int64)
        indices = np.asarray(indices, dtype=np.int64)
        values = np.asarray(values, dtype=np.float64)
        nnz = len(indices)

        # One synthetic zero "entry" per present feature makes the implicit
        # zero an ordinary distinct value — binning, zero position, and
        # capping all handle it uniformly.
        feats_present = np.unique(indices) if nnz else np.zeros(0, np.int64)
        fs_aug = np.concatenate([indices, feats_present])
        vs_aug = np.concatenate([values, np.zeros(len(feats_present))])

        # distinct (feature, value) pairs via one lexsort; per-entry pair id
        order = np.lexsort((vs_aug, fs_aug))
        fs, vs = fs_aug[order], vs_aug[order]
        m = len(fs)
        first = np.ones(m, dtype=bool)
        if m:
            first[1:] = (fs[1:] != fs[:-1]) | (vs[1:] != vs[:-1])
        pair_of_sorted = np.cumsum(first) - 1 if m \
            else np.zeros(0, dtype=np.int64)
        df, dv = fs[first], vs[first]          # value-ascending per feature

        # stride-quantile cap: feature f with d_f distinct values uses
        # stride_f = ceil(d_f / max_bin); local bin = distinct_pos // stride
        # — an even subsample of the value range (a smallest-values prefix
        # cap mixes large values into the zero bin when negatives exist)
        d_per_feat = np.bincount(df, minlength=num_features)
        stride = np.maximum(1, -(-d_per_feat // max_bin))      # [F]
        first_pair = np.searchsorted(df, df)
        pos_in_feat = np.arange(len(df)) - first_pair
        local_of_pair = pos_in_feat // stride[df]
        bins_per_feat = np.where(d_per_feat > 0,
                                 -(-d_per_feat // stride), 0)
        feat_offset = np.zeros(num_features + 1, dtype=np.int64)
        np.cumsum(bins_per_feat, out=feat_offset[1:])
        total_bins = int(feat_offset[-1])

        # upper threshold of flat bin (f, j): midpoint between the last
        # distinct value covered by bin j and the first of bin j+1; the
        # feature's last bin is +inf
        thresholds = np.full(total_bins, np.inf)
        if len(df):
            flat_of_pair = feat_offset[df] + local_of_pair
            # boundary pairs: last pair of its bin, not last of its feature
            not_last = np.zeros(len(df), dtype=bool)
            not_last[:-1] = (df[:-1] == df[1:]) & \
                (flat_of_pair[:-1] != flat_of_pair[1:])
            b_idx = np.nonzero(not_last)[0]
            thresholds[flat_of_pair[b_idx]] = (dv[b_idx] + dv[b_idx + 1]) / 2.0

        # zero position: the synthetic zero is a distinct value of every
        # present feature; find its pair and take its local bin
        zero_local = np.zeros(num_features, dtype=np.int32)
        if len(df):
            zpair = (dv == 0.0)
            zero_local[df[zpair]] = local_of_pair[zpair].astype(np.int32)

        # flat bin per ORIGINAL nnz entry (the synthetic zeros occupy the
        # tail of the augmented arrays)
        bin_of_nnz = np.zeros(nnz, dtype=np.int64)
        if nnz:
            flat_sorted = (feat_offset[df] + local_of_pair)[pair_of_sorted]
            flat_aug = np.zeros(len(fs_aug), dtype=np.int64)
            flat_aug[order] = flat_sorted
            bin_of_nnz = flat_aug[:nnz]
        return SparseDataset(
            indptr=indptr,
            indices=indices.astype(np.int32),
            values=values.astype(np.float32),
            num_features=int(num_features),
            feat_offset=feat_offset,
            thresholds=thresholds,
            zero_local=zero_local,
            bin_of_nnz=bin_of_nnz,
            row_of_nnz=np.repeat(
                np.arange(len(indptr) - 1, dtype=np.int64),
                np.diff(indptr)).astype(np.int32),
        )

    def bin_upper_value(self, f: int, local_bin: int) -> float:
        return float(self.thresholds[int(self.feat_offset[f]) + local_bin])


# ---------------------------------------------------------------------------
# Device histogram + split finding over the flat ragged bin space
# ---------------------------------------------------------------------------


_PREFIX_BLOCK = 512


def _prefix_sum(data, int_channel=None):
    """Inclusive prefix sum of [C, n] with a LEADING zero column -> [C, n+1]
    (so ``out[:, k]`` = sum of the first k elements).

    XLA's native cumsum lowering costs ~645 ms at [3, 50M] on the chip —
    it dominates every sparse split. This is the TPU-native two-level
    scheme instead: inclusive prefixes WITHIN 512-wide blocks via one
    upper-triangular matmul on the MXU (the stream-select kernel's trick),
    plus an ordinary cumsum over the ~n/512 block sums. Also better
    precision than a flat f32 scan: within-block sums cover <= 512 values.
    Small inputs keep jnp.cumsum (cheaper to compile, equally fast).

    ``int_channel``: channel whose values are integers (the COUNT channel)
    — its prefix is ALSO returned as an exact int32 [n+1] array (blocked
    short-scan cumsum + int32 block prefix), because an f32 prefix
    silently rounds once the running total passes 2^24 (at 50M entries a
    bin's boundary difference would be off by up to ~4). Callers must take
    count DIFFERENCES from the int array — storing the int prefix back
    into the f32 result would just reintroduce the rounding. (A variant
    that removed the int channel from the f32 matmul entirely measured
    ~8% SLOWER end to end on the 1M x 2^18 bench than this shared-layout
    form — same-run A/B pending, kept the better-attested shape.)
    Return is ``cs [C, n+1]`` alone when int_channel is None, else
    ``(cs, cs_int [n+1] int32)``; per-bin count differences cast back to
    f32 stay exact below 2^24 rows per bin. SCOPE of the exactness claim:
    per-bin/per-boundary counts are int-exact at any nnz, but node-TOTAL
    counts still live in the f32 [3] sums vector (root_tot, lsum/rsum,
    Tree.count) — a node above 2^24 ROWS rounds its total to the nearest
    representable f32 (~±4 at 50M). Removing that would mean an int32
    carry through the whole grower state; at the engine's practical
    single-chip scale (<=16.7M rows per fit today) the totals are exact."""
    import jax.numpy as jnp

    c, n = data.shape
    zero = jnp.zeros((c, 1), data.dtype)
    if n < (1 << 18):
        cs = jnp.concatenate([zero, jnp.cumsum(data, axis=1)], axis=1)
        if int_channel is None:
            return cs
        xi = jnp.round(data[int_channel]).astype(jnp.int32)
        cs_i = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(xi)])
        return cs, cs_i
    B = _PREFIX_BLOCK
    import jax

    n_pad = (n + B - 1) // B * B
    x = jnp.pad(data, ((0, 0), (0, n_pad - n))).reshape(c, n_pad // B, B)
    iota = jnp.arange(B, dtype=jnp.int32)
    ut = (iota[:, None] <= iota[None, :]).astype(jnp.float32)  # [B, B]
    intra = jax.lax.dot_general(
        x, ut, (((2,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # [c, nb, B] inclusive
    block_excl = jnp.cumsum(intra[:, :, -1], axis=1) - intra[:, :, -1]
    cs = (intra + block_excl[:, :, None]).reshape(c, n_pad)[:, :n]
    out = jnp.concatenate([zero, cs], axis=1)
    if int_channel is None:
        return out
    xi = jnp.round(x[int_channel]).astype(jnp.int32)   # [nb, B]
    intra_i = jnp.cumsum(xi, axis=1)                   # short scans
    bsum = intra_i[:, -1]
    bexcl = jnp.cumsum(bsum) - bsum
    cs_i = (intra_i + bexcl[:, None]).reshape(n_pad)[:n]
    cs_i = jnp.concatenate([jnp.zeros(1, jnp.int32), cs_i])
    return out, cs_i


def _exact_topk_mask(key, k: int, n: int, exclude=None):
    """Boolean [n] mask of EXACTLY ``min(k, n_eligible)`` rows with the
    largest keys, ties broken toward the smallest row index — scatter-free
    (a 32-step bitwise bisection on the nonnegative-f32 int view plus an
    index bisection among threshold ties; every step is one [n]
    compare-and-reduce, ~60 cheap reduces total).

    The exact count is what makes selected-row nnz compaction safe: the
    static capacity bound (sum of the k largest row-nnz, computed on host
    at fit time) only holds if selection can never exceed k rows. The
    >=-threshold GOSS mask cannot promise that — when gradients tie (e.g.
    a constant-label stretch) it selects every tied row. LightGBM's own
    GOSS takes exactly topN by sort (GOSS bagging in its C++ engine);
    this reproduces that count without a device sort.

    ``key``: [n] f32, values >= 0 (|grad| sums / uniform draws).
    ``exclude``: optional [n] bool — ineligible rows, never selected.
    """
    import jax
    import jax.numpy as jnp

    if k <= 0:
        return jnp.zeros(n, dtype=bool)
    # uint32 order-preserving view: bitcast of a nonnegative f32 keeps the
    # sign bit clear (< 2^31), so +1 shifts every eligible key above the
    # excluded-row sentinel 0 without overflow — and keeps the bisection
    # range inside uint32 (an int32 domain of [-1, 2^31-1] overflows the
    # midpoint arithmetic)
    ik = jax.lax.bitcast_convert_type(
        jnp.abs(key.astype(jnp.float32)), jnp.uint32) + jnp.uint32(1)
    if exclude is not None:
        ik = jnp.where(exclude, jnp.uint32(0), ik)
        kk = jnp.minimum(jnp.int32(k),
                         jnp.sum((~exclude).astype(jnp.int32)))
    else:
        kk = jnp.int32(min(k, n))

    # largest t with count(ik >= t) >= kk  (count is monotone in t)
    def bis_t(_, lohi):
        lo, hi = lohi
        mid = lo + ((hi - lo + jnp.uint32(1)) >> 1)
        take = jnp.sum((ik >= mid).astype(jnp.int32)) >= kk
        return (jnp.where(take, mid, lo),
                jnp.where(take, hi, mid - jnp.uint32(1)))

    t, _ = jax.lax.fori_loop(
        0, 32, bis_t, (jnp.uint32(0), jnp.uint32(2**31 + 1)))

    gt = ik > t
    need = kk - jnp.sum(gt.astype(jnp.int32))    # ties still to take, >= 0
    tie = ik == t
    idxv = jnp.arange(n, dtype=jnp.int32)

    # smallest c with count(tie & idx < c) >= need; counts step by <= 1 per
    # c, so the count at the answer is exactly `need`
    def bis_c(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) >> 1
        ok = jnp.sum((tie & (idxv < mid)).astype(jnp.int32)) >= need
        return (jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi))

    c, _ = jax.lax.fori_loop(
        0, 32, bis_c, (jnp.int32(0), jnp.int32(n)))
    return gt | (tie & (idxv < c))


def _entry_gh(dev, grad, hess):
    """Per-ENTRY grad/hess in bin-sorted order: gathered ONCE per
    tree/iteration. The 50M-entry random gather costs ~0.45 s on the chip
    (measured ~30 ns/element — the dominant sparse cost); grad/hess are
    loop-invariant during a tree, so only the node MASK gather stays in
    the per-split path."""
    import jax.numpy as jnp

    rows_bs = dev["row_of_nnz_bs"]
    return jnp.take(grad, rows_bs), jnp.take(hess, rows_bs)


def _flat_histogram(dev, g_bs, h_bs, node_mask_rows):
    """Nonzero-entry histogram: [3, total_bins] sums over the node's rows —
    SCATTER-FREE (the TPU has no scatter hardware; jax segment_sum lowers
    to a serialized XLA scatter — earlier claim: it did not survive 50M
    nnz; not measured in this round). Entries are pre-sorted by flat bin at dataset build, so the
    per-bin sums are differences of ONE masked prefix sum at the
    bin-boundary offsets: O(nnz) block-matmul scan (_prefix_sum) + O(TB)
    gathers. Per split this costs one [nnz] row-mask gather + the scan.

    ``g_bs``/``h_bs``: per-entry grad/hess from _entry_gh (hoisted out of
    the split loop — they are tree-invariant).
    ``dev["nnz_valid"]`` (optional, sharded layouts): 0/1 per BIN-SORTED
    entry — padding entries in equal-shape per-shard slices contribute
    nothing.

    ALL flat-histogram tensors are CHANNEL-MAJOR [3, nnz] / [3, TB]: the
    minor dim must be the big one — a [50M, 3] f32 array tiles 3 -> 128
    lanes on TPU, a 42x HBM blowup that tried to allocate 25.6 GB at the
    1M-row text bench (same trap the dense kernels hit in r3)."""
    import jax.numpy as jnp

    rows_bs = dev["row_of_nnz_bs"]                 # bin-sorted entry order
    m = jnp.take(node_mask_rows, rows_bs).astype(jnp.float32)
    if "nnz_valid" in dev:
        m = m * dev["nnz_valid"]
    data = jnp.stack([g_bs * m, h_bs * m, m], axis=0)   # [3, nnz]
    # hist.csr kernel variant (core/kernels.py): the same sums as a one-hot
    # MXU contraction over nnz chunks (gbdt/pallas_sparse.py). Resolved at
    # trace time; None = the default prefix-sum path, byte-for-byte.
    from .pallas_sparse import flat_hist_dispatch

    hist_p = flat_hist_dispatch(dev, data)
    if hist_p is not None:
        return hist_p
    cs, cs_i = _prefix_sum(data, int_channel=2)
    hist = (jnp.take(cs, dev["bin_end"], axis=1)
            - jnp.take(cs, dev["bin_start"], axis=1))   # [3, TB]
    # count channel: int differences (the f32 prefix rounds past 2^24)
    counts = (jnp.take(cs_i, dev["bin_end"])
              - jnp.take(cs_i, dev["bin_start"]))
    return hist.at[2].set(counts.astype(jnp.float32))


def _zero_completed(dev, flat_hist, node_totals):
    """Add the implicit-zero bin of every feature: node totals minus the
    feature's nonzero-entry sums (LightGBM's default-bin subtraction).
    Scatter-free: per-feature sums are cumsum differences at the feature
    boundaries (bins are grouped by feature in the flat space), and the
    zero-bin add is a masked gather of the per-feature deficit.
    Channel-major [3, TB] layout throughout (see _flat_histogram)."""
    import jax.numpy as jnp

    cs, cs_i = _prefix_sum(flat_hist, int_channel=2)
    feat_sums = (jnp.take(cs, dev["feat_offset_dev"][1:], axis=1)
                 - jnp.take(cs, dev["feat_offset_dev"][:-1], axis=1))
    feat_cnt = (jnp.take(cs_i, dev["feat_offset_dev"][1:])
                - jnp.take(cs_i, dev["feat_offset_dev"][:-1]))
    feat_sums = feat_sums.at[2].set(feat_cnt.astype(jnp.float32))
    zero_sums = node_totals[:, None] - feat_sums          # [3, F]
    add = jnp.where(dev["is_zero_bin"][None, :],
                    jnp.take(zero_sums, dev["feat_of_bin"], axis=1), 0.0)
    return flat_hist + add


def _find_best_split_flat(dev, hist, lambda_l1, lambda_l2, min_sum_hessian,
                          min_data_in_leaf, bin_mask=None):
    """Vectorized gain scan over ALL flat bins: candidate t at flat bin b
    sends local bins <= b left. Per-feature left-cumulative sums come from a
    global cumsum minus the feature's base — no per-feature loop.
    ``hist`` is channel-major [3, TB] (see _flat_histogram).

    ``bin_mask``: optional [TB] bool of ALLOWED candidate bins (feature
    fraction, mapped to the flat bin space by the caller)."""
    import jax.numpy as jnp

    from .histogram import _leaf_objective

    cs, cs_full_i = _prefix_sum(hist, int_channel=2)
    cs, cs_i = cs[:, 1:], cs_full_i[1:]                    # [3, TB] inclusive
    base = (jnp.take(cs, dev["feat_start_of_bin"], axis=1)
            - jnp.take(hist, dev["feat_start_of_bin"], axis=1))
    left = cs - base                                       # [3, TB] within-feature
    total = jnp.take(left, dev["feat_end_of_bin"], axis=1)
    GL, HL = left[0], left[1]
    G, H = total[0], total[1]
    # count channel in exact int32: left/right row counts feed the
    # min_data_in_leaf gates and the emitted Tree.count
    hist_cnt = jnp.round(hist[2]).astype(jnp.int32)
    base_i = (jnp.take(cs_i, dev["feat_start_of_bin"])
              - jnp.take(hist_cnt, dev["feat_start_of_bin"]))
    left_i = cs_i - base_i
    total_i = jnp.take(left_i, dev["feat_end_of_bin"])
    CL = left_i.astype(jnp.float32)
    GR, HR = G - GL, H - HL
    CR = (total_i - left_i).astype(jnp.float32)
    gain = (_leaf_objective(GL, HL, lambda_l1, lambda_l2)
            + _leaf_objective(GR, HR, lambda_l1, lambda_l2)
            - _leaf_objective(G, H, lambda_l1, lambda_l2)) * -1.0
    ok = ((CL >= min_data_in_leaf) & (CR >= min_data_in_leaf)
          & (HL >= min_sum_hessian) & (HR >= min_sum_hessian)
          & ~dev["is_last_bin"])                          # no split after last
    if bin_mask is not None:
        ok &= bin_mask
    gain = jnp.where(ok, gain, -jnp.inf)
    b = jnp.argmax(gain)
    return (b, gain[b], jnp.stack([GL[b], HL[b], CL[b]]),
            jnp.stack([GR[b], HR[b], CR[b]]))


def _row_feature_search(dev, lo0, hi0, f):
    """Vectorized lower-bound search for each row's entry of feature ``f``
    (scalar or per-row array) inside the row's feature-sorted CSR slice
    [lo0, hi0) — pure gathers, no scatter. Per-row ranges are at most
    max_row_nnz wide, so ceil(log2(max_row_nnz)) steps suffice
    (dev["route_steps"]) — at avg-50-nnz text data that is ~9 gathers
    instead of 32 (each step is a random gather from the 200 MB entry
    stream, the dominant routing cost at 50M nnz). Shared by per-split
    routing (_route_rows) and the lazy full-N traversal
    (_assign_leaves_all_rows) so the two can never desynchronize."""
    import jax
    import jax.numpy as jnp

    feats = dev["feat_of_nnz"]
    nnz = feats.shape[0]

    def step(_, lohi):
        lo, hi = lohi
        cont = lo < hi
        mid = (lo + hi) >> 1
        fm = jnp.take(feats, jnp.clip(mid, 0, nnz - 1))
        go_hi = fm < f
        new_lo = jnp.where(go_hi, mid + 1, lo)
        new_hi = jnp.where(go_hi, hi, mid)
        return (jnp.where(cont, new_lo, lo), jnp.where(cont, new_hi, hi))

    n_steps = dev.get("route_steps", 32)
    lo, _ = jax.lax.fori_loop(0, n_steps, step, (lo0, hi0))
    return lo


def _route_rows(dev, node_of_row, node_id, f, t_local, lid, rid):
    """Send the node's rows left iff value-bin <= t_local; absent entries
    carry the feature's zero bin.

    SCATTER-FREE: each row's entry of feature ``f`` (if any) is located by
    the vectorized lower-bound search of _row_feature_search — pure
    gathers over the feature-sorted entries (segment_max over 50M entries
    lowers to a serialized scatter-max; earlier claim: it did not survive
    text scale — not measured in this round)."""
    import jax
    import jax.numpy as jnp

    zero_goes_left = dev["zero_local_dev"][f] <= t_local
    default_child = jnp.where(zero_goes_left, lid, rid)
    in_node = node_of_row == node_id
    out = jnp.where(in_node, default_child, node_of_row)

    feats = dev["feat_of_nnz"]
    nnz = feats.shape[0]
    if "route_lo" in dev:
        # lazy/compacted mode: the routed "rows" are the SELECTED rows;
        # their CSR slices into the global entry stream were gathered at
        # compaction time (slices need not be contiguous across rows)
        lo0 = dev["route_lo"]
        hi0 = dev["route_hi"]
    else:
        indptr = dev["indptr_dev"]
        lo0 = indptr[:-1]
        hi0 = indptr[1:]

    lo = _row_feature_search(dev, lo0, hi0, f)
    pos = jnp.clip(lo, 0, nnz - 1)
    has = (lo < hi0) & (jnp.take(feats, pos) == f)
    local_bin = jnp.take(dev["bin_of_nnz"], pos) - dev["feat_offset_dev"][f]
    target = jnp.where(local_bin <= t_local, lid, rid)
    return jnp.where(in_node & has, target, out)


def _assign_leaves_all_rows(dev, tree_out, n: int):
    """Route ALL n rows through a finished tree by level-synchronous
    traversal: each level advances every row one node via ONE vectorized
    per-row binary search (the row's entry of its CURRENT node's feature —
    the search target varies per row, which the lower-bound gathers handle
    unchanged). Cost is depth x one routing pass instead of
    (num_leaves-1) x one routing pass — the lazy-routing complement: with
    per-split routing restricted to the selected rows, this single
    traversal recovers the full node assignment the score update needs.
    Absent features carry the zero bin, exactly like _route_rows."""
    import jax
    import jax.numpy as jnp

    feat = tree_out["feature"]
    tb_l = tree_out["threshold_bin"]
    li = tree_out["left"]
    ri = tree_out["right"]
    feats = dev["feat_of_nnz"]
    bins = dev["bin_of_nnz"]
    fo = dev["feat_offset_dev"]
    zl = dev["zero_local_dev"]
    nnz = feats.shape[0]
    indptr = dev["indptr_dev"]
    lo_all, hi_all = indptr[:-1], indptr[1:]

    def cond(state):
        pos, it = state
        return (it < feat.shape[0]) & jnp.any(jnp.take(feat, pos) >= 0)

    def body(state):
        pos, it = state
        f = jnp.take(feat, pos)                  # [n]; -1 at leaves
        t_loc = jnp.take(tb_l, pos)
        f_safe = jnp.maximum(f, 0)
        lo = _row_feature_search(dev, lo_all, hi_all, f_safe)
        p = jnp.clip(lo, 0, nnz - 1)
        has = (lo < hi_all) & (jnp.take(feats, p) == f_safe)
        lb = jnp.take(bins, p) - jnp.take(fo, f_safe)
        lb_eff = jnp.where(has, lb, jnp.take(zl, f_safe))
        nxt = jnp.where(lb_eff <= t_loc, jnp.take(li, pos), jnp.take(ri, pos))
        return jnp.where(f >= 0, nxt, pos), it + 1

    pos, _ = jax.lax.while_loop(
        cond, body, (jnp.zeros(n, jnp.int32), jnp.int32(0)))
    return pos


def _bin_sorted_layout(bin_of_nnz: np.ndarray, total_bins: int):
    """Host precompute for the scatter-free histogram: a stable sort of
    entries by flat bin + the per-bin [start, end) offsets into the sorted
    stream. Returns (order, bin_start [TB], bin_end [TB])."""
    order = np.argsort(bin_of_nnz, kind="stable")
    sorted_bins = bin_of_nnz[order]
    bin_start = np.searchsorted(sorted_bins, np.arange(total_bins),
                                side="left")
    bin_end = np.searchsorted(sorted_bins, np.arange(total_bins),
                              side="right")
    return order, bin_start.astype(np.int64), bin_end.astype(np.int64)


def _device_arrays(ds: SparseDataset):
    import jax.numpy as jnp

    tb = ds.total_bins
    feat_of_bin = np.repeat(np.arange(ds.num_features, dtype=np.int64),
                            np.diff(ds.feat_offset))
    feat_start = ds.feat_offset[feat_of_bin]
    feat_end = ds.feat_offset[feat_of_bin + 1] - 1
    is_last = np.arange(tb) == feat_end
    present = np.nonzero(np.diff(ds.feat_offset) > 0)[0]
    zero_flat = (ds.feat_offset[present]
                 + ds.zero_local[present]).astype(np.int64)
    is_zero_bin = np.zeros(tb, dtype=bool)
    is_zero_bin[zero_flat] = True
    order, bin_start, bin_end = _bin_sorted_layout(ds.bin_of_nnz, tb)
    return {
        "bin_of_nnz": jnp.asarray(ds.bin_of_nnz, dtype=jnp.int32),
        "feat_of_nnz": jnp.asarray(ds.indices, dtype=jnp.int32),
        "indptr_dev": jnp.asarray(ds.indptr, dtype=jnp.int32),
        # bin-sorted views for the scatter-free histogram
        "row_of_nnz_bs": jnp.asarray(ds.row_of_nnz[order]),
        "bin_start": jnp.asarray(bin_start, dtype=jnp.int32),
        "bin_end": jnp.asarray(bin_end, dtype=jnp.int32),
        "is_zero_bin": jnp.asarray(is_zero_bin),
        "feat_of_bin": jnp.asarray(feat_of_bin, dtype=jnp.int32),
        "feat_start_of_bin": jnp.asarray(feat_start, dtype=jnp.int32),
        "feat_end_of_bin": jnp.asarray(feat_end, dtype=jnp.int32),
        "is_last_bin": jnp.asarray(is_last),
        "present_feats": jnp.asarray(present, dtype=jnp.int32),
        "zero_flat": jnp.asarray(zero_flat, dtype=jnp.int32),
        "zero_local_dev": jnp.asarray(ds.zero_local, dtype=jnp.int32),
        "feat_offset_dev": jnp.asarray(ds.feat_offset, dtype=jnp.int32),
        "total_bins": tb,
        "num_features": ds.num_features,
        "route_steps": int(
            max(int(np.diff(ds.indptr).max()) if len(ds.indptr) > 1 else 1,
                1)).bit_length(),
    }


_FUSED_SPARSE_GROW_CACHE: dict = {}
_SPARSE_SCAN_CACHE: dict = {}


def _tree_from_fused_out(out_host, config: GrowerConfig,
                         thresholds: np.ndarray) -> Tree:
    """Host-side Tree build from the fused grower's fetched arrays, leaf
    values recomputed in f64 (same precision lineage as the host loop)."""
    nn = int(out_host["n_nodes"])
    feature = out_host["feature"][:nn].astype(np.int32)
    tbin = out_host["threshold_bin"][:nn].astype(np.int32)
    fbin = out_host["flat_bin"][:nn].astype(np.int64)
    sums = out_host["sums"][:nn].astype(np.float64)
    g_thr = np.sign(sums[:, 0]) * np.maximum(
        np.abs(sums[:, 0]) - config.lambda_l1, 0.0)
    value = np.where(feature < 0,
                     -g_thr / (sums[:, 1] + config.lambda_l2), 0.0)
    if config.max_delta_step > 0:
        value = np.clip(value, -config.max_delta_step, config.max_delta_step)
    value[0] = 0.0 if nn == 1 else value[0]
    threshold = np.where(feature >= 0, thresholds[fbin], 0.0)
    return Tree(
        feature=feature,
        threshold=threshold.astype(np.float64),
        threshold_bin=tbin,
        default_left=out_host["default_left"][:nn].astype(bool),
        left=out_host["left"][:nn].astype(np.int32),
        right=out_host["right"][:nn].astype(np.int32),
        value=value,
        gain=out_host["gain"][:nn].astype(np.float32),
        count=sums[:, 2].astype(np.int32),
        weight=sums[:, 1],
    )


def shard_sparse_dataset(ds: SparseDataset, n_shards: int):
    """Partition rows into ``n_shards`` contiguous, nnz-BALANCED blocks and
    build equal-shape per-shard nnz/row arrays (shard_map needs identical
    shard shapes; padding entries carry feat=-1 / nnz_valid=0 so they
    contribute nothing).

    Returns (host dict of [S, ...] arrays, row_bounds [S+1], r_max).
    nnz balancing: block boundaries at equal cumulative-nnz quantiles — the
    reference's equivalent is Spark partition sizing; here the histogram
    cost is O(local nnz), so balanced nnz = balanced step time."""
    n = ds.num_rows
    nnz = len(ds.indices)
    # boundaries: rows where cumulative nnz crosses each 1/S quantile
    targets = (np.arange(1, n_shards) * nnz) // n_shards
    bounds = np.concatenate([
        [0], np.searchsorted(ds.indptr[1:], targets, side="left") + 1, [n]])
    bounds = np.maximum.accumulate(bounds)  # monotone under empty blocks
    r_max = int(np.max(np.diff(bounds))) if n else 1
    nz_max = int(np.max(ds.indptr[bounds[1:]] - ds.indptr[bounds[:-1]])) \
        if n else 1
    nz_max = max(nz_max, 1)

    S = n_shards
    tb = ds.total_bins
    bin_sh = np.zeros((S, nz_max), dtype=np.int32)
    feat_sh = np.full((S, nz_max), -1, dtype=np.int32)
    row_bs = np.zeros((S, nz_max), dtype=np.int32)
    valid_bs = np.zeros((S, nz_max), dtype=np.float32)
    bin_start = np.zeros((S, tb), dtype=np.int32)
    bin_end = np.zeros((S, tb), dtype=np.int32)
    indptr_loc = np.zeros((S, r_max + 1), dtype=np.int32)
    row_valid = np.zeros((S, r_max), dtype=bool)
    for s in range(S):
        r0, r1 = int(bounds[s]), int(bounds[s + 1])
        e0, e1 = int(ds.indptr[r0]), int(ds.indptr[r1])
        m = e1 - e0
        bin_sh[s, :m] = ds.bin_of_nnz[e0:e1]
        feat_sh[s, :m] = ds.indices[e0:e1]
        # bin-sorted views of the REAL entries (pads stay at the tail with
        # valid 0; bin boundaries index only the sorted real stream)
        order, bs, be = _bin_sorted_layout(
            ds.bin_of_nnz[e0:e1].astype(np.int64), tb)
        row_bs[s, :m] = (ds.row_of_nnz[e0:e1] - r0)[order]
        valid_bs[s, :m] = 1.0
        bin_start[s] = bs
        bin_end[s] = be
        # local CSR offsets for the binary-search routing; empty/pad rows
        # collapse to [m, m)
        indptr_loc[s, : r1 - r0 + 1] = ds.indptr[r0: r1 + 1] - e0
        indptr_loc[s, r1 - r0 + 1:] = m
        row_valid[s, : r1 - r0] = True
    return ({"bin_of_nnz": bin_sh,
             "feat_of_nnz": feat_sh, "row_of_nnz_bs": row_bs,
             "nnz_valid": valid_bs, "bin_start": bin_start,
             "bin_end": bin_end, "indptr_dev": indptr_loc,
             "row_valid": row_valid}, bounds, r_max)


_SHARDED_SPARSE_GROW_CACHE: dict = {}


def grow_tree_sparse_sharded(ds: SparseDataset, dev, sharded, mesh,
                             grad_sh, hess_sh, row_mask_sh,
                             config: GrowerConfig, bin_mask=None
                             ) -> Tuple[Tree, np.ndarray]:
    """Row-sharded whole-tree growth: the while_loop runs per shard under
    shard_map with psum'd flat histograms — replicated split decisions,
    sharded row routing (the dense engine's _grow_tree_device_sharded, on
    CSR). One dispatch + one collective stream per tree.

    ``sharded``: device dict from shard_sparse_dataset ([S, ...] arrays,
    device_put with the shard dim split over the mesh's data axis).
    ``grad_sh``/``hess_sh``/``row_mask_sh``: [S, r_max] sharded arrays.
    """
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import shard_map_compat as shard_map

    from ..parallel.mesh import DATA_AXIS

    M = 2 * config.num_leaves - 1
    has_bm = bin_mask is not None
    tb = dev["total_bins"]
    # key carries EVERY closed-over static (tb, num_features) — all array
    # data flows through jit arguments, so a cache hit can never serve a
    # stale dataset (shape changes retrace inside the cached jit)
    key = (mesh, M, config.min_data_in_leaf, config.max_depth, has_bm,
           tb, dev["num_features"], dev.get("route_steps", 32))
    if key not in _SHARDED_SPARSE_GROW_CACHE:
        if len(_SHARDED_SPARSE_GROW_CACHE) >= 8:
            _SHARDED_SPARSE_GROW_CACHE.pop(
                next(iter(_SHARDED_SPARSE_GROW_CACHE)))
        # globals (bin layout) replicate; per-shard arrays split on dim 0;
        # static ints (segment counts) close over — they must not trace
        nf_static = dev["num_features"]
        rs_static = dev.get("route_steps", 32)
        _PER_SHARD = ("bin_of_nnz", "feat_of_nnz", "row_of_nnz_bs",
                      "nnz_valid", "bin_start", "bin_end", "indptr_dev")
        glob = {k: v for k, v in dev.items()
                if k not in _PER_SHARD + ("total_bins", "num_features",
                                          "route_steps")}

        sh_spec = P(DATA_AXIS)
        rep = P()

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=({k: sh_spec for k in _PER_SHARD},
                      sh_spec, sh_spec, sh_spec,
                      {k: rep for k in glob}, rep, rep, rep, rep, rep),
            out_specs={"node_of_row": sh_spec, "feature": rep,
                       "threshold_bin": rep, "flat_bin": rep,
                       "default_left": rep, "left": rep, "right": rep,
                       "gain": rep, "sums": rep, "n_nodes": rep},
            # like tree._grow_tree_device_sharded: the while_loop carry
            # mixes shard-varying (node_of_row) and replicated state
            check_vma=False)
        def go(shd, g, h, m, gl, bm, l1, l2, mshp, mgsp):
            dev_l = dict(gl)
            dev_l["total_bins"] = tb
            dev_l["num_features"] = nf_static
            dev_l["route_steps"] = rs_static
            for kk, v in shd.items():
                dev_l[kk] = v[0]
            g, h, m = g[0], h[0], m[0]
            mask_f = m.astype(jnp.float32)
            root_tot = jax.lax.psum(
                jnp.stack([jnp.sum(g * mask_f), jnp.sum(h * mask_f),
                           jnp.sum(mask_f)]),
                DATA_AXIS)
            out = _grow_tree_sparse_body(
                dev_l, g, h, m, jnp.zeros(g.shape[0], jnp.int32), root_tot,
                l1, l2, mshp, mgsp, bm, total_bins=tb, max_nodes=M,
                min_data_in_leaf=config.min_data_in_leaf,
                max_depth=config.max_depth, has_bin_mask=has_bm,
                psum_axis=DATA_AXIS)
            out["node_of_row"] = out["node_of_row"][None, :]
            return out

        _SHARDED_SPARSE_GROW_CACHE[key] = (jax.jit(go), glob)
    fn, glob = _SHARDED_SPARSE_GROW_CACHE[key]
    bm = bin_mask if has_bm else jnp.zeros(0, dtype=bool)
    out = fn({k: sharded[k] for k in
              ("bin_of_nnz", "feat_of_nnz", "row_of_nnz_bs",
               "nnz_valid", "bin_start", "bin_end", "indptr_dev")},
             grad_sh, hess_sh, row_mask_sh, glob, bm,
             np.float32(config.lambda_l1), np.float32(config.lambda_l2),
             np.float32(config.min_sum_hessian_in_leaf),
             np.float32(config.min_gain_to_split))
    rows_dev = out.pop("node_of_row")
    out_host = fetch_global(out)
    tree = _tree_from_fused_out(out_host, config, ds.thresholds)
    return tree, np.asarray(fetch_global(rows_dev))


def grow_tree_sparse(ds: SparseDataset, dev, grad, hess,
                     config: GrowerConfig, row_mask=None, bin_mask=None,
                     use_fused: Optional[bool] = None
                     ) -> Tuple[Tree, np.ndarray]:
    """Grow one tree over the flat sparse bins; returns (tree, leaf_of_row).

    Default (``use_fused``): the whole tree grows inside one jitted
    ``lax.while_loop`` dispatch (_grow_tree_sparse_body) — one fetch per
    tree. Fallback (state over the memory budget or explicitly disabled):
    the host-orchestrated per-split loop below.

    ``row_mask``: [N] bool device array — bagging/goss subset (histograms
    and totals are masked; routing still covers every row).
    ``bin_mask``: [TB] bool device array of allowed split bins
    (feature_fraction mapped to the flat space).
    """
    import heapq

    import jax
    import jax.numpy as jnp

    n = ds.num_rows
    if use_fused is None:
        use_fused = (_fused_sparse_enabled(2 * config.num_leaves - 1,
                                           ds.total_bins)
                     and jax.default_backend() != "cpu")
    if use_fused:
        M = 2 * config.num_leaves - 1
        has_bm = bin_mask is not None
        tb = dev["total_bins"]
        nf = dev["num_features"]
        rs = dev.get("route_steps", 32)
        # key carries every closed-over static; array data (the dev dict)
        # flows through jit arguments — no id()-keying, no pinned device
        # memory for evicted datasets (numBatches builds a fresh
        # SparseDataset per batch)
        key = (M, config.min_data_in_leaf, config.max_depth, has_bm, tb,
               nf, rs)
        if key not in _FUSED_SPARSE_GROW_CACHE:
            if len(_FUSED_SPARSE_GROW_CACHE) >= 16:
                _FUSED_SPARSE_GROW_CACHE.pop(
                    next(iter(_FUSED_SPARSE_GROW_CACHE)))

            @jax.jit
            def _go(devd, gk, hk, mask, bm, l1, l2, msh, mgs):
                devd = dict(devd)
                devd["total_bins"] = tb
                devd["num_features"] = nf
                devd["route_steps"] = rs
                mask_f = mask.astype(jnp.float32)
                root_tot = jnp.stack([jnp.sum(gk * mask_f),
                                      jnp.sum(hk * mask_f),
                                      jnp.sum(mask_f)])
                return _grow_tree_sparse_body(
                    devd, gk, hk, mask, jnp.zeros(gk.shape[0], jnp.int32),
                    root_tot, l1, l2, msh, mgs, bm, total_bins=tb,
                    max_nodes=M, min_data_in_leaf=config.min_data_in_leaf,
                    max_depth=config.max_depth, has_bin_mask=has_bm)

            _FUSED_SPARSE_GROW_CACHE[key] = _go
        mask = row_mask if row_mask is not None \
            else jnp.ones(n, dtype=bool)
        bm = bin_mask if has_bm else jnp.zeros(0, dtype=bool)
        dev_arrays = {kk_: v for kk_, v in dev.items()
                      if kk_ not in ("total_bins", "num_features",
                                     "route_steps")}
        out = _FUSED_SPARSE_GROW_CACHE[key](
            dev_arrays, mask=mask, bm=bm, gk=grad, hk=hess,
            l1=np.float32(config.lambda_l1), l2=np.float32(config.lambda_l2),
            msh=np.float32(config.min_sum_hessian_in_leaf),
            mgs=np.float32(config.min_gain_to_split))
        rows_dev = out.pop("node_of_row")
        out_host = fetch_global(out)
        tree = _tree_from_fused_out(out_host, config, ds.thresholds)
        return tree, np.asarray(fetch_global(rows_dev))

    node_of_row = jnp.zeros(n, dtype=jnp.int32)
    ones = row_mask if row_mask is not None else jnp.ones(n, dtype=bool)

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

    def leaf_value(sums):
        g_thr = np.sign(sums[0]) * max(abs(sums[0]) - config.lambda_l1, 0.0)
        v = float(-g_thr / (sums[1] + config.lambda_l2))
        if config.max_delta_step > 0:
            v = float(np.clip(v, -config.max_delta_step,
                              config.max_delta_step))
        return v

    g_bs, h_bs = _entry_gh(dev, grad, hess)

    def node_hist(mask_rows, totals):
        flat = _flat_histogram(dev, g_bs, h_bs, mask_rows)
        return _zero_completed(dev, flat, totals)

    mask_f = ones.astype(jnp.float32)
    totals0 = jnp.stack([jnp.sum(grad * mask_f), jnp.sum(hess * mask_f),
                         jnp.sum(mask_f)])
    hist0 = node_hist(ones, totals0)
    totals0_h = np.asarray(fetch_global(totals0), np.float64)
    counts[0] = int(totals0_h[2])
    hweights[0] = float(totals0_h[1])

    def eval_split(hist):
        b, gain, lsum, rsum = _find_best_split_flat(
            dev, hist, np.float32(config.lambda_l1),
            np.float32(config.lambda_l2),
            np.float32(config.min_sum_hessian_in_leaf),
            config.min_data_in_leaf, bin_mask)
        b, gain, lsum, rsum = fetch_global((b, gain, lsum, rsum))
        f = int(np.searchsorted(ds.feat_offset, b, side="right") - 1)
        t_local = int(b - ds.feat_offset[f])
        return f, t_local, float(gain), np.asarray(lsum, np.float64), \
            np.asarray(rsum, np.float64)

    heap = []
    tiebreak = 0

    def push(node_id, depth, hist, sums):
        nonlocal tiebreak
        f, t_local, gain, lsum, rsum = eval_split(hist)
        if np.isfinite(gain) and gain > config.min_gain_to_split:
            if config.max_depth > 0 and depth >= config.max_depth:
                return
            heapq.heappush(heap, (-gain, tiebreak,
                                  (node_id, depth, hist, sums,
                                   f, t_local, lsum, rsum, gain)))
            tiebreak += 1

    push(0, 0, hist0, totals0_h)
    n_leaves = 1

    while heap and n_leaves < config.num_leaves:
        _, _, (nid, depth, hist, sums, f, t_local, lsum, rsum, gain) = \
            heapq.heappop(heap)
        lid, rid = len(feature), len(feature) + 1
        thr = ds.bin_upper_value(f, t_local)
        feature[nid] = f
        threshold[nid] = thr
        threshold_bin[nid] = t_local
        # absent==0.0 routes by value like LightGBM's sparse default bin;
        # keep dense-predict agreement: zeros follow the threshold compare
        default_left[nid] = bool(0.0 <= thr)
        left[nid], right[nid] = lid, rid
        gains[nid] = float(gain)
        value[nid] = 0.0
        for csum in (lsum, rsum):
            feature.append(-1)
            threshold.append(0.0)
            threshold_bin.append(0)
            default_left.append(True)
            left.append(-1)
            right.append(-1)
            value.append(leaf_value(csum))
            gains.append(0.0)
            counts.append(int(csum[2]))
            hweights.append(float(csum[1]))
        n_leaves += 1

        node_of_row = _route_rows(dev, node_of_row, np.int32(nid),
                                  np.int32(f), np.int32(t_local),
                                  np.int32(lid), np.int32(rid))
        small_id, big_id = (lid, rid) if lsum[2] <= rsum[2] else (rid, lid)
        small_sums = lsum if small_id == lid else rsum
        big_sums = rsum if small_id == lid else lsum
        small_hist = node_hist(ones & (node_of_row == small_id),
                               jnp.asarray(small_sums, jnp.float32))
        big_hist = hist - small_hist
        for cid, chist, csums in ((small_id, small_hist, small_sums),
                                  (big_id, big_hist, big_sums)):
            if csums[2] >= 2 * config.min_data_in_leaf:
                push(cid, depth + 1, chist, csums)

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
    )
    return tree, np.asarray(fetch_global(node_of_row))


# ---------------------------------------------------------------------------
# Device-fused whole-tree growth + whole-run scan (the dense engine's
# booster._train_scan / tree._grow_tree_device_body, ported to the flat
# ragged bin space — one dispatch chain for the entire boosting run)
# ---------------------------------------------------------------------------

# Per-node flat-histogram state cap for the fused sparse grower:
# [2L-1, total_bins, 3] f32. Above this, the host-orchestrated per-split
# loop runs instead (its live set is the heap frontier only).
_FUSED_SPARSE_DEFAULT_BUDGET = 2 << 30


def _fused_sparse_enabled(max_nodes: int, total_bins: int) -> bool:
    import os

    if os.environ.get("MMLSPARK_TPU_NO_FUSED_TREE", "") not in ("", "0"):
        return False
    budget = int(os.environ.get("MMLSPARK_TPU_FUSED_TREE_BYTES",
                                _FUSED_SPARSE_DEFAULT_BUDGET))
    return max_nodes * total_bins * 3 * 4 <= budget


def _grow_tree_sparse_body(dev, grad, hess, row_mask, node_of_row, root_tot,
                           l1, l2, msh, mgs, bin_mask, *, total_bins: int,
                           max_nodes: int, min_data_in_leaf: int,
                           max_depth: int, has_bin_mask: bool,
                           psum_axis=None):
    """Grow one whole tree over the flat sparse bins inside a single
    ``lax.while_loop`` (the sparse analogue of tree._grow_tree_device_body).

    ``dev``: the _device_arrays dict (traced pytree — nnz/bin layouts).
    ``root_tot``: [3] f32 masked (grad, hess, count) node totals (already
    psum'd by the caller when sharded).
    ``psum_axis``: set when running per shard under shard_map with rows
    split over that mesh axis — every histogram is psum'd so all shards
    take identical split decisions while the row routing stays sharded
    (LightGBM's socket-ring data-parallel mode as one collective stream,
    TrainUtils.scala:383-418).
    Returns flat node arrays sized ``max_nodes`` plus the final row→node
    routing; node ids are assigned in split order exactly like the dense
    grower, so serialization/merge see an identical tree shape.
    """
    import jax
    import jax.numpy as jnp

    neg_inf = jnp.float32(-jnp.inf)
    M = max_nodes
    num_leaves_target = (max_nodes + 1) // 2
    bm = bin_mask if has_bin_mask else None
    g_bs, h_bs = _entry_gh(dev, grad, hess)  # tree-invariant entry gathers

    def best(hist):
        return _find_best_split_flat(dev, hist, l1, l2, msh,
                                     min_data_in_leaf, bm)

    def node_hist(mask_rows, totals):
        flat = _flat_histogram(dev, g_bs, h_bs, mask_rows)
        if psum_axis is not None:
            flat = jax.lax.psum(flat, psum_axis)
        return _zero_completed(dev, flat, totals)

    root_hist = node_hist(row_mask, root_tot)
    b0, gain0, lsum0, rsum0 = best(root_hist)
    root_ok = jnp.isfinite(gain0) & (gain0 > mgs)

    f32 = jnp.float32
    state = dict(
        node_of_row=node_of_row,
        feature=jnp.full(M, -1, jnp.int32),
        threshold_bin=jnp.zeros(M, jnp.int32),   # LOCAL bin within feature
        flat_bin=jnp.zeros(M, jnp.int32),        # flat bin (threshold lookup)
        default_left=jnp.ones(M, bool),
        left=jnp.full(M, -1, jnp.int32),
        right=jnp.full(M, -1, jnp.int32),
        gain=jnp.zeros(M, f32),
        sums=jnp.zeros((M, 3), f32).at[0].set(root_tot),
        depth=jnp.zeros(M, jnp.int32),
        hists=jnp.zeros((M, 3, total_bins), f32).at[0].set(root_hist),
        cand_gain=jnp.full(M, -jnp.inf, f32).at[0].set(
            jnp.where(root_ok, gain0, neg_inf)),
        cand_bin=jnp.zeros(M, jnp.int32).at[0].set(b0.astype(jnp.int32)),
        cand_lsum=jnp.zeros((M, 3), f32).at[0].set(lsum0),
        cand_rsum=jnp.zeros((M, 3), f32).at[0].set(rsum0),
        n_nodes=jnp.int32(1),
        n_leaves=jnp.int32(1),
    )

    def cond(st):
        return (st["n_leaves"] < num_leaves_target) \
            & (jnp.max(st["cand_gain"]) > neg_inf)

    def body(st):
        leaf = jnp.argmax(st["cand_gain"]).astype(jnp.int32)
        b = st["cand_bin"][leaf]
        f = dev["feat_of_bin"][b]
        t_local = b - dev["feat_start_of_bin"][b]
        dl = dev["zero_local_dev"][f] <= t_local   # absent (0.0) routing
        lsum = st["cand_lsum"][leaf]
        rsum = st["cand_rsum"][leaf]
        lid = st["n_nodes"]
        rid = lid + 1
        dchild = st["depth"][leaf] + 1

        node_of_row = _route_rows(dev, st["node_of_row"], leaf, f, t_local,
                                  lid, rid)

        small_is_left = lsum[2] <= rsum[2]
        small_id = jnp.where(small_is_left, lid, rid)
        big_id = jnp.where(small_is_left, rid, lid)
        small_tot = jnp.where(small_is_left, lsum, rsum)
        small_mask = row_mask & (node_of_row == small_id)
        small_hist = node_hist(small_mask, small_tot)
        big_hist = st["hists"][leaf] - small_hist
        sb, sg, sl, sr = best(small_hist)
        bb, bg, bl, br = best(big_hist)

        cg = st["cand_gain"].at[leaf].set(neg_inf)
        cb = st["cand_bin"]
        cl, cr = st["cand_lsum"], st["cand_rsum"]

        def push(arrs, nid, bsel, gsel, lsel, rsel, csum):
            cg, cb, cl, cr = arrs
            ok = jnp.isfinite(gsel) & (gsel > mgs)
            ok &= csum[2] >= 2 * min_data_in_leaf
            if max_depth > 0:
                ok &= dchild < max_depth
            return (cg.at[nid].set(jnp.where(ok, gsel, neg_inf)),
                    cb.at[nid].set(bsel.astype(jnp.int32)),
                    cl.at[nid].set(lsel), cr.at[nid].set(rsel))

        big_tot = jnp.where(small_is_left, rsum, lsum)
        arrs = push((cg, cb, cl, cr), small_id, sb, sg, sl, sr, small_tot)
        cg, cb, cl, cr = push(arrs, big_id, bb, bg, bl, br, big_tot)

        return dict(
            node_of_row=node_of_row,
            feature=st["feature"].at[leaf].set(f),
            threshold_bin=st["threshold_bin"].at[leaf].set(t_local),
            flat_bin=st["flat_bin"].at[leaf].set(b),
            default_left=st["default_left"].at[leaf].set(dl),
            left=st["left"].at[leaf].set(lid),
            right=st["right"].at[leaf].set(rid),
            gain=st["gain"].at[leaf].set(st["cand_gain"][leaf]),
            sums=st["sums"].at[lid].set(lsum).at[rid].set(rsum),
            depth=st["depth"].at[lid].set(dchild).at[rid].set(dchild),
            hists=st["hists"].at[small_id].set(small_hist)
                             .at[big_id].set(big_hist),
            cand_gain=cg, cand_bin=cb, cand_lsum=cl, cand_rsum=cr,
            n_nodes=lid + 2, n_leaves=st["n_leaves"] + 1,
        )

    out = jax.lax.while_loop(cond, body, state)
    return {k: out[k] for k in (
        "node_of_row", "feature", "threshold_bin", "flat_bin", "default_left",
        "left", "right", "gain", "sums", "n_nodes")}


def _scan_sparse_ok(params, valid, log) -> bool:
    """Whole-run-scan eligibility for the sparse path: mirrors
    booster._scan_train_ok (dart and per-iteration host eval stay on the
    host loop; lambdarank grads are group-segmented and also host-looped)."""
    import os

    import jax

    if os.environ.get("MMLSPARK_TPU_NO_SCAN_TRAIN", "") not in ("", "0"):
        return False
    if params.boosting_type == "dart" or params.objective == "lambdarank":
        return False
    if valid is not None or log is not None or params.train_metric:
        return False
    if 2 * params.num_leaves - 1 < 3:
        return False
    forced = os.environ.get("MMLSPARK_TPU_SCAN_TRAIN", "") not in ("", "0")
    if not forced and jax.default_backend() == "cpu":
        return False
    return True


def _sparse_compact_cap(params, ds, row_masks) -> tuple:
    """Static capacities ``(cap, sel_cap)`` for in-scan selected-row entry
    compaction — ``(0, 0)`` disables it.

    When a row subset is selected per iteration (GOSS / bagging / rf), the
    histogram stream is compacted to the selected rows' entries, shrinking
    every per-split cost from O(nnz) to O(selected nnz) — masking alone
    does not (the round-3 artifact's 'GOSS shows no speedup' finding:
    histogram prefix sums and mask gathers stream all nnz regardless).
    The capacity must be STATIC (the scan's shapes are fixed across
    iterations) and must bound the selected nnz of every iteration:

    - GOSS: selection is exactly top_n + other_n rows (_exact_topk_mask),
      so the sum of that many largest row-nnz is a guarantee;
    - host-precomputed bagging masks: the per-iteration selected nnz is
      known outright — take the max.

    Returns ``(cap, sel_cap)`` — the nnz capacity and the selected-ROW
    capacity. sel_cap > 0 additionally enables LAZY ROUTING: per-split
    routing runs only over the selected rows (the tree's rows), and the
    full-N node assignment the score update needs is recovered once per
    tree by level-synchronous traversal (_assign_leaves_all_rows) —
    depth routing passes instead of num_leaves-1 (at 50M nnz routing is
    ~0.3 s/split over all 1M rows, the largest per-split cost after
    compaction). MMLSPARK_TPU_NO_SPARSE_LAZY_ROUTE=1 keeps compaction but
    routes eagerly.

    Gated to TPU at real scale (compaction costs one drop-scatter +
    cumsum per iteration, ~0.85 s at 50M nnz — profitable only when the
    ~30 splits/tree each save a third of their stream costs);
    MMLSPARK_TPU_SPARSE_COMPACT=1 forces it on (tests),
    MMLSPARK_TPU_NO_SPARSE_COMPACT=1 kills it.
    """
    import os

    import jax

    if os.environ.get("MMLSPARK_TPU_NO_SPARSE_COMPACT", "") not in ("", "0"):
        return 0, 0
    n = ds.num_rows
    nnz = int(ds.indptr[-1])
    row_nnz = np.diff(ds.indptr)
    if params.boosting_type == "goss":
        k_sel = int(n * params.top_rate) + int(n * params.other_rate)
        if k_sel <= 0 or k_sel >= n:
            return 0, 0
        cap = int(np.partition(row_nnz, n - k_sel)[n - k_sel:].sum())
    elif row_masks is not None:
        k_sel = int(row_masks.sum(axis=1).max())
        cap = int((row_masks.astype(np.int64) @ row_nnz.astype(np.int64))
                  .max())
    else:
        return 0, 0
    cap = max(cap, 1)
    sel_cap = max(int(k_sel), 1)
    if os.environ.get("MMLSPARK_TPU_NO_SPARSE_LAZY_ROUTE",
                      "") not in ("", "0"):
        sel_cap = 0
    if os.environ.get("MMLSPARK_TPU_SPARSE_COMPACT", "") not in ("", "0"):
        # forced mode (tests) bypasses profitability gates, not correctness
        return cap, sel_cap
    # lazy-routing profitability: per tree, eager routing costs
    # (num_leaves-1) full-N passes; lazy costs (num_leaves-1) passes over
    # the selected fraction PLUS max_depth full-N traversal levels.
    # Leaf-wise trees on zipf-ish text data grow DEEP (measured: lazy
    # LOST ~50% at 200k x 31 leaves unbounded — depth ~ num_leaves), so
    # lazy only turns on when max_depth bounds the traversal and the
    # model says it wins with margin.
    splits = max(params.num_leaves - 1, 1)
    if params.max_depth <= 0:
        sel_cap = 0
    else:
        sel_frac = sel_cap / max(n, 1)
        if sel_frac * splits + params.max_depth >= 0.9 * splits:
            sel_cap = 0
    if jax.default_backend() != "tpu":
        return 0, 0
    if nnz < 2_000_000 or cap > int(0.75 * nnz):
        return 0, 0
    return cap, sel_cap


def _train_scan_sparse(params, config: GrowerConfig, booster, ds,
                       dev, labels, w_dev, scores, k: int, lr: float,
                       row_masks, feat_masks, compact_cap: int = 0,
                       sel_cap: int = 0) -> None:
    """ALL boosting iterations in one chunked ``lax.scan`` dispatch over the
    flat sparse bin space — no per-tree host round trips (the sparse
    analogue of booster._train_scan; chunking bounds device-runtime per
    dispatch the same way)."""
    import os

    import jax
    import jax.numpy as jnp

    from .booster import grad_hess

    n = ds.num_rows
    iters = params.num_iterations
    M = 2 * config.num_leaves - 1
    tb = dev["total_bins"]
    objective = params.objective
    alpha = params.alpha
    l1 = np.float32(config.lambda_l1)
    l2 = np.float32(config.lambda_l2)
    msh = np.float32(config.min_sum_hessian_in_leaf)
    mgs = np.float32(config.min_gain_to_split)
    has_fm = feat_masks is not None
    shrink = np.float32(lr)

    # in-scan GOSS: EXACT top_n |grad| rows (_exact_topk_mask — LightGBM's
    # sorted-GOSS count semantics, needed for the static compaction bound)
    # + exactly other_n uniform draws among the rest, amplified
    is_goss = params.boosting_type == "goss"
    if is_goss:
        top_n = int(n * params.top_rate)
        other_n = int(n * params.other_rate)
        goss_amp = np.float32((1.0 - params.top_rate)
                              / max(params.other_rate, 1e-12))
        goss_keys = jax.random.split(
            jax.random.PRNGKey(params.seed or params.bagging_seed), iters)

    # The scan is wrapped in a jit whose ARGUMENTS carry every large array
    # (dev layout, labels, weights): a lax.scan traced outside jit embeds
    # closed-over device arrays as program CONSTANTS — at 50M-nnz text
    # scale that serialized ~600 MB of literals into the remote compile
    # request (observed: multi-minute compiles, then HTTP 413).
    # locals only below — closing over `dev` inside _run_chunk would pin
    # the whole dataset's device arrays in the _SPARSE_SCAN_CACHE entry
    nf_s = dev["num_features"]
    rs_s = dev.get("route_steps", 32)
    has_rm = row_masks is not None

    def _run_chunk(devd, lab, wv, carry, xs_c, ipc):
        devt = dict(devd)
        devt["total_bins"] = tb
        devt["num_features"] = nf_s
        devt["route_steps"] = rs_s

        def body(carry, xs):
            score, comp = carry
            row_mask = (xs["rm"] if has_rm
                        else jnp.ones(n, dtype=bool))
            if has_fm:
                bin_mask = jnp.take(xs["fm"], devt["feat_of_bin"])
            else:
                bin_mask = jnp.zeros(0, dtype=bool)
            g, h = grad_hess(objective, score, lab, wv, alpha)
            if is_goss:
                g_sel = jnp.abs(g) if g.ndim == 1 \
                    else jnp.sum(jnp.abs(g), axis=1)
                is_top = _exact_topk_mask(g_sel, top_n, n)
                u = jax.random.uniform(xs["gk"], (n,))
                row_mask = is_top | _exact_topk_mask(u, other_n, n,
                                                     exclude=is_top)
                amp = jnp.where(is_top, jnp.float32(1.0), goss_amp)
                g = g * (amp if g.ndim == 1 else amp[:, None])
                h = h * (amp if h.ndim == 1 else amp[:, None])

            devc = devt
            lazy = bool(compact_cap and sel_cap)
            sel_rows = sel_valid = None
            if compact_cap:
                # selected-row entry compaction: the bin-sorted stream keeps
                # its order under compaction, so the prefix-sum histogram
                # works unchanged with remapped bin boundaries
                # (cnt0[bin_start], cnt0[bin_end] — entries of bin b occupy
                # [cnt0[start_b], cnt0[end_b]) of the compacted stream).
                # Tail slots past the selected count are never read: every
                # remapped boundary is <= the selected total. Drop-scatter
                # with strictly unique indices (unselected entries get
                # distinct out-of-range slots).
                rbs = devt["row_of_nnz_bs"]
                esel = jnp.take(row_mask, rbs)
                # native 1-D int32 cumsum measures 23 ms at 50M (vs 25 ms
                # for the blocked scheme — the 645 ms pathology is the
                # 3-channel f32 case); the drop-scatter is the real cost
                cnt = jnp.cumsum(esel.astype(jnp.int32))
                nnz_i = rbs.shape[0]
                iota = jnp.arange(nnz_i, dtype=jnp.int32)
                idx = jnp.where(esel, cnt - 1, compact_cap + iota)
                rows_cmp = jnp.zeros(compact_cap, jnp.int32).at[idx].set(
                    rbs, mode="drop", unique_indices=True)
                cnt0 = jnp.concatenate([jnp.zeros(1, jnp.int32), cnt])
                bstart_c = jnp.take(cnt0, devt["bin_start"])
                bend_c = jnp.take(cnt0, devt["bin_end"])
                if lazy:
                    # lazy routing: re-parameterize the grower so its
                    # "rows" ARE the selected rows — compacted entries
                    # reference selected-row POSITIONS, per-split routing
                    # searches only the selected rows' CSR slices
                    # (route_lo/route_hi), and the full-N assignment is
                    # recovered once per tree by level traversal below
                    cnt_rows = jnp.cumsum(row_mask.astype(jnp.int32))
                    rank_of_row = cnt_rows - 1       # [N]; valid where sel
                    sel_rows = jnp.nonzero(row_mask, size=sel_cap,
                                           fill_value=0)[0]
                    sel_valid = (jnp.arange(sel_cap, dtype=jnp.int32)
                                 < cnt_rows[-1])
                    selpos = jnp.take(rank_of_row, rows_cmp)   # [cap]
                    ip = devt["indptr_dev"]
                    devc = dict(devt,
                                row_of_nnz_bs=selpos,
                                bin_start=bstart_c, bin_end=bend_c,
                                route_lo=jnp.take(ip, sel_rows),
                                route_hi=jnp.take(ip, sel_rows + 1))
                else:
                    devc = dict(devt,
                                row_of_nnz_bs=rows_cmp,
                                bin_start=bstart_c, bin_end=bend_c)

            mask_f = row_mask.astype(jnp.float32)
            outs = []
            for kk in range(k):
                gk = g if g.ndim == 1 else g[:, kk]
                hk = h if h.ndim == 1 else h[:, kk]
                root_tot = jnp.stack([jnp.sum(gk * mask_f),
                                      jnp.sum(hk * mask_f),
                                      jnp.sum(mask_f)])
                if lazy:
                    out = _grow_tree_sparse_body(
                        devc, jnp.take(gk, sel_rows), jnp.take(hk, sel_rows),
                        sel_valid, jnp.zeros(sel_cap, jnp.int32),
                        root_tot, l1, l2, msh, mgs, bin_mask, total_bins=tb,
                        max_nodes=M,
                        min_data_in_leaf=config.min_data_in_leaf,
                        max_depth=config.max_depth, has_bin_mask=has_fm)
                    out.pop("node_of_row")   # selected-row ids only
                    rows = _assign_leaves_all_rows(devt, out, n)
                else:
                    out = _grow_tree_sparse_body(
                        devc, gk, hk, row_mask, jnp.zeros(n, jnp.int32),
                        root_tot, l1, l2, msh, mgs, bin_mask, total_bins=tb,
                        max_nodes=M,
                        min_data_in_leaf=config.min_data_in_leaf,
                        max_depth=config.max_depth, has_bin_mask=has_fm)
                    rows = out.pop("node_of_row")
                sums, feat = out["sums"], out["feature"]
                g_thr = jnp.sign(sums[:, 0]) * jnp.maximum(
                    jnp.abs(sums[:, 0]) - l1, 0.0)
                val = jnp.where(feat < 0, -g_thr / (sums[:, 1] + l2), 0.0)
                if config.max_delta_step > 0:
                    val = jnp.clip(val, -config.max_delta_step,
                                   config.max_delta_step)
                val = val.at[0].set(
                    jnp.where(out["n_nodes"] > 1, val[0], 0.0))
                upd = (val * shrink)[rows]
                if k == 1:
                    y_ = upd + comp
                    t_ = score + y_
                    score, comp = t_, y_ - (t_ - score)
                else:
                    s_col, c_col = score[:, kk], comp[:, kk]
                    y_ = upd + c_col
                    t_ = s_col + y_
                    score = score.at[:, kk].set(t_)
                    comp = comp.at[:, kk].set(y_ - (t_ - s_col))
                outs.append(out)
            stacked = jax.tree.map(lambda *a: jnp.stack(a), *outs)
            return (score, comp), stacked

        return jax.lax.scan(body, carry, xs_c, length=ipc)

    # the jit wrapper is cached on its STATIC closure values — a fresh
    # jax.jit per train_sparse call recompiled the whole scan every fit
    # (~250 s at 50M-nnz scale; observed as 'warm' fits slower than cold)
    cache_key = (tb, dev["num_features"], dev.get("route_steps", 32), n,
                 iters, k, M, objective, float(alpha), float(shrink),
                 float(l1), float(l2), float(msh), float(mgs),
                 config.min_data_in_leaf, config.max_depth,
                 float(config.max_delta_step), is_goss, has_fm,
                 compact_cap, sel_cap, row_masks is not None,
                 (params.top_rate, params.other_rate,
                  params.seed or params.bagging_seed) if is_goss else None)
    if cache_key not in _SPARSE_SCAN_CACHE:
        if len(_SPARSE_SCAN_CACHE) >= 8:
            _SPARSE_SCAN_CACHE.pop(next(iter(_SPARSE_SCAN_CACHE)))
        _SPARSE_SCAN_CACHE[cache_key] = jax.jit(
            _run_chunk, static_argnames=("ipc",))
    run_chunk = _SPARSE_SCAN_CACHE[cache_key]

    score0 = jnp.asarray(scores[:, 0] if k == 1 else scores,
                         dtype=jnp.float32)
    comp0 = jnp.zeros_like(score0)
    xs = None
    if row_masks is not None or has_fm or is_goss:
        xs = {}
        if row_masks is not None:
            xs["rm"] = jnp.asarray(row_masks)
        if has_fm:
            xs["fm"] = jnp.asarray(feat_masks)
        if is_goss:
            xs["gk"] = goss_keys

    # chunk: bound device-runtime per dispatch (see booster._train_scan's
    # chunking note); sparse per-iter work scales
    # with nnz (histogram streams) + n (routing) + M*tb (state updates)
    per_iter = len(ds.indices) + n + M * tb // 8
    budget = int(os.environ.get("MMLSPARK_TPU_SCAN_CHUNK_ROWS",
                                str(2 * 10**7)))
    ipc = max(1, min(iters, budget // max(per_iter, 1)))

    dev_arrays = {k2: v for k2, v in dev.items()
                  if k2 not in ("total_bins", "num_features", "route_steps")}
    carry = (score0, comp0)
    host_chunks = []
    done = 0
    while done < iters:
        xs_c = None
        if xs is not None:
            idx = np.minimum(np.arange(done, done + ipc), iters - 1)
            xs_c = {kk_: v[idx] for kk_, v in xs.items()}
        carry, ys = run_chunk(dev_arrays, labels, w_dev, carry, xs_c,
                              ipc=ipc)
        host_chunks.append(fetch_global(ys))
        done += ipc
    host = jax.tree.map(lambda *c: np.concatenate(c, axis=0), *host_chunks) \
        if len(host_chunks) > 1 else host_chunks[0]
    host = jax.tree.map(lambda a: a[:iters], host)

    thresholds = ds.thresholds  # [TB] f64 upper values
    for it in range(iters):
        group: List[Tree] = []
        for kk in range(k):
            nn = int(host["n_nodes"][it, kk])
            feature = host["feature"][it, kk][:nn].astype(np.int32)
            tbin = host["threshold_bin"][it, kk][:nn].astype(np.int32)
            fbin = host["flat_bin"][it, kk][:nn].astype(np.int64)
            sums = host["sums"][it, kk][:nn].astype(np.float64)
            g_thr = np.sign(sums[:, 0]) * np.maximum(
                np.abs(sums[:, 0]) - config.lambda_l1, 0.0)
            value = np.where(feature < 0,
                             -g_thr / (sums[:, 1] + config.lambda_l2), 0.0)
            if config.max_delta_step > 0:
                value = np.clip(value, -config.max_delta_step,
                                config.max_delta_step)
            value[0] = 0.0 if nn == 1 else value[0]
            threshold = np.where(feature >= 0, thresholds[fbin], 0.0)
            group.append(Tree(
                feature=feature,
                threshold=threshold.astype(np.float64),
                threshold_bin=tbin,
                default_left=host["default_left"][it, kk][:nn].astype(bool),
                left=host["left"][it, kk][:nn].astype(np.int32),
                right=host["right"][it, kk][:nn].astype(np.int32),
                value=value,
                gain=host["gain"][it, kk][:nn].astype(np.float32),
                count=sums[:, 2].astype(np.int32),
                shrinkage=lr,
                weight=sums[:, 1],
            ))
        booster.trees.append(group)


def train_sparse(params, ds: SparseDataset, y: np.ndarray,
                 weights: Optional[np.ndarray] = None,
                 groups: Optional[np.ndarray] = None,
                 valid: Optional[Tuple] = None,
                 valid_groups: Optional[np.ndarray] = None,
                 init_scores: Optional[np.ndarray] = None,
                 init_model=None,
                 log=None,
                 mesh=None):
    """Boosting over a SparseDataset; returns an ordinary Booster.

    Carries the reference's FULL sparse param surface — in the reference,
    CSR data feeds the same native engine with everything enabled
    (generateSparseDataset → LGBM_DatasetCreateFromCSRSpark,
    lightgbm/TrainUtils.scala:23-66): bagging (incl. pos/neg and rf),
    goss, dart, feature_fraction, weights, init scores, lambdarank groups,
    validation + early stopping, and continued training (init_model).

    The no-valid/no-dart/no-lambdarank case runs the whole boosting run in
    ONE chunked lax.scan dispatch (_train_scan_sparse); everything else
    takes the host-orchestrated loop below.

    ``valid``: optional ((indptr, indices, values), y_valid) CSR holdout.
    ``mesh``: optional jax Mesh — rows are split into nnz-balanced
    contiguous blocks over the ``data`` axis and each tree grows per shard
    under shard_map with psum'd flat histograms (grow_tree_sparse_sharded):
    the CSR counterpart of the dense engine's multi-chip data-parallel
    path, replacing LightGBM's socket-ring allreduce over sparse partitions
    (TrainUtils.scala:23-66 + 383-418).
    """
    import jax
    import jax.numpy as jnp

    from .booster import (_HIGHER_BETTER, Booster, GrowerConfig,
                          _scan_precompute_masks, default_metric, eval_metric,
                          grad_hess, init_score, segment_groups)

    if params.categorical_feature:
        raise ValueError(
            "categorical_feature is not supported on the sparse path "
            "(set splits need the dense bin space; sparse features are "
            "numeric TF counts) — densify for categorical slots")
    k = max(params.num_class, 1)
    n = ds.num_rows
    dev = _device_arrays(ds)
    labels = jnp.asarray(y, dtype=jnp.float32)
    w_dev = jnp.asarray(weights, dtype=jnp.float32) \
        if weights is not None else None
    g_dev = jnp.asarray(groups, dtype=jnp.int32) \
        if groups is not None else None
    group_seg = (segment_groups(groups)
                 if groups is not None and params.objective == "lambdarank"
                 else None)
    rng = np.random.default_rng(params.seed or params.bagging_seed)

    if init_scores is not None:
        base = np.zeros(k, dtype=np.float64)
        scores = np.broadcast_to(
            np.asarray(init_scores, dtype=np.float64).reshape(n, -1),
            (n, k)).copy()
    else:
        base = init_score(params.objective, np.asarray(y, dtype=np.float64),
                          k, alpha=params.alpha)
        scores = np.tile(base, (n, 1)).astype(np.float64)
    booster = Booster(params, None, base_score=base)
    if init_model is not None:
        booster.trees = [list(g) for g in init_model.trees]
        booster.base_score = init_model.base_score
        base = booster.base_score
        if init_model.trees:
            scores = (np.tile(base, (n, 1))
                      + predict_csr(init_model.trees,
                                    ds.indptr, ds.indices, ds.values, k))

    config = GrowerConfig(
        num_leaves=params.num_leaves, max_depth=params.max_depth,
        min_data_in_leaf=params.min_data_in_leaf,
        min_sum_hessian_in_leaf=params.min_sum_hessian_in_leaf,
        min_gain_to_split=params.min_gain_to_split,
        lambda_l1=params.lambda_l1, lambda_l2=params.lambda_l2,
        max_delta_step=params.max_delta_step)

    is_rf = params.boosting_type == "rf"
    is_dart = params.boosting_type == "dart"
    is_goss = params.boosting_type == "goss"
    lr = 1.0 if is_rf else params.learning_rate

    # ----- mesh sharding context (nnz-balanced contiguous row blocks) ---
    shard_ctx = None
    if mesh is not None:
        from ..parallel.mesh import DATA_AXIS

        n_shards = int(mesh.shape.get(DATA_AXIS, 1))
        if n_shards > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            sh_host, bounds, r_max = shard_sparse_dataset(ds, n_shards)
            row_sharding = NamedSharding(mesh, PartitionSpec(DATA_AXIS))
            sharded = {kk_: jax.device_put(jnp.asarray(v), row_sharding)
                       for kk_, v in sh_host.items()
                       if kk_ != "row_valid"}
            row_valid = sh_host["row_valid"]

            # one-time gather plan: [S, r_max] indices into a (sentinel-
            # extended) [N+1] array — per-iteration resharding is a single
            # fancy-index instead of a Python loop over shards
            pad_idx = np.full((n_shards, r_max), n, dtype=np.int64)
            for s in range(n_shards):
                ln = bounds[s + 1] - bounds[s]
                pad_idx[s, :ln] = np.arange(bounds[s], bounds[s + 1])

            def _to_shards(a, fill=0):
                ext = np.append(a, np.asarray(fill, dtype=a.dtype))
                return ext[pad_idx]

            def _from_shards(a_sh):
                return np.concatenate(
                    [a_sh[s, : bounds[s + 1] - bounds[s]]
                     for s in range(n_shards)])

            shard_ctx = (sharded, row_sharding, _to_shards, _from_shards)

    # ----- whole-run fused scan path ------------------------------------
    if (shard_ctx is None and _scan_sparse_ok(params, valid, log)
            and _fused_sparse_enabled(2 * config.num_leaves - 1,
                                      ds.total_bins)):
        row_masks, feat_masks, ok = _scan_precompute_masks(
            params, rng, n, ds.num_features, np.asarray(y), is_rf)
        if ok:
            from ..core.runtime import ensure_compile_cache

            ensure_compile_cache()
            ccap, scap = _sparse_compact_cap(params, ds, row_masks)
            _train_scan_sparse(params, config, booster, ds, dev, labels,
                               w_dev, scores, k, lr, row_masks, feat_masks,
                               compact_cap=ccap, sel_cap=scap)
            if is_rf and booster.trees:
                inv = 1.0 / len(booster.trees)
                for gtrees in booster.trees:
                    for t in gtrees:
                        t.shrinkage = inv
            return booster

    # ----- host-orchestrated loop (valid/early-stop, dart, lambdarank) --
    metric = params.metric or default_metric(params.objective)
    higher_better = metric in _HIGHER_BETTER
    best_val = -np.inf if higher_better else np.inf
    best_iter = -1
    rounds_no_improve = 0
    val_csr = val_y = None
    val_scores = None
    if valid is not None:
        val_csr, val_y = valid
        nv = len(val_csr[0]) - 1
        val_scores = np.tile(base, (nv, 1)).astype(np.float64)
        if init_model is not None and init_model.trees:
            val_scores += predict_csr(init_model.trees, *val_csr, k)

    def _csr_contrib(tree_group):
        return predict_csr([tree_group], ds.indptr, ds.indices, ds.values, k)

    bag_mask = np.ones(n, dtype=bool)
    use_fused = _fused_sparse_enabled(2 * config.num_leaves - 1,
                                      ds.total_bins)
    for it in range(params.num_iterations):
        dropped: List[int] = []
        if is_dart and booster.trees:
            n_trees = len(booster.trees)
            if params.uniform_drop:
                drop_mask = rng.random(n_trees) < params.drop_rate
                dropped = list(np.where(drop_mask)[0][: params.max_drop])
            else:
                n_drop = min(max(1, int(n_trees * params.drop_rate)),
                             params.max_drop)
                dropped = list(rng.choice(n_trees, size=n_drop,
                                          replace=False))
            for di in dropped:
                scores -= _csr_contrib(booster.trees[di])
                if val_csr is not None:
                    # keep the holdout scores in lockstep (the dropped
                    # trees are rescaled below; stale valid contributions
                    # would corrupt the early-stopping metric)
                    val_scores -= predict_csr([booster.trees[di]],
                                              *val_csr, k)

        score_dev = jnp.asarray(scores[:, 0] if k == 1 else scores,
                                dtype=jnp.float32)
        g, h = grad_hess(params.objective, score_dev, labels, w_dev,
                         params.alpha, g_dev, group_segments=group_seg)

        # bagging / goss row selection (host RNG: same draws as dense)
        row_mask = bag_mask
        if is_goss:
            g_abs = np.abs(np.asarray(fetch_global(g)))
            if g_abs.ndim == 2:
                g_abs = g_abs.sum(axis=1)
            top_n = int(n * params.top_rate)
            other_n = int(n * params.other_rate)
            order = np.argsort(-g_abs)
            row_mask = np.zeros(n, dtype=bool)
            row_mask[order[:top_n]] = True
            rest = order[top_n:]
            picked = rng.choice(len(rest), size=min(other_n, len(rest)),
                                replace=False)
            row_mask[rest[picked]] = True
            amplify = (1.0 - params.top_rate) / max(params.other_rate, 1e-12)
            amp = np.ones(n, dtype=np.float32)
            amp[rest] = amplify
            amp_dev = jnp.asarray(amp)
            g = g * (amp_dev if g.ndim == 1 else amp_dev[:, None])
            h = h * (amp_dev if h.ndim == 1 else amp_dev[:, None])
        elif ((params.bagging_fraction < 1.0
               or params.pos_bagging_fraction < 1.0
               or params.neg_bagging_fraction < 1.0)
              and (is_rf or params.bagging_freq > 0)
              and it % max(params.bagging_freq, 1) == 0):
            if (params.pos_bagging_fraction < 1.0
                    or params.neg_bagging_fraction < 1.0):
                pos = np.asarray(y) > 0.5
                frac = np.where(pos, params.pos_bagging_fraction,
                                params.neg_bagging_fraction)
                bag_mask = rng.random(n) < frac
            else:
                bag_mask = rng.random(n) < params.bagging_fraction
            row_mask = bag_mask

        bin_mask = None
        if params.feature_fraction < 1.0:
            m = np.zeros(ds.num_features, dtype=bool)
            n_feat = max(1, int(ds.num_features * params.feature_fraction))
            m[rng.choice(ds.num_features, size=n_feat, replace=False)] = True
            bin_mask = jnp.asarray(m)[dev["feat_of_bin"]]

        mask_dev = jnp.asarray(row_mask) if shard_ctx is None else None
        group: List[Tree] = []
        for kk in range(k):
            gk = g if g.ndim == 1 else g[:, kk]
            hk = h if h.ndim == 1 else h[:, kk]
            if shard_ctx is not None:
                sharded, row_sharding, _to_shards, _from_shards = shard_ctx
                gh = np.asarray(fetch_global(gk), dtype=np.float32)
                hh = np.asarray(fetch_global(hk), dtype=np.float32)
                g_sh = jax.device_put(jnp.asarray(_to_shards(gh)),
                                      row_sharding)
                h_sh = jax.device_put(jnp.asarray(_to_shards(hh)),
                                      row_sharding)
                m_sh = jax.device_put(
                    jnp.asarray(_to_shards(row_mask)
                                & sh_host["row_valid"]), row_sharding)
                tree, rows_sh = grow_tree_sparse_sharded(
                    ds, dev, sharded, mesh, g_sh, h_sh, m_sh, config,
                    bin_mask=bin_mask)
                leaf_of_row = _from_shards(rows_sh)
            else:
                tree, leaf_of_row = grow_tree_sparse(
                    ds, dev, gk, hk, config, row_mask=mask_dev,
                    bin_mask=bin_mask, use_fused=use_fused)
            shrink = lr
            if is_dart and dropped:
                shrink = lr / (len(dropped) + lr)
            tree.shrinkage = shrink
            group.append(tree)
            scores[:, kk] += tree.value[leaf_of_row] * shrink
        if is_dart and dropped:
            factor = len(dropped) / (len(dropped) + lr)
            for di in dropped:
                for kk in range(k):
                    booster.trees[di][kk].shrinkage *= factor
                scores += _csr_contrib(booster.trees[di])
                if val_csr is not None:
                    val_scores += predict_csr([booster.trees[di]],
                                              *val_csr, k)
        booster.trees.append(group)

        # eval + early stopping on the CSR holdout
        if val_csr is not None:
            val_scores += predict_csr([group], *val_csr, k)
            vs = val_scores[:, 0] if k == 1 else val_scores
            m = eval_metric(metric, vs, np.asarray(val_y, dtype=np.float64),
                            valid_groups)
            improved = m > best_val if higher_better else m < best_val
            if improved:
                best_val, best_iter, rounds_no_improve = \
                    m, len(booster.trees), 0
            else:
                rounds_no_improve += 1
            if log:
                log(f"[{it + 1}] valid {metric}={m:.6f}")
            if params.early_stopping_round > 0 \
                    and rounds_no_improve >= params.early_stopping_round:
                booster.best_iteration = best_iter
                if log:
                    log(f"early stopping at iteration {it + 1}, "
                        f"best {best_iter}")
                break
        elif log and (it + 1) % 10 == 0:
            sc = scores[:, 0] if k == 1 else scores
            m = eval_metric(metric, sc, np.asarray(y, dtype=np.float64),
                            groups)
            log(f"[{it + 1}] train {metric}={m:.6f}")

    if is_rf and booster.trees:
        inv = 1.0 / len(booster.trees)
        for gtrees in booster.trees:
            for t in gtrees:
                t.shrinkage = inv
    return booster


def _flatten_forest(tree_groups):
    """Concatenated node arrays + per-tree offsets for the C++ CSR
    traversal, memoized/validated by predict.memoize_forest (shared with
    the dense layout — one shrinkage-invalidation contract)."""
    from .predict import memoize_forest

    def build():
        feats, thrs, lefts, rights, vals_ = [], [], [], [], []
        offs, shr, cls = [0], [], []
        for group in tree_groups:
            for kcls, tree in enumerate(group):
                feats.append(np.asarray(tree.feature, dtype=np.int32))
                thrs.append(np.asarray(tree.threshold, dtype=np.float64))
                lefts.append(np.asarray(tree.left, dtype=np.int32))
                rights.append(np.asarray(tree.right, dtype=np.int32))
                vals_.append(np.asarray(tree.value, dtype=np.float64))
                offs.append(offs[-1] + len(tree.feature))
                shr.append(float(tree.shrinkage))
                cls.append(kcls)
        return (np.concatenate(feats), np.concatenate(thrs),
                np.concatenate(lefts), np.concatenate(rights),
                np.concatenate(vals_), np.asarray(offs, dtype=np.int64),
                np.asarray(shr, dtype=np.float64),
                np.asarray(cls, dtype=np.int32))

    return memoize_forest(tree_groups, "csr", build)


def _predict_csr_native(tree_groups, indptr, indices, values, n: int,
                        num_class: int):
    """Flatten the forest and call the C++ traversal
    (native_loader.csr_forest_predict); None when the library is
    unavailable so the caller keeps its numpy path."""
    from .. import native_loader

    if not any(len(g) for g in tree_groups):
        return np.zeros((n, num_class), dtype=np.float64)
    flat = _flatten_forest(tree_groups)
    return native_loader.csr_forest_predict(
        indptr, indices, values, *flat[:6], flat[6], flat[7], num_class)


def predict_csr(tree_groups: List[List[Tree]], indptr, indices, values,
                num_class: int) -> np.ndarray:
    """[CSR rows] -> [N, num_class] raw score deltas (PredictForCSRSingle
    parity, LightGBMBooster.scala:21-148 — fully vectorized over rows).

    Value lookup rides ONE global searchsorted per depth step over the
    composite (row, feature) key — CSR rows are sorted, so
    ``row * (F+1) + feature`` is globally ascending."""
    for group in tree_groups:
        for tree in group:
            if tree.cat_sets is not None:
                raise ValueError(
                    "categorical set splits cannot be evaluated on sparse "
                    "CSR rows (sparse features are numeric); densify for "
                    "categorical models")
    indptr = np.asarray(indptr, dtype=np.int64)
    indices = np.asarray(indices, dtype=np.int64)
    values = np.asarray(values, dtype=np.float64)
    n = len(indptr) - 1

    # native fast path: flattened per-row traversal in C++ (the reference's
    # predict is LightGBM's C++ core; the numpy path below stays as the
    # toolchain-free fallback and the parity reference — gated equal in
    # tests). MMLSPARK_TPU_NO_NATIVE_CSR_PREDICT=1 disables.
    import os as _os

    if _os.environ.get("MMLSPARK_TPU_NO_NATIVE_CSR_PREDICT",
                       "") in ("", "0"):
        native_out = _predict_csr_native(tree_groups, indptr, indices,
                                         values, n, num_class)
        if native_out is not None:
            return native_out

    out = np.zeros((n, num_class), dtype=np.float64)
    width = int(indices.max()) + 2 if len(indices) else 1
    row_of = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    key = row_of * width + indices                    # globally ascending

    def lookup(rows: np.ndarray, feats: np.ndarray) -> np.ndarray:
        res = np.zeros(len(feats), dtype=np.float64)
        if not len(key):
            return res
        inr = feats < width  # features beyond the data's width are absent
        q = rows[inr] * width + feats[inr]
        pos = np.searchsorted(key, q)
        ok = (pos < len(key)) & (key[np.minimum(pos, len(key) - 1)] == q)
        sub = np.zeros(len(q), dtype=np.float64)
        sub[ok] = values[pos[ok]]
        res[inr] = sub
        return res

    all_rows = np.arange(n, dtype=np.int64)
    for group in tree_groups:
        for kcls, tree in enumerate(group):
            node = np.zeros(n, dtype=np.int64)
            active = tree.feature[node] != -1
            while active.any():
                cur = node[active]
                f = tree.feature[cur].astype(np.int64)
                x = lookup(all_rows[active], f)
                go_left = x <= tree.threshold[cur]
                node[active] = np.where(go_left, tree.left[cur],
                                        tree.right[cur])
                active = tree.feature[node] != -1
            out[:, kcls] += tree.value[node] * tree.shrinkage
    return out
