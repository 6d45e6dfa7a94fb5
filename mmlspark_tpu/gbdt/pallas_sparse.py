"""Pallas TPU kernels for the CSR sparse path (docs/sparse.md).

Two device kernels ride the CSR wire triple ``(indptr, indices, values)``
that core/fusion.py stages for a sparse-capable segment:

  - **CSR gather** (``csr_gather``): wire triple -> the dense ``[N, U]``
    matrix of the forest's *used* feature columns — the only columns the
    traversal ever reads. ``U = |used features|`` is forest-sized (tens to
    hundreds), not data-sized (VW widths, 2^18+), so the gather replaces an
    ``N x width`` densify with an ``N x U`` one: bytes scale with nnz + the
    forest, not the feature space. The XLA formulation is one global
    ``searchsorted`` over composite ``row * width + index`` keys (CSR rows
    are sorted, so the flat key array is globally ascending — the same
    trick as sparse.predict_csr's lookup); the Pallas formulation contracts
    transposed one-hots on the MXU, chunk by chunk, like pallas_hist.py.
    Both are EXACT: every output cell receives at most one nonzero (CSR
    rows carry distinct indices), and f32 adds of zeros are exact, so the
    two formulations — and the densify path they replace — are bitwise
    equal.

  - **Sparse histogram** (``sparse_histogram_mxu``): the GBDT sparse
    engine's nonzero-entry histogram ([3, total_bins] grad/hess/count sums
    over the flat ragged bin space) as a one-hot MXU contraction over nnz
    chunks — the sparse sibling of pallas_hist's dense kernel, hooked into
    sparse._flat_histogram behind the ``hist.csr`` kernel variant. Unlike
    the gather, bins accumulate MANY entries, so chunk order changes the
    f32 summation order versus the prefix-sum path: the variant declares a
    tolerance (core/kernels.py) instead of bitwise equality.

Parity contract for the gather (enforced in tests/test_sparse_e2e.py):
``csr_gather(triple, width, used)[:, u]`` is bitwise-equal to
``densify(triple, width)[:, min(used[u], width - 1)]`` — including the
upper clamp, because the dense traversal reads features through
``take_along_axis``/advanced indexing, which XLA clamps out-of-range.
Padded CSR tail entries (fusion pads nnz to a power-of-two bucket)
resolve to row ``N`` in composite-key space — past every real query, so
they can never alias a live cell.

``remap_ensemble`` rewrites a DeviceEnsemble's feature ids into positions
in the used-feature set so the unmodified traversal kernels (gather loop
and path-matrix GEMM, gbdt/predict.py) run on the compacted ``[N, U]``
matrix: internal-node features remap by position, leaf markers (-1) and
GEMM pad slots (ivalid == 0) stay inert exactly as on the dense path.

Dispatch mirrors pallas_hist.py: the Pallas kernels run on TPU (or in
interpreter mode for CPU tests, MMLSPARK_TPU_PALLAS_INTERPRET=1); every
other configuration takes the XLA formulation, which is what the CPU test
suite and the serving bench exercise.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

# Row-chunk size for the one-hot contractions (bounds the [*, CHUNK] VMEM
# tiles), as pallas_hist.CHUNK.
CHUNK = 512
#: Work bound for the MXU gather (its cost is nnz x N x U_pad one-hot
#: products). Not a VMEM guard: the kernel tiles N and U, so its VMEM
#: footprint is bounded by the tile constants below whatever the shape.
_GATHER_MAX_CELLS = 1 << 21
#: Gather output tile. Measured on v5e (jax 0.9.0, 16 MiB scoped VMEM): an
#: UNTILED [N_pad, U_pad] accumulator compiles at 8192 x 128 and is refused
#: at 16384 x 128 (24.2 MiB) and 4096 x 512 (34.1 MiB) — both inside the
#: cell guard — so the kernel tiles to [4096, 128] blocks.
_GATHER_TILE_N = 4096
_GATHER_TILE_U = 128
#: Largest flat bin space the sparse-hist kernel takes ([3, TB_pad] f32
#: accumulator, ~1.5 MB). Measured on v5e (jax 0.9.0): compiles and agrees
#: with the reference at this limit.
_SPARSE_HIST_MAX_TB = 128 * 1024


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# used-feature set + ensemble remap (host, once per forest)
# ---------------------------------------------------------------------------


def used_features(ens) -> np.ndarray:
    """Sorted unique feature ids the forest's internal nodes read (i64).
    Never empty: an all-leaf forest reads no features, but the traversal
    kernels still gather column 0 through the leaf markers — keep one
    column so the compacted matrix has a valid shape."""
    feats = np.asarray(ens.feature)
    pos = np.unique(feats[feats >= 0]).astype(np.int64)
    if len(pos) == 0:
        pos = np.zeros(1, dtype=np.int64)
    return pos


def remap_ensemble(ens, used: np.ndarray):
    """A shallow-copied DeviceEnsemble whose feature ids are POSITIONS in
    ``used`` — ready to traverse the compacted [N, U] matrix csr_gather
    produces. Leaf markers (-1) are kept; GEMM pad slots (ivalid == 0,
    feature 0) map to a clipped in-range position, where their sign
    products are zeroed exactly as on the dense path. Compiled-forward
    caches are reset so the remapped copy traces its own programs."""
    import copy

    used = np.asarray(used, dtype=np.int64)
    remapped = copy.copy(ens)
    feats = np.asarray(ens.feature)
    pos = np.searchsorted(used, np.maximum(feats.astype(np.int64), 0))
    pos = np.minimum(pos, len(used) - 1)
    remapped.feature = np.where(feats >= 0, pos, feats).astype(feats.dtype)
    if getattr(ens, "_gemm", None) is not None:
        feat_g, thr, dl, ivalid, C, plen, lval = ens._gemm
        gpos = np.searchsorted(used, np.asarray(feat_g, dtype=np.int64))
        gpos = np.minimum(gpos, len(used) - 1)
        remapped._gemm = (gpos.astype(np.asarray(feat_g).dtype), thr, dl,
                          ivalid, C, plen, lval)
    remapped._jitted = None
    remapped._jitted_gather = None
    return remapped


# ---------------------------------------------------------------------------
# CSR gather: wire triple -> [N, U] used-feature matrix
# ---------------------------------------------------------------------------


def _csr_row_of(indptr, nnz: int):
    """Row id per CSR entry position (traced). Padded tail positions
    (>= indptr[-1]) land on row N — past every composite-key query."""
    import jax.numpy as jnp

    j = jnp.arange(nnz, dtype=jnp.int32)
    return (jnp.searchsorted(indptr.astype(jnp.int32), j, side="right")
            .astype(jnp.int32) - 1)


def csr_gather_xla(indptr, indices, values, width, used):
    """XLA formulation: one searchsorted over globally ascending composite
    ``row * width + index`` keys answers all N x U "value of feature u in
    row n" lookups at once (absent -> 0.0, exactly the densify fill)."""
    import jax.numpy as jnp

    n = indptr.shape[0] - 1
    nnz = indices.shape[0]
    w = jnp.asarray(width, dtype=jnp.int32)
    used_q = jnp.minimum(jnp.asarray(used, dtype=jnp.int32), w - 1)
    row_of = _csr_row_of(indptr, nnz)
    key = row_of * w + indices.astype(jnp.int32)
    q = (jnp.arange(n, dtype=jnp.int32)[:, None] * w
         + used_q[None, :]).reshape(-1)
    pos = jnp.searchsorted(key, q)
    pos_c = jnp.minimum(pos, nnz - 1)
    ok = (pos < nnz) & (jnp.take(key, pos_c) == q)
    x = jnp.where(ok, jnp.take(values, pos_c), jnp.float32(0.0))
    return x.reshape(n, used_q.shape[0]).astype(jnp.float32)


def _gather_kernel(row_ref, idx_ref, val_ref, uq_ref, out_ref):
    """One (row tile, feature tile, nnz chunk) grid cell of the Pallas
    gather; the nnz axis is innermost, so each output tile accumulates over
    every chunk before the grid moves on.

    row_ref/idx_ref: [1, CHUNK] i32 (entry row / feature id; padded rows
    are out of range -> all-zero row one-hot), val_ref: [1, CHUNK] f32,
    uq_ref: [TU, 1] i32 (this tile's clamped used-feature ids),
    out_ref: [TN, TU] f32 accumulator tile, VMEM-resident across the
    chunk axis.

    out[n, u] += sum_k (row[k] == n) * (uq[u] == idx[k]) * val[k] — both
    one-hots built transposed against dim-0 iotas (the pallas_hist idiom;
    no in-kernel transposes), contracted over the chunk on the MXU. At
    most one k matches any (n, u), so the f32 accumulation is exact.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tn, tu = out_ref.shape
    chunk = row_ref.shape[1]
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (tn, chunk), 0) \
        + pl.program_id(0) * tn
    row_onehot = (jnp.broadcast_to(row_ref[...], (tn, chunk))
                  == iota_n).astype(jnp.float32)              # [TN, CHUNK]
    feat_onehot = (jnp.broadcast_to(uq_ref[...], (tu, chunk))
                   == jnp.broadcast_to(idx_ref[...], (tu, chunk)))
    contrib = feat_onehot.astype(jnp.float32) \
        * jnp.broadcast_to(val_ref[...], (tu, chunk))         # [TU, CHUNK]
    out_ref[...] += jax.lax.dot_general(
        row_onehot, contrib,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def csr_gather_pallas(indptr, indices, values, width, used,
                      interpret: bool = False):
    """MXU formulation of csr_gather: one-hot contraction per nnz chunk.
    Bitwise-equal to csr_gather_xla (at most one hit per output cell)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = indptr.shape[0] - 1
    nnz = indices.shape[0]
    u = int(np.shape(used)[0])
    w = jnp.asarray(width, dtype=jnp.int32)
    used_q = jnp.minimum(jnp.asarray(used, dtype=jnp.int32), w - 1)

    tn = min(_round_up(max(n, 8), 8), _GATHER_TILE_N)
    tu = _GATHER_TILE_U
    n_pad = _round_up(max(n, 8), tn)
    u_pad = _round_up(max(u, tu), tu)
    nnz_pad = _round_up(max(nnz, 1), CHUNK)
    row_of = _csr_row_of(indptr, nnz)
    # kernel pad entries: out-of-range row (-1) zeroes the row one-hot
    row2 = jnp.full((1, nnz_pad), -1, dtype=jnp.int32)
    row2 = row2.at[0, :nnz].set(row_of)
    idx2 = jnp.zeros((1, nnz_pad), dtype=jnp.int32)
    idx2 = idx2.at[0, :nnz].set(indices.astype(jnp.int32))
    val2 = jnp.zeros((1, nnz_pad), dtype=jnp.float32)
    val2 = val2.at[0, :nnz].set(values.astype(jnp.float32))
    uq2 = jnp.full((u_pad, 1), -1, dtype=jnp.int32)
    uq2 = uq2.at[:u, 0].set(used_q)

    out = pl.pallas_call(
        _gather_kernel,
        grid=(n_pad // tn, u_pad // tu, nnz_pad // CHUNK),
        in_specs=[
            pl.BlockSpec((1, CHUNK), lambda i, k, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, CHUNK), lambda i, k, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, CHUNK), lambda i, k, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tu, 1), lambda i, k, j: (k, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tn, tu), lambda i, k, j: (i, k),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_pad, u_pad), jnp.float32),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * nnz_pad * n_pad * u_pad,
            bytes_accessed=3 * nnz_pad * 4 + u_pad * 4 + n_pad * u_pad * 4,
            transcendentals=0,
        ),
    )(row2, idx2, val2, uq2)
    return out[:n, :u]


def csr_gather(indptr, indices, values, width, used,
               pallas: bool = False):
    """Dispatching CSR gather (traced; called inside the fused program).
    ``pallas=True`` (the ``forest.csr`` variant) routes to the MXU kernel
    when the backend supports it — bitwise-equal either way, so the
    routing can never change results."""
    from .pallas_hist import interpret_mode, use_pallas

    n = indptr.shape[0] - 1
    u = int(np.shape(used)[0])
    if pallas and n * _round_up(max(u, 128), 128) <= _GATHER_MAX_CELLS:
        if use_pallas():
            return csr_gather_pallas(indptr, indices, values, width, used)
        if interpret_mode():
            return csr_gather_pallas(indptr, indices, values, width, used,
                                     interpret=True)
    return csr_gather_xla(indptr, indices, values, width, used)


# ---------------------------------------------------------------------------
# Sparse histogram: flat ragged bin sums as a one-hot MXU contraction
# ---------------------------------------------------------------------------


def _sparse_hist_kernel(bins_ref, stats_ref, out_ref):
    """One nnz-chunk grid cell: bins_ref [1, CHUNK] i32 flat bin ids,
    stats_ref [3, CHUNK] f32 pre-masked (g, h, count) channels, out_ref
    [3, TB_pad] f32 accumulator resident across the grid. The transposed
    one-hot ([TB_pad, CHUNK], dim-0 iota) is contracted over the chunk on
    the MXU — pallas_hist's reduction pattern over the flat ragged bin
    space instead of the [F, B] grid."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tb_pad = out_ref.shape[1]
    chunk = bins_ref.shape[1]
    iota0 = jax.lax.broadcasted_iota(jnp.int32, (tb_pad, chunk), 0)
    onehot = jnp.broadcast_to(bins_ref[...], (tb_pad, chunk)) == iota0
    out_ref[...] += jax.lax.dot_general(
        stats_ref[...], onehot.astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def sparse_histogram_mxu(flat_bins, stats, total_bins: int,
                         interpret: bool = False):
    """[nnz] i32 flat bin ids + [3, nnz] pre-masked channel stats ->
    [3, total_bins] f32 sums. Masked/padded entries carry zero stats, so
    their one-hot column contributes nothing wherever it lands. Chunk
    order changes the f32 accumulation order versus the prefix-sum path
    (sparse._flat_histogram): callers gate on the ``hist.csr`` variant's
    declared tolerance, and the count channel is exact below 2^24."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nnz = flat_bins.shape[0]
    tb_pad = _round_up(max(total_bins, 128), 128)
    nnz_pad = _round_up(max(nnz, 1), CHUNK)
    bins2 = jnp.zeros((1, nnz_pad), dtype=jnp.int32)
    bins2 = bins2.at[0, :nnz].set(flat_bins.astype(jnp.int32))
    stats2 = jnp.zeros((3, nnz_pad), dtype=jnp.float32)
    stats2 = stats2.at[:, :nnz].set(stats.astype(jnp.float32))

    out = pl.pallas_call(
        _sparse_hist_kernel,
        grid=(nnz_pad // CHUNK,),
        in_specs=[
            pl.BlockSpec((1, CHUNK), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, CHUNK), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((3, tb_pad), lambda j: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((3, tb_pad), jnp.float32),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * 3 * nnz_pad * tb_pad,
            bytes_accessed=nnz_pad * 4 + 3 * nnz_pad * 4 + 3 * tb_pad * 4,
            transcendentals=0,
        ),
    )(bins2, stats2)
    return out[:, :total_bins]


def flat_hist_dispatch(dev, data) -> Optional[object]:
    """sparse._flat_histogram's Pallas route: [3, TB] sums when the
    ``hist.csr`` kernel variant is active AND the backend runs Pallas
    (TPU, or interpreter mode for CPU tests) AND the flat bin space fits
    the VMEM accumulator guard; None keeps the prefix-sum path. Resolved
    at trace time — the executor/trainer activates the variant around its
    jit trace, so the choice is a static program property.

    ``data`` is the channel-major [3, nnz] masked (g, h, count) stack in
    BIN-SORTED entry order; the per-entry flat bin id is recovered from
    the bin boundary offsets (entry j belongs to the first bin whose end
    offset exceeds j — empty bins skip naturally)."""
    from ..core import kernels as _kernels

    from .pallas_hist import interpret_mode, use_pallas

    var = _kernels.active("hist")
    if var is None or var.params.get("layout") != "csr":
        return None
    if use_pallas():
        interpret = False
    elif interpret_mode():
        interpret = True
    else:
        return None
    total_bins = int(dev["bin_end"].shape[0])
    if total_bins > _SPARSE_HIST_MAX_TB:
        return None
    import jax.numpy as jnp

    nnz = data.shape[1]
    j = jnp.arange(nnz, dtype=jnp.int32)
    bin_of = jnp.searchsorted(dev["bin_end"].astype(jnp.int32), j,
                              side="right").astype(jnp.int32)
    return sparse_histogram_mxu(bin_of, data, total_bins,
                                interpret=interpret)
