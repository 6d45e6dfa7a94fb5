"""Pallas TPU kernel for masked row compaction (stream select).

The tier-compacted histogram path (tree.py small_child_hist) needs the rows
of a boolean mask gathered to the front of a static-capacity buffer. XLA's
``jnp.nonzero(size=cap)`` lowers to a full-width cumsum + scatter — measured
~56 ms at 3.2M rows on the chip, paid once per tiered split, which makes
row compaction (not the histogram kernel) the largest per-split cost of
GBDT training (reference analogue: LightGBM's DataPartition::Split, which
is a cache-local CPU pass).

This kernel reformulates compaction the same way pallas_hist.py
reformulates the histogram scatter: as **one-hot contractions on the MXU**
over feature-major inputs.

Per row tile of CHUNK columns (grid is 1-D over tiles, executed in order):

1. within-tile exclusive prefix of the mask — a [1, CHUNK] x [CHUNK, CHUNK]
   strict-upper-triangular matmul (0/1 operands, f32 accumulate: exact);
2. transposed one-hot W[p, i] = (prefix[i] == p) & mask[i];
3. compacted tile = V @ W^T on the MXU, where V = [bins; grad; hess] is the
   [F+2, CHUNK] channel-major value block. One-hot rows pass values through
   untouched (products are v*1 and v*0 with f32 accumulation), so grad/hess
   come out bit-exact and bins cast back to uint8 losslessly;
4. the tile lands in the output at the tile's global offset (exclusive
   cumsum of per-tile counts, computed by the XLA wrapper and handed to the
   kernel via scalar prefetch) with a dynamic-slice DMA. Tiles overlap the
   previous tile's invalid tail; the grid's sequential order makes the
   overwrite well-defined, and rows past the total count are masked by the
   caller's validity mask (histogram vals are pre-masked; garbage bins fall
   outside the one-hot range).

Row order is preserved (stable within tiles, tiles in order), so histogram
summation order matches the nonzero+gather path bit-for-bit — verified by
an exact-equality unit test in interpret mode.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_hist import _round_up  # shared: keep rounding rules in one place

CHUNK = 1024


def _select_kernel(offs_ref, bins_ref, g_ref, h_ref, m_ref,
                   out_ref, s_ref, sem, *, nf: int, chunk: int,
                   c_pad: int):
    j = pl.program_id(0)
    off = offs_ref[j]

    m = m_ref[...].astype(jnp.float32)                       # [1, CHUNK]
    # 1. exclusive prefix within the tile: pos[i] = sum_{i'<i} m[i']
    iota0 = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota1 = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    upper = (iota0 < iota1).astype(jnp.float32)              # [i', i]
    pos = jax.lax.dot_general(                               # [1, CHUNK] f32
        m, upper, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    # 2. transposed one-hot: W[p, i] = (pos[i] == p) & m[i]
    pos_b = jnp.broadcast_to(pos.astype(jnp.int32), (chunk, chunk))
    sel = jnp.broadcast_to(m, (chunk, chunk)) > 0.0
    wt = ((pos_b == iota0) & sel).astype(jnp.float32)        # [p, i]

    # 3. compacted tile on the MXU: [p, i] x [C, i] -> [p, C] (row-major:
    # the tile then lands with a major-dim dynamic offset, the layout the
    # DMA engine slices without minor-dim tiling constraints)
    v = jnp.concatenate(
        # via int32: Mosaic has no direct uint8 -> f32 cast
        [bins_ref[...].astype(jnp.int32).astype(jnp.float32),
         g_ref[...].astype(jnp.float32),
         h_ref[...].astype(jnp.float32),
         # lane padding: HBM minor dims are (1,128)-tiled, so the output
         # carries c_pad >= 128 channels; surplus lanes are zeros
         jnp.zeros((c_pad - nf - 2, chunk), jnp.float32)], axis=0)
    # wt is exactly 0/1 (bf16-exact), so out = wt@v_hi + wt@v_mid + wt@v_lo
    # with the classic 3-term bf16 split of v reconstructs every selected
    # f32 bit-exactly (each product is v_term*1 or *0; accumulation is f32)
    # in 3 single-pass bf16 matmuls — Mosaic has no per-operand precision,
    # and HIGHEST on both operands would cost 6 passes
    wt_bf = wt.astype(jnp.bfloat16)
    v_hi = v.astype(jnp.bfloat16)
    r = v - v_hi.astype(jnp.float32)
    v_mid = r.astype(jnp.bfloat16)
    v_lo = (r - v_mid.astype(jnp.float32)).astype(jnp.bfloat16)
    dn = (((1,), (1,)), ((), ()))
    # precision pinned: under an ambient jax.default_matmul_precision(
    # "highest") Mosaic refuses bf16 operands ("Bad lhs type")
    one_pass = functools.partial(
        jax.lax.dot_general, dimension_numbers=dn,
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)
    acc = one_pass(wt_bf, v_hi) + one_pass(wt_bf, v_mid) \
        + one_pass(wt_bf, v_lo)
    s_ref[...] = acc                                         # [CHUNK, c_pad]

    # 4. land the tile at its global offset (sequential grid: later tiles
    # overwrite this tile's invalid tail)
    cp = pltpu.make_async_copy(
        s_ref, out_ref.at[pl.ds(off, chunk), :], sem)
    cp.start()
    cp.wait()


def select_rows(bins_fm, grad, hess, mask, cap: int, interpret: bool = False):
    """Compact the masked rows of feature-major data to the buffer front.

    bins_fm: [F, N] int (bin ids, exact through f32 for num_bins <= 2^24 —
    the engine caps bins at 65535), grad/hess: [N] f32, mask: [N] bool,
    cap: static output width (caller guarantees mask.sum() <= cap; rows
    beyond the count are zero).
    Returns (bins_c [F, cap] int32, grad_c [cap] f32, hess_c [cap] f32).

    The row-tile width (the Tuner's ``select.c*`` kernel variants) resolves
    from the variant registry OUTSIDE the jit boundary — it is a static arg
    of the jitted body, so resolving inside would freeze the first call's
    value into the cache. Compaction is exact at every tile width: each
    selected row is written exactly once by pass-through one-hot products.
    """
    from ..core import kernels as _kernels

    chunk = int(_kernels.active_param("select", "chunk", CHUNK))
    return _select_rows(bins_fm, grad, hess, mask, cap, interpret, chunk)


@functools.partial(jax.jit,
                   static_argnames=("cap", "interpret", "chunk"))
def _select_rows(bins_fm, grad, hess, mask, cap: int, interpret: bool = False,
                 chunk: int = CHUNK):
    f, n = bins_fm.shape
    n_pad = _round_up(max(n, 1), chunk)
    n_tiles = n_pad // chunk
    cap_pad = _round_up(cap, chunk) + chunk  # slack: every tile writes chunk
    c_pad = _round_up(f + 2, 128)            # HBM minor-dim (1,128) tiling

    m2 = jnp.pad(mask, (0, n_pad - n)).astype(jnp.float32).reshape(1, n_pad)
    bins_p = jnp.pad(bins_fm, ((0, 0), (0, n_pad - n)))
    g2 = jnp.pad(grad.astype(jnp.float32), (0, n_pad - n)).reshape(1, n_pad)
    h2 = jnp.pad(hess.astype(jnp.float32), (0, n_pad - n)).reshape(1, n_pad)

    counts = m2.reshape(n_tiles, chunk).sum(axis=1).astype(jnp.int32)
    offs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((f, chunk), lambda j, offs: (0, j)),
            pl.BlockSpec((1, chunk), lambda j, offs: (0, j)),
            pl.BlockSpec((1, chunk), lambda j, offs: (0, j)),
            pl.BlockSpec((1, chunk), lambda j, offs: (0, j)),
        ],
        out_specs=[
            # HBM explicitly: ANY may place small tiers in VMEM, where
            # dynamic slicing of the tiled memref is not lowerable; the DMA
            # engine slices the HBM case at any (unaligned, data-dependent)
            # row offset — verified bit-exact on v5e under jax 0.9.0
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        scratch_shapes=[
            pltpu.VMEM((chunk, c_pad), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out = pl.pallas_call(
        functools.partial(_select_kernel, nf=f, chunk=chunk, c_pad=c_pad),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((cap_pad, c_pad), jnp.float32),
        ],
        interpret=interpret,
        # the name a device trace shows (`%_select_rows.NN`), pinned
        name="_select_rows",
        cost_estimate=pl.CostEstimate(
            flops=2 * n_pad * chunk * (f + 3),
            bytes_accessed=bins_p.size * bins_p.dtype.itemsize
            + (f + 8) * n_pad * 4,
            transcendentals=0,
        ),
    )(offs, bins_p, g2, h2, m2)[0]
    # rows in [count+CHUNK, cap) are never written by any tile: scrub the
    # uninitialized HBM tail (recycled buffers can hold NaN/Inf bit
    # patterns, and downstream masking is multiplicative — NaN*0=NaN would
    # poison whole histograms)
    total = jnp.sum(counts)
    valid = jnp.arange(cap, dtype=jnp.int32) < total
    # feature-major views: one small XLA transpose ([cap, F] f32 ~ 0.1 ms at
    # tier caps) + lossless int cast (bin ids <= 65535 are exact in f32)
    bins_c = jnp.where(valid[None, :], out[:cap, :f].T, 0.0).astype(jnp.int32)
    return (bins_c, jnp.where(valid, out[:cap, f], 0.0),
            jnp.where(valid, out[:cap, f + 1], 0.0))


def use_select(n_rows: int = 0, interpret: bool = False) -> bool:
    """Dispatch gate: on for TPU (or interpret mode, for tests) when the
    mask width reaches MMLSPARK_TPU_SELECT_MIN_ROWS (default 500k);
    MMLSPARK_TPU_NO_PALLAS_SELECT=1 kills it.

    Earlier claim, not measured in this round: the kernel beat XLA's
    cumsum+scatter+gathers at multi-million-row widths and lost below
    ~500k (per-tile fixed costs: one synchronous DMA per tile), so small
    fits keep the XLA path. An earlier gate required uint8 bins, which the
    engine widens to int32 on device — that gate was dead; the current one
    is proven live by a dispatch-count spy in
    test_select_tier_growth_matches_xla_path (and the kernel itself now
    takes uint8 bins)."""
    if os.environ.get("MMLSPARK_TPU_NO_PALLAS_SELECT", "") not in ("", "0"):
        return False
    min_rows = int(os.environ.get("MMLSPARK_TPU_SELECT_MIN_ROWS",
                                  str(500_000)))
    if n_rows and n_rows < min_rows:
        return False
    if interpret:
        return True
    return jax.default_backend() == "tpu"
