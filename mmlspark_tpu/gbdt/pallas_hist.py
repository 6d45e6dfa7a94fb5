"""Pallas TPU kernel for binned histogram accumulation.

The GBDT hot loop (reference: LGBM_BoosterUpdateOneIter's histogram build,
lightgbm/TrainUtils.scala:170-233) is a scatter-add of per-row (grad, hess,
count) triples into [F, B] bins. XLA lowers ``hist.at[idx].add(vals)`` to a
serialized sort-major scatter on TPU — correct but far off the roofline.

This kernel reformulates the scatter as a **one-hot contraction on the MXU**
over FEATURE-MAJOR inputs (bins [F, N], vals [3, N] — minor dim rows, so the
HBM arrays carry no lane padding; an [N, 28] int32 layout tiles 28 -> 128
lanes, a 4.6x HBM blowup that runs a 10M-row fit out of memory):

    hist[f, b, c] = sum_n (bins[f, n] == b) * vals[c, n]
                  = vals @ onehot_t(bins[f, :]).T          # [3, B] per feature

The transposed one-hot ([B_pad, CHUNK]: the feature row broadcast over
sublanes against a dim-0 iota) is materialized only inside VMEM, one chunk
at a time, and immediately contracted — it never exists in HBM, so HBM
traffic is exactly the input reads (bins, vals) plus one [3, F*B_pad]
accumulator. The grid is 1-D over row chunks with the accumulator block
resident in VMEM across the whole grid (standard Pallas reduction pattern);
the feature dim is never block-sliced — inputs wider than FMAX features are
split into separate pallas_call slabs on the host, bounding the accumulator
at [3, FMAX*B_pad].

Bin counts are padded to a multiple of 128 (the TPU lane width) so every
slice write is tile-aligned; features are padded to the feature-tile size.
Padded rows/features contribute zero because ``vals`` is pre-masked.

Dispatch: ``histogram.compute_histogram`` routes here when the default backend
is TPU (env ``MMLSPARK_TPU_NO_PALLAS=1`` forces the XLA path). On CPU the
kernel runs in interpreter mode for tests only.

Speed against the XLA scatter: earlier claim (an order of magnitude at
N=100k, F=32, B=256), not measured in this round. Verified on a v5e under
jax 0.9.0 (chip_smoke.py): Mosaic compiles the kernel for uint8 and int32
bins at chunks 256/512/1024, F=28, B=256, N=1M, and the sums agree with a
float64 reference (counts exactly).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Row-chunk size: bounds the one-hot VMEM tile ([CHUNK, B_pad] f32 = 256 KB at
# B_pad=128). FMAX bounds features handled per pallas_call — wider inputs are
# processed in host-side slabs so the [3, F*B_pad] accumulator stays in VMEM.
CHUNK = 512
FMAX = 64


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _hist_kernel(bins_ref, vals_ref, out_ref, *, nf: int, b_pad: int,
                 hilo: bool):
    """One row-chunk grid cell, feature-major layout.

    bins_ref: [nf, CHUNK] int (feature-major: minor dim = rows, so the HBM
    array carries no lane padding — an [N, F] layout tiles F up to 128 lanes,
    a 4.6x HBM blowup at F=28 that runs a 10M-row fit out of memory),
    vals_ref: [3, CHUNK] f32 (pre-masked channels x rows) — or, in hi/lo
    mode, [5, CHUNK] bf16 (g_hi, g_lo, h_hi, h_lo, mask),
    out_ref:  [3, nf*B_pad] f32 accumulator, VMEM-resident across the grid.

    The one-hot is built TRANSPOSED ([B_pad, CHUNK]: sublane broadcast of the
    feature row against a dim-0 iota) and contracted over rows on the MXU —
    no in-kernel transposes or minor-dim reshapes (Mosaic rejects those).

    ``hilo`` (default on — see hist_hilo() for the N-dependent
    measurements): the one-hot is EXACT in bf16 (0/1), so splitting
    grad/hess into bf16 (hi, lo) pairs turns the 3-pass f32-HIGHEST
    contraction into ONE bf16 MXU pass over 5 channels.
    """
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = vals_ref[...]                          # [3, CHUNK] f32 | [5] bf16
    chunk = vals.shape[1]
    iota0 = jax.lax.broadcasted_iota(jnp.int32, (b_pad, chunk), 0)
    for f in range(nf):                                      # static unroll
        col = bins_ref[f : f + 1, :].astype(jnp.int32)       # [1, CHUNK]
        onehot = jnp.broadcast_to(col, (b_pad, chunk)) == iota0
        if hilo:
            acc5 = jax.lax.dot_general(                      # [5, B_pad], 1 pass
                vals, onehot.astype(jnp.bfloat16),
                dimension_numbers=(((1,), (1,)), ((), ())),
                # pinned: under an ambient jax.default_matmul_precision(
                # "highest") Mosaic refuses bf16 operands ("Bad lhs type")
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)
            acc = jnp.concatenate(
                [acc5[0:1] + acc5[1:2],                      # grad = hi + lo
                 acc5[2:3] + acc5[3:4],                      # hess = hi + lo
                 acc5[4:5]], axis=0)                         # count
        else:
            acc = jax.lax.dot_general(                       # [3, B_pad] on MXU
                vals, onehot.astype(jnp.float32),
                dimension_numbers=(((1,), (1,)), ((), ())),
                # HIGHEST = full-f32 MXU passes: gradient sums feed split
                # gains, and plain bf16 rounding of vals costs ~1e-3 relative
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        out_ref[:, f * b_pad : (f + 1) * b_pad] += acc


def _hist_slab(bins_slab, vals, b_pad: int, interpret: bool, hilo: bool,
               chunk: int):
    """[Fs, N_pad] bins + [3|5, N_pad] masked vals -> [3, Fs*b_pad] sums."""
    fs, n_pad = bins_slab.shape
    n_chunks = n_pad // chunk
    nch = vals.shape[0]
    return pl.pallas_call(
        functools.partial(_hist_kernel, nf=fs, b_pad=b_pad, hilo=hilo),
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((fs, chunk), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((nch, chunk), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((3, fs * b_pad), lambda j: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((3, fs * b_pad), jnp.float32),
        interpret=interpret,
        # the name a device trace shows (`%_compute_histogram_mxu.NN`), pinned
        # so that a refactor of the jitted caller cannot rename the kernel
        name="_compute_histogram_mxu",
        cost_estimate=pl.CostEstimate(
            flops=2 * nch * n_pad * fs * b_pad,
            bytes_accessed=bins_slab.size * 4
            + vals.size * vals.dtype.itemsize
            + 3 * fs * b_pad * 4,
            transcendentals=0,
        ),
    )(bins_slab, vals)


def hist_hilo() -> bool:
    """bf16 hi/lo histogram contraction: default ON
    (MMLSPARK_TPU_HIST_EXACT=1 restores the full-f32 3-pass path).

    Speed: earlier claim, not measured in this round — the modes tie
    below ~2M rows (VPU one-hot build bound) and hi/lo wins past that,
    where the three f32-HIGHEST passes dominate.
    Precision (measured, v5e, jax 0.9.0, chip_smoke.py): at 1M rows, F=28,
    B=256 the grad/hess bin sums differ from a float64 reference by up to
    0.32 absolute in hi/lo mode and 0.004 in exact mode; counts are exact
    in both. The histogram noise is far below LightGBM's own
    quantized-training regime (8-bit gradients)."""
    return os.environ.get("MMLSPARK_TPU_HIST_EXACT", "") in ("", "0")


def compute_histogram_mxu(bins_fm, grad, hess, row_mask, num_bins: int,
                          interpret: bool = False,
                          hilo: Optional[bool] = None,
                          chunk: Optional[int] = None):
    """[F,N] feature-major int bins + per-row grad/hess + row mask ->
    [F, num_bins, 3] sums.

    Drop-in replacement for histogram.compute_histogram's XLA scatter path.
    Rows are padded to a CHUNK multiple here; callers that keep N a CHUNK
    multiple (booster.train pads once on host) make the pad a no-op.

    ``hilo`` resolves from the env OUTSIDE the jit boundary so flipping
    MMLSPARK_TPU_HIST_EXACT between calls takes effect (it is a static jit
    arg below — resolving it inside would freeze the first call's value
    into the cache). Jitted callers (the fused tree/scan bodies) resolve it
    at their own trace time. ``chunk`` (row-chunk size — the Tuner's
    ``hist.c*`` kernel variants) resolves from the variant registry the
    same way, falling back to the env-tuned module default.
    """
    if hilo is None:
        hilo = hist_hilo()
    if chunk is None:
        from ..core import kernels as _kernels

        chunk = int(_kernels.active_param("hist", "chunk", CHUNK))
    return _compute_histogram_mxu(bins_fm, grad, hess, row_mask, num_bins,
                                  interpret, hilo, chunk)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "interpret", "hilo", "chunk"))
def _compute_histogram_mxu(bins_fm, grad, hess, row_mask, num_bins: int,
                           interpret: bool, hilo: bool, chunk: int = CHUNK):
    f, n = bins_fm.shape
    b_pad = max(128, _round_up(num_bins, 128))
    n_pad = _round_up(max(n, 1), chunk)

    m = row_mask.astype(jnp.float32)
    g = (grad * m).astype(jnp.float32)
    h = (hess * m).astype(jnp.float32)
    if hilo:
        # channel-major [5, N] bf16: exact one-hot x (hi, lo) value split —
        # one bf16 MXU pass reconstructs ~17 value mantissa bits
        g_hi = g.astype(jnp.bfloat16)
        h_hi = h.astype(jnp.bfloat16)
        vals = jnp.stack([
            g_hi, (g - g_hi.astype(jnp.float32)).astype(jnp.bfloat16),
            h_hi, (h - h_hi.astype(jnp.float32)).astype(jnp.bfloat16),
            m.astype(jnp.bfloat16)], axis=0)
    else:
        # channel-major [3, N]: minor dim rows -> no lane padding (an [N, 3]
        # layout pads 3 -> 128 lanes, a 42x HBM blowup at large N)
        vals = jnp.stack([g, h, m], axis=0)
    vals = jnp.pad(vals, ((0, 0), (0, n_pad - n)))
    bins_p = jnp.pad(bins_fm, ((0, 0), (0, n_pad - n)))

    slabs = []
    for f0 in range(0, f, FMAX):
        fs = min(FMAX, f - f0)
        out = _hist_slab(bins_p[f0 : f0 + fs, :], vals, b_pad, interpret,
                         hilo, chunk)
        slabs.append(out.reshape(3, fs, b_pad))
    hist = jnp.concatenate(slabs, axis=1)        # [3, F, b_pad]
    return hist.transpose(1, 2, 0)[:, :num_bins, :]


def compute_histogram_sharded(bins_fm, grad, hess, row_mask, num_bins: int,
                              interpret: bool = False):
    """Row-sharded variant: per-shard Pallas histogram + psum over the row
    axes — the multi-chip data-parallel path (LightGBM's socket-ring
    allreduce as one XLA collective). ``bins_fm`` is feature-major [F, N]
    and must be a concrete jax.Array with a NamedSharding whose spec shards
    dim 1 (the row dim)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import shard_map_compat as shard_map

    sh = bins_fm.sharding
    mesh = sh.mesh
    row_axes = sh.spec[1]
    specs = (sh.spec, P(row_axes), P(row_axes), P(row_axes))

    # check_vma=False: pallas_call can't declare varying-mesh-axes metadata
    @functools.partial(shard_map, mesh=mesh, in_specs=specs, out_specs=P(),
                       check_vma=False)
    def _go(b, g, h, m):
        local = compute_histogram_mxu(b, g, h, m, num_bins,
                                      interpret=interpret)
        return jax.lax.psum(local, row_axes)

    return _go(bins_fm, grad, hess, row_mask)


def _row_sharded_spec(x):
    """Return True if x is a concrete feature-major [F, N] array with a
    NamedSharding that splits dim 1 (rows) over >1 device (the GBDT
    data-parallel layout)."""
    from jax.sharding import NamedSharding

    if not isinstance(x, jax.Array) or isinstance(x, jax.core.Tracer):
        return False
    sh = getattr(x, "sharding", None)
    if not isinstance(sh, NamedSharding) or len(sh.device_set) <= 1:
        return False
    spec = sh.spec
    return len(spec) > 1 and spec[1] is not None


def dispatch(bins, grad, hess, row_mask, num_bins: int):
    """Backend/sharding-aware histogram dispatch used by
    histogram.compute_histogram. Returns None when the caller should use the
    XLA scatter path (non-TPU backend, traced values, or exotic shardings
    GSPMD already partitions correctly)."""
    if not use_pallas():
        return None
    if isinstance(bins, jax.core.Tracer):
        return None  # inside someone else's jit: let GSPMD lower the scatter
    if _row_sharded_spec(bins):
        return compute_histogram_sharded(bins, grad, hess, row_mask, num_bins)
    if isinstance(bins, jax.Array) and len(bins.sharding.device_set) > 1:
        return None  # replicated/oddly-sharded multi-device input: XLA path
    return compute_histogram_mxu(bins, grad, hess, row_mask, num_bins)


def use_mxu_single_device(bins) -> bool:
    """Should a jitted caller lower its histogram through the single-device
    MXU kernel? (The fused split step's routing — kept here, next to
    dispatch(), so the backend predicates cannot drift apart.) Row-sharded
    inputs must NOT take this path OR the in-jit XLA scatter: they need
    dispatch()'s per-shard kernel + psum."""
    if not use_pallas():
        return False
    if isinstance(bins, jax.core.Tracer):
        return False
    if isinstance(bins, jax.Array) and len(bins.sharding.device_set) > 1:
        return False
    return True


def interpret_mode() -> bool:
    """MMLSPARK_TPU_PALLAS_INTERPRET=1: run the Pallas kernels (histogram,
    tier select) in interpreter mode — CPU test coverage of the MXU paths.
    Single parser so the scan path and the per-tree path cannot diverge."""
    return os.environ.get("MMLSPARK_TPU_PALLAS_INTERPRET",
                          "") not in ("", "0")


def use_pallas() -> bool:
    """True when the Pallas path should be dispatched (TPU backend, not
    disabled via MMLSPARK_TPU_NO_PALLAS)."""
    if os.environ.get("MMLSPARK_TPU_NO_PALLAS", "") not in ("", "0"):
        return False
    return jax.default_backend() == "tpu"
