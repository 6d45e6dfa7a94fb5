"""Mixture-of-Experts FFN with expert parallelism over the ``expert`` axis.

No reference counterpart (MMLSpark predates MoE); this is the expert-
parallel leg of the framework's parallelism story (dp/fsdp/tp/sp/ep/pp).
Design follows the standard switch-transformer dispatch expressed as dense
einsums so GSPMD shards it (scaling-book style — annotate, let XLA insert
the all_to_alls):

  - router: tokens [B, T, D] -> logits [B, T, E], top-1 expert per token;
  - dispatch: one-hot [B, T, E, C] capacity mask (first C tokens per expert
    keep their slot, overflow drops — switch semantics), contracted against
    tokens to form per-expert buffers [E, B, C, D];
  - expert FFN: per-expert weights W1 [E, D, H], W2 [E, H, D] applied with a
    batched einsum (leading E dim shards over ``expert`` — with the buffers
    sharded the same way, XLA inserts the dispatch/return all_to_all);
  - combine: the same mask scatters expert outputs back to token positions,
    scaled by the router probability.

``expert_shardings(mesh)`` gives the NamedShardings to place params/buffers;
the equality test (sharded == single-device) runs on an 8-device mesh.

``ExpertLayer`` is the layer a present-day sparse model asks for, and what
expert parallelism asks of a chip anyway: told how many experts there are
and which of them it holds, it routes every token over all of them (top-k,
no capacity, nothing dropped) and computes the part of the result its own
experts give. The visits to its experts are sorted by expert, go through one
grouped product for gate/up and one for down (``moe_gmm``, a Pallas kernel on
a TPU, whose first product copies its visits' rows itself, ``moe_gmm_gather``,
where two row tiles fit VMEM; ``lax.ragged_dot`` on rows XLA gathers
elsewhere), and are combined by weight into the token order (``moe_combine``,
a Pallas kernel where a layer on a TPU holds a share of the experts; a scan
over the choices elsewhere): memory and work grow with the visits, never
with tokens x experts. On one chip it
runs without an exchange; ``MoE`` keeps the mesh tests until ``ExpertLayer``
has one.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np

from ..obs.scopes import scope
from .module import Module, _rng_split, matmul_dtype


class MoE(Module):
    """Top-1 (switch) MoE FFN on [T, D] rows (batch dim added at apply)."""

    def __init__(self, num_experts: int, hidden: Optional[int] = None,
                 capacity_factor: float = 1.5):
        self.num_experts = num_experts
        self.hidden = hidden
        self.capacity_factor = capacity_factor

    def init(self, rng, in_shape):
        import jax

        t, d = in_shape
        h = self.hidden or 4 * d
        kr, k1, k2 = _rng_split(rng, 3)
        e = self.num_experts
        return {
            "router": jax.random.normal(kr, (d, e), dtype=np.float32)
            * np.float32(1.0 / math.sqrt(d)),
            "w1": jax.random.normal(k1, (e, d, h), dtype=np.float32)
            * np.float32(1.0 / math.sqrt(d)),
            "w2": jax.random.normal(k2, (e, h, d), dtype=np.float32)
            * np.float32(1.0 / math.sqrt(h)),
        }, (t, d)

    def _capacity(self, tokens: int) -> int:
        return max(1, int(math.ceil(
            tokens * self.capacity_factor / self.num_experts)))

    def apply(self, params, x, train: bool = False):
        import jax
        import jax.numpy as jnp

        B, T, D = x.shape
        E = self.num_experts
        C = self._capacity(T)
        dt = getattr(jnp, matmul_dtype())

        logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32),
                            jnp.asarray(params["router"]))
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)                  # [B, T]
        gate = jnp.max(probs, axis=-1)                       # [B, T]
        onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # [B, T, E]
        # position of each token within its expert's buffer; >=C overflows drop
        pos = jnp.cumsum(onehot, axis=1) * onehot - 1.0      # [B, T, E]
        keep = (pos >= 0) & (pos < C)
        dispatch = jax.nn.one_hot(pos.astype(jnp.int32), C,
                                  dtype=jnp.float32) * keep[..., None]
        # [B, T, E, C] x [B, T, D] -> expert buffers [E, B, C, D]
        buf = jnp.einsum("btec,btd->ebcd", dispatch, x.astype(jnp.float32))
        w1 = jnp.asarray(params["w1"]).astype(dt)
        w2 = jnp.asarray(params["w2"]).astype(dt)
        hmid = jax.nn.relu(jnp.einsum("ebcd,edh->ebch", buf.astype(dt), w1,
                                      preferred_element_type=jnp.float32))
        out_buf = jnp.einsum("ebch,ehd->ebcd", hmid.astype(dt), w2,
                             preferred_element_type=jnp.float32)
        # combine back to token positions, gate-scaled
        combined = jnp.einsum("btec,ebcd->btd", dispatch,
                              out_buf.astype(jnp.float32))
        return combined * gate[..., None]


# ---------------------------------------------------------------------------
# grouped matrix product over ragged groups of rows
# ---------------------------------------------------------------------------

GMM_TILE = (512, 512, 1024)      # rows, contraction, columns of one kernel step


def _whole_tile(dim: int, most: int) -> int:
    """The widest tile of at most ``most`` that ``dim`` is whole tiles of: a
    multiple of 128 lanes where there is one (896 for a width of 3,584), else
    ``most`` itself, which the caller then refuses."""
    if dim <= most:
        return dim
    return next((t for t in range(most, 0, -128) if dim % t == 0), most)


def _tile_groups(group_sizes, tiles: int, tm: int):
    """(each row tile's group, ``[tiles]``; the number of live tiles, ``[1]``)
    for groups of ``group_sizes`` rows, each whole tiles of ``tm``: a tile
    past the last group's rows takes the last group."""
    import jax.numpy as jnp

    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    tile_start = jnp.arange(tiles, dtype=jnp.int32) * tm
    group_of = jnp.minimum(jnp.sum(tile_start[:, None] >= ends[None, :], axis=1),
                           group_sizes.shape[0] - 1).astype(jnp.int32)
    return group_of, (ends[-1:] // tm).astype(jnp.int32)


def _gmm_kernel(group_of, active, lhs_ref, rhs_ref, out_ref, acc_scr, *, k_steps):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    del group_of
    i, kk = pl.program_id(0), pl.program_id(2)
    live = i < active[0]

    @pl.when(kk == 0)
    def _zero():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _accumulate():
        acc_scr[...] += jnp.dot(lhs_ref[...], rhs_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(kk == k_steps - 1)
    def _store():            # a tile past the last group's rows stores zeros
        out_ref[...] = acc_scr[...].astype(out_ref.dtype)


def gmm_pallas(lhs, rhs, group_sizes, out_dtype, interpret: bool = False):
    """``lhs [M, K]`` rows in consecutive groups of ``group_sizes [G]`` rows,
    group g times ``rhs[g] [K, N]`` -> ``[M, N]``. Every group's size is a
    multiple of the row tile (the caller pads each group), so a tile of rows
    belongs to one group; tiles past the last group's rows cost a step that
    computes nothing and fetches nothing new."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (M, K), (_, _, N) = lhs.shape, rhs.shape
    tm, tk, tn = (_whole_tile(d, t) for t, d in zip(GMM_TILE, (M, K, N)))
    if M % tm or K % tk or N % tn:
        raise ValueError(f"moe_gmm: {(M, K, N)} is not whole tiles of {(tm, tk, tn)}")
    group_of, active = _tile_groups(group_sizes, M // tm, tm)

    def at(i, j, kk, active):
        """(row tile, column tile, contraction step) a step reads: a tile past
        the last group's rows stays on the block fetched last, so nothing
        moves for it."""
        live = i < active[0]
        return (jnp.where(live, i, jnp.maximum(active[0] - 1, 0)),
                jnp.where(live, j, N // tn - 1), jnp.where(live, kk, K // tk - 1))

    def lhs_index(i, j, kk, group_of, active):
        i, _, kk = at(i, j, kk, active)
        return i, kk

    def rhs_index(i, j, kk, group_of, active):
        i, j, kk = at(i, j, kk, active)
        return group_of[i], kk, j

    return pl.pallas_call(
        functools.partial(_gmm_kernel, k_steps=K // tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(M // tm, N // tn, K // tk),
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((None, tk, tn), rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk, g, a: (i, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="moe_gmm",          # the name a device trace shows
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N, transcendentals=0,
            bytes_accessed=int(lhs.size * lhs.dtype.itemsize * (N // tn)
                               + (M // tm) * K * N * rhs.dtype.itemsize
                               + M * N * np.dtype(out_dtype).itemsize)),
        interpret=interpret,
    )(group_of, active, lhs, rhs)


def _gmm_ragged(lhs, rhs, group_sizes, out_dtype):
    import jax
    import jax.numpy as jnp

    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32).astype(out_dtype)


@functools.lru_cache(maxsize=None)
def _gmm_kernel_vjp(interpret: bool = False):
    """The kernel with a backward pass: the ragged product's, recomputed."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def gmm(lhs, rhs, group_sizes, out_dtype):
        return gmm_pallas(lhs, rhs, group_sizes, out_dtype, interpret)

    def fwd(lhs, rhs, group_sizes, out_dtype):
        return (gmm_pallas(lhs, rhs, group_sizes, out_dtype, interpret),
                (lhs, rhs, group_sizes))

    def bwd(out_dtype, res, g):
        lhs, rhs, group_sizes = res
        _, vjp = jax.vjp(lambda a, b: _gmm_ragged(a, b, group_sizes, out_dtype),
                         lhs, rhs)
        return (*vjp(g), None)

    gmm.defvjp(fwd, bwd)
    return gmm


def _gmm_tile_rows(x) -> int:
    """Rows every group is padded to: the kernel's row tile where the kernel
    runs (a TPU, bfloat16 operands), 1 (no padding) for the ragged product."""
    import jax
    import jax.numpy as jnp

    return GMM_TILE[0] if jax.default_backend() == "tpu" \
        and x.dtype == jnp.bfloat16 else 1


def grouped_matmul(lhs, rhs, group_sizes, out_dtype, tile_rows: int):
    if tile_rows > 1:
        return _gmm_kernel_vjp()(lhs, rhs, group_sizes, out_dtype)
    return _gmm_ragged(lhs, rhs, group_sizes, out_dtype)


# ---------------------------------------------------------------------------
# the same product with its rows gathered as they load
# ---------------------------------------------------------------------------

GATHER_VMEM = 48 * 1024 * 1024   # what a step of the gathering product may hold


def _gather_lanes(K: int) -> int:
    """Rows of 128 lanes one token's ``K`` values are laid out in for its
    copy: whole ``(8, 128)`` float32 tiles, so that a token is a slice of the
    major dimension (one row of a tiled ``[T, K]`` is no whole tile)."""
    return -(-K // 1024) * 8


def _gather_vmem_bytes(K: int, N: int) -> int:
    """VMEM a step of ``gmm_gather_pallas`` holds: the float32 copies of two
    row tiles, the tile in bfloat16 for the product, the weight and output
    blocks (two each) and the float32 accumulator."""
    tm, tk, tn = (_whole_tile(d, t) for t, d in zip(GMM_TILE, (GMM_TILE[0], K, N)))
    return (2 * tm * _gather_lanes(K) * 128 * 4 + tm * K * 2
            + 2 * tk * tn * 2 + 2 * tm * tn * 2 + tm * tn * 4)


def _gmm_gathers(backend: str, dtype, K: int, N: int) -> bool:
    """The gathering form where ``moe_gmm`` runs (a TPU, bfloat16 operands),
    its contraction is whole tiles of 128 lanes and its step fits VMEM;
    elsewhere XLA gathers the rows and ``gmm_pallas`` takes them."""
    import jax.numpy as jnp

    return (backend == "tpu" and dtype == jnp.bfloat16 and K % 128 == 0
            and _gather_vmem_bytes(K, N) <= GATHER_VMEM)


def _gather_kernel(group_of, active, real, token_hbm, rows_hbm, rhs_ref, out_ref,
                   copies, lhs, idx, sem, idx_sem, acc_scr, *, k_steps):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del group_of
    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    _, tm, lanes, _ = copies.shape
    live, slot = i < active[0], i % 2

    def fetch(t, slot):              # tile t's real rows on their way into `copies[slot]`
        index = pltpu.make_async_copy(token_hbm.at[t], idx, idx_sem.at[0])
        index.start()
        index.wait()

        def row(r, carry):
            pltpu.make_async_copy(rows_hbm.at[idx[0, r]], copies.at[slot, r],
                                  sem.at[slot]).start()
            return carry

        jax.lax.fori_loop(0, real[t], row, 0)

    @pl.when(live & (j == 0) & (kk == 0))
    def _gather():
        @pl.when(i == 0)
        def _first():
            fetch(0, 0)

        @pl.when(i + 1 < active[0])
        def _next():
            fetch(i + 1, 1 - slot)

        def wait(r, carry):
            pltpu.make_async_copy(rows_hbm.at[0], copies.at[slot, 0], sem.at[slot]).wait()
            return carry

        jax.lax.fori_loop(0, real[i], wait, 0)
        got = copies.at[slot].reshape(tm * lanes, 128)   # token r's lanes: rows r * lanes + c
        keep = jax.lax.broadcasted_iota(jnp.int32, (tm, 128), 0) < real[i]
        per_step = lhs.shape[2] // 128

        def lay_out(c, carry):       # contraction step c's lanes in the operands' dtype, pads 0
            for p in range(per_step):
                piece = got[pl.ds(c * per_step + p, tm, stride=lanes), :]
                lhs[c, :, 128 * p:128 * (p + 1)] = jnp.where(keep, piece, 0.0).astype(lhs.dtype)
            return carry

        jax.lax.fori_loop(0, k_steps, lay_out, 0)

    @pl.when(kk == 0)
    def _zero():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _accumulate():
        acc_scr[...] += jnp.dot(lhs[kk], rhs_ref[...], preferred_element_type=jnp.float32)

    @pl.when(kk == k_steps - 1)
    def _store():            # a tile past the last group's rows stores zeros
        out_ref[...] = acc_scr[...].astype(out_ref.dtype)


def gather_layout(x):
    """``x [T, K]`` as ``gmm_gather_pallas`` copies it, a token at a time:
    ``[T, lanes, 128]`` float32, each token's values whole ``(8, 128)`` tiles
    of its own (zeros past ``K``). The plain form of ``gather_layout_pallas``,
    and its VJP."""
    import jax.numpy as jnp

    T, K = x.shape
    lanes = _gather_lanes(K)
    return jnp.pad(x.astype(jnp.float32), ((0, 0), (0, lanes * 128 - K))).reshape(T, lanes, 128)


LAYOUT_TOKENS = 256              # tokens a step of `moe_gather_layout` lays out


def _layout_kernel(x_ref, o_ref, *, lanes):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    tb, K = x_ref.shape
    for c in range(lanes):       # lanes c * 128 ... of every token: row c of its tiles
        rows = pl.ds(c, tb, stride=lanes)
        if c * 128 < K:
            o_ref[rows, :] = x_ref[:, c * 128:(c + 1) * 128].astype(o_ref.dtype)
        else:
            o_ref[rows, :] = jnp.zeros((tb, 128), o_ref.dtype)


def gather_layout_pallas(x, interpret: bool = False):
    """``gather_layout(x)`` as one pass: a step reads 256 tokens' rows and
    writes each 128 lanes of them as a row of the token's own tiles (a
    strided store), so the relayout is one read of ``x`` and one write, and
    ``x`` may be the bfloat16 copy the layer makes anyway (XLA's relayout
    copy would need the float32 input written out first)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, K = x.shape
    lanes, tb = _gather_lanes(K), min(LAYOUT_TOKENS, T)
    out = pl.pallas_call(
        functools.partial(_layout_kernel, lanes=lanes),
        grid=(pl.cdiv(T, tb),),
        in_specs=[pl.BlockSpec((tb, K), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tb * lanes, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T * lanes, 128), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",),
                                             vmem_limit_bytes=64 * 1024 * 1024),
        name="moe_gather_layout",  # the name a device trace shows
        cost_estimate=pl.CostEstimate(flops=0, transcendentals=0,
                                      bytes_accessed=int(x.size * x.dtype.itemsize
                                                         + T * lanes * 128 * 4)),
        interpret=interpret,
    )(x)
    return out.reshape(T, lanes, 128)


@functools.lru_cache(maxsize=None)
def _layout_kernel_vjp(interpret: bool = False):
    """The layout kernel with a backward pass: the plain form's."""
    import jax

    @jax.custom_vjp
    def layout(x):
        return gather_layout_pallas(x, interpret)

    def fwd(x):
        return gather_layout_pallas(x, interpret), x

    def bwd(x, g):
        return jax.vjp(gather_layout, x)[1](g)

    layout.defvjp(fwd, bwd)
    return layout


def gmm_gather_pallas(rows, token, rhs, group_sizes, out_dtype, interpret: bool = False):
    """``gmm_pallas(xg, rhs, group_sizes, out_dtype)`` for ``xg[r] = x[token[r]]``
    rounded to ``rhs``'s dtype (``rows = gather_layout(x)``), zero where
    ``token[r]`` is negative, without ``xg``: a row tile's rows are copied
    from ``rows`` one token a copy into VMEM once, for all its column tiles
    and contraction steps, the next live tile's copies in flight while this
    one computes. A tile's rows that gather come first in it (each group is
    padded at its end), so a tile copies its first ``real`` rows and zeroes
    the rest. The steps, their order and the float32 accumulator are
    ``gmm_pallas``'s: every output bit is the same."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (_, lanes, _), (M,), (_, K, N) = rows.shape, token.shape, rhs.shape
    tm, tk, tn = (_whole_tile(d, t) for t, d in zip(GMM_TILE, (M, K, N)))
    if M % tm or K % tk or N % tn or tk % 128 or lanes != _gather_lanes(K):
        raise ValueError(f"moe_gmm: {(M, K, N)} is not whole tiles of {(tm, tk, tn)}")
    tiles = M // tm
    group_of, active = _tile_groups(group_sizes, tiles, tm)
    real = jnp.sum(token.reshape(tiles, tm) >= 0, axis=1).astype(jnp.int32)

    def rhs_index(i, j, kk, group_of, active, real):
        live = i < active[0]         # a tile past the last group's stays on the last block
        i = jnp.where(live, i, jnp.maximum(active[0] - 1, 0))
        return (group_of[i], jnp.where(live, kk, K // tk - 1),
                jnp.where(live, j, N // tn - 1))

    return pl.pallas_call(
        functools.partial(_gather_kernel, k_steps=K // tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles, N // tn, K // tk),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((None, tk, tn), rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk, g, a, r: (i, j)),
            scratch_shapes=[pltpu.VMEM((2, tm, lanes, 128), rows.dtype),
                            pltpu.VMEM((K // tk, tm, tk), rhs.dtype),
                            pltpu.SMEM((1, tm), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((1,)),
                            pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="moe_gmm_gather",   # the name a device trace shows
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N, transcendentals=0,
            bytes_accessed=int(M * lanes * 128 * rows.dtype.itemsize
                               + tiles * K * N * rhs.dtype.itemsize
                               + M * N * np.dtype(out_dtype).itemsize)),
        interpret=interpret,
    )(group_of, active, real, jnp.maximum(token, 0).reshape(tiles, 1, tm), rows, rhs)


def _gathered(rows, token, K: int, dtype):
    """The rows ``gmm_gather_pallas`` reads, as one array: ``xg``."""
    import jax.numpy as jnp

    x = rows.reshape(rows.shape[0], -1)[:, :K].astype(dtype)
    return jnp.where((token >= 0)[:, None], x[jnp.maximum(token, 0)], 0)


@functools.lru_cache(maxsize=None)
def _gather_kernel_vjp(interpret: bool = False):
    """The gathering kernel with a backward pass: the gather's and the ragged
    product's, recomputed."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
    def gmm(rows, token, rhs, group_sizes, out_dtype):
        return gmm_gather_pallas(rows, token, rhs, group_sizes, out_dtype, interpret)

    def fwd(rows, token, rhs, group_sizes, out_dtype):
        return (gmm_gather_pallas(rows, token, rhs, group_sizes, out_dtype, interpret),
                (rows, token, rhs, group_sizes))

    def bwd(out_dtype, res, g):
        rows, token, rhs, group_sizes = res
        _, vjp = jax.vjp(lambda r, b: _gmm_ragged(_gathered(r, token, b.shape[1], b.dtype),
                                                  b, group_sizes, out_dtype), rows, rhs)
        d_rows, d_rhs = vjp(g)
        return d_rows, None, d_rhs, None

    gmm.defvjp(fwd, bwd)
    return gmm


# ---------------------------------------------------------------------------
# the combine: every visit's row, weighted, onto its token
# ---------------------------------------------------------------------------

COMBINE_TILE = (512, 2048)       # tokens, columns of one kernel step
COMBINE_CHUNK = 128              # rows of `out` one copy brings into VMEM


def combine_xla(y, out, gate, place, mine, at):
    """``y [N, D]`` float32 plus every token's visits among this trip's rows
    (``out [rows, D]``, the padded rows ``at .. at + rows``), each row widened
    to float32 and scaled by its float32 weight: one pass over all the tokens
    a choice. ``place [N, K]`` is where each (token, choice) sits among the
    padded rows, ``mine`` whether its expert is held here, ``gate`` its weight."""
    import jax
    import jax.numpy as jnp

    rows = out.shape[0]

    def choice(y, c):           # each token's s-th choice, if it is here
        at_s, here, weight = c
        here &= (at_s >= at) & (at_s < at + rows)
        got = out[jnp.clip(at_s - at, 0, rows - 1)].astype(jnp.float32)
        return y + jnp.where(here[:, None], weight[:, None] * got, 0.0), None

    return jax.lax.scan(choice, y, (place.T, mine.T, gate.T))[0]


def _combine_tokens(N: int) -> int:
    """Tokens of one kernel step: the tile, or all of a smaller batch."""
    return min(COMBINE_TILE[0], -(-N // 8) * 8)


def _combine_kernel(lo_ref, hi_ref, token_ref, weight_ref, y_ref, out_hbm, o_ref,
                    buf, sem, plan, *, held, tm, td):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i, j = pl.program_id(0), pl.program_id(1)
    chunk, piece = COMBINE_CHUNK, min(td, 512)

    def of_expert(e, n):         # the chunks that hold this tile's run of expert e
        lo, hi = lo_ref[i * held + e], hi_ref[i * held + e]
        first = lo // chunk
        chunks = jnp.where(hi > lo, (hi - 1) // chunk - first + 1, 0)

        def note(k, n):
            plan[3 * n], plan[3 * n + 1], plan[3 * n + 2] = first + k, lo, hi
            return n + 1

        return jax.lax.fori_loop(0, chunks, note, n)

    total = jax.lax.fori_loop(0, held, of_expert, 0)

    def copy(t, slot):
        return pltpu.make_async_copy(
            out_hbm.at[plan[3 * t], :, pl.ds(pl.multiple_of(j * td, 128), td)],
            buf.at[slot], sem.at[slot])

    o_ref[...] = y_ref[...]

    @pl.when(total > 0)
    def _first():
        copy(0, 0).start()

    tokens = i * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, chunk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def step(t, carry):
        slot = t % 2

        @pl.when(t + 1 < total)
        def _next():
            copy(t + 1, 1 - slot).start()

        copy(t, slot).wait()
        c, lo, hi = plan[3 * t], plan[3 * t + 1], plan[3 * t + 2]
        row = c * chunk + lane
        token = jnp.where((row >= lo) & (row < hi), token_ref[pl.ds(c, 1), :], -1)
        hit = tokens == token                            # [tm, chunk]: row r is token t's
        weight = jnp.sum(jnp.where(hit, weight_ref[pl.ds(c, 1), :], 0.0),
                         axis=1, keepdims=True)          # float32, one term a token
        onto = jnp.where(hit, 1.0, 0.0).astype(buf.dtype)
        for col in range(0, td, piece):                  # exact: one 1 a row of `onto`
            cols = slice(col, min(col + piece, td))
            got = jnp.dot(onto, buf[slot, :, cols], preferred_element_type=jnp.float32)
            o_ref[:, cols] += weight * got
        return carry

    jax.lax.fori_loop(0, total, step, 0)


def combine_pallas(y, out, gate, visit, group, held: int, interpret: bool = False):
    """The kernel form of ``combine_xla``: a step takes a tile of tokens and a
    block of columns of ``y`` (written over ``y``), copies in the chunks of
    ``out`` that hold the tile's visits, places each chunk's rows on their
    tokens by a product with zeros and ones (exact) and adds them times their
    float32 weights: ``y`` is read and written once, a visit's row about once.
    ``visit [rows]`` is each row's place in ``gate.reshape(N * K)`` (``N * K``
    for padding), ``group [rows]`` its expert (``held`` past the last). The
    sort is stable and a token chooses an expert once, so inside an expert's
    rows the tokens ascend and a tile's visits to it are ONE run: where it
    starts and ends is looked up in the rows' (expert, token), which ascend
    through the whole trip. A token's visits are added by expert, not by
    choice; a row that is not finite reaches the tokens of its tile."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (N, D), (rows, _), K = y.shape, out.shape, gate.shape[1]
    tm, td, chunk = _combine_tokens(N), _whole_tile(D, COMBINE_TILE[1]), COMBINE_CHUNK
    tiles = -(-N // tm)
    if rows % chunk or D % td:
        raise ValueError(f"moe_combine: {(rows, D)} is not whole tiles of {(chunk, td)}")
    token = visit // K                                     # N for padding
    edges = jnp.minimum(jnp.arange(tiles + 1, dtype=jnp.int32) * tm, N)
    cuts = jnp.searchsorted(
        group * (N + 1) + token,
        (jnp.arange(held, dtype=jnp.int32) * (N + 1) + edges[:, None]).reshape(-1),
    ).astype(jnp.int32).reshape(tiles + 1, held)
    lo, hi = cuts[:-1].reshape(tiles * held), cuts[1:].reshape(tiles * held)
    weight = gate.reshape(N * K)[jnp.minimum(visit, N * K - 1)]
    most = held * ((tm + chunk - 2) // chunk + 1)        # chunks a tile's runs can touch
    whole = pl.BlockSpec((rows // chunk, chunk), lambda i, j, lo, hi: (0, 0))
    block = pl.BlockSpec((tm, td), lambda i, j, lo, hi: (i, j))
    got = pl.pallas_call(
        functools.partial(_combine_kernel, held=held, tm=tm, td=td),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles, D // td),
            in_specs=[whole, whole, block, pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=block,
            scratch_shapes=[pltpu.VMEM((2, chunk, td), out.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((3 * most,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((tiles * tm, D), jnp.float32),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="moe_combine",      # the name a device trace shows
        cost_estimate=pl.CostEstimate(
            flops=int(2 * tm * (rows + tiles * held * chunk) * D), transcendentals=0,
            bytes_accessed=int(8 * N * D + (rows + tiles * held * chunk) * D
                               * out.dtype.itemsize)),
        interpret=interpret,
    )(lo, hi, token.reshape(rows // chunk, chunk), weight.reshape(rows // chunk, chunk),
      jnp.pad(y, ((0, tiles * tm - N), (0, 0))), out.reshape(rows // chunk, chunk, D))
    return got[:N]


@functools.lru_cache(maxsize=None)
def _combine_kernel_vjp(interpret: bool = False):
    """The kernel with a backward pass: the scan's, recomputed."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
    def combine(y, out, gate, place, mine, at, visit, group, held):
        return combine_pallas(y, out, gate, visit, group, held, interpret)

    def fwd(y, out, gate, place, mine, at, visit, group, held):
        return (combine_pallas(y, out, gate, visit, group, held, interpret),
                (y, out, gate, place, mine, at))

    def bwd(held, res, g):
        y, out, gate, place, mine, at = res
        _, vjp = jax.vjp(lambda y, out, gate: combine_xla(y, out, gate, place, mine, at),
                         y, out, gate)
        return (*vjp(g), None, None, None, None, None)

    combine.defvjp(fwd, bwd)
    return combine


def _combine_kernel_applies(experts_held: int, num_experts: int, backend: str,
                            dtype) -> bool:
    """The kernel where ``moe_gmm`` runs (a TPU, bfloat16 operands) and the
    layer holds a share of the router's experts: there most of a scan pass
    over all the tokens is thrown away. A layer that holds them all needs
    every row of every pass, and keeps the scan."""
    import jax.numpy as jnp

    return backend == "tpu" and dtype == jnp.bfloat16 and experts_held < num_experts


class ExpertLayer(Module):
    """Dropless top-k expert FFN on ``[B, T, D]`` that holds ``experts_held``
    of ``num_experts`` SwiGLU experts of width ``hidden``, from
    ``first_expert`` on. The router is ``num_experts`` wide: scores
    (``scoring``: sigmoid or softmax) in float32, the ``top_k`` largest of
    score + selection bias chosen, their scores normalised over the chosen
    (``norm_topk``) and scaled by ``scale``. With ``n_group`` > 1 the choice
    is group-limited (DeepSeek-V3's ``noaux_tc``): the experts fall in
    ``n_group`` equal groups, a group scores the sum of its two largest score
    + bias, and the ``top_k`` are chosen among the ``topk_group`` best
    groups' experts alone. The result is the held experts'
    part of the sum; what the others would add is another holder's to compute.

    The first product has two forms of one result, chosen the same way
    (``_gmm_gathers``): ``moe_gmm_gather``, which copies each visit's row
    from the layer's input (laid out once by ``moe_gather_layout``) into
    VMEM itself, where ``moe_gmm`` runs and two row tiles fit VMEM; else XLA
    gathers the rows and the product takes them, which is also the kernel's
    VJP. Their outputs are the same to the last bit.

    The combine (each visit's row, widened to float32, times its float32
    weight, added to the token's float32 sum) has two forms of one arithmetic,
    chosen by what the layer sees in its own shape (``_combine_kernel_applies``):
    the kernel ``moe_combine`` where ``moe_gmm`` runs (a TPU, bfloat16
    operands) and the layer holds a share of the router's experts, else the
    scan over the choices (``combine_xla``), which is also the kernel's VJP.
    They differ only in the order a token's visits are added: by expert, by
    choice.

    Parameters: ``router [D, E]``, ``router_bias [E]``, ``w1 [held, D, 2
    hidden]`` (gate's columns first), ``w2 [held, hidden, D]``: the leaves
    ``expert_shardings`` shards by their leading dim."""

    n_group, topk_group = 1, 1           # a layer pickled before groups has neither

    def __init__(self, num_experts: int, experts_held: int, top_k: int,
                 hidden: int, scoring: str = "sigmoid", norm_topk: bool = True,
                 scale: float = 1.0, first_expert: int = 0,
                 param_dtype: str = "float32", n_group: int = 1, topk_group: int = 1):
        if scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown scoring {scoring!r}")
        if not 0 <= first_expert <= num_experts - experts_held:
            raise ValueError("the experts held are not among the router's")
        if num_experts % n_group or not 1 <= topk_group <= n_group:
            raise ValueError(f"{num_experts} experts in {n_group} groups, "
                             f"{topk_group} of them kept")
        self.n_group, self.topk_group = n_group, topk_group
        self.num_experts, self.experts_held = num_experts, experts_held
        self.top_k, self.hidden, self.scoring = top_k, hidden, scoring
        self.norm_topk, self.scale, self.first_expert = norm_topk, scale, first_expert
        self.param_dtype = param_dtype

    def init(self, rng, in_shape):
        import jax

        t, d = in_shape
        kr, kb, k1, k2 = _rng_split(rng, 4)
        dt, held, h = self.param_dtype, self.experts_held, self.hidden

        def normal(k, shape, std):
            return (jax.random.normal(k, shape, np.float32)
                    * np.float32(std)).astype(dt)

        return {"router": normal(kr, (d, self.num_experts), d ** -0.5),
                "router_bias": normal(kb, (self.num_experts,), 0.01),
                "w1": normal(k1, (held, d, 2 * h), d ** -0.5),
                "w2": normal(k2, (held, h, d), h ** -0.5)}, (t, d)

    def route(self, params, x):
        """``x [N, D]`` -> (chosen experts ``[N, k]`` int32, their weights
        ``[N, k]`` float32): the whole router, in float32."""
        import jax
        import jax.numpy as jnp

        logits = jnp.dot(x.astype(jnp.float32),
                         jnp.asarray(params["router"]).astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits) if self.scoring == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        chosen_by = s + jnp.asarray(params["router_bias"]).astype(jnp.float32)
        if self.n_group > 1:
            chosen_by = self._in_kept_groups(chosen_by)
        _, idx = jax.lax.top_k(chosen_by, self.top_k)
        g = jnp.take_along_axis(s, idx, axis=-1)
        if self.norm_topk:
            g = g / jnp.sum(g, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), g * np.float32(self.scale)

    def _in_kept_groups(self, chosen_by):
        """``chosen_by [N, E]`` with the experts outside each token's
        ``topk_group`` best groups at minus infinity."""
        import jax
        import jax.numpy as jnp

        N, E = chosen_by.shape
        grouped = chosen_by.reshape(N, self.n_group, E // self.n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)     # [N, groups]
        _, kept = jax.lax.top_k(group_score, self.topk_group)
        keep = jnp.any(kept[:, :, None] == jnp.arange(self.n_group), axis=1)
        return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(N, E)

    def apply_with_load(self, params, x, add_to=None):
        """(``[B, T, D]`` float32, ``[B, experts held]`` float32 visits);
        ``add_to`` (``[B, T, D]`` float32) is what the result is added to."""
        import jax
        import jax.numpy as jnp

        B, T, D = x.shape
        N, K, held = B * T, self.top_k, self.experts_held
        dt = getattr(jnp, matmul_dtype())
        xd = x.reshape(N, D).astype(dt)
        with scope("route"):
            idx, gate = self.route(params, x.reshape(N, D))
            local = idx - self.first_expert
            mine = (local >= 0) & (local < held)                       # [N, K]
            local = jnp.where(mine, local, held)
            hot = (local[..., None] == jnp.arange(held)).astype(jnp.int32)   # [N, K, held]
            load = hot.reshape(B, T * K, held).sum(axis=1)
            counts = load.sum(axis=0)                                  # [held]

        # the visits sorted by expert, each expert's rows padded to whole tiles
        tile = _gmm_tile_rows(xd)
        with scope("sort"):
            order = jnp.argsort(local.reshape(N * K), stable=True).astype(jnp.int32)
            rank = jnp.zeros((N * K,), jnp.int32).at[order].set(
                jnp.arange(N * K, dtype=jnp.int32))                    # place in the sort
            padded = -(-counts // tile) * tile
            p_end = jnp.cumsum(padded)
            p_start, start = p_end - padded, jnp.cumsum(counts) - counts
            start_of = jnp.concatenate([start, start[-1:]])            # the sentinel's: unused
            p_start_of = jnp.concatenate([p_start, p_start[-1:]])
            # where each (token, choice) sits among the padded rows
            place = rank.reshape(N, K) - start_of[local] + p_start_of[local]

        # the rows go through `rows` at a time: twice what the held experts
        # expect, so one trip unless the routing is very uneven (a second
        # trip costs its gathers again); a trip with nothing in it is skipped.
        # A layer that holds every expert expects every visit: `rows` is
        # `most`, one trip whatever the routing
        most = -(-(N * K + held * (tile - 1)) // tile) * tile
        expect = -(-N * K * held // self.num_experts)
        rows = min(-(-(2 * max(expect, tile) + held * (tile - 1)) // tile) * tile, most)
        trips = -(-most // rows)
        w1 = jnp.asarray(params["w1"]).astype(dt)
        w2 = jnp.asarray(params["w2"]).astype(dt)
        in_kernel = _combine_kernel_applies(held, self.num_experts,
                                            jax.default_backend(), xd.dtype)
        gathers = _gmm_gathers(jax.default_backend(), xd.dtype, D, w1.shape[2])
        laid_out = _layout_kernel_vjp()(xd) if gathers else None

        def trip(y, c):
            at = c * rows

            def run(y):
                with scope("gather"):
                    j = at + jnp.arange(rows, dtype=jnp.int32)
                    g = jnp.sum(j[:, None] >= p_end[None, :], axis=1)      # group of row j
                    gc = jnp.minimum(g, held - 1)
                    r = j - p_start[gc]
                    real = (g < held) & (r < counts[gc])
                    visit = order[jnp.where(real, start[gc] + r, 0)]
                    if gathers:          # the first product fetches its rows itself
                        token = jnp.where(real, visit // K, -1)
                    else:
                        xg = jnp.where(real[:, None], xd[visit // K], 0).astype(dt)
                with scope("experts"):
                    sizes = jnp.clip(p_end - at, 0, rows) - jnp.clip(p_start - at, 0, rows)
                    hid = _gather_kernel_vjp()(laid_out, token, w1, sizes, dt) if gathers \
                        else grouped_matmul(xg, w1, sizes, dt, tile)
                    gate_h, up_h = jnp.split(hid.astype(jnp.float32), 2, axis=-1)
                    act = (jax.nn.silu(gate_h) * up_h).astype(dt)
                    out = grouped_matmul(act, w2, sizes, dt, tile)         # [rows, D]

                with scope("combine"):
                    if not in_kernel:
                        return combine_xla(y, out, gate, place, mine, at)
                    return _combine_kernel_vjp()(
                        y, out, gate, place, mine, at,
                        jnp.where(real, visit, N * K), g, held)

            return jax.lax.cond(at < p_end[-1], run, lambda y: y, y), None

        y = jnp.zeros((N, D), jnp.float32) if add_to is None \
            else add_to.reshape(N, D).astype(jnp.float32)
        y, _ = jax.lax.scan(trip, y, jnp.arange(trips, dtype=jnp.int32))
        return y.reshape(B, T, D), load.astype(jnp.float32)

    def apply(self, params, x, train: bool = False):
        return self.apply_with_load(params, x)[0]


def expert_shardings(mesh, params):
    """Shardings pytree mirroring ``params``: expert-indexed leaves (w1/w2)
    shard their leading E dim over the 'expert' axis; the router replicates.
    Pass straight to ``jax.device_put(params, expert_shardings(mesh, params))``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def place(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("w1", "w2"):
            return NamedSharding(mesh, P("expert"))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(place, params)
