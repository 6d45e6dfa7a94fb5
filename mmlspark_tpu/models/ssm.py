"""State-space layers: ``Mamba`` (Mamba-1, arXiv:2312.00752: an in-projection,
a causal depthwise convolution, the input-dependent ``delta``, ``B``, ``C``,
the selective scan, a gate, an out-projection), ``GatedMemoryUnit``
(arXiv:2507.06607: a later layer gates an earlier layer's scan output, position
by position), and the scan both are built around.

**The scan.** For a row, with ``u_t = delta_t * x_t`` and ``A`` negative::

    H_t = exp(delta_t (x) A) * H_{t-1} + u_t (x) B_t      H [channels, states] float32
    y_t = H_t C_t + D * x_t

It runs one of two ways, one arithmetic:

  - ``ssm_scan``: a Pallas kernel under that name in a device trace. A grid
    step holds the state of 1,024 channels on the chip (one ``[8, 128]``
    float32 tile a state index, in registers through the block's time steps
    and in the resident output block across the blocks of time, whose grid
    axis is ``arbitrary``); ``delta`` and ``x`` stream in a block of time at
    a time, a tile a time step (the caller lays them out ``[T, channels /
    1024, 8, 128]``); ``B_t`` and ``C_t`` are scalars from SMEM, so nothing is
    broadcast along lanes and nothing reduced across them; ``y`` is written
    once. Taken on a TPU where the channels are whole tiles of 1,024.
  - plain XLA otherwise and as the kernel's VJP (``ssm_scan_xla``): a
    ``lax.scan`` over chunks of ``SCAN_CHUNK`` steps carrying ``H``; inside a
    chunk the closed form over the cumulative ``delta A``, every exponent at
    most 0. Neither form holds ``[T, channels, states]`` for a row.

**Pieces.** A sublayer here is position-wise in its products and sequential
in its convolution (``d_conv - 1`` positions of carry) and its scan (the
state). ``over_pieces`` runs it over equal pieces of at most ``PIECE``
positions, one after the other, handing both on, so what stands between the
products (``[positions, 2 d_inner]``) is a piece's, never a row's.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence

import numpy as np

from ..obs.scopes import scope
from .module import Module, _rng_split
from .transformer import _mm_dtype, _normal

SCAN_CHUNK = 16     # time steps of the plain form's closed form
PIECE = 8192        # positions a sublayer holds between its products
LANES = 8 * 128     # channels of one float32 tile: what a kernel step holds


def over_pieces(fn, carry, arrays: Sequence[Any]):
    """``fn(carry, *pieces) -> (carry, outs)`` over equal pieces of at most
    ``PIECE`` positions of the ``[B, T, ...]`` ``arrays``, in order; ``outs``
    (an array or a tuple of them, None where a piece gives nothing) come back
    joined along the positions."""
    import jax
    import jax.numpy as jnp

    B, T = arrays[0].shape[:2]
    pieces = -(-T // PIECE)
    while T % pieces:
        pieces += 1
    if pieces == 1:
        return fn(carry, *arrays)
    cut = tuple(jnp.moveaxis(a.reshape(B, pieces, T // pieces, *a.shape[2:]), 1, 0)
                for a in arrays)
    carry, outs = jax.lax.scan(lambda c, xs: fn(c, *xs), carry, cut)
    return carry, jax.tree.map(
        lambda o: jnp.moveaxis(o, 0, 1).reshape(B, T, *o.shape[3:]), outs)


# ---------------------------------------------------------------------------
# the selective scan: plain XLA
# ---------------------------------------------------------------------------

def ssm_scan_xla(delta, x, Bm, Cm, A, D, h0, chunk: int = SCAN_CHUNK):
    """``delta``, ``x`` ``[B, T, C]``; ``Bm``, ``Cm`` ``[B, T, N]``; ``A``
    ``[C, N]`` (negative); ``D`` ``[C]``; ``h0`` ``[B, C, N]`` -> (``y [B, T,
    C]``, ``H_T [B, C, N]``), float32. Chunks of ``chunk`` steps one after the
    other; inside one, with ``S_t`` the cumulative ``delta A`` from the
    chunk's start, ``H_t = exp(S_t) H_0 + sum_{s <= t} exp(S_t - S_s) u_s (x)
    B_s``."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    delta, x, Bm, Cm = (a.astype(f32) for a in (delta, x, Bm, Cm))
    B_, T, C = delta.shape
    pad = -T % chunk
    if pad:     # a step of delta 0 leaves the state as it is
        delta, x, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                            for a in (delta, x, Bm, Cm))
    steps = (T + pad) // chunk
    earlier = np.tril(np.ones((chunk, chunk), bool))[None, :, :, None, None]

    def one(h, a):
        d, u, b, c = a                                  # [B, L, C | N]
        s = jnp.cumsum(d[..., None] * A, axis=1)        # [B, L, C, N]
        decay = jnp.exp(jnp.where(earlier, s[:, :, None] - s[:, None, :], -jnp.inf))
        hs = jnp.exp(s) * h[:, None] + jnp.einsum("btscn,bsc,bsn->btcn", decay, u, b)
        return hs[:, -1], jnp.einsum("btcn,btn->btc", hs, c)

    def chunks(a):
        return jnp.moveaxis(a.reshape(B_, steps, chunk, a.shape[-1]), 1, 0)

    h, y = jax.lax.scan(one, h0.astype(f32),
                        (chunks(delta), chunks(delta * x), chunks(Bm), chunks(Cm)))
    y = jnp.moveaxis(y, 0, 1).reshape(B_, T + pad, C)
    return (y + D.astype(f32) * x)[:, :T], h


# ---------------------------------------------------------------------------
# the selective scan: the Pallas kernel
# ---------------------------------------------------------------------------

def _scan_kernel(bc_ref, d_ref, x_ref, a_ref, skip_ref, h0_ref, y_ref, h_ref, *,
                 tb: int, n: int):
    """One block of ``tb`` time steps of 1,024 channels: the state's ``n``
    tiles ride in registers through the steps and rest in ``h_ref`` (the
    resident output block) between blocks."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_ref[...] = h0_ref[...]

    a = [a_ref[i] for i in range(n)]
    skip = skip_ref[...]

    def step(t, hs):
        d, x = d_ref[t], x_ref[t]
        dx = d * x
        at = t * (2 * n)
        # four partial sums: a chain of n dependent adds would be the step
        sums = [skip * x] + [jnp.zeros_like(x)] * 3
        new = []
        for i in range(n):
            h = jnp.exp(d * a[i]) * hs[i] + dx * bc_ref[at + i]
            sums[i % 4] = sums[i % 4] + h * bc_ref[at + n + i]
            new.append(h)
        y_ref[t] = (sums[0] + sums[1]) + (sums[2] + sums[3])
        return tuple(new)

    hs = jax.lax.fori_loop(0, tb, step, tuple(h_ref[i] for i in range(n)))
    for i in range(n):
        h_ref[i] = hs[i]


def _time_block(T: int) -> Optional[int]:
    return next((b for b in (256, 128, 64, 32, 16, 8) if T % b == 0), None)


def ssm_scan_pallas(delta, x, Bm, Cm, A, D, h0, interpret: bool = False):
    """The kernel form of ``ssm_scan_xla`` (same arguments and results):
    channels in whole tiles of 1,024, ``T`` a multiple of 8."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    B_, T, C = delta.shape
    n = A.shape[1]
    G, tb = C // LANES, _time_block(T)

    def tiles(a):                     # [B, T, C] -> a tile a time step
        return a.astype(f32).reshape(B_, T, G, 8, 128)

    def state_tiles(a):               # [..., C, N] -> [..., G, N, 8, 128]
        a = a.astype(f32).reshape(*a.shape[:-2], G, 8, 128, n)
        return jnp.moveaxis(a, -1, -3)

    # B_t then C_t, a time step after the other, as SMEM reads them
    bc = jnp.concatenate([Bm, Cm], axis=-1).astype(f32).reshape(-1)
    blocks = T // tb
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, tb=tb, n=n),
        grid=(B_, G, blocks),
        in_specs=[pl.BlockSpec((tb * 2 * n,), lambda b, g, j: (b * blocks + j,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((None, tb, None, 8, 128), lambda b, g, j: (b, j, g, 0, 0)),
                  pl.BlockSpec((None, tb, None, 8, 128), lambda b, g, j: (b, j, g, 0, 0)),
                  pl.BlockSpec((None, n, 8, 128), lambda b, g, j: (g, 0, 0, 0)),
                  pl.BlockSpec((None, 8, 128), lambda b, g, j: (g, 0, 0)),
                  pl.BlockSpec((None, None, n, 8, 128), lambda b, g, j: (b, g, 0, 0, 0))],
        out_specs=[pl.BlockSpec((None, tb, None, 8, 128), lambda b, g, j: (b, j, g, 0, 0)),
                   pl.BlockSpec((None, None, n, 8, 128), lambda b, g, j: (b, g, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B_, T, G, 8, 128), f32),
                   jax.ShapeDtypeStruct((B_, G, n, 8, 128), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ssm_scan",             # the name a device trace shows
        cost_estimate=pl.CostEstimate(
            flops=int(6 * B_ * T * C * n), transcendentals=int(B_ * T * C * n),
            bytes_accessed=int(4 * B_ * T * (3 * C + 2 * n * G))),
        interpret=interpret,
    )(bc, tiles(delta), tiles(x), state_tiles(A), D.astype(f32).reshape(G, 8, 128),
      state_tiles(h0))
    return y.reshape(B_, T, C), jnp.moveaxis(h, -3, -1).reshape(B_, C, n)


@functools.lru_cache(maxsize=None)
def _scan_kernel_vjp(interpret: bool = False):
    """The kernel with a backward pass: the plain form's, recomputed."""
    import jax

    @jax.custom_vjp
    def scan(*operands):
        return ssm_scan_pallas(*operands, interpret=interpret)

    def fwd(*operands):
        return ssm_scan_pallas(*operands, interpret=interpret), operands

    def bwd(operands, g):
        return jax.vjp(ssm_scan_xla, *operands)[1](g)

    scan.defvjp(fwd, bwd)
    return scan


def _scan_kernel_applies(delta) -> bool:
    """Whether the scan of ``delta [B, T, C]`` takes the kernel: a TPU,
    channels in whole tiles of 1,024, a length the time blocks divide."""
    import jax

    return (jax.default_backend() == "tpu" and delta.shape[2] % LANES == 0
            and _time_block(delta.shape[1]) is not None)


def ssm_scan(delta, x, Bm, Cm, A, D, h0):
    """The selective scan: the kernel where it applies, the plain form elsewhere."""
    if _scan_kernel_applies(delta):
        return _scan_kernel_vjp()(delta, x, Bm, Cm, A, D, h0)
    return ssm_scan_xla(delta, x, Bm, Cm, A, D, h0)


# ---------------------------------------------------------------------------
# modules
# ---------------------------------------------------------------------------

class Mamba(Module):
    """A Mamba-1 mixer on ``u [B, T, D]``: ``[xs, z] = u W_in``; ``xc =
    silu(b_conv + sum_j w[:, j] xs_{t - (d_conv - 1) + j})`` (depthwise,
    causal: the last tap meets the current position, zeros before the row);
    ``[dl (dt_rank), B_t, C_t] = xc W_x``; ``delta = softplus(dl W_dt +
    b_dt)``; ``A = -exp(A_log)``; the scan (module docstring); output ``(y *
    silu(z)) W_out``. Where ``hands_on``, the scan's output ``y`` (before the
    gate, with the ``D`` term) goes into the layers' carry as ``memory``
    ``[B, T, d_inner]`` in the operands' dtype, for a ``GatedMemoryUnit``.
    No bias on the projections, one on the convolution and on ``delta``."""

    def __init__(self, d_inner: int, d_state: int = 16, d_conv: int = 4,
                 dt_rank: Optional[int] = None, hands_on: bool = False,
                 param_dtype: str = "float32"):
        self.d_inner, self.d_state, self.d_conv = d_inner, d_state, d_conv
        self.dt_rank = dt_rank
        self.hands_on = self.crosses_layers = hands_on
        self.param_dtype = param_dtype

    def _rank(self, d: int) -> int:
        return self.dt_rank if self.dt_rank is not None else -(-d // 16)

    def init(self, rng, in_shape):
        import jax
        import jax.numpy as jnp

        t, d = in_shape
        c, n, r, dt = self.d_inner, self.d_state, self._rank(d), self.param_dtype
        keys = _rng_split(rng, 7)
        # delta's bias: the inverse softplus of a log-uniform draw (Mamba's init)
        step = jnp.exp(jax.random.uniform(keys[4], (c,), np.float32)
                       * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return {"w_in": _normal(keys[0], (d, 2 * c), d ** -0.5, dt),
                "conv_w": _normal(keys[1], (c, self.d_conv), self.d_conv ** -0.5, dt),
                "conv_b": _normal(keys[5], (c,), 0.02, dt),
                "w_x": _normal(keys[2], (c, r + 2 * n), c ** -0.5, dt),
                "w_dt": _normal(keys[3], (r, c), r ** -0.5, dt),
                "b_dt": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                "a_log": np.log(np.tile(np.arange(1, n + 1, dtype=np.float32), (c, 1))
                                ).astype(dt),
                "d": np.ones((c,), dt),
                "w_out": _normal(keys[6], (c, d), c ** -0.5, dt)}, (t, d)

    def _run(self, params, u):
        import jax
        import jax.numpy as jnp

        dt, f32 = _mm_dtype(), jnp.float32
        c, n, taps = self.d_inner, self.d_state, self.d_conv
        r = params["w_dt"].shape[0]

        def weight(name, to=dt):
            return jnp.asarray(params[name]).astype(to)

        A, D = -jnp.exp(weight("a_log", f32)), weight("d", f32)
        conv_w = weight("conv_w", f32)

        def piece(carry, up):
            tail, h = carry
            with scope("proj_in"):
                xz = jnp.dot(up.astype(dt), weight("w_in"), preferred_element_type=dt)
                z = xz[..., c:]
            with scope("conv"):
                run = jnp.concatenate([tail, xz[..., :c]], axis=1)
                acc = weight("conv_b", f32) + sum(
                    conv_w[:, j] * run[:, j:j + up.shape[1]].astype(f32)
                    for j in range(taps))
                xc = jax.nn.silu(acc)
                tail = run[:, run.shape[1] - (taps - 1):]
            with scope("scan"):
                dbc = jnp.dot(xc.astype(dt), weight("w_x"), preferred_element_type=f32)
                delta = jax.nn.softplus(
                    jnp.dot(dbc[..., :r].astype(dt), weight("w_dt"),
                            preferred_element_type=f32) + weight("b_dt", f32))
                y, h = ssm_scan(delta, xc, dbc[..., r:r + n], dbc[..., r + n:], A, D, h)
            with scope("gate"):
                gated = (y * jax.nn.silu(z.astype(f32))).astype(dt)
            with scope("proj_out"):
                out = jnp.dot(gated, weight("w_out"), preferred_element_type=f32)
            return (tail, h), (out, y.astype(dt) if self.hands_on else None)

        B = u.shape[0]
        start = (jnp.zeros((B, taps - 1, c), dt), jnp.zeros((B, c, n), f32))
        return over_pieces(piece, start, [u])[1]

    def apply_carry(self, params, u, carry: Dict[str, Any]):
        out, memory = self._run(params, u)
        if self.hands_on:
            carry["memory"] = memory
        return out

    def apply(self, params, x, train: bool = False):
        return self._run(params, x)[0]


class GatedMemoryUnit(Module):
    """``(m * silu(u W_1)) W_2`` on ``u [B, T, D]``: ``m [B, T, d_inner]`` is
    the ``memory`` an earlier ``Mamba(hands_on=True)`` left in the layers'
    carry, read position by position; no bias."""

    crosses_layers = True

    def __init__(self, d_inner: int, param_dtype: str = "float32"):
        self.d_inner, self.param_dtype = d_inner, param_dtype

    def init(self, rng, in_shape):
        t, d = in_shape
        k1, k2 = _rng_split(rng, 2)
        return {"w1": _normal(k1, (d, self.d_inner), d ** -0.5, self.param_dtype),
                "w2": _normal(k2, (self.d_inner, d), self.d_inner ** -0.5,
                              self.param_dtype)}, (t, d)

    def apply_carry(self, params, u, carry: Dict[str, Any]):
        import jax
        import jax.numpy as jnp

        dt, f32 = _mm_dtype(), jnp.float32
        w1 = jnp.asarray(params["w1"]).astype(dt)
        w2 = jnp.asarray(params["w2"]).astype(dt)

        def piece(_, up, mp):
            with scope("proj_in"):
                a = jnp.dot(up.astype(dt), w1, preferred_element_type=dt)
            with scope("gate"):
                gated = (mp.astype(f32) * jax.nn.silu(a.astype(f32))).astype(dt)
            with scope("proj_out"):
                return None, jnp.dot(gated, w2, preferred_element_type=f32)

        return over_pieces(piece, None, [u, carry["memory"]])[1]
