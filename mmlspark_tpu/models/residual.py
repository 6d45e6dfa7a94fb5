"""The residual path of a decoder layer as a part of its own: hyper-connections
with the manifold constraint (mHC, arXiv:2512.24880 over arXiv:2409.19606).

A token's state is ``n`` streams of the hidden width, ``X [n, d]``, kept flat
as ``[..., n * d]`` (stream ``i`` in lanes ``i * d`` to ``(i + 1) * d``). Around
a sublayer ``F`` (``HyperConnection.pre`` / ``post``):

    u = vec(X);  m = (u phi) * rsqrt(mean(u^2) + norm_eps)
    H_pre = sigmoid(a_pre m_pre + b_pre);  H_post = 2 sigmoid(a_post m_post + b_post)
    H_res = Sinkhorn(exp(clip(a_res m_res + b_res)))    (rows, then columns, ``iters`` times)
    y = F(sum_i H_pre[i] X[i]);  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

The streams, the maps and the Sinkhorn loop are float32; ``u phi`` has
``matmul_dtype()`` operands and a float32 result. Reading and writing the
streams is memory-bound work, so it runs as two Pallas kernels where they
apply (a TPU, whole token blocks), under the names a device trace shows:

  - ``mhc_pre`` reads X once and gives ``u phi``, the sum of squares and the
    sublayer's input (it works ``H_pre`` out for its own block of tokens);
  - ``mhc_post`` reads X and y once and writes X' over X.

Between them the sigmoids and the Sinkhorn steps run as plain XLA on
``[maps, tokens]`` (the tokens last, so every pass is dense). Elsewhere, and as
the kernels' VJP (recomputed), the plain ``jax.numpy`` forms below.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

from ..obs.scopes import scope
from .module import Module, _rng_split, matmul_dtype
from .paired import pair

TOKEN_BLOCK = 128         # tokens a kernel step reads: 7.3 MB of four 3584-wide streams
_LANES = 128


def _maps_width(n: int) -> int:
    return n * n + 2 * n


def _h_pre(m_pre, ssq, pre, width: int, norm_eps: float):
    """``sigmoid(a_pre m_pre rsqrt(mean(u^2) + eps) + b_pre)``: tokens first,
    ``m_pre [N, n]``, ``ssq [N, 1]``, ``pre`` = (a_pre, b_pre[0..n-1])."""
    import jax

    inv = jax.lax.rsqrt(ssq / np.float32(width) + np.float32(norm_eps))
    return [jax.nn.sigmoid(pre[0] * m_pre[:, i:i + 1] * inv + pre[1 + i])
            for i in range(m_pre.shape[1])]


def mhc_pre_plain(x, phi, pre, n: int, norm_eps: float):
    """``x [N, n d]`` float32, ``phi [n d, n^2 + 2n]``, ``pre [1 + n]`` float32
    -> (``u phi [N, n^2 + 2n]``, sum of squares ``[N, 1]``, the sublayer's
    input ``[N, d]``), all float32."""
    import jax.numpy as jnp

    dt = getattr(jnp, matmul_dtype())
    d = x.shape[1] // n
    ssq = jnp.sum(x * x, axis=1, keepdims=True)
    m = jnp.dot(x.astype(dt), jnp.asarray(phi).astype(dt),
                preferred_element_type=jnp.float32)
    h = _h_pre(m[:, :n], ssq, pre, x.shape[1], norm_eps)
    x_in = sum(h[i] * x[:, i * d:(i + 1) * d] for i in range(n))
    return m, ssq, x_in


def mhc_post_plain(x, y, h, n: int):
    """``x [N, n d]``, ``y [N, d]``, ``h [N, n + n^2]`` (``H_post``, then
    ``H_res`` row-major) -> ``X' [N, n d]``."""
    import jax.numpy as jnp

    d = y.shape[1]
    xs = [x[:, j * d:(j + 1) * d] for j in range(n)]
    return jnp.concatenate(
        [h[:, i:i + 1] * y + sum(h[:, n + i * n + j:n + i * n + j + 1] * xs[j]
                                 for j in range(n)) for i in range(n)], axis=1)


# ---------------------------------------------------------------------------
# the Pallas kernels
# ---------------------------------------------------------------------------

def _mhc_pre_kernel(pre_ref, x_ref, phit_ref, stats_ref, xin_ref, *,
                    n: int, d: int, norm_eps: float):
    import jax
    import jax.numpy as jnp

    bt = x_ref.shape[0]
    ssq = jnp.zeros((bt, 1), jnp.float32)
    m = jnp.zeros((bt, _LANES), jnp.float32)
    for i in range(n):                      # a stream at a time: X is read once
        xi = x_ref[:, i * d:(i + 1) * d]
        ssq += jnp.sum(xi * xi, axis=1, keepdims=True)
        m += jax.lax.dot_general(
            xi.astype(phit_ref.dtype), phit_ref[:, i * d:(i + 1) * d],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    # lane n^2 + 2n carries the sum of squares beside the maps
    lane = jax.lax.broadcasted_iota(jnp.int32, (bt, _LANES), 1)
    stats_ref[...] = jnp.where(lane == _maps_width(n), ssq, m)
    h = _h_pre(m[:, :n], ssq, [pre_ref[k] for k in range(1 + n)], n * d, norm_eps)
    acc = h[0] * x_ref[:, :d]
    for i in range(1, n):
        acc += h[i] * x_ref[:, i * d:(i + 1) * d]
    xin_ref[...] = acc


def mhc_pre_pallas(x, phi, pre, n: int, norm_eps: float, interpret: bool = False):
    """The kernel form of ``mhc_pre_plain``: same arguments and results."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, nd = x.shape
    d, width, bt = nd // n, _maps_width(n), TOKEN_BLOCK
    dt = getattr(jnp, matmul_dtype())
    # phi as [maps, n d], the maps padded to a whole tile of lanes of the result
    phit = jnp.pad(jnp.asarray(phi).astype(dt).T, ((0, _LANES - width), (0, 0)))
    stats, x_in = pl.pallas_call(
        functools.partial(_mhc_pre_kernel, n=n, d=d, norm_eps=norm_eps),
        grid=(N // bt,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((bt, nd), lambda t: (t, 0)),
                  pl.BlockSpec((_LANES, nd), lambda t: (0, 0))],
        out_specs=[pl.BlockSpec((bt, _LANES), lambda t: (t, 0)),
                   pl.BlockSpec((bt, d), lambda t: (t, 0))],
        out_shape=[jax.ShapeDtypeStruct((N, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((N, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=64 * 1024 * 1024),
        name="mhc_pre",          # the name a device trace shows
        cost_estimate=pl.CostEstimate(
            flops=int(2 * N * nd * (width + 2)), transcendentals=int(N * (n + 1)),
            bytes_accessed=int(4 * N * (nd + d + _LANES))),
        interpret=interpret,
    )(jnp.asarray(pre, jnp.float32), x, phit)
    return stats[:, :width], stats[:, width:width + 1], x_in


def _mhc_post_kernel(x_ref, y_ref, h_ref, o_ref, *, n: int, d: int):
    h, y = h_ref[...], y_ref[...]
    xs = [x_ref[:, j * d:(j + 1) * d] for j in range(n)]
    for i in range(n):
        acc = h[:, i:i + 1] * y
        for j in range(n):
            k = n + i * n + j
            acc += h[:, k:k + 1] * xs[j]
        o_ref[:, i * d:(i + 1) * d] = acc


def mhc_post_pallas(x, y, h, n: int, interpret: bool = False):
    """The kernel form of ``mhc_post_plain``; X' is written over X."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, nd = x.shape
    d, bt = nd // n, TOKEN_BLOCK
    return pl.pallas_call(
        functools.partial(_mhc_post_kernel, n=n, d=d),
        grid=(N // bt,),
        in_specs=[pl.BlockSpec((bt, nd), lambda t: (t, 0)),
                  pl.BlockSpec((bt, d), lambda t: (t, 0)),
                  pl.BlockSpec((bt, h.shape[1]), lambda t: (t, 0))],
        out_specs=pl.BlockSpec((bt, nd), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=64 * 1024 * 1024),
        name="mhc_post",         # the name a device trace shows
        cost_estimate=pl.CostEstimate(
            flops=int(2 * N * nd * (n + 1)), transcendentals=0,
            bytes_accessed=int(4 * N * (2 * nd + d + h.shape[1]))),
        interpret=interpret,
    )(x, y, h)


def _kernels_apply(x, n: int) -> bool:
    import jax
    import jax.numpy as jnp

    return (jax.default_backend() == "tpu" and x.dtype == jnp.float32
            and x.shape[0] % TOKEN_BLOCK == 0 and (x.shape[1] // n) % _LANES == 0
            and _maps_width(n) < _LANES)


_pre_pair = pair(mhc_pre_pallas, mhc_pre_plain, static=2)
_post_pair = pair(mhc_post_pallas, mhc_post_plain, static=1)


def mhc_pre(x, phi, pre, n: int, norm_eps: float):
    if _kernels_apply(x, n):
        return _pre_pair(x, phi, pre, n, norm_eps)
    return mhc_pre_plain(x, phi, pre, n, norm_eps)


def mhc_post(x, y, h, n: int):
    if _kernels_apply(x, n):
        return _post_pair(x, y, h, n)
    return mhc_post_plain(x, y, h, n)


# ---------------------------------------------------------------------------
# the module
# ---------------------------------------------------------------------------

def sinkhorn(a, iters: int, eps: float):
    """``a [n, n, N]`` -> ``exp(a)`` with rows, then columns, divided by
    their sums (+ ``eps``), ``iters`` times: doubly stochastic in the limit.
    The sums are adds of slices, so a step is one elementwise pass over the
    tokens; the steps are a loop, not ``iters`` copies of it in the program."""
    import jax
    import jax.numpy as jnp

    n = a.shape[0]
    eps = np.float32(eps)

    def step(_, m):
        m = m / (sum(m[:, j:j + 1] for j in range(n)) + eps)
        return m / (sum(m[i:i + 1] for i in range(n)) + eps)

    return jax.lax.fori_loop(0, iters, step, jnp.exp(a))


class HyperConnection(Module):
    """One sublayer's hyper-connection over ``streams`` residual streams
    (module docstring). Parameters: ``phi [streams hidden, streams^2 + 2
    streams]`` (columns: pre, post, res row-major), ``alpha [3]``, ``b
    [streams^2 + 2 streams]``. ``pre(params, X)`` gives the sublayer's input
    and the coefficients ``post(X, y, coeffs)`` mixes the streams with."""

    def __init__(self, streams: int, iters: int = 20, eps: float = 1e-6,
                 clamp: Tuple[float, float] = (-30.0, 30.0),
                 norm_eps: float = 1e-6, param_dtype: str = "float32"):
        self.streams, self.iters, self.eps = streams, iters, eps
        self.clamp, self.norm_eps, self.param_dtype = tuple(clamp), norm_eps, param_dtype

    def init(self, rng, in_shape):
        import jax

        t, d = in_shape
        n, dt = self.streams, self.param_dtype
        k1, k2 = _rng_split(rng, 2)
        nd, width = n * d, _maps_width(n)
        b = jax.random.normal(k2, (width,), np.float32) * np.float32(0.5)
        b = b.at[2 * n + np.arange(n) * (n + 1)].add(2.0)     # H_res starts near I
        return {"phi": (jax.random.normal(k1, (nd, width), np.float32)
                        * np.float32(nd ** -0.5)).astype(dt),
                "alpha": np.ones((3,), dt), "b": b.astype(dt)}, (t, d)

    def coefficients(self, alpha, b, m, ssq, width: int):
        """``u phi [N, n^2 + 2n]`` and the sum of squares ``[N, 1]`` of the
        ``width`` = n d numbers of a token -> ``[N, n + n^2]``: ``H_post``,
        then ``H_res`` row-major. ``alpha [3]``, ``b [n^2 + 2n]`` float32."""
        import jax
        import jax.numpy as jnp

        n, N = self.streams, m.shape[0]
        inv = jax.lax.rsqrt(ssq.T / np.float32(width) + np.float32(self.norm_eps))
        mt, b = m.T * inv, b[:, None]                            # [maps, N]
        post = 2.0 * jax.nn.sigmoid(alpha[1] * mt[n:2 * n] + b[n:2 * n])
        res = jnp.clip(alpha[2] * mt[2 * n:] + b[2 * n:], *self.clamp)
        res = sinkhorn(res.reshape(n, n, N), self.iters, self.eps)
        return jnp.concatenate([post, res.reshape(n * n, N)], axis=0).T

    def pre(self, params, x):
        """``X [B, T, n d]`` float32 -> (the sublayer's input ``[B, T, d]``,
        the coefficients for ``post``)."""
        import jax.numpy as jnp

        B, T, nd = x.shape
        n = self.streams
        alpha = jnp.asarray(params["alpha"]).astype(jnp.float32)
        b = jnp.asarray(params["b"]).astype(jnp.float32)
        with scope("pre"):
            m, ssq, x_in = mhc_pre(x.reshape(B * T, nd), params["phi"],
                                   jnp.concatenate([alpha[:1], b[:n]]), n, self.norm_eps)
            x_in = x_in.reshape(B, T, nd // n)
        with scope("coeff"):             # the sigmoids and the Sinkhorn steps
            return x_in, self.coefficients(alpha, b, m, ssq, nd)

    def post(self, x, y, coeffs):
        """``X'``: the streams mixed by ``H_res`` plus ``H_post`` times the
        sublayer's output ``y [B, T, d]``."""
        import jax.numpy as jnp

        B, T, nd = x.shape
        with scope("post"):
            return mhc_post(x.reshape(B * T, nd), y.reshape(B * T, -1).astype(jnp.float32),
                            coeffs, self.streams).reshape(x.shape)
