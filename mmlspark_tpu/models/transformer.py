"""The parts of a present-day causal language model, and the scorer built
from them: RMSNorm, rotary positions (plain or YaRN), three attention
modules, SwiGLU, a dropless expert layer (``moe.ExpertLayer``), a decoder
layer whose token mixer (an attention, or a state-space layer or memory unit
of ``ssm.py``) and residual path are parts of it, and ``CausalLM`` /
``causal_lm`` / ``latent_causal_lm`` / ``hybrid_causal_lm``, whose output is
one number a position (the next token's log-probability): the logits never
leave the device.

Parameters keep the dtype they are handed (``causal_lm(param_dtype=
"bfloat16")`` makes them bfloat16): a weight matrix is cast to
``matmul_dtype()`` where it is used, which is no copy when it already has it.
Norms, the router's scores, softmax and ``log_softmax`` run in float32, and so
does the residual path.

**The residual path** is a part of ``DecoderLayer``: plain, ``h = x +
F(norm(x))`` on one stream of the hidden width, or hyper-connected
(``residual.HyperConnection``): ``n`` streams a token, each sublayer reading
a learned mix of them and writing back through maps that are made doubly
stochastic by Sinkhorn steps. ``CausalLM`` widens the embedding to the
streams and sums them before the final norm.

**The three attention modules**, all causal:

  - ``GQAttention``: grouped queries (a key/value head is read by its query
    heads in place, never repeated in memory), an optional window, RMSNorm
    over each head, rotary positions over the whole head;
  - ``LatentAttention`` (MLA): queries through a low-rank bottleneck, keys
    and values decompressed from one narrow latent a token, and a rotary key
    that all heads share; a score is the sum of a head's own product and the
    shared rotary one, the value narrower than the two together;
  - ``DiffAttention`` (differential attention): heads of 64 in pairs, two
    softmaxes a pair against the pair's 128-wide values, subtracted with a
    learned ``lambda`` and RMS-normed; a window, a whole row, or (a cross
    layer) its own queries over the keys and values of an earlier layer,
    which reach it through the carry between layers (``DecoderLayer``).

Their cores run one of two ways:

  - ``attn_window`` / ``attn_full`` / ``attn_mla`` / ``attn_window_diff`` /
    ``attn_full_diff``: Pallas kernels under the
    names a device trace shows, one streaming softmax over key blocks (two a
    pair in the differential kernel). A
    sliding layer visits only the blocks its window touches, so its work and
    memory grow with ``T x window``; a full or latent layer stops at the
    diagonal; the latent kernel fetches the shared rotary key once a key
    block for all the heads of a step. Taken on a TPU for bfloat16 heads of
    128 lanes at block-aligned ``T``.
  - plain XLA otherwise (the CPU, float32 tests): a banded two-block form
    for a window, query blocks against all keys for a full or latent layer;
    none holds a ``[heads, T, T]`` tensor. It is also the kernels' VJP
    (recomputed).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..obs.scopes import scope
from .module import FunctionModel, Module, _rng_split, matmul_dtype

_NEG = -1e30          # masked score: finite, so a fully masked block stays finite
LOGITS_BLOCK = 2 ** 28    # logits the head holds at a time: 1.07 GB of float32


def _mm_dtype():
    import jax.numpy as jnp

    return getattr(jnp, matmul_dtype())


def _normal(rng, shape, std: float, dtype):
    import jax

    return (jax.random.normal(rng, shape, np.float32) * np.float32(std)).astype(dtype)


def rms_norm(x, gain, eps: float):
    """RMSNorm over the last dim in float32 (the result stays float32)."""
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return xf * inv * jnp.asarray(gain).astype(jnp.float32)


def rope_frequencies(dim: int, theta: float, scaling: Optional[Dict[str, Any]] = None):
    """(the ``dim / 2`` inverse frequencies, float64; what cos and sin are
    scaled by). ``scaling`` is a configuration's ``rope_scaling``: None or
    type ``default`` for the plain ``theta^(-2i/dim)``, ``yarn`` for YaRN
    (arXiv:2309.00071): a frequency that turns more than ``beta_fast`` times
    over the original context stays, one that turns less than ``beta_slow``
    times is divided by ``factor``, a linear ramp between."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    kind = (scaling or {}).get("type", (scaling or {}).get("rope_type", "default"))
    if kind == "default":
        return inv, 1.0
    if kind != "yarn":
        raise ValueError(f"unknown rope scaling {kind!r}")
    factor = float(scaling["factor"])
    context = float(scaling["original_max_position_embeddings"])

    def dim_of(turns: float) -> float:     # the dim that turns so often in `context`
        return dim * math.log(context / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(float(scaling.get("beta_fast", 32)))), 0)
    high = min(math.ceil(dim_of(float(scaling.get("beta_slow", 1)))), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low if high != low else 0.001), 0.0, 1.0)

    return (inv / factor * ramp + inv * (1.0 - ramp),
            _yarn_mscale(factor, float(scaling.get("mscale", 1)))
            / _yarn_mscale(factor, float(scaling.get("mscale_all_dim", 0))))


def _yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_softmax_scale(scaling: Optional[Dict[str, Any]]) -> float:
    """What a YaRN model multiplies its softmax scale by: ``m^2``, ``m = 0.1
    mscale_all_dim ln(factor) + 1`` (DeepSeek-V3's attention); 1 without."""
    if not scaling:
        return 1.0
    return _yarn_mscale(float(scaling["factor"]), float(scaling.get("mscale_all_dim", 0))) ** 2


def rotary(x, theta: float, scaling: Optional[Dict[str, Any]] = None):
    """Rotary positions over the whole head of ``[B, T, heads, hd]`` float32:
    the head's two halves rotate against each other (``rotate_half``), at
    the frequencies ``rope_frequencies`` gives."""
    import jax.numpy as jnp

    t, hd = x.shape[1], x.shape[-1]
    inv, factor = rope_frequencies(hd, theta, scaling)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang) * factor, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * factor, jnp.float32)[None, :, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# ---------------------------------------------------------------------------
# grouped-query attention: plain XLA
# ---------------------------------------------------------------------------

def _softmax_rows(s, seen):
    """Softmax over the last dim of float32 scores, keys not ``seen`` out."""
    import jax.numpy as jnp

    s = jnp.where(seen, s, _NEG)
    p = jnp.where(seen, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    return p / jnp.sum(p, axis=-1, keepdims=True)


def gqa_xla(q, k, v, window: int, out_dtype=None):
    """q ``[B, T, H, D]``, k ``[B, T, KV, D]``, v ``[B, T, KV, Dv]`` -> ``[B,
    T, H, Dv]`` (``out_dtype``; None: v's); causal, query t sees keys ``t -
    window < s <= t`` (every ``s <= t`` at window 0). Blocked over queries: a
    window reads its own and the previous block of ``window`` keys, a full
    layer a block of queries against all keys."""
    import jax
    import jax.numpy as jnp

    B, T, H, D = q.shape
    KV = k.shape[2]
    blk = window if window else min(T, 512)
    pad = -T % blk
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v))
    nb = (T + pad) // blk
    scale = np.float32(1.0 / math.sqrt(D))
    qb = q.reshape(B, nb, blk, KV, H // KV, D)

    def probs_values(qn, kk, vv, qpos, kpos):
        # qn [B, q, KV, G, D], kk / vv [B, s, KV, D]; positions are the row's own
        s = jnp.einsum("bqkgd,bskd->bkgqs", qn, kk,
                       preferred_element_type=jnp.float32) * scale
        seen = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
        if window:
            seen &= kpos[None, :] > qpos[:, None] - window
        p = _softmax_rows(s, seen)
        return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(vv.dtype), vv,
                          preferred_element_type=jnp.float32)

    first = jnp.arange(nb) * blk                  # each query block's first position
    if window:
        def band(a):       # each block of keys behind its predecessor: [B, nb, 2 blk, KV, D]
            a = a.reshape(B, nb, blk, KV, a.shape[-1])
            prev = jnp.concatenate([jnp.zeros_like(a[:, :1]), a[:, :-1]], axis=1)
            return jnp.concatenate([prev, a], axis=2)

        # the first block's predecessor lies before the row: positions below 0
        o = jax.vmap(lambda qn, kk, vv, at: probs_values(
            qn, kk, vv, at + jnp.arange(blk), at + jnp.arange(-blk, blk)),
            in_axes=(1, 1, 1, 0), out_axes=1)(qb, band(k), band(v), first)
    else:
        kpos = jnp.arange(T + pad)
        o = jax.lax.map(
            lambda a: probs_values(a[0], k, v, a[1] + jnp.arange(blk), kpos),
            (jnp.moveaxis(qb, 1, 0), first))
        o = jnp.moveaxis(o, 0, 1)
    return o.reshape(B, T + pad, H, v.shape[-1])[:, :T].astype(out_dtype or v.dtype)


# ---------------------------------------------------------------------------
# grouped-query attention: the Pallas kernel
# ---------------------------------------------------------------------------

def _attn_blocks(T: int, window: int) -> Optional[Tuple[int, int]]:
    """(query block, key block) the kernel takes at this length, or None."""
    if T % 128:
        return None
    bq = next(b for b in (512, 256, 128) if T % b == 0)
    if window:
        return min(bq, 256), 128
    return bq, bq


def _kv_steps(bq: int, bk: int, T: int, window: int) -> int:
    """Key blocks a query block may touch: all of them, or the window's band."""
    if not window:
        return T // bk
    return min(T // bk, bq // bk + -(-(window - 1) // bk))


def _first_kv(qi, bq: int, bk: int, window: int):
    """The first key block the query block ``qi`` reads."""
    import jax.numpy as jnp

    if not window:
        return 0
    return jnp.maximum(qi * bq - (window - 1), 0) // bk


def _softmax_step(s, seen, v, m_scr, l_scr, acc_scr, g: int):
    """One key block of head ``g``'s streaming softmax: scores ``s [bq, bk]``
    float32 (keys not ``seen`` out; None: all are seen) fold into the head's
    running maximum, denominator and accumulator of ``P v``."""
    import jax
    import jax.numpy as jnp

    if seen is not None:
        s = jnp.where(seen, s, _NEG)
    m_prev = m_scr[g]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new[:, :1])
    if seen is not None:
        p = jnp.where(seen, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[g] = alpha * l_scr[g] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[g] = alpha[:, :1] * acc_scr[g] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[g] = m_new


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 bq: int, bk: int, window: int, steps: int, scale: float,
                 group: int, d: int):
    """One block of queries of the ``group`` query heads that share a
    key/value head, against one block of its keys: the heads run in turn over
    the same key and value tiles (fetched once for all of them), each with
    its own running maximum, denominator and accumulator."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kb = _first_kv(qi, bq, bk, window) + j
    last = (qi * bq + bq - 1) // bk              # the block of the diagonal

    @pl.when(kb <= last)
    def _block():
        k, v = k_ref[...], v_ref[...]
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        seen = kpos <= qpos
        if window:
            seen = jnp.logical_and(seen, kpos > qpos - window)
        for g in range(group):
            q = q_ref[:, g * d:(g + 1) * d]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            _softmax_step(s, seen, v, m_scr, l_scr, acc_scr, g)

    @pl.when(j == steps - 1)
    def _store():
        for g in range(group):
            o_ref[:, g * d:(g + 1) * d] = (
                acc_scr[g] / l_scr[g][:, :1]).astype(o_ref.dtype)


def gqa_pallas(q, k, v, window: int, heads: int, kv_heads: int,
               interpret: bool = False):
    """q ``[B, T, H * D]``, k / v ``[B, T, KV * D]`` -> ``[B, T, H * D]``: a
    head is a block of 128 lanes of the projection's own layout, so nothing
    is transposed; the ``H / KV`` query heads of a key/value head lie side by
    side and go through one grid step together."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, _ = q.shape
    D = q.shape[2] // heads
    group = heads // kv_heads
    bq, bk = _attn_blocks(T, window)
    steps = _kv_steps(bq, bk, T, window)

    def kv_index(b, h, qi, j):
        # past the diagonal the index stays put: no block is fetched for a
        # step that computes nothing
        kb = jnp.minimum(_first_kv(qi, bq, bk, window) + j, (qi * bq + bq - 1) // bk)
        return b, kb, h

    kernel = functools.partial(_attn_kernel, bq=bq, bk=bk, window=window,
                               steps=steps, scale=1.0 / math.sqrt(D),
                               group=group, d=D)
    pairs = T * (min(window, T) if window else (T + 1) / 2)   # (query, key) seen
    return pl.pallas_call(
        kernel,
        grid=(B, kv_heads, T // bq, steps),
        in_specs=[pl.BlockSpec((None, bq, group * D), lambda b, h, qi, j: (b, qi, h)),
                  pl.BlockSpec((None, bk, D), kv_index),
                  pl.BlockSpec((None, bk, D), kv_index)],
        out_specs=pl.BlockSpec((None, bq, group * D), lambda b, h, qi, j: (b, qi, h)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((group, bq, 128), jnp.float32),
                        pltpu.VMEM((group, bq, 128), jnp.float32),
                        pltpu.VMEM((group, bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        # the names a device trace shows (docs/observability.md)
        name="attn_window" if window else "attn_full",
        cost_estimate=pl.CostEstimate(
            flops=int(4 * B * heads * D * pairs),
            transcendentals=int(B * heads * pairs),
            bytes_accessed=int(2 * q.size * q.dtype.itemsize
                               + 2 * k.size * k.dtype.itemsize * (T // bq))),
        interpret=interpret,
    )(q, k, v)


def _pallas_applies(q, heads: int, window: int) -> bool:
    """Whether grouped-query heads take ``gqa_pallas``: a TPU, bfloat16, a
    block-aligned ``T``, and heads of exactly 128 lanes, because a head is
    read in place as one lane tile of the projection's flat layout. A 64-wide
    head alone is half a tile: slicing it out would copy, and its product
    costs the MXU a pass of 128 anyway. Such heads go to a kernel only in
    PAIRS that fill a tile (``diff_pallas``: differential attention's ``[q1 |
    q2]``, ``[k1 | k2]``, ``[v1 | v2]``), never through this one."""
    import jax
    import jax.numpy as jnp

    return (jax.default_backend() == "tpu" and q.dtype == jnp.bfloat16
            and q.shape[2] // heads == 128
            and _attn_blocks(q.shape[1], window) is not None)


def _gqa_flat_xla(q, k, v, window: int, heads: int, kv_heads: int):
    B, T, _ = q.shape
    o = gqa_xla(q.reshape(B, T, heads, -1), k.reshape(B, T, kv_heads, -1),
                v.reshape(B, T, kv_heads, -1), window)
    return o.reshape(B, T, -1)


@functools.lru_cache(maxsize=None)
def _gqa_kernel():
    """The kernel with a backward pass: it has none of its own, so the
    gradient is the plain form's, recomputed."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
    def attend(q, k, v, window, heads, kv_heads):
        return gqa_pallas(q, k, v, window, heads, kv_heads)

    def fwd(q, k, v, window, heads, kv_heads):
        return gqa_pallas(q, k, v, window, heads, kv_heads), (q, k, v)

    def bwd(window, heads, kv_heads, res, g):
        _, vjp = jax.vjp(lambda q, k, v: _gqa_flat_xla(
            q, k, v, window, heads, kv_heads), *res)
        return vjp(g)

    attend.defvjp(fwd, bwd)
    return attend


def gq_attention(q, k, v, window: int, heads: int, kv_heads: int):
    """Causal grouped-query attention over flat heads (``[B, T, heads * D]``):
    the kernel where it applies, the plain form elsewhere."""
    if _pallas_applies(q, heads, window):
        return _gqa_kernel()(q, k, v, window, heads, kv_heads)
    return _gqa_flat_xla(q, k, v, window, heads, kv_heads)


# ---------------------------------------------------------------------------
# differential attention: two softmaxes a pair of heads, subtracted
# ---------------------------------------------------------------------------
#
# Layout, flat as the projections leave it: heads are ``PAIR / 2`` = 64 wide
# and come in pairs that fill one tile of 128 lanes: a query pair ``[q1 |
# q2]`` (``q [B, T, pairs * 128]``), a key pair ``[k1 | k2]`` and a value pair
# ``[v1 | v2]`` (``k``, ``v`` ``[B, T, kv_pairs * 128]``); the ``pairs /
# kv_pairs`` query pairs of a key pair lie side by side. ``O = softmax(q1 k1^T
# / 8) V - lam softmax(q2 k2^T / 8) V`` over the 128-wide ``V``, then RMSNorm
# over the 128 times ``gain`` (arXiv:2410.05258; ``gain`` already carries the
# ``1 - lambda_init``). ``k`` and ``v`` may be another layer's.

PAIR = 128


def diff_xla(q, k, v, lam, gain, window: int, pairs: int, kv_pairs: int, eps: float):
    """-> ``[B, T, pairs * 128]`` in q's dtype: the two softmaxes through
    ``gqa_xla`` (heads of 64 against values of 128), everything after them in
    float32."""
    import jax.numpy as jnp

    B, T, _ = q.shape
    q4 = q.reshape(B, T, pairs, 2, -1)             # a pair of any width here
    k4 = k.reshape(B, T, kv_pairs, 2, -1)
    v4 = v.reshape(B, T, kv_pairs, -1)
    o1, o2 = (gqa_xla(q4[:, :, :, i], k4[:, :, :, i], v4, window, jnp.float32)
              for i in range(2))
    o = rms_norm(o1 - lam * o2, gain, eps)
    return o.reshape(q.shape).astype(q.dtype)


def _diff_kernel(q_ref, k_ref, v_ref, p_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 bq: int, bk: int, window: int, steps: int, scale: float,
                 group: int, eps: float):
    """``_attn_kernel`` for pairs: a block of queries of the ``group`` query
    pairs that share a key pair against one block of its keys. A pair's two
    score maps contract its own 64 lanes (the other half of the query tile
    zeroed: a 64-wide product costs the MXU a pass of 128 anyway) and both go
    against the whole 128-wide value tile, each with its own running maximum,
    denominator and accumulator; ``p_ref`` row 0 is ``lam``, row 1 ``gain``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kb = _first_kv(qi, bq, bk, window) + j
    last = (qi * bq + bq - 1) // bk              # the block of the diagonal

    def pairs(masked: bool):
        k, v = k_ref[...], v_ref[...]
        seen = None
        if masked:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            seen = kpos <= qpos
            if window:
                seen = jnp.logical_and(seen, kpos > qpos - window)
        first = jax.lax.broadcasted_iota(jnp.int32, (bq, PAIR), 1) < PAIR // 2
        for g in range(group):
            q = q_ref[:, g * PAIR:(g + 1) * PAIR]
            for half in range(2):
                qh = jnp.where(first if half == 0 else jnp.logical_not(first),
                               q, jnp.zeros_like(q))
                s = jax.lax.dot_general(qh, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32) * scale
                _softmax_step(s, seen, v, m_scr, l_scr, acc_scr, 2 * g + half)

    if window:
        pl.when(kb <= last)(lambda: pairs(True))
    else:                    # blocks alike: only the diagonal's needs its mask
        pl.when(kb < last)(lambda: pairs(False))
        pl.when(kb == last)(lambda: pairs(True))

    @pl.when(j == steps - 1)
    def _store():
        lam, gain = p_ref[0:1, :], p_ref[1:2, :]
        for g in range(group):
            o = acc_scr[2 * g] / l_scr[2 * g][:, :1] \
                - lam * (acc_scr[2 * g + 1] / l_scr[2 * g + 1][:, :1])
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps) * gain
            o_ref[:, g * PAIR:(g + 1) * PAIR] = o.astype(o_ref.dtype)


def diff_pallas(q, k, v, lam, gain, window: int, pairs: int, kv_pairs: int,
                eps: float, interpret: bool = False):
    """The kernel form of ``diff_xla`` (same arguments and result): a pair
    is a block of 128 lanes of the projections' own layout, read in place,
    so another layer's ``k`` and ``v`` cost no copy."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, _ = q.shape
    group = pairs // kv_pairs
    bq, bk = _attn_blocks(T, window)
    steps = _kv_steps(bq, bk, T, window)

    def kv_index(b, h, qi, j):       # past the diagonal nothing is fetched
        kb = jnp.minimum(_first_kv(qi, bq, bk, window) + j, (qi * bq + bq - 1) // bk)
        return b, kb, h

    rows = jnp.zeros((8, PAIR), jnp.float32)
    rows = rows.at[0].set(jnp.asarray(lam, jnp.float32)).at[1].set(gain.astype(jnp.float32))
    seen = T * (min(window, T) if window else (T + 1) / 2)    # (query, key) seen
    return pl.pallas_call(
        functools.partial(_diff_kernel, bq=bq, bk=bk, window=window, steps=steps,
                          scale=1.0 / math.sqrt(PAIR // 2), group=group, eps=eps),
        grid=(B, kv_pairs, T // bq, steps),
        in_specs=[pl.BlockSpec((None, bq, group * PAIR), lambda b, h, qi, j: (b, qi, h)),
                  pl.BlockSpec((None, bk, PAIR), kv_index),
                  pl.BlockSpec((None, bk, PAIR), kv_index),
                  pl.BlockSpec((8, PAIR), lambda b, h, qi, j: (0, 0))],
        out_specs=pl.BlockSpec((None, bq, group * PAIR), lambda b, h, qi, j: (b, qi, h)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((2 * group, bq, 128), jnp.float32),
                        pltpu.VMEM((2 * group, bq, 128), jnp.float32),
                        pltpu.VMEM((2 * group, bq, PAIR), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        # the names a device trace shows (docs/observability.md)
        name="attn_window_diff" if window else "attn_full_diff",
        cost_estimate=pl.CostEstimate(
            flops=int(6 * B * pairs * PAIR * seen),
            transcendentals=int(2 * B * pairs * seen),
            bytes_accessed=int(2 * q.size * q.dtype.itemsize
                               + 2 * k.size * k.dtype.itemsize * (T // bq))),
        interpret=interpret,
    )(q, k, v, rows)


@functools.lru_cache(maxsize=None)
def _diff_kernel_vjp(interpret: bool = False):
    """The kernel with a backward pass: the plain form's, recomputed."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
    def attend(q, k, v, lam, gain, window, pairs, kv_pairs, eps):
        return diff_pallas(q, k, v, lam, gain, window, pairs, kv_pairs, eps, interpret)

    def fwd(q, k, v, lam, gain, window, pairs, kv_pairs, eps):
        return diff_pallas(q, k, v, lam, gain, window, pairs, kv_pairs, eps,
                           interpret), (q, k, v, lam, gain)

    def bwd(window, pairs, kv_pairs, eps, res, g):
        return jax.vjp(lambda *a: diff_xla(*a, window, pairs, kv_pairs, eps), *res)[1](g)

    attend.defvjp(fwd, bwd)
    return attend


def _diff_pallas_applies(q, window: int, pairs: int) -> bool:
    """A TPU, bfloat16 pairs of exactly 128 lanes, a block-aligned ``T``
    (``_pallas_applies`` says why 64-wide heads come here, in pairs)."""
    import jax
    import jax.numpy as jnp

    return (jax.default_backend() == "tpu" and q.dtype == jnp.bfloat16
            and q.shape[2] // pairs == PAIR
            and _attn_blocks(q.shape[1], window) is not None)


def diff_attention(q, k, v, lam, gain, window: int, pairs: int, kv_pairs: int,
                   eps: float):
    """Causal differential attention over flat pairs: the kernel where it
    applies, the plain form elsewhere."""
    if _diff_pallas_applies(q, window, pairs):
        return _diff_kernel_vjp()(q, k, v, lam, gain, window, pairs, kv_pairs, eps)
    return diff_xla(q, k, v, lam, gain, window, pairs, kv_pairs, eps)


# ---------------------------------------------------------------------------
# latent attention: the core over decompressed heads and one shared rotary key
# ---------------------------------------------------------------------------
#
# Layout, flat as the projections leave it: ``q [B, T, H * dq]``, a head's
# ``dn`` content lanes then its rotary lanes (already scaled: the softmax
# scale is folded into the queries); ``kv [B, T, H * (dn + dv)]``, a head's
# content keys then its values; ``kr [B, T, dq - dn]``, the rotary key all
# heads share. The kernel wants whole tiles of 128 lanes, so its caller pads
# the rotary lanes of q and kr with zeros (a 64-wide contraction costs the
# MXU a pass of 128 anyway).

def mla_xla(q, kv, kr, heads: int, dn: int):
    """-> ``[B, T, H * dv]``; causal, a block of queries against all keys:
    ``s = q_nope . k_nope + q_rope . k_r``, the second product against the
    one shared key, never repeated to the heads."""
    import jax
    import jax.numpy as jnp

    B, T, _ = q.shape
    blk = min(T, 512)
    pad = -T % blk
    if pad:
        q, kv, kr = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (q, kv, kr))
    q4 = q.reshape(B, (T + pad) // blk, blk, heads, -1)
    kv4 = kv.reshape(B, T + pad, heads, -1)
    kn, v = kv4[..., :dn], kv4[..., dn:]
    kpos = jnp.arange(T + pad)

    def block(a):
        qb, first = a                                   # [B, blk, H, dq]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb[..., :dn], kn,
                       preferred_element_type=jnp.float32) \
            + jnp.einsum("bqhd,bkd->bhqk", qb[..., dn:], kr,
                         preferred_element_type=jnp.float32)
        p = _softmax_rows(s, kpos[None, :] <= (first + jnp.arange(blk))[:, None])
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    o = jax.lax.map(block, (jnp.moveaxis(q4, 1, 0),
                            jnp.arange((T + pad) // blk) * blk))
    return jnp.moveaxis(o, 0, 1).reshape(B, T + pad, -1)[:, :T].astype(kv.dtype)


MLA_HEADS_A_STEP = 4      # heads that share one fetch of the rotary key


def _mla_kernel(q_ref, kv_ref, kr_ref, o_ref, m_scr, l_scr, acc_scr, *,
                blk: int, group: int, dn: int, dq: int, dv: int):
    """One block of queries of ``group`` heads against one block of keys:
    the shared rotary key is fetched once for all of them and joins each
    head's content key as the last lanes of one ``dq``-wide contraction."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def heads(seen):
        kr = kr_ref[...]
        for g in range(group):
            at = g * (dn + dv)
            k = jnp.concatenate([kv_ref[:, at:at + dn], kr], axis=1)
            s = jax.lax.dot_general(q_ref[:, g * dq:(g + 1) * dq], k,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            _softmax_step(s, seen, kv_ref[:, at + dn:at + dn + dv],
                          m_scr, l_scr, acc_scr, g)

    @pl.when(j < qi)
    def _below():            # every key of the block is before every query
        heads(None)

    @pl.when(j == qi)
    def _diagonal():
        heads(jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
              <= jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0))
        for g in range(group):
            o_ref[:, g * dv:(g + 1) * dv] = (
                acc_scr[g] / l_scr[g][:, :1]).astype(o_ref.dtype)


def mla_pallas(q, kv, kr, heads: int, dn: int, interpret: bool = False):
    """The kernel form of ``mla_xla`` (same arguments and result): ``dn``,
    ``dq`` and ``dv`` whole tiles of 128 lanes, query and key blocks alike."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, _ = q.shape
    dq, dv = q.shape[2] // heads, kv.shape[2] // heads - dn
    group = next(g for g in (MLA_HEADS_A_STEP, 2, 1) if heads % g == 0)
    blk = _attn_blocks(T, 0)[0]

    def key_index(b, h, qi, j):
        return b, jnp.minimum(j, qi), h      # past the diagonal nothing is fetched

    pairs = T * (T + 1) / 2
    return pl.pallas_call(
        functools.partial(_mla_kernel, blk=blk, group=group, dn=dn, dq=dq, dv=dv),
        grid=(B, heads // group, T // blk, T // blk),
        in_specs=[pl.BlockSpec((None, blk, group * dq), lambda b, h, qi, j: (b, qi, h)),
                  pl.BlockSpec((None, blk, group * (dn + dv)), key_index),
                  pl.BlockSpec((None, blk, dq - dn),
                               lambda b, h, qi, j: (b, jnp.minimum(j, qi), 0))],
        out_specs=pl.BlockSpec((None, blk, group * dv), lambda b, h, qi, j: (b, qi, h)),
        out_shape=jax.ShapeDtypeStruct((B, T, heads * dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((group, blk, 128), jnp.float32),
                        pltpu.VMEM((group, blk, 128), jnp.float32),
                        pltpu.VMEM((group, blk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="attn_mla",         # the name a device trace shows
        cost_estimate=pl.CostEstimate(
            flops=int(2 * B * heads * (dq + dv) * pairs),
            transcendentals=int(B * heads * pairs),
            bytes_accessed=int(q.size * q.dtype.itemsize * (1 + dv / dq)
                               + kv.size * kv.dtype.itemsize * (T // blk + 1) / 2)),
        interpret=interpret,
    )(q, kv, kr)


@functools.lru_cache(maxsize=None)
def _mla_kernel_vjp(interpret: bool = False):
    """The kernel with a backward pass: the plain form's, recomputed."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
    def attend(q, kv, kr, heads, dn):
        return mla_pallas(q, kv, kr, heads, dn, interpret)

    def fwd(q, kv, kr, heads, dn):
        return mla_pallas(q, kv, kr, heads, dn, interpret), (q, kv, kr)

    def bwd(heads, dn, res, g):
        return jax.vjp(lambda q, kv, kr: mla_xla(q, kv, kr, heads, dn), *res)[1](g)

    attend.defvjp(fwd, bwd)
    return attend


def _mla_pallas_applies(x, nope: int, v_dim: int) -> bool:
    """Whether a latent layer's core takes the kernel for input ``x [B, T,
    D]``: a TPU, bfloat16 operands, content and value heads of whole tiles."""
    import jax
    import jax.numpy as jnp

    return (jax.default_backend() == "tpu" and _mm_dtype() == jnp.bfloat16
            and nope % 128 == 0 and v_dim % 128 == 0
            and _attn_blocks(x.shape[1], 0) is not None)


# ---------------------------------------------------------------------------
# modules
# ---------------------------------------------------------------------------

class RMSNorm(Module):
    """RMSNorm over the last dim: float32 in the arithmetic and the result."""

    def __init__(self, eps: float = 1e-5, param_dtype: str = "float32"):
        self.eps = eps
        self.param_dtype = param_dtype

    def init(self, rng, in_shape):
        return {"scale": np.ones((in_shape[-1],), self.param_dtype)}, tuple(in_shape)

    def apply(self, params, x, train: bool = False):
        return rms_norm(x, params["scale"], self.eps)


class GQAttention(Module):
    """Causal grouped-query self-attention on ``[B, T, D]``: ``heads`` query
    heads over ``kv_heads`` key/value heads of ``head_dim``, ``window`` keys
    back (0: all of them), RMSNorm over each query and key head (``qk_norm``),
    rotary positions (``rope_theta``; None: none)."""

    def __init__(self, heads: int, kv_heads: int, head_dim: int, window: int = 0,
                 qk_norm: bool = True, rope_theta: Optional[float] = None,
                 eps: float = 1e-5, param_dtype: str = "float32"):
        if heads % kv_heads:
            raise ValueError(f"{heads} query heads over {kv_heads} key/value heads")
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.window, self.qk_norm, self.rope_theta = window, qk_norm, rope_theta
        self.eps, self.param_dtype = eps, param_dtype

    def init(self, rng, in_shape):
        t, d = in_shape
        hq, hkv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        keys = _rng_split(rng, 4)
        dt = self.param_dtype
        params = {"wq": _normal(keys[0], (d, hq), d ** -0.5, dt),
                  "wk": _normal(keys[1], (d, hkv), d ** -0.5, dt),
                  "wv": _normal(keys[2], (d, hkv), d ** -0.5, dt),
                  "wo": _normal(keys[3], (hq, d), hq ** -0.5, dt)}
        if self.qk_norm:
            params["q_norm"] = np.ones((self.head_dim,), dt)
            params["k_norm"] = np.ones((self.head_dim,), dt)
        return params, (t, d)

    def apply(self, params, x, train: bool = False):
        import jax.numpy as jnp

        dt = _mm_dtype()
        B, T, _ = x.shape
        xd = x.astype(dt)

        def project(name):
            return jnp.dot(xd, jnp.asarray(params[name]).astype(dt),
                           preferred_element_type=jnp.float32)

        def heads_of(name, n, gain):
            y = project(name).reshape(B, T, n, self.head_dim)
            if gain is not None:
                y = rms_norm(y, gain, self.eps)
            if self.rope_theta is not None:
                y = rotary(y, self.rope_theta)
            return y.astype(dt).reshape(B, T, n * self.head_dim)

        with scope("proj_in"):
            q = heads_of("wq", self.heads, params.get("q_norm"))
            k = heads_of("wk", self.kv_heads, params.get("k_norm"))
            v = project("wv").astype(dt)
        with scope("core"):
            o = gq_attention(q, k, v, self.window, self.heads, self.kv_heads)
        with scope("proj_out"):
            return jnp.dot(o, jnp.asarray(params["wo"]).astype(dt),
                           preferred_element_type=jnp.float32)


class LatentAttention(Module):
    """Causal multi-head latent attention (DeepSeek-V3's MLA) on ``[B, T,
    D]``, in the decompressed form a long pass wants: ``c_q = RMSNorm(x
    W_qa)`` (``q_rank``), a head's query ``[q_nope (nope), q_rope (rope)] =
    c_q W_qb``; ``[c_kv (kv_rank), k_r (rope)] = x W_kva``, a head's
    ``[k_nope (nope), v (v_dim)] = RMSNorm(c_kv) W_kvb``; rotary positions
    (``rope_theta``, ``rope_scaling``: YaRN) on ``q_rope`` and on ``k_r``,
    which all heads share; ``s = (q_nope . k_nope + q_rope . k_r) (nope +
    rope)^-0.5 m^2`` (``yarn_softmax_scale``); output ``(P v) W_o``."""

    def __init__(self, heads: int, q_rank: int, kv_rank: int, nope: int, rope: int,
                 v_dim: int, rope_theta: float = 10000.0,
                 rope_scaling: Optional[Dict[str, Any]] = None,
                 eps: float = 1e-6, param_dtype: str = "float32"):
        self.heads, self.q_rank, self.kv_rank = heads, q_rank, kv_rank
        self.nope, self.rope, self.v_dim = nope, rope, v_dim
        self.rope_theta, self.rope_scaling = rope_theta, rope_scaling
        self.eps, self.param_dtype = eps, param_dtype

    def init(self, rng, in_shape):
        t, d = in_shape
        h, dt = self.heads, self.param_dtype
        keys = _rng_split(rng, 5)
        return {"wq_a": _normal(keys[0], (d, self.q_rank), d ** -0.5, dt),
                "q_norm": np.ones((self.q_rank,), dt),
                "wq_b": _normal(keys[1], (self.q_rank, h * (self.nope + self.rope)),
                                self.q_rank ** -0.5, dt),
                "wkv_a": _normal(keys[2], (d, self.kv_rank + self.rope), d ** -0.5, dt),
                "kv_norm": np.ones((self.kv_rank,), dt),
                "wkv_b": _normal(keys[3], (self.kv_rank, h * (self.nope + self.v_dim)),
                                 self.kv_rank ** -0.5, dt),
                "wo": _normal(keys[4], (h * self.v_dim, d),
                              (h * self.v_dim) ** -0.5, dt)}, (t, d)

    def apply(self, params, x, train: bool = False):
        import jax.numpy as jnp

        dt = _mm_dtype()
        B, T, _ = x.shape
        h, nope, rope = self.heads, self.nope, self.rope
        kernel = _mla_pallas_applies(x, nope, self.v_dim)
        lanes = -rope % 128 if kernel else 0         # zeros after the rotary lanes

        def dot(a, name, out=jnp.float32):
            return jnp.dot(a.astype(dt), jnp.asarray(params[name]).astype(dt),
                           preferred_element_type=out)

        def positions(a):                            # [B, T, heads, rope] float32
            with scope("proj_in"):
                a = rotary(a, self.rope_theta, self.rope_scaling)
            with scope("core"):                      # laid out as the core reads it
                return jnp.pad(a, ((0, 0),) * 3 + ((0, lanes),)).astype(dt)

        # the softmax scale rides on the queries' latent: one pass over [T, q_rank]
        scale = (nope + rope) ** -0.5 * yarn_softmax_scale(self.rope_scaling)
        with scope("proj_in"):
            c_q = rms_norm(dot(x, "wq_a"), params["q_norm"], self.eps) * np.float32(scale)
            q = dot(c_q, "wq_b").reshape(B, T, h, nope + rope)
        with scope("core"):
            q_nope = q[..., :nope].astype(dt)
        q_rope = positions(q[..., nope:])
        with scope("core"):
            q = jnp.concatenate([q_nope, q_rope], axis=-1).reshape(B, T, -1)
        with scope("proj_in"):
            ckv = dot(x, "wkv_a")
        kr = positions(ckv[..., None, self.kv_rank:]).reshape(B, T, -1)
        with scope("proj_in"):
            kv = dot(rms_norm(ckv[..., :self.kv_rank], params["kv_norm"], self.eps),
                     "wkv_b", dt)                    # a head's keys, then its values
        attend = _mla_kernel_vjp() if kernel else mla_xla
        with scope("core"):
            o = attend(q, kv, kr, h, nope)
        with scope("proj_out"):
            return dot(o, "wo")


class DiffAttention(Module):
    """Causal differential attention (arXiv:2410.05258) on ``[B, T, D]``:
    ``heads`` query heads over ``kv_heads`` key/value heads of ``head_dim``
    (64 where the kernel serves), adjacent heads paired (the layout above
    ``diff_xla``), ``window`` keys back (0: all of them), biases on every
    projection, no positions.
    ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init =
    0.8 - 0.6 exp(-0.3 layer_index)``; the RMSNorm after the subtraction has
    one gain of ``2 head_dim`` and is scaled by ``1 - lambda_init``.

    Through the layers' carry: with ``hands_on`` the layer leaves its keys
    and values there (``kv``: the flat ``[B, T, kv_heads head_dim]`` arrays
    its own core reads, no copy); a ``cross`` layer has no ``W_kv`` and takes
    those in place of its own."""

    def __init__(self, heads: int, kv_heads: int, head_dim: int, layer_index: int,
                 window: int = 0, cross: bool = False, hands_on: bool = False,
                 eps: float = 1e-5, param_dtype: str = "float32"):
        if heads % 2 or kv_heads % 2 or (heads // 2) % (kv_heads // 2):
            raise ValueError(f"{heads} query heads over {kv_heads} key/value heads: "
                             f"no pairs")
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.window, self.cross, self.hands_on = window, cross, hands_on
        self.lambda_init = 0.8 - 0.6 * math.exp(-0.3 * layer_index)
        self.crosses_layers = cross or hands_on
        self.eps, self.param_dtype = eps, param_dtype

    def init(self, rng, in_shape):
        t, d = in_shape
        hq, hkv, dt = self.heads * self.head_dim, self.kv_heads * self.head_dim, \
            self.param_dtype
        keys = _rng_split(rng, 10)
        params = {"wq": _normal(keys[0], (d, hq), d ** -0.5, dt),
                  "bq": _normal(keys[1], (hq,), 0.02, dt),
                  "wo": _normal(keys[2], (hq, d), hq ** -0.5, dt),
                  "bo": _normal(keys[3], (d,), 0.02, dt),
                  "subln": np.ones((2 * self.head_dim,), dt)}
        for name, k in zip(("lq1", "lk1", "lq2", "lk2"), keys[4:8]):
            params[name] = _normal(k, (self.head_dim,), 0.1, dt)
        if not self.cross:       # a key head's columns, then a value head's
            params["wkv"] = _normal(keys[8], (d, 2 * hkv), d ** -0.5, dt)
            params["bkv"] = _normal(keys[9], (2 * hkv,), 0.02, dt)
        return params, (t, d)

    def apply_carry(self, params, x, carry: Dict[str, Any]):
        import jax.numpy as jnp

        dt, f32 = _mm_dtype(), jnp.float32
        xd = x.astype(dt)

        def affine(a, w, b):
            return jnp.dot(a, jnp.asarray(params[w]).astype(dt),
                           preferred_element_type=f32) + jnp.asarray(params[b]).astype(f32)

        def vector(name):
            return jnp.asarray(params[name]).astype(f32)

        with scope("proj_in"):
            q = affine(xd, "wq", "bq").astype(dt)
            if self.cross:
                k, v = carry["kv"]
            else:
                k, v = jnp.split(affine(xd, "wkv", "bkv").astype(dt), 2, axis=-1)
                if self.hands_on:
                    carry["kv"] = (k, v)
        with scope("core"):
            lam = jnp.exp(jnp.sum(vector("lq1") * vector("lk1"))) \
                - jnp.exp(jnp.sum(vector("lq2") * vector("lk2"))) + self.lambda_init
            o = diff_attention(q, k, v, lam, vector("subln") * (1.0 - self.lambda_init),
                               self.window, self.heads // 2, self.kv_heads // 2, self.eps)
        with scope("proj_out"):
            return affine(o, "wo", "bo")

    def apply(self, params, x, train: bool = False):
        return self.apply_carry(params, x, {})


def _by_rows(fn, x, most_tokens: int = 8192, positionwise: bool = False):
    """``fn`` over ``x [B, T, D]``, rows in equal groups of at most
    ``most_tokens`` tokens one after the other: what a sublayer holds between
    its products (float32 heads before their norm, a wide layer's hidden
    units) then never stands for the whole batch. A row longer than
    ``most_tokens`` goes through alone; where ``fn`` is ``positionwise`` (it
    treats every position by itself: a norm, a SwiGLU) such a row is cut into
    equal pieces of at most ``most_tokens`` positions, which go through as
    rows do."""
    import jax

    B, T, D = x.shape
    if positionwise and T > most_tokens:
        pieces = -(-T // most_tokens)
        while T % pieces:
            pieces += 1
        return _by_rows(fn, x.reshape(B * pieces, T // pieces, D),
                        most_tokens).reshape(B, T, -1)
    groups = -(-B // max(1, most_tokens // T))
    while B % groups:
        groups += 1
    if groups == 1:
        return fn(x)
    return jax.lax.map(fn, x.reshape(groups, B // groups, T, D)).reshape(x.shape)


class SwiGLU(Module):
    """``W_down(silu(W_gate x) * W_up x)`` over the last dim, gate and up as
    one ``[D, 2 hidden]`` matrix (the gate's columns first)."""

    def __init__(self, hidden: int, param_dtype: str = "float32"):
        self.hidden = hidden
        self.param_dtype = param_dtype

    def init(self, rng, in_shape):
        d = in_shape[-1]
        k1, k2 = _rng_split(rng, 2)
        return {"w_gate_up": _normal(k1, (d, 2 * self.hidden), d ** -0.5,
                                     self.param_dtype),
                "w_down": _normal(k2, (self.hidden, d), self.hidden ** -0.5,
                                  self.param_dtype)}, tuple(in_shape)

    def apply(self, params, x, train: bool = False):
        import jax
        import jax.numpy as jnp

        dt = _mm_dtype()
        w1 = jnp.asarray(params["w_gate_up"]).astype(dt)
        w2 = jnp.asarray(params["w_down"]).astype(dt)

        # the hidden pre-activations leave the product in the operands' dtype
        # (accumulated in float32): a float32 copy of them would be the
        # largest array of a wide layer
        hid = jnp.dot(x.astype(dt), w1, preferred_element_type=dt)
        gate, up = jnp.split(hid.astype(jnp.float32), 2, axis=-1)
        return jnp.dot((jax.nn.silu(gate) * up).astype(dt), w2,
                       preferred_element_type=jnp.float32)


def _norm(kind: str, eps: float, param_dtype: str) -> Module:
    """A sublayer's norm: ``"rms"`` (a gain) or ``"layer"`` (mean, gain and
    bias), float32 in the arithmetic and, on float32 input, the result."""
    if kind == "rms":
        return RMSNorm(eps, param_dtype)
    if kind == "layer":
        from .attention import LayerNorm

        return LayerNorm(eps, param_dtype)
    raise ValueError(f"unknown norm {kind!r}")


class DecoderLayer(Module):
    """Two sublayers, a token mixer and an FFN: a ``SwiGLU`` (``mlp``), or an
    ``ExpertLayer`` (``moe``) beside a shared ``SwiGLU`` (``shared``), each
    behind its norm (``norm``: RMSNorm, or LayerNorm with a bias) and inside
    the layer's residual path, which is a part of the layer.

    **The mixer** (the first argument; its part, its norm ``<mixer>_norm``
    and its scope are named by ``mixer``) is one of five: ``GQAttention`` or
    ``LatentAttention`` (``attn``), ``DiffAttention`` (``attn``: window,
    full, or cross over another layer's keys and values), ``ssm.Mamba``
    (``ssm``), ``ssm.GatedMemoryUnit`` (``gmu``).

    **The carry between layers.** ``apply_with_load`` takes, beside ``x``, a
    small mapping the model's loop over layers hands from layer to layer. A
    mixer that ``crosses_layers`` gets it (``apply_carry(params, u, carry)``)
    and may write it (``memory``: a ``Mamba``'s scan output; ``kv``: a
    ``DiffAttention``'s keys and values) or read what an earlier layer wrote
    (``GatedMemoryUnit``, a cross ``DiffAttention``). Such a mixer sees the
    whole batch at once (what it writes must outlive the layer, so no loop
    over rows may hold it) and bounds its own memory (``ssm.over_pieces``).
    Every other mixer runs under ``_by_rows`` and never sees the carry.

    The residual path:

      - plain (``hyper`` None), on ``x [B, T, D]``: ``h = x + Mixer(norm(x));
        x' = h + FFN(norm(h))``;
      - hyper-connected (``hyper`` a ``residual.HyperConnection``, the parts
        ``attn_hc`` and ``mlp_hc``), on ``X [B, T, streams D]``: a sublayer
        reads ``RMSNorm(sum_i H_pre[i] X[i])`` and its output ``y`` goes back
        as ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``; the expert
        layer's sum (``add_to`` the shared expert's) is such a ``y``.

    ``apply_with_load`` also returns the expert layer's ``[B, experts held]``
    visit counts (None for a dense layer)."""

    mixer = "attn"           # a layer pickled before the other mixers has this one

    def __init__(self, attn: Module, mlp: Optional[SwiGLU] = None,
                 moe: Optional[Module] = None, shared: Optional[SwiGLU] = None,
                 eps: float = 1e-5, param_dtype: str = "float32",
                 hyper: Optional[Module] = None, norm: str = "rms",
                 mixer: str = "attn"):
        if (mlp is None) == (moe is None):
            raise ValueError("a layer has a dense MLP or an expert layer, one of them")
        self.mixer = mixer
        self.parts: List[Tuple[str, Module]] = [
            (mixer + "_norm", _norm(norm, eps, param_dtype)), (mixer, attn),
            ("mlp_norm", _norm(norm, eps, param_dtype))]
        self.parts += [(n, m) for n, m in (("mlp", mlp), ("moe", moe),
                                           ("shared", shared)) if m is not None]
        if hyper is not None:        # one module, a set of parameters a sublayer
            self.parts += [("attn_hc", hyper), ("mlp_hc", hyper)]

    def init(self, rng, in_shape):
        keys = _rng_split(rng, len(self.parts))
        return {n: m.init(k, in_shape)[0]
                for (n, m), k in zip(self.parts, keys)}, tuple(in_shape)

    def apply_with_load(self, params, x, carry: Optional[Dict[str, Any]] = None):
        part = dict(self.parts)
        mixer = self.mixer

        def apply(name, xc):
            with scope(name):
                return part[name].apply(params[name], xc)

        def normed(name, norm):          # the sublayer behind its norm
            return lambda xc: part[name].apply(params[name], apply(norm, xc))

        if "attn_hc" in part:
            return self._hyper_connected(part, params, x, normed)

        def sublayer(name, norm):
            def run(xc):
                return xc + normed(name, norm)(xc)
            return run

        # a part's scope is pushed around its `_by_rows`, so the loop's own
        # slicing and stacking is counted with the part it serves
        with scope(mixer):
            if getattr(part[mixer], "crosses_layers", False):
                h = x + part[mixer].apply_carry(params[mixer],
                                                apply(mixer + "_norm", x), carry)
            else:
                h = _by_rows(sublayer(mixer, mixer + "_norm"), x)
        if "mlp" in part:
            with scope("mlp"):
                return _by_rows(sublayer("mlp", "mlp_norm"), h, positionwise=True), None
        hn = apply("mlp_norm", h)
        if "shared" in part:
            h = h + apply("shared", hn)
        with scope("moe"):
            return part["moe"].apply_with_load(params["moe"], hn, add_to=h)

    def _hyper_connected(self, part, params, x, normed):
        T = x.shape[1]
        hc = part["attn_hc"]
        with scope("attn_hc"):
            x_in, coeffs = hc.pre(params["attn_hc"], x)
        # attention needs a row's every key: rows one after the other, whole
        with scope("attn"):
            y = _by_rows(normed("attn", "attn_norm"), x_in, max(T, 8192))
        with scope("attn_hc"):
            x = hc.post(x, y, coeffs)
        with scope("mlp_hc"):
            x_in, coeffs = hc.pre(params["mlp_hc"], x)
        if "mlp" in part:
            with scope("mlp"):
                y = _by_rows(normed("mlp", "mlp_norm"), x_in, positionwise=True)
            with scope("mlp_hc"):
                return hc.post(x, y, coeffs), None
        with scope("mlp_norm"):
            hn = part["mlp_norm"].apply(params["mlp_norm"], x_in)
        y = None
        if "shared" in part:
            with scope("shared"):
                y = part["shared"].apply(params["shared"], hn)
        with scope("moe"):
            y, load = part["moe"].apply_with_load(params["moe"], hn, add_to=y)
        with scope("mlp_hc"):
            return hc.post(x, y, coeffs), load

    def apply(self, params, x, train: bool = False):
        return self.apply_with_load(params, x)[0]


class CausalLM(Module):
    """Token ids ``[B, T]`` -> the next token's log-probability at every
    position, ``[B, T]`` float32: ``out[t] = log_softmax(logits_t)[id_{t+1}]``,
    the last position's target the pad id. The logits exist a block of
    positions at a time (at most ``LOGITS_BLOCK`` numbers: a whole row where
    the vocabulary lets it), on the device, and are never an output.

    With ``streams`` > 1 (layers with a hyper-connected residual path) the
    state between layers is ``[B, T, streams hidden]``: every stream starts as
    a copy of the embedding, and their sum goes into the final norm.

    **The carry between layers.** Beside ``h`` the loop over layers hands on
    one small mapping, empty at layer 0, which a layer's mixer may write and
    a later one read (``DecoderLayer``): a scan's output as ``memory``, a
    layer's keys and values as ``kv``. It lives for one call and is no output.

    ``norm`` chooses the final norm (``"rms"`` or ``"layer"``); with ``tied``
    the head is the embedding table transposed and ``params`` has no ``head``.

    A container for ``DNNModel``'s ``fetchDict``: the node ``expert_load``
    is ``[B, sparse layers, experts held]``, the visits each held expert took
    from that row's positions (float32: what the routing counted)."""

    is_container = True
    LOAD = "expert_load"
    tied = False             # a model pickled before the tied head has none

    def __init__(self, vocab_size: int, hidden: int, layers: Sequence[DecoderLayer],
                 pad_id: int = 0, eps: float = 1e-5, param_dtype: str = "float32",
                 streams: int = 1, norm: str = "rms", tied: bool = False):
        self.vocab_size, self.hidden, self.pad_id = vocab_size, hidden, pad_id
        self.layers = list(layers)
        self.final_norm = _norm(norm, eps, param_dtype)
        self.param_dtype = param_dtype
        self.streams = streams
        self.tied = tied

    def init(self, rng, in_shape):
        (t,) = in_shape
        keys = _rng_split(rng, len(self.layers) + 2)
        # a tied table is the head too: its logits are of order 1 at this scale
        params: Dict[str, Any] = {
            "embed": {"table": _normal(keys[0], (self.vocab_size, self.hidden),
                                       self.hidden ** -0.5 if self.tied else 1.0,
                                       self.param_dtype)},
            "final_norm": self.final_norm.init(None, (t, self.hidden))[0]}
        if not self.tied:
            params["head"] = {"kernel": _normal(keys[1], (self.hidden, self.vocab_size),
                                                self.hidden ** -0.5, self.param_dtype)}
        for i, (layer, k) in enumerate(zip(self.layers, keys[2:])):
            params[f"layer{i}"] = layer.init(k, (t, self.hidden))[0]
        return params, (t,)

    def layer_paths(self, prefix: str = "") -> List[str]:
        return [prefix + self.LOAD] + [f"{prefix}layer{i}" for i in range(len(self.layers))]

    def _log_probs(self, params, x, ids):
        import jax
        import jax.numpy as jnp

        dt = _mm_dtype()
        # [hidden, vocab], or the tied table [vocab, hidden] contracted in place
        head = jnp.asarray(params["embed"]["table"] if self.tied
                           else params["head"]["kernel"]).astype(dt)
        over = (((1,), (1 if self.tied else 0,)), ((), ()))
        target = jnp.concatenate(
            [ids[:, 1:], jnp.full_like(ids[:, :1], self.pad_id)], axis=1)

        def row(a):
            xr, tr = a
            xn = self.final_norm.apply(params["final_norm"], xr).astype(dt)
            logits = jax.lax.dot_general(xn, head, over,
                                         preferred_element_type=jnp.float32)
            picked = jnp.take_along_axis(logits, tr[:, None], axis=-1)[:, 0]
            return picked - jax.nn.logsumexp(logits, axis=-1)

        # a row in equal pieces whose logits are at most LOGITS_BLOCK numbers
        B, T, D = x.shape
        pieces = -(-T // max(1, LOGITS_BLOCK // self.vocab_size))
        while T % pieces:
            pieces += 1
        return jax.lax.map(row, (x.reshape(B * pieces, T // pieces, D),
                                 target.reshape(B * pieces, T // pieces))).reshape(B, T)

    def apply(self, params, x, train: bool = False,
              taps: Optional[Set[str]] = None,
              taps_out: Optional[Dict[str, Any]] = None,
              stats_out: Optional[Dict[str, Any]] = None, _prefix: str = ""):
        import jax.numpy as jnp

        ids = x.astype(jnp.int32)
        with scope("embed"):
            h = jnp.take(jnp.asarray(params["embed"]["table"]), ids, axis=0
                         ).astype(jnp.float32)
            if self.streams > 1:
                h = jnp.tile(h, (1, 1, self.streams))
        loads = []
        carry: Dict[str, Any] = {}          # what a layer hands a later one
        for i, layer in enumerate(self.layers):
            with scope(f"layer{i}"):
                h, load = layer.apply_with_load(params[f"layer{i}"], h, carry)
            if load is not None:
                loads.append(load)
            if taps and taps_out is not None and f"{_prefix}layer{i}" in taps:
                taps_out[f"{_prefix}layer{i}"] = h
        if taps and taps_out is not None and _prefix + self.LOAD in taps:
            if not loads:
                raise KeyError("expert_load: the model has no expert layer")
            taps_out[_prefix + self.LOAD] = jnp.stack(loads, axis=1)
        with scope("head"):          # the final norm and the loop over the logits
            if self.streams > 1:
                h = h.reshape(*h.shape[:2], self.streams, self.hidden).sum(axis=2)
            return self._log_probs(params, h, ids)


def causal_lm(seq_len: int, vocab_size: int, hidden: int, heads: int,
              kv_heads: int, head_dim: int, windows: Sequence[int],
              sparse: Sequence[bool], dense_hidden: int, expert_hidden: int,
              num_experts: int, experts_held: int, top_k: int,
              first_expert: int = 0, scoring: str = "sigmoid",
              norm_topk: bool = True, scale: float = 1.0,
              shared_experts: int = 1, rope_theta: float = 1e6,
              rope_layers: str = "sliding", qk_norm: bool = True,
              eps: float = 1e-5, pad_id: int = 0,
              param_dtype: str = "float32", seed: int = 0,
              init: bool = True) -> FunctionModel:
    """A decoder-only scorer as a FunctionModel: layer i attends
    ``windows[i]`` keys back (0: full) and has an expert layer where
    ``sparse[i]``, else a dense SwiGLU. ``rope_layers``: which layers get
    rotary positions (``"sliding"``, ``"all"`` or ``"none"``). ``init=False``
    leaves ``params`` empty for a caller that brings its own
    (``dataclasses.replace(model, params=...)``)."""
    import jax

    from .moe import ExpertLayer

    if len(windows) != len(sparse):
        raise ValueError("windows and sparse name the same layers")
    layers = []
    for window, is_sparse in zip(windows, sparse):
        rope = rope_layers == "all" or (rope_layers == "sliding" and window > 0)
        attn = GQAttention(heads, kv_heads, head_dim, window, qk_norm,
                           rope_theta if rope else None, eps, param_dtype)
        if is_sparse:
            moe = ExpertLayer(num_experts, experts_held, top_k, expert_hidden,
                              scoring=scoring, norm_topk=norm_topk, scale=scale,
                              first_expert=first_expert, param_dtype=param_dtype)
            shared = SwiGLU(expert_hidden * shared_experts, param_dtype) \
                if shared_experts else None
            layers.append(DecoderLayer(attn, moe=moe, shared=shared, eps=eps,
                                       param_dtype=param_dtype))
        else:
            layers.append(DecoderLayer(attn, mlp=SwiGLU(dense_hidden, param_dtype),
                                       eps=eps, param_dtype=param_dtype))
    module = CausalLM(vocab_size, hidden, layers, pad_id, eps, param_dtype)
    params = module.init(jax.random.key(seed), (seq_len,))[0] if init else {}
    names = [CausalLM.LOAD] + [f"layer{i}" for i in reversed(range(len(layers)))]
    return FunctionModel(module, params, (seq_len,), names, "causal_lm")


def latent_causal_lm(seq_len: int, vocab_size: int, hidden: int, heads: int,
                     q_rank: int, kv_rank: int, nope: int, rope: int, v_dim: int,
                     sparse: Sequence[bool], dense_hidden: int, expert_hidden: int,
                     num_experts: int, experts_held: int, top_k: int,
                     first_expert: int = 0, scoring: str = "sigmoid",
                     norm_topk: bool = True, scale: float = 1.0,
                     shared_experts: int = 1, rope_theta: float = 10000.0,
                     rope_scaling: Optional[Dict[str, Any]] = None,
                     streams: int = 1, sinkhorn_iters: int = 20,
                     hc_eps: float = 1e-6, hc_clamp: Tuple[float, float] = (-30.0, 30.0),
                     eps: float = 1e-6, pad_id: int = 0,
                     param_dtype: str = "float32", seed: int = 0,
                     init: bool = True) -> FunctionModel:
    """``causal_lm``'s sibling for a model with latent attention and, where
    ``streams`` > 1, a hyper-connected residual path: the same ``CausalLM`` of
    the same ``DecoderLayer`` s, every layer a ``LatentAttention`` and an
    expert layer where ``sparse[i]``, else a dense SwiGLU."""
    import jax

    from .moe import ExpertLayer
    from .residual import HyperConnection

    hyper = HyperConnection(streams, sinkhorn_iters, hc_eps, hc_clamp, eps,
                            param_dtype) if streams > 1 else None
    layers = []
    for is_sparse in sparse:
        attn = LatentAttention(heads, q_rank, kv_rank, nope, rope, v_dim, rope_theta,
                               rope_scaling, eps, param_dtype)
        if is_sparse:
            moe = ExpertLayer(num_experts, experts_held, top_k, expert_hidden,
                              scoring=scoring, norm_topk=norm_topk, scale=scale,
                              first_expert=first_expert, param_dtype=param_dtype)
            shared = SwiGLU(expert_hidden * shared_experts, param_dtype) \
                if shared_experts else None
            layers.append(DecoderLayer(attn, moe=moe, shared=shared, eps=eps,
                                       param_dtype=param_dtype, hyper=hyper))
        else:
            layers.append(DecoderLayer(attn, mlp=SwiGLU(dense_hidden, param_dtype),
                                       eps=eps, param_dtype=param_dtype, hyper=hyper))
    module = CausalLM(vocab_size, hidden, layers, pad_id, eps, param_dtype, streams)
    params = module.init(jax.random.key(seed), (seq_len,))[0] if init else {}
    names = [CausalLM.LOAD] + [f"layer{i}" for i in reversed(range(len(layers)))]
    return FunctionModel(module, params, (seq_len,), names, "latent_causal_lm")


def hybrid_plan(num_layers: int, mb_per_layer: int = 2) -> List[str]:
    """The mixer of each layer of a SambaY decoder (arXiv:2507.06607): in the
    first half (the self-decoder, which ends at layer ``num_layers / 2``)
    every ``mb_per_layer``-th layer is ``"mamba"`` and the others ``"window"``
    attention; layer ``num_layers / 2`` is the Mamba whose memory is handed
    on (``"mamba_memory"``), the next is ``"full"`` attention whose keys and
    values are handed on; after it (the cross-decoder) ``"gmu"`` and
    ``"cross"`` attention take turns."""
    half = num_layers // 2
    plan = []
    for i in range(num_layers):
        state = i % mb_per_layer == 0
        if i <= half:
            plan.append(("mamba_memory" if i == half else "mamba") if state else "window")
        elif i == half + 1:
            plan.append("full")
        else:
            plan.append("gmu" if state else "cross")
    return plan


def hybrid_causal_lm(seq_len: int, vocab_size: int, hidden: int, heads: int,
                     kv_heads: int, num_layers: int, dense_hidden: int,
                     window: int, mb_per_layer: int = 2, d_state: int = 16,
                     d_conv: int = 4, expand: int = 2, dt_rank: Optional[int] = None,
                     eps: float = 1e-5, pad_id: int = 0,
                     param_dtype: str = "float32", seed: int = 0,
                     init: bool = True) -> FunctionModel:
    """``causal_lm``'s sibling for a decoder of state-space layers,
    differential attention and Gated Memory Units (``hybrid_plan``): the same
    ``CausalLM`` of the same ``DecoderLayer`` s, each with the mixer its
    index gives it and a dense SwiGLU, LayerNorm before every sublayer and
    the head, the head tied to the embedding, no positions."""
    import jax

    from .ssm import GatedMemoryUnit, Mamba

    d_inner, head_dim = expand * hidden, hidden // heads
    layers = []
    for i, kind in enumerate(hybrid_plan(num_layers, mb_per_layer)):
        if kind.startswith("mamba"):
            part, name = Mamba(d_inner, d_state, d_conv, dt_rank,
                               kind == "mamba_memory", param_dtype), "ssm"
        elif kind == "gmu":
            part, name = GatedMemoryUnit(d_inner, param_dtype), "gmu"
        else:
            part, name = DiffAttention(
                heads, kv_heads, head_dim, i, window if kind == "window" else 0,
                kind == "cross", kind == "full", eps, param_dtype), "attn"
        layers.append(DecoderLayer(part, mlp=SwiGLU(dense_hidden, param_dtype), eps=eps,
                                   param_dtype=param_dtype, norm="layer", mixer=name))
    module = CausalLM(vocab_size, hidden, layers, pad_id, eps, param_dtype,
                      norm="layer", tied=True)
    params = module.init(jax.random.key(seed), (seq_len,))[0] if init else {}
    names = [f"layer{i}" for i in reversed(range(len(layers)))]
    return FunctionModel(module, params, (seq_len,), names, "hybrid_causal_lm")
