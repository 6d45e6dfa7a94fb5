"""The parts of a present-day causal language model, and the scorer built
from them: RMSNorm, rotary positions (plain or YaRN), three attention
modules, SwiGLU, a dropless expert layer (``moe.ExpertLayer``), a decoder
layer whose token mixer (an attention, or a state-space layer or memory unit
of ``ssm.py``) and residual path are parts of it, and ``CausalLM`` /
``causal_lm`` / ``latent_causal_lm`` / ``hybrid_causal_lm``, whose output is
one number a position (the next token's log-probability; with a
multi-token-prediction module, a second node, the token two ahead's): the
logits never leave the device.

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
    shared rotary one, the value narrower than the two together. Optionally
    sparse (DeepSeek Sparse Attention's lightning indexer: a query reads only
    the ``index_topk`` keys an indexer of its own scores highest) and with an
    elementwise sigmoid gate on each head's output;
  - ``DiffAttention`` (differential attention): heads of 64 in pairs, two
    softmaxes a pair against the pair's 128-wide values, subtracted with a
    learned ``lambda`` and RMS-normed; a window, a whole row, or (a cross
    layer) its own queries over the keys and values of an earlier layer,
    which reach it through the carry between layers (``DecoderLayer``).

Their cores run one of two ways:

  - ``attn_window`` / ``attn_full`` / ``attn_mla`` / ``attn_window_diff`` /
    ``attn_full_diff`` / ``attn_mla_sparse``: Pallas kernels under the
    names a device trace shows, one streaming softmax over key blocks (two a
    pair in the differential kernel; the sparse latent one masked by the
    selection ``mla_index`` made, a kernel of its own). A
    sliding layer visits only the blocks its window touches, so its work and
    memory grow with ``T x window``; a full or latent layer stops at the
    diagonal; the latent kernel fetches the shared rotary key once a key
    block for all the heads of a step. Taken on a TPU for bfloat16 heads of
    128 lanes at block-aligned ``T`` (a latent head's content lanes may be no
    whole tile: its caller lays them out in tiles).
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
from .module import FunctionModel, Module, _rng_split, equal_pieces, matmul_dtype
from . import paired

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
# the bound: a row's positions up to its last real token
# ---------------------------------------------------------------------------

LIVE_COUNT_BLOCK = 512    # the query block the counter counts in (a full layer's at 16,384)


def live_lengths(ids, pad_id: int):
    """``[B]`` int32 of ``ids [B, T]`` (a NumPy or a JAX array, and the same
    kind back): the positions of each row up to and including its last id
    that is not ``pad_id``, 0 for a row of pads alone. What the attention
    kernels stop at (``FlashSchedule``), and what ``CausalLM.batch_counts``
    counts on the host: one function, so the two cannot drift."""
    through = (ids != pad_id) * np.arange(1, ids.shape[-1] + 1, dtype=np.int32)
    return through.max(axis=-1).astype(np.int32)


def _zero_past(o, live):
    """``o [B, T, ...]`` with the positions of each row at and past ``live``
    zero, as the kernels store them (None: ``o`` as it is)."""
    if live is None:
        return o
    import jax.numpy as jnp

    keep = jnp.arange(o.shape[1])[None, :] < live[:, None]
    return jnp.where(keep.reshape(keep.shape + (1,) * (o.ndim - 2)), o,
                     jnp.zeros((), o.dtype))


def _bound(live, x):
    """The bound a kernel reads: ``live``, or every row whole where there is
    none."""
    import jax.numpy as jnp

    return jnp.full((x.shape[0],), x.shape[1], jnp.int32) if live is None \
        else live.astype(jnp.int32)


def _bound_last(fn, operands: int):
    """``fn`` (which takes ``live`` by keyword) as a pair's side: ``f(*its
    operands, live, *config)``, the bound an operand of the pair."""
    def call(*args, **kw):
        return fn(*args[:operands], *args[operands + 1:], live=args[operands], **kw)

    return call


# ---------------------------------------------------------------------------
# grouped-query attention: plain XLA
# ---------------------------------------------------------------------------

def _softmax_rows(s, seen):
    """Softmax over the last dim of float32 scores, keys not ``seen`` out."""
    import jax.numpy as jnp

    s = jnp.where(seen, s, _NEG)
    p = jnp.where(seen, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    return p / jnp.sum(p, axis=-1, keepdims=True)


def gqa_xla(q, k, v, window: int, out_dtype=None, live=None):
    """q ``[B, T, H, D]``, k ``[B, T, KV, D]``, v ``[B, T, KV, Dv]`` -> ``[B,
    T, H, Dv]`` (``out_dtype``; None: v's); causal, query t sees keys ``t -
    window < s <= t`` (every ``s <= t`` at window 0), and a position at or
    past its row's ``live`` is zero. Blocked over queries: a window reads its
    own and the previous block of ``window`` keys, a full layer a block of
    queries against all keys."""
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
    o = o.reshape(B, T + pad, H, v.shape[-1])[:, :T].astype(out_dtype or v.dtype)
    return _zero_past(o, live)


# ---------------------------------------------------------------------------
# the flash schedule: the blocks and steps of the four flash-attention kernels
# ---------------------------------------------------------------------------

def _attn_blocks(T: int, window: int) -> Optional[Tuple[int, int]]:
    """(query block, key block) the kernels take at this length, or None."""
    if T % 128:
        return None
    bq = next(b for b in (512, 256, 128) if T % b == 0)
    if window:
        return min(bq, 256), 128
    return bq, bq


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


class FlashSchedule:
    """The block schedule of the flash-attention kernels (``attn_window`` /
    ``attn_full``, their differential forms, ``attn_mla``,
    ``attn_mla_sparse``), written once. The grid is ``(B, head groups, query
    blocks, steps)``; query block ``qi`` takes ``steps`` key blocks from its
    window's first on (from 0 at window 0) up to its diagonal. A step past
    the diagonal computes nothing and fetches nothing: its key index stays on
    the diagonal's block. Each softmax of a step has its running maximum,
    denominator and accumulator (``m``, ``l``, ``acc``) in VMEM.

    **The bound.** A call's first operand is ``live [B]`` int32, prefetched
    into SMEM: the positions of each row up to and including its last real
    token (``live_lengths``). A query block wholly past it (``qi * bq >=
    live[b]``) is dead: its steps compute nothing, and every index map stays
    on the last live block's last step, which the grid step before fetched,
    so they fetch nothing; its store writes zeros, and so does the store of
    the block that holds the bound, in its rows past it. A block wholly
    before the bound runs as it would without one, to the bit. The bound is
    data: one program serves every length.

    A kernel is a body over it: ``step(refs, qi, kb, masked)`` folds key
    block ``kb`` into the softmaxes (``refs``: the inputs, the output, then
    ``m``, ``l``, ``acc``), ``store(refs)`` writes the result. Which blocks
    it masks (every block or the diagonal's alone) and where it stores (at
    the last step or at the diagonal's) are the two choices it makes."""

    def __init__(self, T: int, window: int):
        self.window = window
        self.bq, self.bk = _attn_blocks(T, window)
        self.blocks = T // self.bq
        self.steps = T // self.bk if not window else min(
            T // self.bk, self.bq // self.bk + -(-(window - 1) // self.bk))

    def key_block(self, qi, j):
        """The key block step ``j`` of query block ``qi`` reads."""
        import jax.numpy as jnp

        first = 0 if not self.window else \
            jnp.maximum(qi * self.bq - (self.window - 1), 0) // self.bk
        return first + j

    def diagonal(self, qi):
        """The key block of query block ``qi``'s diagonal: the last it reads."""
        return (qi * self.bq + self.bq - 1) // self.bk

    def fetched(self, qi, j):
        """The key block a step fetches: past the diagonal the index stays
        put, so no block is fetched for a step that computes nothing."""
        import jax.numpy as jnp

        return jnp.minimum(self.key_block(qi, j), self.diagonal(qi))

    def at(self, qi, j, live):
        """``(query block, step)`` whose blocks grid step ``(qi, j)`` of a row
        of ``live`` fetches: its own in a live query block; in a dead one the
        last live block's last step (block 0's in a row of no real token)."""
        import jax.numpy as jnp

        last = jnp.maximum(live - 1, 0) // self.bq
        return jnp.minimum(qi, last), jnp.where(qi * self.bq >= live, self.steps - 1, j)

    def seen(self, qi, kb):
        """``[bq, bk]``: the (query, key) pairs of a block that are causal and
        inside the window."""
        import jax
        import jax.numpy as jnp

        qpos = qi * self.bq + jax.lax.broadcasted_iota(jnp.int32, (self.bq, self.bk), 0)
        kpos = kb * self.bk + jax.lax.broadcasted_iota(jnp.int32, (self.bq, self.bk), 1)
        seen = kpos <= qpos
        if self.window:
            seen = jnp.logical_and(seen, kpos > qpos - self.window)
        return seen

    def query_spec(self, width: int):
        """A block of queries of one head group."""
        from jax.experimental import pallas as pl

        def index(b, h, qi, j, live):
            return b, self.at(qi, j, live[b])[0], h

        return pl.BlockSpec((None, self.bq, width), index)

    def key_spec(self, width: int, shared: bool = False):
        """A block of keys of one head group, or (``shared``) of the one
        column block all groups read."""
        from jax.experimental import pallas as pl

        def index(b, h, qi, j, live):
            return b, self.fetched(*self.at(qi, j, live[b])), 0 if shared else h

        return pl.BlockSpec((None, self.bk, width), index)

    def kernel(self, step, store, mask_all: bool, store_at_diagonal: bool = False):
        """The kernel over ``live`` and ``refs``: ``m``, ``l``, ``acc`` set at
        the first step, ``step`` on a live block's blocks up to the diagonal
        (``masked`` on every one of them with ``mask_all``, else on the
        diagonal's alone), ``store`` at the last step or
        (``store_at_diagonal``) the diagonal's, and zeros past the bound."""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def run(live, *refs):
            m_scr, l_scr, acc_scr = refs[-3:]
            b, qi, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
            edge = live[b] - qi * self.bq        # the block's queries before the bound

            @pl.when(j == 0)
            def _init():
                m_scr[...] = jnp.full_like(m_scr, _NEG)
                l_scr[...] = jnp.zeros_like(l_scr)
                acc_scr[...] = jnp.zeros_like(acc_scr)

            kb, last = self.key_block(qi, j), self.diagonal(qi)
            if mask_all:
                pl.when((edge > 0) & (kb <= last))(lambda: step(refs, qi, kb, True))
            else:                # blocks alike: only the diagonal's needs its mask
                pl.when((edge > 0) & (kb < last))(lambda: step(refs, qi, kb, False))
                pl.when((edge > 0) & (kb == last))(lambda: step(refs, qi, kb, True))
            stores = kb == last if store_at_diagonal else j == self.steps - 1
            pl.when(stores & (edge > 0))(lambda: store(refs))

            @pl.when(stores & (edge < self.bq))
            def _past_bound():   # zeros at and past the bound: a dead block's all
                o_ref = refs[-4]
                rows = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
                o_ref[...] = jnp.where(rows < edge, o_ref[...],
                                       jnp.zeros(o_ref.shape, o_ref.dtype))

        return run

    def call(self, kernel, live, operands, in_specs, out_shape, out_width: int,
             softmaxes: int, value_width: int, name: str, cost, interpret: bool):
        """``pallas_call`` of ``kernel`` over the grid, ``live`` prefetched:
        ``out_shape``'s head groups ``out_width`` lanes each, ``softmaxes`` a
        step with values ``value_width`` wide, under the name a device trace
        shows (docs/observability.md)."""
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        B = operands[0].shape[0]
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B, out_shape.shape[2] // out_width, self.blocks, self.steps),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((None, self.bq, out_width),
                                       lambda b, h, qi, j, live: (b, qi, h)),
                scratch_shapes=[pltpu.VMEM((softmaxes, self.bq, 128), jnp.float32),
                                pltpu.VMEM((softmaxes, self.bq, 128), jnp.float32),
                                pltpu.VMEM((softmaxes, self.bq, value_width), jnp.float32)]),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=64 * 1024 * 1024),
            name=name,
            cost_estimate=cost,
            interpret=interpret,
        )(live, *operands)


def _divide(refs, group: int, width: int):
    """The store of a plain softmax: each head's accumulator over its
    denominator."""
    o_ref, _, l_scr, acc_scr = refs[-4:]
    for g in range(group):
        o_ref[:, g * width:(g + 1) * width] = (
            acc_scr[g] / l_scr[g][:, :1]).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# grouped-query attention: the Pallas kernel
# ---------------------------------------------------------------------------

def _gqa_step(refs, qi, kb, masked: bool, *, sched: FlashSchedule, scale: float,
              group: int, d: int):
    """One block of queries of the ``group`` query heads that share a
    key/value head, against one block of its keys: the heads run in turn over
    the same key and value tiles (fetched once for all of them)."""
    import jax
    import jax.numpy as jnp

    q_ref, k_ref, v_ref, _, *softmax = refs
    k, v = k_ref[...], v_ref[...]
    seen = sched.seen(qi, kb) if masked else None
    for g in range(group):
        q = q_ref[:, g * d:(g + 1) * d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        _softmax_step(s, seen, v, *softmax, g)


def gqa_pallas(q, k, v, window: int, heads: int, kv_heads: int,
               interpret: bool = False, live=None):
    """q ``[B, T, H * D]``, k / v ``[B, T, KV * D]`` -> ``[B, T, H * D]``: a
    head is a block of 128 lanes of the projection's own layout, so nothing
    is transposed; the ``H / KV`` query heads of a key/value head lie side by
    side and go through one grid step together. Every block is masked; the
    schedule stops at ``live`` (None: every row whole)."""
    import jax
    from jax.experimental import pallas as pl

    B, T, _ = q.shape
    D = q.shape[2] // heads
    group = heads // kv_heads
    sched = FlashSchedule(T, window)
    step = functools.partial(_gqa_step, sched=sched, scale=1.0 / math.sqrt(D),
                             group=group, d=D)
    pairs = T * (min(window, T) if window else (T + 1) / 2)   # (query, key) seen
    return sched.call(
        sched.kernel(step, functools.partial(_divide, group=group, width=D), mask_all=True),
        _bound(live, q), (q, k, v),
        [sched.query_spec(group * D), sched.key_spec(D), sched.key_spec(D)],
        jax.ShapeDtypeStruct(q.shape, q.dtype), out_width=group * D, softmaxes=group,
        value_width=D, name="attn_window" if window else "attn_full",
        cost=pl.CostEstimate(
            flops=int(4 * B * heads * D * pairs),
            transcendentals=int(B * heads * pairs),
            bytes_accessed=int(2 * q.size * q.dtype.itemsize
                               + 2 * k.size * k.dtype.itemsize * (T // sched.bq))),
        interpret=interpret)


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


def _gqa_flat_xla(q, k, v, window: int, heads: int, kv_heads: int, live=None):
    B, T, _ = q.shape
    o = gqa_xla(q.reshape(B, T, heads, -1), k.reshape(B, T, kv_heads, -1),
                v.reshape(B, T, kv_heads, -1), window, live=live)
    return o.reshape(B, T, -1)


_gqa_pair = paired.pair(_bound_last(gqa_pallas, 3), _bound_last(_gqa_flat_xla, 3),
                        static=3, nondiff=(3,))


def gq_attention(q, k, v, window: int, heads: int, kv_heads: int, live=None):
    """Causal grouped-query attention over flat heads (``[B, T, heads * D]``),
    zero at and past each row's ``live`` (None: no bound): the kernel where
    it applies, the plain form elsewhere."""
    if _pallas_applies(q, heads, window):
        return _gqa_pair(q, k, v, _bound(live, q), window, heads, kv_heads)
    return _gqa_flat_xla(q, k, v, window, heads, kv_heads, live)


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


def diff_xla(q, k, v, lam, gain, window: int, pairs: int, kv_pairs: int, eps: float,
             live=None):
    """-> ``[B, T, pairs * 128]`` in q's dtype, zero at and past each row's
    ``live``: the two softmaxes through ``gqa_xla`` (heads of 64 against
    values of 128), everything after them in float32."""
    import jax.numpy as jnp

    B, T, _ = q.shape
    q4 = q.reshape(B, T, pairs, 2, -1)             # a pair of any width here
    k4 = k.reshape(B, T, kv_pairs, 2, -1)
    v4 = v.reshape(B, T, kv_pairs, -1)
    o1, o2 = (gqa_xla(q4[:, :, :, i], k4[:, :, :, i], v4, window, jnp.float32)
              for i in range(2))
    o = rms_norm(o1 - lam * o2, gain, eps)
    return _zero_past(o.reshape(q.shape).astype(q.dtype), live)


def _diff_step(refs, qi, kb, masked: bool, *, sched: FlashSchedule, scale: float,
               group: int):
    """``_gqa_step`` for pairs: a block of queries of the ``group`` query
    pairs that share a key pair against one block of its keys. A pair's two
    score maps contract its own 64 lanes (the other half of the query tile
    zeroed: a 64-wide product costs the MXU a pass of 128 anyway) and both go
    against the whole 128-wide value tile, each its own softmax."""
    import jax
    import jax.numpy as jnp

    q_ref, k_ref, v_ref, _, _, *softmax = refs
    k, v = k_ref[...], v_ref[...]
    seen = sched.seen(qi, kb) if masked else None
    first = jax.lax.broadcasted_iota(jnp.int32, (sched.bq, PAIR), 1) < PAIR // 2
    for g in range(group):
        q = q_ref[:, g * PAIR:(g + 1) * PAIR]
        for half in range(2):
            qh = jnp.where(first if half == 0 else jnp.logical_not(first),
                           q, jnp.zeros_like(q))
            s = jax.lax.dot_general(qh, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            _softmax_step(s, seen, v, *softmax, 2 * g + half)


def _diff_store(refs, group: int, eps: float):
    """A pair's two softmaxes subtracted and RMS-normed: ``p_ref`` row 0 is
    ``lam``, row 1 ``gain``."""
    import jax
    import jax.numpy as jnp

    p_ref, o_ref, _, l_scr, acc_scr = refs[-5:]
    lam, gain = p_ref[0:1, :], p_ref[1:2, :]
    for g in range(group):
        o = acc_scr[2 * g] / l_scr[2 * g][:, :1] \
            - lam * (acc_scr[2 * g + 1] / l_scr[2 * g + 1][:, :1])
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps) * gain
        o_ref[:, g * PAIR:(g + 1) * PAIR] = o.astype(o_ref.dtype)


def diff_pallas(q, k, v, lam, gain, window: int, pairs: int, kv_pairs: int,
                eps: float, interpret: bool = False, live=None):
    """The kernel form of ``diff_xla`` (same arguments and result): a pair
    is a block of 128 lanes of the projections' own layout, read in place,
    so another layer's ``k`` and ``v`` cost no copy. A window's blocks are
    all masked, a full layer's diagonal alone."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, T, _ = q.shape
    group = pairs // kv_pairs
    sched = FlashSchedule(T, window)
    step = functools.partial(_diff_step, sched=sched, scale=1.0 / math.sqrt(PAIR // 2),
                             group=group)
    rows = jnp.zeros((8, PAIR), jnp.float32)
    rows = rows.at[0].set(jnp.asarray(lam, jnp.float32)).at[1].set(gain.astype(jnp.float32))
    seen = T * (min(window, T) if window else (T + 1) / 2)    # (query, key) seen
    return sched.call(
        sched.kernel(step, functools.partial(_diff_store, group=group, eps=eps),
                     mask_all=bool(window)),
        _bound(live, q), (q, k, v, rows),
        [sched.query_spec(group * PAIR), sched.key_spec(PAIR), sched.key_spec(PAIR),
         pl.BlockSpec((8, PAIR), lambda b, h, qi, j, live: (0, 0))],
        jax.ShapeDtypeStruct(q.shape, q.dtype), out_width=group * PAIR,
        softmaxes=2 * group, value_width=PAIR,
        name="attn_window_diff" if window else "attn_full_diff",
        cost=pl.CostEstimate(
            flops=int(6 * B * pairs * PAIR * seen),
            transcendentals=int(2 * B * pairs * seen),
            bytes_accessed=int(2 * q.size * q.dtype.itemsize
                               + 2 * k.size * k.dtype.itemsize * (T // sched.bq))),
        interpret=interpret)


_diff_pair = paired.pair(_bound_last(diff_pallas, 5), _bound_last(diff_xla, 5),
                         static=4, nondiff=(5,))


def _diff_pallas_applies(q, window: int, pairs: int) -> bool:
    """A TPU, bfloat16 pairs of exactly 128 lanes, a block-aligned ``T``
    (``_pallas_applies`` says why 64-wide heads come here, in pairs)."""
    import jax
    import jax.numpy as jnp

    return (jax.default_backend() == "tpu" and q.dtype == jnp.bfloat16
            and q.shape[2] // pairs == PAIR
            and _attn_blocks(q.shape[1], window) is not None)


def diff_attention(q, k, v, lam, gain, window: int, pairs: int, kv_pairs: int,
                   eps: float, live=None):
    """Causal differential attention over flat pairs, zero at and past each
    row's ``live`` (None: no bound): the kernel where it applies, the plain
    form elsewhere."""
    if _diff_pallas_applies(q, window, pairs):
        return _diff_pair(q, k, v, lam, gain, _bound(live, q), window, pairs, kv_pairs,
                          eps)
    return diff_xla(q, k, v, lam, gain, window, pairs, kv_pairs, eps, live)


# ---------------------------------------------------------------------------
# latent attention: the core over decompressed heads and one shared rotary key
# ---------------------------------------------------------------------------
#
# Layout, flat as the projections leave it: ``q [B, T, H * dq]``, a head's
# content lanes then its rotary lanes (already scaled: the softmax scale is
# folded into the queries); ``kv [B, T, H * (dn + dv)]``, a head's ``dn`` key
# lanes then its values; ``kr [B, T, r]``, the rotary key all heads share,
# which meets a head's last ``r`` query lanes: ``s = q[:dn] . k + q[dq - r:]
# . kr``. The plain layout has ``dn`` the content width and ``dn + r = dq``.
# The kernel wants whole tiles of 128 lanes, so its caller lays the operands
# out (``LatentAttention.apply``): where the content keys are whole tiles
# (128), the rotary lanes of q and kr are padded with zeros to a tile; where
# they are not (192), a head's keys in kv are its content keys and zeros to
# the width of its query, and kr holds the rotary key in the lanes of that
# last tile the content keys leave free (``[0 (64) | k_r (64)]``): the score
# is one contraction over the query's own width, ``dq = 256``, with no pad
# lane in it.

def mla_xla(q, kv, kr, heads: int, dn: int, bits=None, live=None):
    """-> ``[B, T, H * dv]``; causal, a block of queries against all keys:
    ``s = q_nope . k_nope + q_rope . k_r``, the second product against the
    one shared key, never repeated to the heads (on the kernel's layout,
    ``q[:dn] . k + q[dq - r:] . kr``). With ``bits`` (a selection,
    ``pack_selection``'s) a query sees the keys they select. A position at or
    past its row's ``live`` is zero."""
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
    rq = q4.shape[-1] - kr.shape[-1]                   # the query lanes kr meets
    kpos = jnp.arange(T + pad)

    def block(a):
        qb, first = a[0], a[1]                          # [B, blk, H, dq]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb[..., :dn], kn,
                       preferred_element_type=jnp.float32) \
            + jnp.einsum("bqhd,bkd->bhqk", qb[..., rq:], kr,
                         preferred_element_type=jnp.float32)
        seen = kpos[None, :] <= (first + jnp.arange(blk))[:, None]
        if bits is not None:     # a padded query sees its causal keys: no 0 / 0
            pad_row = (first + jnp.arange(blk) >= T)[:, None]
            seen = (seen & pad_row) | unpack_selection(a[2], T + pad)[:, None]
        p = _softmax_rows(s, seen)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    blocks = (jnp.moveaxis(q4, 1, 0), jnp.arange((T + pad) // blk) * blk)
    if bits is not None:
        bits = jnp.pad(bits, ((0, 0), (0, pad), (0, 0)))
        blocks += (jnp.moveaxis(bits.reshape(B, -1, blk, bits.shape[-1]), 1, 0),)
    o = jax.lax.map(block, blocks)
    return _zero_past(jnp.moveaxis(o, 0, 1).reshape(B, T + pad, -1)[:, :T].astype(kv.dtype),
                      live)


MLA_HEADS_A_STEP = 4      # heads that share one fetch of the rotary key


def _mla_step(refs, qi, kb, masked: bool, *, sched: FlashSchedule, group: int,
              dn: int, dq: int, dv: int, bits: bool = False):
    """One block of queries of ``group`` heads against one block of keys:
    the shared rotary key is fetched once for all of them and joins each
    head's content key as the last lanes of one ``dq``-wide contraction,
    after the head's ``dn`` key lanes where they are whole tiles, else added
    into the zero lanes of their last tile (the layout above ``mla_xla``).
    With ``bits`` every block is masked by the queries' selection: the
    block's 128-lane chunks of keys are bits of the one tile of words the
    block lies in, unpacked in place. The selection lies inside the causal
    keys, so the diagonal block needs nothing more."""
    import jax
    import jax.numpy as jnp

    q_ref, kv_ref, kr_ref = refs[:3]
    if bits:
        words = refs[3][...]
        c0 = (kb * sched.bk) % SELECT_GROUP // 128
        seen = jnp.concatenate(
            [jax.lax.shift_right_logical(words, c0 + c) & 1
             for c in range(sched.bk // 128)], axis=1) != 0
    else:
        seen = sched.seen(qi, kb) if masked else None
    kr = kr_ref[...]
    lead = dq - kr.shape[1]                  # the key lanes before the rotary key's
    for g in range(group):
        at = g * (dn + dv)
        if dn == lead:
            k = jnp.concatenate([kv_ref[:, at:at + dn], kr], axis=1)
        else:
            tail = kv_ref[:, at + lead:at + dn] + kr
            k = jnp.concatenate([kv_ref[:, at:at + lead], tail], axis=1) if lead else tail
        s = jax.lax.dot_general(q_ref[:, g * dq:(g + 1) * dq], k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        _softmax_step(s, seen, kv_ref[:, at + dn:at + dn + dv], *refs[-3:], g)


def _mla_call(q, kv, kr, bits, heads: int, dn: int, interpret: bool, live):
    """``attn_mla`` (``bits`` None: the diagonal block masked, causal) or
    ``attn_mla_sparse`` (every block masked by the selection's tile of words
    beside it): ``dq``, ``dv`` and ``kr``'s width whole tiles of 128 lanes,
    ``dn`` the lanes before the rotary key's (``dq - r``) or ``dq``, query and
    key blocks alike, the result stored at the diagonal's step, the schedule
    stopped at ``live`` (None: every row whole)."""
    import jax
    from jax.experimental import pallas as pl

    B, T, _ = q.shape
    dq, dv = q.shape[2] // heads, kv.shape[2] // heads - dn
    group = next(g for g in (MLA_HEADS_A_STEP, 2, 1) if heads % g == 0)
    sched = FlashSchedule(T, 0)
    sparse = bits is not None
    step = functools.partial(_mla_step, sched=sched, group=group, dn=dn, dq=dq, dv=dv,
                             bits=sparse)
    specs = [sched.query_spec(group * dq), sched.key_spec(group * (dn + dv)),
             sched.key_spec(kr.shape[2], shared=True)]
    operands = (q, kv, kr)
    pairs = T * (T + 1) / 2
    moved = (q.size * q.dtype.itemsize * (1 + dv / dq)
             + kv.size * kv.dtype.itemsize * (T // sched.bq + 1) / 2)
    if sparse:
        def words(b, h, qi, j, live):     # the query block's tile beside the key block
            at, step = sched.at(qi, j, live[b])
            return b, at, sched.fetched(at, step) * sched.bk // SELECT_GROUP

        specs.append(pl.BlockSpec((None, sched.bq, 128), words))
        operands += (bits,)
        moved += bits.size * 4 * heads // group
    return sched.call(
        sched.kernel(step, functools.partial(_divide, group=group, width=dv),
                     mask_all=sparse, store_at_diagonal=True),
        _bound(live, q), operands, specs, jax.ShapeDtypeStruct((B, T, heads * dv), q.dtype),
        out_width=group * dv, softmaxes=group, value_width=dv,
        name="attn_mla_sparse" if sparse else "attn_mla",
        cost=pl.CostEstimate(flops=int(2 * B * heads * (dq + dv) * pairs),
                             transcendentals=int(B * heads * pairs),
                             bytes_accessed=int(moved)),
        interpret=interpret)


def mla_pallas(q, kv, kr, heads: int, dn: int, interpret: bool = False, live=None):
    """The kernel form of ``mla_xla`` (same arguments and result)."""
    return _mla_call(q, kv, kr, None, heads, dn, interpret, live)


_mla_pair = paired.pair(_bound_last(mla_pallas, 3), _bound_last(mla_xla, 3), static=2,
                        nondiff=(3,))


def _mla_pallas_applies(x, v_dim: int) -> bool:
    """Whether a latent layer's core takes the kernel for input ``x [B, T,
    D]``: a TPU, bfloat16 operands, value heads of whole tiles. Content heads
    of any width: the caller lays them out in whole tiles."""
    import jax
    import jax.numpy as jnp

    return (jax.default_backend() == "tpu" and _mm_dtype() == jnp.bfloat16
            and v_dim % 128 == 0 and _attn_blocks(x.shape[1], 0) is not None)


# ---------------------------------------------------------------------------
# sparse latent attention: an indexer chooses the keys, the core reads them
# ---------------------------------------------------------------------------
#
# The indexer (DeepSeek Sparse Attention's, arXiv:2512.02556): ``qi [B, T,
# heads * dim]`` (a head's lanes side by side), ``ki [B, T, dim]`` one key for
# all its heads, ``wi [B, T, heads]`` float32 weights that already carry every
# scale; ``I[t, s] = sum_j wi[t, j] relu(qi[t, j] . ki[s])`` for ``s <= t``,
# and a query keeps the ``topk`` keys of largest ``I`` (all of them where it
# has fewer), ties to the lower position.
#
# The selection travels as bits, 32 keys an int32: a query's ``SELECT_GROUP``
# = 4,096 keys are one tile of 128 words, and key ``s`` is bit ``(s % 4096) //
# 128`` of word ``s // 4096 * 128 + s % 128``. A key block of 128-lane chunks
# then unpacks with a shift and a mask a chunk, lanes in place: ``[B, T,
# ceil(T / 4096) * 128]`` int32, 134 MB for a row of 32,768.

SELECT_GROUP = 4096
_INT_MIN = -2 ** 31


def selection_words(t: int) -> int:
    """int32 words a query's selection takes at a row of ``t`` keys."""
    return -(-t // SELECT_GROUP) * 128


def pack_selection(sel):
    """``sel [..., T]`` bool -> ``[..., selection_words(T)]`` int32."""
    import jax.numpy as jnp

    t = sel.shape[-1]
    pad = selection_words(t) * 32 - t
    sel = jnp.pad(sel, [(0, 0)] * (sel.ndim - 1) + [(0, pad)]).astype(jnp.uint32)
    sel = sel.reshape(*sel.shape[:-1], -1, 32, 128)
    word = jnp.sum(sel << jnp.arange(32, dtype=jnp.uint32)[:, None], axis=-2,
                   dtype=jnp.uint32)
    return word.reshape(*word.shape[:-2], -1).view(jnp.int32)


def unpack_selection(bits, t: int):
    """``pack_selection``'s inverse: ``[..., words]`` -> ``[..., t]`` bool."""
    import jax.numpy as jnp

    b = bits.view(jnp.uint32).reshape(*bits.shape[:-1], -1, 1, 128)
    sel = (b >> jnp.arange(32, dtype=jnp.uint32)[:, None]) & 1
    return sel.reshape(*bits.shape[:-1], -1)[..., :t] != 0


def index_scores_xla(qi, ki, wi, heads: int):
    """``I [B, T, T]`` float32 (the plain form; keys past a query left in)."""
    import jax.numpy as jnp

    B, T, _ = qi.shape
    s = jnp.einsum("btjd,bsd->btjs", qi.reshape(B, T, heads, -1), ki,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("btjs,btj->bts", jnp.maximum(s, 0.0), wi,
                      precision="highest")


def mla_index_xla(qi, ki, wi, heads: int, topk: int):
    """The selection bits of every query (the plain form of ``mla_index``):
    the scores a block of queries at a time, ``lax.top_k`` over the causal
    ones (it keeps the lower position of a tie), packed."""
    import jax
    import jax.numpy as jnp

    B, T, _ = qi.shape
    blk = min(T, 512)
    pad = -T % blk
    if pad:
        qi, wi = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (qi, wi))
    kpos = jnp.arange(T)

    def block(a):
        qb, wb, first = a
        causal = kpos[None, :] <= (first + jnp.arange(blk))[:, None]
        s = jnp.where(causal, index_scores_xla(qb, ki, wb, heads), -jnp.inf)
        _, idx = jax.lax.top_k(s, min(topk, T))                       # [B, blk, k]
        sel = jnp.any(idx[..., None] == kpos, axis=-2) & causal
        return pack_selection(sel)

    n = (T + pad) // blk
    bits = jax.lax.map(block, (jnp.moveaxis(qi.reshape(B, n, blk, -1), 1, 0),
                               jnp.moveaxis(wi.reshape(B, n, blk, -1), 1, 0),
                               jnp.arange(n) * blk))
    return jnp.moveaxis(bits, 0, 1).reshape(B, T + pad, -1)[:, :T]


INDEX_QUERY_BLOCK = 128     # queries a step of `mla_index`: their scores stay in VMEM
INDEX_KEY_BLOCK = 512       # keys a product of the scores


def _sortable(x):
    """float32 -> int32 in the same order (-0.0 as +0.0)."""
    import jax
    import jax.numpy as jnp

    u = jax.lax.bitcast_convert_type(x + 0.0, jnp.int32)
    return u ^ (jax.lax.shift_right_arithmetic(u, 31) & 0x7FFFFFFF)


def _index_kernel(q_ref, k_ref, w_ref, o_ref, s_scr, *, bq: int, bk: int,
                  heads: int, dim: int, topk: int, groups: int):
    """One block of ``bq`` queries: their scores against every key block up
    to the diagonal into VMEM (``s_scr``: 128-lane chunks of sortable int32,
    keys past a query at the least int32), the exact ``topk``-th largest a
    query by a bitwise search of 32 passes, the lower positions of a tie at
    that threshold by a search of positions only where a tie crosses it, and
    the bits written."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    first = pl.program_id(1) * bq
    last = (first + bq - 1) // bk                  # the key block of the diagonal
    per = bk // 128
    qpos = first + jax.lax.broadcasted_iota(jnp.int32, (bq, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bq, 128), 1)

    def scores(kb, carry):
        at = pl.multiple_of(kb * bk, bk)
        k = k_ref[pl.ds(at, bk), :]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for j in range(heads):
            s = jax.lax.dot_general(q_ref[:, j * dim:(j + 1) * dim], k,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * w_ref[:, j:j + 1]
        key = _sortable(acc)
        for c in range(per):
            s_scr[kb * per + c] = jnp.where(at + c * 128 + lane <= qpos,
                                            key[:, c * 128:(c + 1) * 128], _INT_MIN)
        return carry

    jax.lax.fori_loop(0, last + 1, scores, 0)

    def count(test):
        """(bq, 128), the same in every lane: a query's keys for which
        ``test(key, position)`` holds, over the chunks that hold scores."""
        def body(c, acc):
            return acc + test(s_scr[c], c * 128 + lane).astype(jnp.int32)

        acc = jax.lax.fori_loop(0, (last + 1) * per, body, jnp.zeros((bq, 128), jnp.int32))
        return jnp.broadcast_to(jnp.sum(acc, axis=1, keepdims=True), (bq, 128))

    def bit(b, tau):                               # tau in the unsigned order
        cand = tau | jax.lax.shift_left(jnp.int32(1), 31 - b)
        ok = count(lambda key, _pos: key >= (cand ^ _INT_MIN)) >= topk
        return jnp.where(ok, cand, tau)

    tau = jax.lax.fori_loop(0, 32, bit, jnp.zeros((bq, 128), jnp.int32)) ^ _INT_MIN
    need = topk - count(lambda key, _pos: key > tau)   # of the keys equal to tau
    over = count(lambda key, _pos: key >= tau) - topk
    # a query with fewer causal keys than `topk` keeps them all (tau the least int32)
    tied = jnp.max(jnp.where(tau == _INT_MIN, 0, over)) > 0
    nbits = (groups * SELECT_GROUP - 1).bit_length()

    def cutoff():
        """The position of the ``need``-th key equal to tau."""
        def pos_bit(b, r):
            cand = r + jax.lax.shift_left(jnp.int32(1), nbits - 1 - b)
            seen = count(lambda key, pos: (key == tau) & (pos < cand))
            return jnp.where(seen < need, cand, r)

        return jax.lax.fori_loop(0, nbits, pos_bit, jnp.zeros((bq, 128), jnp.int32))

    p = jax.lax.cond(tied, cutoff, lambda: jnp.full((bq, 128), 2 ** 31 - 1, jnp.int32))
    done = (first + bq - 1) // SELECT_GROUP       # the last group with causal keys

    for g in range(groups):
        @pl.when(g <= done)
        def _write():
            word = jnp.zeros((bq, 128), jnp.int32)
            for c in range(32):
                key = s_scr[g * 32 + c]
                pos = g * SELECT_GROUP + c * 128 + lane
                sel = ((key > tau) | ((key == tau) & (pos <= p))) & (pos <= qpos)
                word = word | jax.lax.shift_left(sel.astype(jnp.int32), c)
            o_ref[:, g * 128:(g + 1) * 128] = word

        @pl.when(g > done)
        def _nothing():
            o_ref[:, g * 128:(g + 1) * 128] = jnp.zeros((bq, 128), jnp.int32)


def mla_index_pallas(qi, ki, wi, heads: int, topk: int, interpret: bool = False):
    """The kernel form of ``mla_index_xla`` (same arguments and result): a
    grid step is a block of queries, every key of the row is in VMEM."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, _ = qi.shape
    dim = ki.shape[2]
    bq, bk = INDEX_QUERY_BLOCK, min(INDEX_KEY_BLOCK, T)
    words = selection_words(T)
    ki = jnp.pad(ki, ((0, 0), (0, words * 32 - T), (0, 0)))
    pairs = T * (T + 1) / 2
    return pl.pallas_call(
        functools.partial(_index_kernel, bq=bq, bk=bk, heads=heads, dim=dim,
                          topk=topk, groups=words // 128),
        grid=(B, T // bq),
        in_specs=[pl.BlockSpec((None, bq, heads * dim), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((None, words * 32, dim), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((None, bq, heads), lambda b, i: (b, i, 0))],
        out_specs=pl.BlockSpec((None, bq, words), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, words), jnp.int32),
        scratch_shapes=[pltpu.VMEM((words // 4, bq, 128), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
        name="mla_index",        # the name a device trace shows
        cost_estimate=pl.CostEstimate(
            flops=int(2 * B * heads * dim * pairs), transcendentals=0,
            bytes_accessed=int(qi.size * qi.dtype.itemsize + ki.size * ki.dtype.itemsize
                               + 4 * B * T * (heads + words))),
        interpret=interpret,
    )(qi, ki, wi)


def mla_sparse_xla(q, kv, kr, bits, heads: int, dn: int, live=None):
    """``mla_xla`` over the keys each query's ``bits`` select."""
    return mla_xla(q, kv, kr, heads, dn, bits, live)


def mla_sparse_pallas(q, kv, kr, bits, heads: int, dn: int, interpret: bool = False,
                      live=None):
    """The kernel form of ``mla_sparse_xla`` (same arguments and result):
    ``mla_pallas``'s grid and blocks, the selection's tile of words beside
    each key block."""
    return _mla_call(q, kv, kr, bits, heads, dn, interpret, live)


_mla_sparse_pair = paired.pair(_bound_last(mla_sparse_pallas, 4),
                               _bound_last(mla_sparse_xla, 4), static=2, nondiff=(3, 4))


def _index_pallas_applies(x, index_dim: int) -> bool:
    """Whether the indexer takes ``mla_index``: the latent kernel's
    conditions (a TPU, bfloat16, block-aligned ``T``), heads of whole tiles,
    and a row whose scores for a block of queries and whose keys fit VMEM."""
    import jax
    import jax.numpy as jnp

    T = x.shape[1]
    words = selection_words(T) * 32
    return (jax.default_backend() == "tpu" and _mm_dtype() == jnp.bfloat16
            and index_dim % 128 == 0 and T % INDEX_QUERY_BLOCK == 0
            and words * (4 * INDEX_QUERY_BLOCK + 4 * index_dim) <= 80 * 2 ** 20)


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


class GatedRMSNorm(Module):
    """RMSNorm with a low-rank sigmoid gate on its own output: ``n =
    RMSNorm(x)``, ``n sigmoid((n A) B)``, ``A [D, rank]``, ``B [rank, D]``
    (the products with the matmul dtype's operands, float32 accumulation);
    float32 in the arithmetic and the result."""

    def __init__(self, eps: float = 1e-5, rank: int = 16, param_dtype: str = "float32"):
        self.eps, self.rank, self.param_dtype = eps, rank, param_dtype

    def init(self, rng, in_shape):
        d = in_shape[-1]
        ka, kb = _rng_split(rng, 2)
        return {"scale": np.ones((d,), self.param_dtype),
                "gate_a": _normal(ka, (d, self.rank), d ** -0.5, self.param_dtype),
                "gate_b": _normal(kb, (self.rank, d), self.rank ** -0.5,
                                  self.param_dtype)}, tuple(in_shape)

    def apply(self, params, x, train: bool = False):
        import jax
        import jax.numpy as jnp

        dt = _mm_dtype()
        n = rms_norm(x, params["scale"], self.eps)
        low = jnp.dot(n.astype(dt), jnp.asarray(params["gate_a"]).astype(dt),
                      preferred_element_type=jnp.float32)
        gate = jnp.dot(low.astype(dt), jnp.asarray(params["gate_b"]).astype(dt),
                       preferred_element_type=jnp.float32)
        return n * jax.nn.sigmoid(gate)


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

    def apply(self, params, x, train: bool = False, live=None):
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
            o = gq_attention(q, k, v, self.window, self.heads, self.kv_heads, live)
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
    rope)^-0.5 m^2`` (``yarn_softmax_scale``); output ``(P v) W_o``.

    **Sparse** (``index_topk`` > 0; DeepSeek Sparse Attention's lightning
    indexer): ``index_heads`` heads of ``index_dim``, ``q^I = c_q W^I_q`` (the
    latent before the softmax scale), one key ``k^I = LayerNorm(x W^I_k)``,
    rotary positions on the first ``rope`` lanes of both, ``w = x W^I_w
    index_heads^-0.5``; ``I[t, s] = index_dim^-0.5 sum_j w_j relu(q^I_j .
    k^I_s)``, and a query's softmax runs over its ``index_topk`` keys of
    largest ``I`` alone (every key it has where it has fewer: a row no
    longer than ``index_topk`` is the dense layer). Scopes ``index`` (the
    indexer's projections, norm and positions), ``select`` (the scores and
    the choice: ``mla_index``), ``core``.

    **Gated** (``out_gate``): ``W_qb`` is ``v_dim`` columns a head wider,
    ``[q_nope, q_rope, g] = c_q W_qb``, and a head's output is ``o
    sigmoid(g)`` before ``W_o``.

    ``index_topk`` 0 and ``out_gate`` False are the dense, ungated layer."""

    index_topk, out_gate = 0, False      # a layer pickled before them has neither

    def __init__(self, heads: int, q_rank: int, kv_rank: int, nope: int, rope: int,
                 v_dim: int, rope_theta: float = 10000.0,
                 rope_scaling: Optional[Dict[str, Any]] = None,
                 eps: float = 1e-6, param_dtype: str = "float32",
                 index_heads: int = 0, index_dim: int = 0, index_topk: int = 0,
                 out_gate: bool = False):
        self.heads, self.q_rank, self.kv_rank = heads, q_rank, kv_rank
        self.nope, self.rope, self.v_dim = nope, rope, v_dim
        self.rope_theta, self.rope_scaling = rope_theta, rope_scaling
        self.eps, self.param_dtype = eps, param_dtype
        self.index_heads, self.index_dim = index_heads, index_dim
        self.index_topk, self.out_gate = index_topk, out_gate

    def init(self, rng, in_shape):
        t, d = in_shape
        h, dt = self.heads, self.param_dtype
        keys = _rng_split(rng, 8)
        head = self.nope + self.rope + (self.v_dim if self.out_gate else 0)
        params = {"wq_a": _normal(keys[0], (d, self.q_rank), d ** -0.5, dt),
                  "q_norm": np.ones((self.q_rank,), dt),
                  "wq_b": _normal(keys[1], (self.q_rank, h * head), self.q_rank ** -0.5, dt),
                  "wkv_a": _normal(keys[2], (d, self.kv_rank + self.rope), d ** -0.5, dt),
                  "kv_norm": np.ones((self.kv_rank,), dt),
                  "wkv_b": _normal(keys[3], (self.kv_rank, h * (self.nope + self.v_dim)),
                                   self.kv_rank ** -0.5, dt),
                  "wo": _normal(keys[4], (h * self.v_dim, d),
                                (h * self.v_dim) ** -0.5, dt)}
        if self.index_topk:
            ih, idim = self.index_heads, self.index_dim
            params.update(
                index_wq=_normal(keys[5], (self.q_rank, ih * idim), self.q_rank ** -0.5, dt),
                index_wk=_normal(keys[6], (d, idim), d ** -0.5, dt),
                index_k_scale=np.ones((idim,), dt), index_k_bias=np.zeros((idim,), dt),
                index_ww=_normal(keys[7], (d, ih), d ** -0.5, dt))
        return params, (t, d)

    def index_inputs(self, params, x, c_q):
        """The indexer's ``(qi [B, T, heads * dim], ki [B, T, dim], wi [B, T,
        heads])`` for ``mla_index`` from the layer's input ``x`` and its
        query latent ``c_q`` (after its norm, before the softmax scale)."""
        import jax.numpy as jnp

        from .attention import LayerNorm

        dt = _mm_dtype()
        B, T, _ = x.shape
        ih, idim, rope = self.index_heads, self.index_dim, self.rope

        def dot(a, name, out=jnp.float32):
            return jnp.dot(a.astype(dt), jnp.asarray(params[name]).astype(dt),
                           preferred_element_type=out)

        def positions(a):                  # rotary positions on the first lanes
            return jnp.concatenate(
                [rotary(a[..., :rope].astype(jnp.float32), self.rope_theta,
                        self.rope_scaling).astype(dt), a[..., rope:].astype(dt)], axis=-1)

        # the queries leave their product in the matmul dtype: [T, heads * dim]
        qi = positions(dot(c_q, "index_wq", dt).reshape(B, T, ih, idim)).reshape(B, T, -1)
        ki = LayerNorm(self.eps).apply(
            {"scale": jnp.asarray(params["index_k_scale"]).astype(jnp.float32),
             "bias": jnp.asarray(params["index_k_bias"]).astype(jnp.float32)},
            dot(x, "index_wk"))
        ki = positions(ki[:, :, None, :]).reshape(B, T, idim)
        return qi, ki, dot(x, "index_ww") * np.float32(ih ** -0.5 * idim ** -0.5)

    def _selection(self, params, x, c_q):
        """The selection bits of every query (``pack_selection``'s layout)."""
        with scope("index"):
            qi, ki, wi = self.index_inputs(params, x, c_q)
        with scope("select"):
            if _index_pallas_applies(x, self.index_dim):
                return mla_index_pallas(qi, ki, wi, self.index_heads, self.index_topk,
                                        paired.INTERPRET)
            return mla_index_xla(qi, ki, wi, self.index_heads, self.index_topk)

    def apply(self, params, x, train: bool = False, live=None):
        import jax
        import jax.numpy as jnp

        dt = _mm_dtype()
        B, T, _ = x.shape
        h, nope, rope = self.heads, self.nope, self.rope
        kernel = _mla_pallas_applies(x, self.v_dim)
        # the kernel's layout (above `mla_xla`): a query is its lanes and zeros
        # to a whole tile; content keys of no whole tile are followed by zeros
        # to the query's width, and the rotary key lies in the lanes of their
        # last tile they leave free
        front = nope % 128 if kernel else 0          # zeros before k_r's lanes
        lanes = -(nope + rope) % 128 if kernel else 0    # zeros after the rotary lanes
        keys = nope + rope + lanes if front else nope    # a head's key lanes in kv

        def dot(a, name, out=jnp.float32):
            return jnp.dot(a.astype(dt), jnp.asarray(params[name]).astype(dt),
                           preferred_element_type=out)

        def positions(a, before=0):                  # [B, T, heads, rope] float32
            with scope("proj_in"):
                a = rotary(a, self.rope_theta, self.rope_scaling)
            with scope("core"):                      # laid out as the core reads it
                return jnp.pad(a, ((0, 0),) * 3 + ((before, lanes),)).astype(dt)

        def columns(first, last):          # of every head's columns of W_qb
            w = jnp.asarray(params["wq_b"]).astype(dt)
            return w.reshape(self.q_rank, h, -1)[..., first:last].reshape(self.q_rank, -1)

        # the softmax scale rides on the queries' latent: one pass over [T,
        # q_rank]. Where W_qb holds the gates' columns too, the queries'
        # columns and the gates' are two products: the gates' comes after the
        # core, so the two never stand in memory together, and the queries'
        # leaves its product in the matmul dtype (a float32 copy of it would be
        # the layer's largest array)
        scale = (nope + rope) ** -0.5 * yarn_softmax_scale(self.rope_scaling)
        sparse = self.index_topk and T > self.index_topk
        with scope("proj_in"):
            c_q = rms_norm(dot(x, "wq_a"), params["q_norm"], self.eps)
            if self.out_gate:
                q = jnp.dot((c_q * np.float32(scale)).astype(dt), columns(0, nope + rope),
                            preferred_element_type=dt).reshape(B, T, h, nope + rope)
            else:
                q = dot(c_q * np.float32(scale), "wq_b").reshape(B, T, h, nope + rope)
        bits = self._selection(params, x, c_q) if sparse else None
        with scope("core"):
            q_nope = q[..., :nope].astype(dt)
        q_rope = positions(q[..., nope:].astype(jnp.float32))
        with scope("core"):
            q = jnp.concatenate([q_nope, q_rope], axis=-1).reshape(B, T, -1)
        with scope("proj_in"):
            ckv = dot(x, "wkv_a")
        kr = positions(ckv[..., None, self.kv_rank:], front).reshape(B, T, -1)
        with scope("proj_in"):
            c_kv = rms_norm(ckv[..., :self.kv_rank], params["kv_norm"], self.eps)
            if keys == nope:                         # a head's keys, then its values
                kv = dot(c_kv, "wkv_b", dt)
            else:                # W_kvb's columns laid out: zero columns after the keys
                w = jnp.asarray(params["wkv_b"]).astype(dt).reshape(self.kv_rank, h, -1)
                w = jnp.concatenate([w[..., :nope], jnp.zeros_like(w[..., :keys - nope]),
                                     w[..., nope:]], axis=-1).reshape(self.kv_rank, -1)
                kv = jnp.dot(c_kv.astype(dt), w, preferred_element_type=dt)
        with scope("core"):
            if not kernel:
                o = mla_xla(q, kv, kr, h, keys, bits, live)
            elif bits is None:
                o = _mla_pair(q, kv, kr, _bound(live, x), h, keys)
            else:
                o = _mla_sparse_pair(q, kv, kr, bits, _bound(live, x), h, keys)
        with scope("proj_out"):
            if self.out_gate:
                gate = jnp.dot(c_q.astype(dt), columns(nope + rope, None),
                               preferred_element_type=jnp.float32)
                o = (o.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(dt)
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
                               self.window, self.heads // 2, self.kv_heads // 2, self.eps,
                               carry.get("live"))
        with scope("proj_out"):
            return affine(o, "wo", "bo")

    def apply(self, params, x, train: bool = False, live=None):
        return self.apply_carry(params, x, {} if live is None else {"live": live})


def _by_rows(fn, x, most_tokens: int = 8192, positionwise: bool = False, live=None):
    """``fn`` over ``x [B, T, D]``, rows in equal groups of at most
    ``most_tokens`` tokens one after the other: what a sublayer holds between
    its products (float32 heads before their norm, a wide layer's hidden
    units) then never stands for the whole batch. A row longer than
    ``most_tokens`` goes through alone; where ``fn`` is ``positionwise`` (it
    treats every position by itself: a norm, a SwiGLU) such a row is cut into
    equal pieces of at most ``most_tokens`` positions, which go through as
    rows do. With ``live`` (``[B]``: each row's bound, ``live_lengths``) it
    is ``fn(rows, their live)``."""
    import jax

    B, T, D = x.shape
    if positionwise and T > most_tokens:
        pieces = equal_pieces(T, most_tokens)
        return _by_rows(fn, x.reshape(B * pieces, T // pieces, D),
                        most_tokens).reshape(B, T, -1)
    rows = (x,) if live is None else (x, live)
    groups = equal_pieces(B, max(1, most_tokens // T))
    if groups == 1:
        return fn(*rows)
    return jax.lax.map(lambda a: fn(*a), tuple(
        a.reshape(groups, B // groups, *a.shape[1:]) for a in rows)).reshape(x.shape)


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


def _norm(kind: str, eps: float, param_dtype: str, rank: int = 0) -> Module:
    """A sublayer's norm: ``"rms"`` (a gain), ``"gated"`` (``GatedRMSNorm``
    of ``rank``) or ``"layer"`` (mean, gain and bias), float32 in the
    arithmetic and, on float32 input, the result."""
    if kind == "rms":
        return RMSNorm(eps, param_dtype)
    if kind == "gated":
        return GatedRMSNorm(eps, rank, param_dtype)
    if kind == "layer":
        from .attention import LayerNorm

        return LayerNorm(eps, param_dtype)
    raise ValueError(f"unknown norm {kind!r}")


class DecoderLayer(Module):
    """Two sublayers, a token mixer and an FFN: a ``SwiGLU`` (``mlp``), or an
    ``ExpertLayer`` (``moe``) beside a shared ``SwiGLU`` (``shared``), each
    behind its norm (``norm``: RMSNorm, a gated RMSNorm of ``norm_rank``, or
    LayerNorm with a bias) and inside
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
    Every other mixer runs under ``_by_rows`` and never sees the carry; an
    attention gets its ``live`` (``CausalLM``'s ``[B]`` bound, which
    ``_by_rows`` slices with the rows) as ``apply(..., live=...)``.

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
                 mixer: str = "attn", norm_rank: int = 0):
        if (mlp is None) == (moe is None):
            raise ValueError("a layer has a dense MLP or an expert layer, one of them")
        self.mixer = mixer
        self.parts: List[Tuple[str, Module]] = [
            (mixer + "_norm", _norm(norm, eps, param_dtype, norm_rank)), (mixer, attn),
            ("mlp_norm", _norm(norm, eps, param_dtype, norm_rank))]
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
        # the attention's bound: a state-space mixer reads every position
        live = (carry or {}).get("live") if mixer == "attn" else None

        def apply(name, xc):
            with scope(name):
                return part[name].apply(params[name], xc)

        def normed(name, norm):          # the sublayer behind its norm
            def run(xc, live=None):
                bound = {} if live is None else {"live": live}
                return part[name].apply(params[name], apply(norm, xc), **bound)
            return run

        if "attn_hc" in part:
            return self._hyper_connected(part, params, x, normed, live)

        def sublayer(name, norm):
            def run(xc, live=None):
                return xc + normed(name, norm)(xc, live)
            return run

        # a part's scope is pushed around its `_by_rows`, so the loop's own
        # slicing and stacking is counted with the part it serves
        with scope(mixer):
            if getattr(part[mixer], "crosses_layers", False):
                h = x + part[mixer].apply_carry(params[mixer],
                                                apply(mixer + "_norm", x), carry)
            else:
                h = _by_rows(sublayer(mixer, mixer + "_norm"), x, live=live)
        if "mlp" in part:
            with scope("mlp"):
                return _by_rows(sublayer("mlp", "mlp_norm"), h, positionwise=True), None
        hn = apply("mlp_norm", h)
        if "shared" in part:
            h = h + apply("shared", hn)
        with scope("moe"):
            return part["moe"].apply_with_load(params["moe"], hn, add_to=h)

    def _hyper_connected(self, part, params, x, normed, live):
        T = x.shape[1]
        hc = part["attn_hc"]
        with scope("attn_hc"):
            x_in, coeffs = hc.pre(params["attn_hc"], x)
        # attention needs a row's every key: rows one after the other, whole
        with scope("attn"):
            y = _by_rows(normed("attn", "attn_norm"), x_in, max(T, 8192), live=live)
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


# ---------------------------------------------------------------------------
# the language model's head: each position's log-probability of its target
# ---------------------------------------------------------------------------
#
# ``x [B, T, hidden]`` (normed rows in the matmul dtype), the head ``[hidden,
# vocab]`` or a tied table ``[vocab, hidden]`` contracted in place, targets
# ``[B, T]`` int32 -> ``logits[target] - logsumexp(logits)`` a position,
# float32; the logits are products in the operands' dtype accumulated in
# float32.

HEAD_VMEM = 48 * 2 ** 20       # what a step of `head_logprob` may hold
HEAD_ROWS = 512                # positions a tile: the head is read once a tile
HEAD_LOGITS = 2 ** 20          # float32 logits a step may hold (half from a tied table)


def _head_tiles(hidden: int, vocab: int, tied: bool = False) -> Optional[Tuple[int, int]]:
    """``(tm, tn)`` of ``head_logprob``: ``HEAD_ROWS`` positions, and a
    vocabulary tile of whole 128-lane tiles whose step fits ``HEAD_VMEM`` (two
    buffers of the rows' and the head's bfloat16 tiles, three float32 ``[tm,
    tn]`` temporaries: the product, its exponentials, the target's pick) and
    holds at most ``HEAD_LOGITS`` logits (a tied table's block is contracted
    on its minor dimension; at ``2 ** 20`` such a step ran 1.4 times as long
    on a v5e). Of those, the widest that divides the vocabulary if one is at
    least half the widest: a last tile that is partial in the head's minor
    dimension ran its step several times as long on a v5e. None where no
    tile fits."""
    tm = HEAD_ROWS
    most = HEAD_LOGITS // (2 if tied else 1) // tm
    fits = [tn for tn in range(128, most + 1, 128)
            if 4 * hidden * (tm + tn) + 12 * tm * tn <= HEAD_VMEM]
    if not fits:
        return None
    whole = [tn for tn in fits if vocab % tn == 0 and 2 * tn >= fits[-1]]
    return tm, (whole or fits)[-1]


def _head_step(x_ref, w_ref, t_ref, m_scr, l_scr, p_scr, j, *, tn: int, tied: bool,
               valid: Optional[int]):
    """Vocabulary tile ``j`` of a position tile: its block of logits, float32,
    folded into the rows' running maximum and sum of exponentials; the
    target's logit added where it falls in the tile. Columns from ``valid``
    on (a last tile past the vocabulary) are ``-inf``."""
    import jax
    import jax.numpy as jnp

    s = jax.lax.dot_general(x_ref[...], w_ref[...],
                            (((1,), (1 if tied else 0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    p_scr[...] += jnp.sum(jnp.where(col == t_ref[...] - j * tn, s, 0.0),
                          axis=1, keepdims=True)
    if valid is not None:
        s = jnp.where(col < valid, s, -jnp.inf)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    l_scr[...] = jnp.exp(m_prev - m_new) * l_scr[...] + jnp.sum(
        jnp.exp(s - m_new), axis=1, keepdims=True)
    m_scr[...] = m_new


def head_logprob_pallas(x, head, target, tied: bool, interpret: bool = False):
    """The kernel form of ``head_logprob_xla`` (same arguments and result):
    the ``N = B T`` positions in tiles, ``parallel``, the vocabulary in tiles,
    ``arbitrary``; a step's ``[tm, tn]`` logits never leave VMEM, and the
    head is read once a position tile. A last tile of positions or of the
    vocabulary may be partial: its rows past ``N`` are never stored, its
    columns past ``vocab`` are masked, and the head is never padded."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    B, T, hidden = x.shape
    N = B * T
    vocab = head.shape[0 if tied else 1]
    tm, tn = _head_tiles(hidden, vocab, tied)
    tm, tn = min(tm, N), min(tn, vocab)       # a whole dimension is a legal block
    nm, nv = -(-N // tm), -(-vocab // tn)
    step = functools.partial(_head_step, tn=tn, tied=tied)

    def kernel(x_ref, w_ref, t_ref, o_ref, m_scr, l_scr, p_scr):
        j = pl.program_id(1)
        args = (x_ref, w_ref, t_ref, m_scr, l_scr, p_scr, j)

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, _NEG)
            l_scr[...] = jnp.zeros_like(l_scr)
            p_scr[...] = jnp.zeros_like(p_scr)

        if vocab % tn:       # only the last tile has columns past the vocabulary
            pl.when(j < nv - 1)(lambda: step(*args, valid=None))
            pl.when(j == nv - 1)(lambda: step(*args, valid=vocab - (nv - 1) * tn))
        else:
            step(*args, valid=None)

        @pl.when(j == nv - 1)
        def _store():
            o_ref[...] = p_scr[...] - (m_scr[...] + jnp.log(l_scr[...]))

    head_spec = (pl.BlockSpec((tn, hidden), lambda i, j: (j, 0)) if tied
                 else pl.BlockSpec((hidden, tn), lambda i, j: (0, j)))
    out = pl.pallas_call(
        kernel,
        grid=(nm, nv),
        in_specs=[pl.BlockSpec((tm, hidden), lambda i, j: (i, 0)), head_spec,
                  pl.BlockSpec((tm, 1), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((tm, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), f32),
        scratch_shapes=[pltpu.VMEM((tm, 1), f32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="head_logprob",         # the name a device trace shows
        cost_estimate=pl.CostEstimate(
            flops=int(2 * N * hidden * vocab), transcendentals=int(N * vocab),
            bytes_accessed=int(x.size * x.dtype.itemsize
                               + nm * head.size * head.dtype.itemsize + 8 * N)),
        interpret=interpret,
    )(x.reshape(N, hidden), head, target.astype(jnp.int32).reshape(N, 1))
    return out.reshape(B, T)


def head_logprob_xla(x, head, target, tied: bool):
    """The plain form: a row in equal pieces whose float32 logits are at
    most ``LOGITS_BLOCK`` numbers, one piece at a time; the target's logit
    picked from them and their ``logsumexp`` taken. It is also the kernel's
    VJP (recomputed)."""
    import jax
    import jax.numpy as jnp

    B, T, hidden = x.shape
    vocab = head.shape[0 if tied else 1]
    over = (((1,), (1 if tied else 0,)), ((), ()))

    def piece(a):
        xr, tr = a
        logits = jax.lax.dot_general(xr, head, over, preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, tr[:, None], axis=-1)[:, 0]
        return picked - jax.nn.logsumexp(logits, axis=-1)

    pieces = equal_pieces(T, max(1, LOGITS_BLOCK // vocab))
    return jax.lax.map(piece, (x.reshape(B * pieces, T // pieces, hidden),
                               target.reshape(B * pieces, T // pieces))).reshape(B, T)


_head_pair = paired.pair(head_logprob_pallas, head_logprob_xla, static=1, nondiff=(2,))


def _head_kernel_applies(backend: str, dtype, hidden: int, vocab: int) -> bool:
    """Whether the head takes ``head_logprob_pallas``: a TPU, bfloat16
    operands, a hidden width of whole 128-lane tiles, and tiles whose step
    fits VMEM (``_head_tiles``)."""
    import jax.numpy as jnp

    return (backend == "tpu" and dtype == jnp.bfloat16 and hidden % 128 == 0
            and _head_tiles(hidden, vocab) is not None)


def head_logprob(x, head, target, tied: bool):
    """Each position's ``log_softmax(x head)[target]``, float32: the kernel
    where it applies, the plain form elsewhere."""
    import jax

    vocab = head.shape[0 if tied else 1]
    if _head_kernel_applies(jax.default_backend(), x.dtype, x.shape[2], vocab):
        return _head_pair(x, head, target, tied)
    return head_logprob_xla(x, head, target, tied)


class CausalLM(Module):
    """Token ids ``[B, T]`` -> the next token's log-probability at every
    position, ``[B, T]`` float32: ``out[t] = log_softmax(logits_t)[id_{t+1}]``,
    the last position's target the pad id. The head is ``head_logprob``: on
    a TPU one kernel whose float32 logits never leave VMEM, elsewhere blocks
    of positions at a time (at most ``LOGITS_BLOCK`` numbers); the logits are
    never an output.

    With ``streams`` > 1 (layers with a hyper-connected residual path) the
    state between layers is ``[B, T, streams hidden]``: every stream starts as
    a copy of the embedding, and their sum goes into the final norm.

    **The carry between layers.** Beside ``h`` the loop over layers hands on
    one small mapping, which a layer's mixer may write and a later one read
    (``DecoderLayer``): a scan's output as ``memory``, a layer's keys and
    values as ``kv``. At layer 0 it holds ``live``, each row's positions up
    to its last id that is not ``pad_id`` (``live_lengths``), at which the
    attention kernels stop: a position past it is a pad no real position
    reads (the model is causal), and its attention output is zero. It lives
    for one call and is no output.

    ``norm`` chooses the final norm (``"rms"`` or ``"layer"``); with ``tied``
    the head is the embedding table transposed and ``params`` has no ``head``.

    **The multi-token-prediction module** (``mtp``, a ``DecoderLayer``;
    DeepSeek-V3's, arXiv:2412.19437 section 2.2): with ``h_t`` the last
    layer's output before the final norm, ``u_t = [RMSNorm_e(E[id_{t+1}]) ;
    RMSNorm_h(h_t)] W_eh``, ``v = mtp(u)``, and ``mtp[t] =
    log_softmax(RMSNorm_s(v_t) W_head)[id_{t+2}]``, the embedding and the head
    the model's own, the last two positions' targets (and position ``T -
    1``'s ``id_{t+1}``) the pad id. It runs only where the node
    ``mtp_logprob`` (``[B, T]`` float32) is fetched; its logits go through
    ``head_logprob`` as the main ones do. Scopes
    ``mtp``: ``fuse`` (the embedding of the next ids, the two norms, ``W_eh``),
    ``layer<L>`` (its decoder layer, named as the layer after the ``L`` held
    ones), ``logits`` (its norm and the head).

    A container for ``DNNModel``'s ``fetchDict``: the node ``expert_load``
    is ``[B, sparse layers, experts held]``, the visits each held expert took
    from that row's positions (float32: what the routing counted); where
    ``mtp_logprob`` is fetched too, the module's expert layer is its last
    row."""

    is_container = True
    LOAD = "expert_load"
    MTP = "mtp_logprob"
    tied = False             # a model pickled before the tied head has none
    mtp = None               # nor one pickled before the MTP module

    def __init__(self, vocab_size: int, hidden: int, layers: Sequence[DecoderLayer],
                 pad_id: int = 0, eps: float = 1e-5, param_dtype: str = "float32",
                 streams: int = 1, norm: str = "rms", tied: bool = False,
                 mtp: Optional[DecoderLayer] = None):
        if mtp is not None and streams > 1:
            raise ValueError("a multi-token-prediction module reads one stream")
        self.vocab_size, self.hidden, self.pad_id = vocab_size, hidden, pad_id
        self.layers = list(layers)
        self.final_norm = _norm(norm, eps, param_dtype)
        self.param_dtype = param_dtype
        self.streams = streams
        self.tied = tied
        self.mtp = mtp
        if mtp is not None:                  # RMSNorm_e, RMSNorm_h, RMSNorm_s
            self.mtp_norm = RMSNorm(eps, param_dtype)

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
        if self.mtp is not None:
            import jax

            k_eh, k_layer = _rng_split(jax.random.fold_in(rng, len(keys)), 2)
            gain = self.mtp_norm.init(None, (t, self.hidden))[0]
            params["mtp"] = {
                "enorm": gain, "hnorm": dict(gain), "norm": dict(gain),
                "eh_proj": {"kernel": _normal(k_eh, (2 * self.hidden, self.hidden),
                                              (2 * self.hidden) ** -0.5, self.param_dtype)},
                "layer": self.mtp.init(k_layer, (t, self.hidden))[0]}
        return params, (t,)

    def batch_counts(self, ids) -> Dict[str, int]:
        """What the attention kernels' work depends on in a host batch of ids
        ``[B, T]``, by the function that bounds them (``live_lengths``):
        ``attn_query_blocks``, the rows' query blocks of ``LIVE_COUNT_BLOCK``
        positions, and ``attn_live_blocks``, those that hold a position up to
        their row's bound (``DNNModel`` keeps them in ``BatchTiming.counts``)."""
        ids = np.asarray(ids)
        live = live_lengths(ids.reshape(-1, ids.shape[-1]), self.pad_id)
        return {"attn_query_blocks": int(live.size * -(-ids.shape[-1] // LIVE_COUNT_BLOCK)),
                "attn_live_blocks": int(np.sum(-(-live // LIVE_COUNT_BLOCK)))}

    def layer_paths(self, prefix: str = "") -> List[str]:
        return [prefix + self.LOAD] + [prefix + self.MTP] * (self.mtp is not None) \
            + [f"{prefix}layer{i}" for i in range(len(self.layers))]

    def _log_probs(self, params, x, ids, norm=None):
        """The log-probability of each position's next id (``norm``: the
        ``(module, params)`` before the head; the final norm without it)."""
        import jax.numpy as jnp

        norm, gain = norm or (self.final_norm, params["final_norm"])
        dt = _mm_dtype()
        # [hidden, vocab], or the tied table [vocab, hidden] contracted in place
        head = jnp.asarray(params["embed"]["table"] if self.tied
                           else params["head"]["kernel"]).astype(dt)
        target = jnp.concatenate(
            [ids[:, 1:], jnp.full_like(ids[:, :1], self.pad_id)], axis=1)
        return head_logprob(norm.apply(gain, x).astype(dt), head, target, self.tied)

    def _mtp(self, params, h, ids, live):
        """(``mtp[t]``, ``[B, T]`` float32; the module's expert visits or
        None) from the last layer's output ``h`` before the final norm, its
        attention bounded at ``live``."""
        import jax.numpy as jnp

        dt, p = _mm_dtype(), params["mtp"]
        following = jnp.concatenate(
            [ids[:, 1:], jnp.full_like(ids[:, :1], self.pad_id)], axis=1)
        with scope("fuse"):
            e = jnp.take(jnp.asarray(params["embed"]["table"]), following, axis=0
                         ).astype(jnp.float32)
            joined = jnp.concatenate([self.mtp_norm.apply(p["enorm"], e),
                                      self.mtp_norm.apply(p["hnorm"], h)], axis=-1)
            u = jnp.dot(joined.astype(dt), jnp.asarray(p["eh_proj"]["kernel"]).astype(dt),
                        preferred_element_type=jnp.float32)
        with scope(f"layer{len(self.layers)}"):
            v, load = self.mtp.apply_with_load(p["layer"], u, {"live": live})
        with scope("logits"):        # the targets: the ids two ahead
            return self._log_probs(params, v, following, (self.mtp_norm, p["norm"])), load

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
            live = live_lengths(ids, self.pad_id)
        loads = []
        carry: Dict[str, Any] = {"live": live}      # what a layer hands a later one
        for i, layer in enumerate(self.layers):
            with scope(f"layer{i}"):
                h, load = layer.apply_with_load(params[f"layer{i}"], h, carry)
            if load is not None:
                loads.append(load)
            if taps and taps_out is not None and f"{_prefix}layer{i}" in taps:
                taps_out[f"{_prefix}layer{i}"] = h
        if taps and taps_out is not None and _prefix + self.MTP in taps:
            if self.mtp is None:
                raise KeyError("mtp_logprob: the model has no multi-token-prediction module")
            with scope("mtp"):
                taps_out[_prefix + self.MTP], load = self._mtp(params, h, ids, live)
            if load is not None:
                loads.append(load)
        if taps and taps_out is not None and _prefix + self.LOAD in taps:
            if not loads:
                raise KeyError("expert_load: the model has no expert layer")
            taps_out[_prefix + self.LOAD] = jnp.stack(loads, axis=1)
        with scope("head"):          # the final norm and head_logprob
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
                     init: bool = True, index_heads: int = 0, index_dim: int = 0,
                     index_topk: int = 0, out_gate: bool = False,
                     gated_norm_rank: int = 0, n_group: int = 1,
                     topk_group: int = 1, nextn: int = 0) -> FunctionModel:
    """``causal_lm``'s sibling for a model with latent attention and, where
    ``streams`` > 1, a hyper-connected residual path: the same ``CausalLM`` of
    the same ``DecoderLayer`` s, every layer a ``LatentAttention`` and an
    expert layer where ``sparse[i]``, else a dense SwiGLU. ``index_*`` make
    the attention sparse and ``out_gate`` gates its heads (``LatentAttention``);
    ``gated_norm_rank`` > 0 gates the two norms before a layer's sublayers
    (``GatedRMSNorm``; the final norm stays plain); ``n_group`` /
    ``topk_group`` limit the routing to groups (``moe.ExpertLayer``);
    ``nextn`` 1 adds the multi-token-prediction module (``CausalLM``: a
    latent-attention and expert layer, the config's
    ``num_nextn_predict_layers``). Their defaults are the model without any
    of them."""
    import jax

    from .moe import ExpertLayer
    from .residual import HyperConnection

    if nextn not in (0, 1):
        raise ValueError(f"{nextn} multi-token-prediction modules: one is built")
    hyper = HyperConnection(streams, sinkhorn_iters, hc_eps, hc_clamp, eps,
                            param_dtype) if streams > 1 else None
    norm = dict(norm="gated", norm_rank=gated_norm_rank) if gated_norm_rank else {}
    layers = []
    for is_sparse in list(sparse) + [True] * nextn:     # the MTP's layer last
        attn = LatentAttention(heads, q_rank, kv_rank, nope, rope, v_dim, rope_theta,
                               rope_scaling, eps, param_dtype, index_heads, index_dim,
                               index_topk, out_gate)
        if is_sparse:
            moe = ExpertLayer(num_experts, experts_held, top_k, expert_hidden,
                              scoring=scoring, norm_topk=norm_topk, scale=scale,
                              first_expert=first_expert, param_dtype=param_dtype,
                              n_group=n_group, topk_group=topk_group)
            shared = SwiGLU(expert_hidden * shared_experts, param_dtype) \
                if shared_experts else None
            layers.append(DecoderLayer(attn, moe=moe, shared=shared, eps=eps,
                                       param_dtype=param_dtype, hyper=hyper, **norm))
        else:
            layers.append(DecoderLayer(attn, mlp=SwiGLU(dense_hidden, param_dtype),
                                       eps=eps, param_dtype=param_dtype, hyper=hyper,
                                       **norm))
    mtp = layers.pop() if nextn else None
    module = CausalLM(vocab_size, hidden, layers, pad_id, eps, param_dtype, streams,
                      mtp=mtp)
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
