"""Sequence models: attention (dense + ring), transformer encoder, (Bi)LSTM.

The reference's sequence story is CNTK BiLSTM inference (notebooks
"DeepLearning - BiLSTM Medical Entity Extraction"; the CNTK model is loaded
through the generic evaluator, CNTK/SerializableFunction.scala:23-143). The
TPU-first redesign makes sequence modeling a native model family on the
module tree — addressable layers, taps, DNNModel/ImageFeaturizer machinery —
and makes LONG sequences first-class:

  - ``ring_attention``: blockwise attention with the KV shards rotating
    around the ``seq`` mesh axis via ``ppermute`` (one ICI hop per step)
    and a streaming, numerically-stable softmax (flash-style running
    max/denominator). Peak memory per chip is O(T_local^2) instead of
    O(T^2); the sequence scales with the number of chips.
  - ``MultiHeadAttention(ring_axis="seq")``: the same module runs dense
    single-chip or ring-parallel under ``shard_map`` — the module code does
    not change, only the mesh placement does (scaling-book style: annotate,
    let XLA/collectives do the rest).
  - ``LSTM``/``BiLSTM``: the recurrence ``lstm_scan``, one of two forms of
    one arithmetic. On a TPU the Pallas kernel ``lstm_scan_pallas`` (state in
    VMEM through the blocks of time, the input product inside a step, the
    reverse direction by its index map, a ``BiLSTM``'s two halves written
    side by side by the second direction's call). Elsewhere, and as the
    kernel's VJP, ``lstm_scan_xla``: ``lax.scan`` over time, a block of K
    steps a loop trip (static shapes; the K steps are the same ``cell``
    called in turn), concat of forward/backward passes. One step a trip made
    XLA write each ``h_t`` as a row of every tile of the ``[T, B, H]`` output,
    57% of the tagger's device time; K is the rows of a tile, read from
    ``h``'s dtype.

All modules follow module.py conventions: shapes exclude the batch dim,
``init -> (params, out_shape)``, bf16 matmuls via matmul_dtype().
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple

import numpy as np

from ..obs.scopes import scope
from .module import Fn, Module, Sequential, _rng_split, matmul_dtype
from .paired import pair


# ---------------------------------------------------------------------------
# functional attention kernels
# ---------------------------------------------------------------------------

def _flash_dispatch(q, k, v, causal, q_offset, k_offset):
    """Route to the Pallas TPU flash-attention kernel when it applies.

    Dispatch conditions: TPU backend, bf16 inputs (the kernel's MXU passes
    round like bf16, so the f32 path keeps the exact XLA lowering for
    matmul_precision('float32') equivalence tests), no shard offsets,
    full-square causal only, seq lens divisible by the kernel's 128 block,
    head dim 64 or a multiple of 128 (lane width). Returns None to fall back.
    ``MMLSPARK_TPU_NO_FLASH=1`` forces the XLA path.

    Dispatch requires T >= MMLSPARK_TPU_FLASH_MIN_T (default 1024). Earlier
    claim, not measured in this round: the speedup over the XLA lowering
    grows with length (about even at T=1024) and the decisive win is
    MEMORY — the XLA path's f32 score tensor alone is ~17 GB at B=2, H=8,
    T=16384, while the flash kernel streams K/V blocks through VMEM.
    Verified on a v5e under jax 0.9.0 (chip_smoke.py): the library kernel's
    default block sizes compile at D=64, T=2048 and 8192, causal and not,
    and agree with the f32 XLA reference to bf16 scale.
    """
    if os.environ.get("MMLSPARK_TPU_NO_FLASH", "") not in ("", "0"):
        return None
    import jax
    import jax.numpy as jnp

    if q.dtype != jnp.bfloat16:
        return None
    if jax.default_backend() != "tpu":
        return None
    if q_offset or k_offset:
        return None
    _, tq, _, d = q.shape
    tk = k.shape[1]
    if causal and tq != tk:
        return None
    if tq % 128 or tk % 128 or (d != 64 and d % 128):
        return None
    min_t = int(os.environ.get("MMLSPARK_TPU_FLASH_MIN_T", "1024"))
    if tk < min_t:
        return None
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention)

    o = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal, sm_scale=1.0 / math.sqrt(d))
    return o.transpose(0, 2, 1, 3).astype(v.dtype)


def dense_attention(q, k, v, causal: bool = False,
                    q_offset: int = 0, k_offset: int = 0):
    """Reference attention. q:[B,Tq,H,D] k/v:[B,Tk,H,D] -> [B,Tq,H,D].
    ``*_offset`` are global position offsets for causal masking of shards.
    On TPU with bf16 inputs the inner computation dispatches to the Pallas
    flash-attention kernel (see _flash_dispatch)."""
    import jax.numpy as jnp

    flash = _flash_dispatch(q, k, v, causal, q_offset, k_offset)
    if flash is not None:
        return flash

    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, v.dtype.type(scale) * k,
                   preferred_element_type=jnp.float32)
    if causal:
        qpos = jnp.arange(q.shape[1]) + q_offset
        kpos = jnp.arange(k.shape[1]) + k_offset
        s = jnp.where(kpos[None, :] > qpos[:, None], -jnp.inf, s)
    # rows with no valid key (a query shard strictly before every key in the
    # block) must yield zeros, not NaN from exp(-inf - -inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(s), s - safe_m, -jnp.inf))
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(denom == 0.0, 1.0, denom)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def ring_attention(q, k, v, axis_name: str, axis_size: int,
                   causal: bool = False):
    """Sequence-parallel attention inside ``shard_map``: every chip holds a
    [B, T_local, H, D] shard of q/k/v along ``axis_name``; KV blocks rotate
    around the ring (ppermute) while each chip accumulates its queries'
    output with a streaming softmax (running max ``m``, denominator ``l``).

    Design: the scaling-book recipe for context parallelism — compute rides
    the MXU on [T_local, T_local] blocks, comms ride ICI one neighbor hop per
    step, overlap comes from XLA pipelining the permute with the block
    matmul. Equivalent to dense attention over the gathered sequence to
    ~1e-5 (test_attention.py proves it on an 8-device mesh).
    """
    import jax
    import jax.numpy as jnp

    B, T, H, D = q.shape
    my = jax.lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32)

    o = jnp.zeros((B, T, H, D), dtype=jnp.float32)
    m = jnp.full((B, H, T), -jnp.inf, dtype=jnp.float32)
    l = jnp.zeros((B, H, T), dtype=jnp.float32)
    # mark the fresh accumulators as device-varying over the ring axis
    # (shard_map's vma typing requires scan carries in == carries out)
    # analysis: allow J001 -- pinned jax 0.9.0 always has pcast
    o, m, l = (jax.lax.pcast(a, (axis_name,), to="varying")
               for a in (o, m, l))

    def block(carry, step):
        o, m, l, kb, vb = carry
        kv_idx = (my - step) % axis_size  # whose KV shard we hold this step
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32)) * scale
        if causal:
            qpos = jnp.arange(T) + my * T
            kpos = jnp.arange(T) + kv_idx * T
            s = jnp.where(kpos[None, :] > qpos[:, None], -jnp.inf, s)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # exp(-inf - -inf) guards: rows with no valid keys yet stay zeroed
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(jnp.where(jnp.isfinite(s), s - safe_m[..., None], -jnp.inf))
        p = jnp.where(jnp.isfinite(p), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, vb.astype(jnp.float32))
        o = o * corr.transpose(0, 2, 1)[..., None] + pv
        # rotate KV to the next neighbor (ring over ICI)
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (o, m_new, l, kb, vb), None

    (o, m, l, _, _), _ = jax.lax.scan(
        block, (o, m, l, k, v), jnp.arange(axis_size))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (causal edge) -> 0 out
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# modules
# ---------------------------------------------------------------------------

class LayerNorm(Module):
    """LayerNorm over the last dim (f32 statistics, dtype-preserving)."""

    def __init__(self, eps: float = 1e-5, param_dtype: str = "float32"):
        self.eps = eps
        self.param_dtype = param_dtype

    def init(self, rng, in_shape):
        d = in_shape[-1]
        return {"scale": np.ones((d,), self.param_dtype),
                "bias": np.zeros((d,), self.param_dtype)}, tuple(in_shape)

    def apply(self, params, x, train: bool = False):
        import jax
        import jax.numpy as jnp

        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + self.eps)
        return (y * params["scale"] + params["bias"]).astype(x.dtype)


class Embed(Module):
    """Token ids [T] -> embeddings [T, dim] (gather; rides HBM, not MXU)."""

    def __init__(self, vocab_size: int, dim: int):
        self.vocab_size = vocab_size
        self.dim = dim

    def init(self, rng, in_shape):
        import jax

        table = jax.random.normal(rng, (self.vocab_size, self.dim),
                                  dtype=np.float32) * 0.02
        return {"table": table}, tuple(in_shape) + (self.dim,)

    def apply(self, params, x, train: bool = False):
        import jax.numpy as jnp

        return jnp.take(jnp.asarray(params["table"]), x.astype(jnp.int32),
                        axis=0)


class MultiHeadAttention(Module):
    """Self-attention on [B, T, D]. ``ring_axis`` switches the inner kernel
    to ring_attention when applied under shard_map with that axis present
    (T then is the LOCAL shard length); dense otherwise."""

    def __init__(self, num_heads: int, causal: bool = False,
                 ring_axis: Optional[str] = None,
                 ring_axis_size: Optional[int] = None):
        self.num_heads = num_heads
        self.causal = causal
        self.ring_axis = ring_axis
        self.ring_axis_size = ring_axis_size

    def init(self, rng, in_shape):
        import jax

        t, d = in_shape
        if d % self.num_heads:
            raise ValueError(f"dim {d} not divisible by heads {self.num_heads}")
        keys = _rng_split(rng, 4)
        std = np.float32(1.0 / math.sqrt(d))
        params = {name: jax.random.normal(k, (d, d), dtype=np.float32) * std
                  for name, k in zip(("wq", "wk", "wv", "wo"), keys)}
        return params, (t, d)

    def apply(self, params, x, train: bool = False):
        import jax.numpy as jnp

        dt = getattr(jnp, matmul_dtype())
        B, T, D = x.shape
        H = self.num_heads
        xd = x.astype(dt)

        def proj(w):
            return jnp.einsum("btd,de->bte", xd, jnp.asarray(w).astype(dt),
                              preferred_element_type=jnp.float32
                              ).reshape(B, T, H, D // H).astype(dt)

        q, k, v = proj(params["wq"]), proj(params["wk"]), proj(params["wv"])
        if self.ring_axis is not None:
            if self.ring_axis_size is None:
                raise ValueError("ring_axis requires ring_axis_size "
                                 "(static ring length)")
            o = ring_attention(q, k, v, self.ring_axis, self.ring_axis_size,
                               causal=self.causal)
        else:
            o = dense_attention(q, k, v, causal=self.causal)
        o = o.reshape(B, T, D)
        out = jnp.einsum("btd,de->bte", o.astype(dt),
                         jnp.asarray(params["wo"]).astype(dt),
                         preferred_element_type=jnp.float32)
        return out.astype(jnp.float32)


def _gelu(x):
    import jax

    return jax.nn.gelu(x)


def transformer_block(dim: int, num_heads: int, mlp_ratio: int = 4,
                      causal: bool = False, ring_axis: Optional[str] = None,
                      ring_axis_size: Optional[int] = None,
                      moe_experts: Optional[int] = None,
                      moe_capacity_factor: float = 1.5) -> Sequential:
    """Pre-norm transformer block as a named Sequential (taps work).
    ``moe_experts``: replace the dense FFN with a switch-MoE of that many
    experts (shard their weights over the ``expert`` axis via
    ``moe.expert_shardings`` for expert parallelism)."""
    from .module import Dense, Residual

    attn = Sequential([
        ("ln", LayerNorm()),
        ("attn", MultiHeadAttention(num_heads, causal=causal,
                                    ring_axis=ring_axis,
                                    ring_axis_size=ring_axis_size)),
    ])
    if moe_experts:
        from .moe import MoE

        mlp = Sequential([
            ("ln", LayerNorm()),
            ("moe", MoE(moe_experts, hidden=dim * mlp_ratio,
                        capacity_factor=moe_capacity_factor)),
        ])
    else:
        mlp = Sequential([
            ("ln", LayerNorm()),
            ("fc1", Dense(dim * mlp_ratio)),
            ("gelu", Fn(_gelu, lambda s: s)),
            ("fc2", Dense(dim)),
        ])
    return Sequential([
        ("attn", Residual(attn, activation=None)),
        ("mlp", Residual(mlp, activation=None)),
    ])


def _sublane_rows(dtype) -> int:
    """Rows of one TPU tile of ``dtype``: 8 sublanes of 32 bits, narrower
    types packed (float32 8, bfloat16 16)."""
    return 32 // np.dtype(dtype).itemsize


# ---------------------------------------------------------------------------
# the LSTM recurrence
# ---------------------------------------------------------------------------
#
# ``gates_t = x_t Wx + b + h_{t-1} Wh`` split ``i, f, g, o``; ``c_t = sig(f)
# c_{t-1} + sig(i) tanh(g)``; ``h_t = sig(o) tanh(c_t)``; from zeros; a reverse
# direction runs t = T-1 .. 0 and writes each ``h_t`` at its own position. It
# runs one of two ways, one arithmetic:
#
#   - ``lstm_scan_pallas``: a Pallas kernel, ``lstm_scan`` in a device trace.
#     Grid (row blocks, time blocks), time ``arbitrary``: ``h`` and ``c`` of a
#     row block stay in VMEM from the first time block to the last. Each gate
#     is padded to ``Hp`` lanes (whole lane tiles; zero columns and a zero bias
#     keep the padded lanes of ``c`` and ``h`` at 0), and the input product is
#     inside the step: the left operand is ``[h_{t-1} | x_t | 0]`` against
#     ``[[Wh]; [Wx]; [0]]`` where ``x`` fits the pad of ``h``'s last tile, else
#     ``[h_{t-1} | 0 | x_t | 0]``: one bfloat16 product a step, float32
#     accumulation, gates and state float32. The reverse direction is the
#     index map (time block ``n - 1 - j``, a block's steps last to first). A
#     step's ``h`` goes straight into the batch-major ``[B, T, H]`` result;
#     in a ``BiLSTM`` the first direction leaves its own time-major and
#     lane-padded, and the second writes both into ``[B, T, 2 H]``.
#   - ``lstm_scan_xla``: ``lax.scan`` over time, a block of K steps a trip;
#     every other backend's form and the kernel's VJP.

LSTM_ROWS = (512, 256, 128, 64, 32, 16, 8)   # rows of a kernel step: the first to divide B and fit
LSTM_STEPS = 8                               # time steps of a kernel step
LSTM_VMEM = 64 * 2 ** 20                     # bytes a kernel step may hold (a v5e has 128 MiB)


def _whole_tiles(n: int) -> int:
    """``n`` lanes rounded up to whole tiles of 128."""
    return -(-n // 128) * 128


def lstm_scan_xla(x, wx, wh, b, reverse: bool = False):
    """``x [B, T, D]``, ``wx [D, 4H]``, ``wh [H, 4H]``, ``b [4H]`` -> ``[B, T,
    H]`` float32. The scan advances K steps a trip and writes their ``h`` as
    one ``[K, B, H]`` block, so a write covers whole tiles of the output
    instead of one row of each; K = min(rows of a tile of ``h``'s dtype, T),
    the ``T % K`` steps left over run one a trip."""
    import jax
    import jax.numpy as jnp

    B, T, D = x.shape
    h = wh.shape[0]
    # time-major BEFORE the projection ([T, B, D] is small): transposing
    # the projected [T, B, 4H] costs XLA a copy of it once the scan blocks
    xt = jnp.swapaxes(x.astype(jnp.float32), 0, 1)

    def project(v):
        # the input projection, hoisted out of the scan: MXU matmuls
        return jnp.einsum("tbd,dk->tbk", v, wx) + b

    def cell(carry, xt):
        hprev, cprev = carry
        gates = xt + hprev @ wh
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * cprev + jax.nn.sigmoid(i) * jnp.tanh(g)
        hh = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (hh, c), hh

    def run(carry, part, k):
        """``part`` ([n * k, B, D], a stretch of ``xt``) through the
        recurrence, k steps a trip -> (carry, [n * k, B, H])."""
        n = part.shape[0] // k
        part = part.reshape(n, k, B, D)
        # one projection a position of the block, each [n, B, 4H], in the
        # order a trip runs them: a step then reads its input in place
        # (one [T, B, 4H] scanned by blocks is copied out a block a trip)
        xs = tuple(project(part[:, j]) for j in range(k))
        if reverse:
            xs = xs[::-1]

        def block(carry, xb):
            hs = []
            for xk in xb:
                carry, hk = cell(carry, xk)
                hs.append(hk)
            return carry, jnp.stack(hs)

        carry, ys = jax.lax.scan(block, carry, xs, reverse=reverse)
        if reverse:  # a block was stacked last step first
            ys = ys[:, ::-1]
        return carry, ys.reshape(n * k, B, h)

    zeros = jnp.zeros((B, h), dtype=jnp.float32)
    init = (zeros, zeros)
    k = min(_sublane_rows(zeros.dtype), T) or 1
    whole = T - T % k
    # the T % k steps past the whole blocks (none where k divides T) run
    # one a trip
    if reverse:
        carry, tail = run(init, xt[whole:], 1)
        _, ys = run(carry, xt[:whole], k)
    else:
        carry, ys = run(init, xt[:whole], k)
        _, tail = run(carry, xt[whole:], 1)
    ys = jnp.concatenate([ys, tail])
    return jnp.swapaxes(ys, 0, 1)  # [B, T, H]


def _side_by_side(left, h, H: int):
    """``[rows, 2 H]``: ``left``'s first ``H`` lanes, then ``h``'s; both
    ``[rows, Hp]`` with zeros past lane ``H``. ``h`` is rotated to the lane
    of its tile where ``left`` ends, so the tile they share is a sum of two
    with disjoint lanes and every other tile is one of theirs, whole."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    base = H // 128 * 128
    if base == H:
        return jnp.concatenate([left, h], axis=1)
    wide = _whole_tiles(2 * H - base)
    if wide > h.shape[1]:
        h = jnp.concatenate([h, jnp.zeros((h.shape[0], wide - h.shape[1]), h.dtype)], axis=1)
    h = pltpu.roll(h, H - base, 1)
    tiles = [left[:, :base], left[:, base:] + h[:, :128], h[:, 128:]]
    return jnp.concatenate([t for t in tiles if t.shape[1]], axis=1)[:, :2 * H]


def _lstm_kernel(*refs, T: int, H: int, base: int, reverse: bool, padded: bool):
    """One block of time steps of one block of rows. ``x_ref [steps, rows,
    Kp - base]`` holds ``x_t`` on the lanes its rows of ``w_ref [Kp, 4 Hp]``
    have past lane ``base``; ``h_ref``, ``c_ref`` ``[rows, Hp]`` float32
    scratch, the state between steps and blocks. ``o_ref`` is ``[steps, rows,
    Hp]`` where ``padded``, else ``[rows, steps, H]``, or ``[rows, steps, 2 H]``
    where ``refs`` has a fourth input, another direction's ``padded`` result
    that goes before this one's at each position."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    x_ref, w_ref, b_ref, *left_ref, o_ref, h_ref, c_ref = refs
    steps, rows = x_ref.shape[:2]
    Hp = h_ref.shape[1]
    j, n = pl.program_id(1), pl.num_programs(1)

    @pl.when(j == 0)
    def _start():
        h_ref[...] = jnp.zeros_like(h_ref)
        c_ref[...] = jnp.zeros_like(c_ref)

    first = (n - 1 - j if reverse else j) * steps
    f32, dt = jnp.float32, w_ref.dtype

    def sigmoid(a):                         # one pass of the transcendental unit
        return 0.5 * jnp.tanh(0.5 * a) + 0.5

    def step(k):
        h, x = h_ref[...], x_ref[k]
        if base < Hp:   # x_t rides the pad of h's last lane tile: their lanes are disjoint
            u = (h[:, base:] + x.astype(f32)).astype(dt)
            if base:
                u = jnp.concatenate([h[:, :base].astype(dt), u], axis=1)
        else:
            u = jnp.concatenate([h.astype(dt), x], axis=1)
        gates = jnp.dot(u, w_ref[...], preferred_element_type=f32) + b_ref[...]
        i, f, g, o = (gates[:, q * Hp:(q + 1) * Hp] for q in range(4))
        c = sigmoid(f) * c_ref[...] + sigmoid(i) * jnp.tanh(g)
        h = sigmoid(o) * jnp.tanh(c)
        c_ref[...] = c
        h_ref[...] = h
        if padded:
            o_ref[k] = h
        elif left_ref:
            o_ref[:, k, :] = _side_by_side(left_ref[0][k], h, H)
        else:
            o_ref[:, k, :] = h[:, :H]

    for k in (range(steps - 1, -1, -1) if reverse else range(steps)):
        if T % steps:   # the last block of time holds steps past the row's end
            pl.when(first + k < T)(functools.partial(step, k))
        else:
            step(k)


def _lstm_layout(D: int, H: int):
    """``(Hp, xo, base, Kp)``: the lanes of a gate; the row of the weights
    ``x``'s first lane meets (``H`` where ``x`` fits the pad of ``h``'s last
    tile, else ``Hp``); the lanes before it that are ``h``'s alone; the
    operand's width."""
    Hp = _whole_tiles(H)
    xo = H if H + D <= Hp else Hp
    return Hp, xo, xo // 128 * 128, _whole_tiles(xo + D)


def _lstm_blocks(B: int, T: int, D: int, H: int):
    """(rows, steps) of a kernel step: the most rows that divide ``B`` and
    keep a step within ``LSTM_VMEM``, None where no block does."""
    Hp, _, base, Kp = _lstm_layout(D, H)
    steps = LSTM_STEPS
    weights = 2 * (Kp * 4 * Hp * 2 + 4 * Hp * 4)        # bfloat16, fetched into two buffers
    # a row's share: x, the widest result and another direction's block in two
    # buffers each; h and c; a step's operand, gates and some six temporaries
    row = (2 * steps * ((Kp - base) * 2 + _whole_tiles(2 * H) * 4 + Hp * 4)
           + 2 * Hp * 4 + Kp * 2 + (4 + 6) * Hp * 4)
    rows = next((r for r in LSTM_ROWS if B % r == 0 and weights + r * row <= LSTM_VMEM), None)
    return (rows, steps) if rows and T >= steps else None


def lstm_scan_pallas(x, wx, wh, b, reverse: bool = False, beside=None,
                     padded: bool = False, interpret: bool = False,
                     operands="bfloat16"):
    """The kernel form of ``lstm_scan_xla`` (same arguments and result): ``B``
    a multiple of 8, ``T`` at least a block of steps. ``operands`` is the
    dtype of the product's two sides. ``padded``: the result as another
    direction's call takes it for ``beside``, time-major ``[blocks * steps, B,
    Hp]`` with zeros past lane ``H`` (steps past ``T`` not written). With
    ``beside`` the result is ``[B, T, 2 H]``: at each position ``beside``'s
    ``h``, then this direction's."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    B, T, D = x.shape
    H = wh.shape[0]
    rows, steps = _lstm_blocks(B, T, D, H)
    Hp, xo, base, Kp = _lstm_layout(D, H)
    blocks = -(-T // steps)

    def gates(m):                           # [r, 4 H] -> [r, 4 Hp], a gate a whole tile
        m = jnp.pad(m.astype(f32).reshape(-1, 4, H), ((0, 0), (0, 0), (0, Hp - H)))
        return m.reshape(-1, 4 * Hp)

    w = jnp.zeros((Kp, 4 * Hp), f32).at[:H].set(gates(wh)).at[xo:xo + D].set(gates(wx))
    # time-major, on its lanes of the operand, whole blocks of steps
    xp = jnp.pad(jnp.swapaxes(x, 0, 1).astype(operands),
                 ((0, blocks * steps - T), (0, 0), (xo - base, Kp - xo - D)))

    def at(j):                              # the time block of grid step j
        return blocks - 1 - j if reverse else j

    def by_time(width):                     # a [steps, rows, width] block of a time-major array
        return pl.BlockSpec((steps, rows, width), lambda i, j: (at(j), i, 0))

    def by_rows(width):                     # a [rows, steps, width] block of a batch-major one
        return pl.BlockSpec((rows, steps, width), lambda i, j: (i, at(j), 0))

    ins = [xp, w.astype(operands), gates(b[None])]
    in_specs = [by_time(Kp - base), pl.BlockSpec((Kp, 4 * Hp), lambda i, j: (0, 0)),
                pl.BlockSpec((1, 4 * Hp), lambda i, j: (0, 0))]
    if padded:
        out_spec, out_shape = by_time(Hp), (blocks * steps, B, Hp)
    else:
        width = H if beside is None else 2 * H
        out_spec, out_shape = by_rows(width), (B, T, width)
    if beside is not None:
        ins.append(beside)
        in_specs.append(by_time(Hp))
    return pl.pallas_call(
        functools.partial(_lstm_kernel, T=T, H=H, base=base, reverse=reverse,
                          padded=padded),
        grid=(B // rows, blocks),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, f32),
        scratch_shapes=[pltpu.VMEM((rows, Hp), f32), pltpu.VMEM((rows, Hp), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024),
        name="lstm_scan",            # the name a device trace shows
        cost_estimate=pl.CostEstimate(
            flops=int(2 * B * T * Kp * 4 * Hp), transcendentals=int(5 * B * T * Hp),
            bytes_accessed=int(B * T * (2 * (Kp - base) + 4 * out_shape[2]
                                        + (4 * Hp if beside is not None else 0)))),
        interpret=interpret,
    )(*ins)


def _lstm_kernel_plain(x, wx, wh, b, beside, reverse: bool, padded: bool):
    """What ``lstm_scan_pallas`` returns, from the plain form."""
    import jax.numpy as jnp

    T, H = x.shape[1], wh.shape[0]
    y = lstm_scan_xla(x, wx, wh, b, reverse)
    if beside is not None:
        y = jnp.concatenate([jnp.swapaxes(beside[:T, :, :H], 0, 1), y], axis=-1)
    if padded:
        y = jnp.pad(jnp.swapaxes(y, 0, 1),
                    ((0, -T % LSTM_STEPS), (0, 0), (0, _whole_tiles(H) - H)))
    return y


_lstm_pair = pair(
    lambda x, wx, wh, b, beside, reverse, padded, interpret: lstm_scan_pallas(
        x, wx, wh, b, reverse, beside, padded, interpret),
    _lstm_kernel_plain, static=2)


def _lstm_kernel_applies(x, hidden: int) -> bool:
    """Whether the recurrence over ``x [B, T, D]`` into ``hidden`` units takes
    the kernel: a TPU at its default precision (one bfloat16 pass, what the
    kernel's product is), float32 or bfloat16 rows, a ``B`` and a ``T`` its
    blocks fit, widths whose step fits VMEM."""
    import jax
    import jax.numpy as jnp

    return (jax.default_backend() == "tpu"
            and jax.config.jax_default_matmul_precision in (None, "default", "bfloat16")
            and x.dtype in (jnp.float32, jnp.bfloat16)
            and _lstm_blocks(*x.shape, hidden) is not None)


def _lstm_weights(params):
    import jax.numpy as jnp

    return tuple(jnp.asarray(params[k]) for k in ("wx", "wh", "b"))


def lstm_scan(x, wx, wh, b, reverse: bool = False):
    """The recurrence: the kernel where it applies, the plain form elsewhere."""
    if _lstm_kernel_applies(x, wh.shape[0]):
        return _lstm_pair(x, wx, wh, b, None, reverse, False)
    return lstm_scan_xla(x, wx, wh, b, reverse)


class LSTM(Module):
    """Unidirectional LSTM: [B, T, D] -> [B, T, H] (``lstm_scan``)."""

    def __init__(self, hidden: int, reverse: bool = False):
        self.hidden = hidden
        self.reverse = reverse

    def init(self, rng, in_shape):
        import jax

        t, d = in_shape
        k1, k2 = _rng_split(rng, 2)
        h = self.hidden
        std_x = np.float32(1.0 / math.sqrt(d))
        std_h = np.float32(1.0 / math.sqrt(h))
        return {
            "wx": jax.random.normal(k1, (d, 4 * h), dtype=np.float32) * std_x,
            "wh": jax.random.normal(k2, (h, 4 * h), dtype=np.float32) * std_h,
            "b": np.zeros((4 * h,), np.float32),
        }, (t, h)

    def apply(self, params, x, train: bool = False):
        return lstm_scan(x, *_lstm_weights(params), self.reverse)


class BiLSTM(Module):
    """Concat of forward and backward LSTM: [B, T, D] -> [B, T, 2H]
    (the CNTK BiLSTM tagger's core, TPU-native)."""

    def __init__(self, hidden: int):
        self.fwd = LSTM(hidden)
        self.bwd = LSTM(hidden, reverse=True)

    def init(self, rng, in_shape):
        k1, k2 = _rng_split(rng, 2)
        pf, (t, h) = self.fwd.init(k1, in_shape)
        pb, _ = self.bwd.init(k2, in_shape)
        return {"fwd": pf, "bwd": pb}, (t, 2 * h)

    def apply(self, params, x, train: bool = False):
        import jax.numpy as jnp

        if _lstm_kernel_applies(x, self.fwd.hidden):
            # the second direction's kernel writes both halves of a position:
            # no concatenation, no pass over either result
            with scope("fwd"):
                fwd = _lstm_pair(x, *_lstm_weights(params["fwd"]), None, False, True)
            with scope("bwd"):
                return _lstm_pair(x, *_lstm_weights(params["bwd"]), fwd, True, False)
        with scope("fwd"):
            fwd = self.fwd.apply(params["fwd"], x)
        with scope("bwd"):
            bwd = self.bwd.apply(params["bwd"], x)
        return jnp.concatenate([fwd, bwd], axis=-1)


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def transformer_encoder(seq_len: int, dim: int, depth: int, num_heads: int,
                        vocab_size: Optional[int] = None,
                        num_classes: Optional[int] = None,
                        causal: bool = False,
                        ring_axis: Optional[str] = None,
                        ring_axis_size: Optional[int] = None,
                        seed: int = 0):
    """Named-layer transformer encoder as a FunctionModel (taps address
    "block3", "block3/mlp/fc1", ... the way ResNet layers do)."""
    from .module import Dense, FunctionModel
    import jax

    layers = []
    if vocab_size is not None:
        layers.append(("embed", Embed(vocab_size, dim)))
        in_shape: Tuple[int, ...] = (seq_len,)
    else:
        in_shape = (seq_len, dim)
    for i in range(depth):
        layers.append((f"block{i}", transformer_block(
            dim, num_heads, causal=causal, ring_axis=ring_axis,
            ring_axis_size=ring_axis_size)))
    layers.append(("ln_f", LayerNorm()))
    if num_classes is not None:
        layers.append(("head", Dense(num_classes)))
    module = Sequential(layers, name="transformer")
    params, out_shape = module.init(jax.random.key(seed), in_shape)
    layer_names = [name for name, _ in reversed(layers)]
    return FunctionModel(module, params, in_shape, layer_names, "transformer")


def bilstm_tagger(seq_len: int, vocab_size: int, embed_dim: int,
                  hidden: int, num_tags: int, seed: int = 0):
    """Embed -> BiLSTM -> per-token tag logits (the medical entity
    extraction architecture, notebooks/DeepLearning - BiLSTM)."""
    from .module import Dense, FunctionModel
    import jax

    module = Sequential([
        ("embed", Embed(vocab_size, embed_dim)),
        ("bilstm", BiLSTM(hidden)),
        ("tags", Dense(num_tags)),
    ], name="bilstm_tagger")
    params, _ = module.init(jax.random.key(seed), (seq_len,))
    return FunctionModel(module, params, (seq_len,),
                         ["tags", "bilstm", "embed"], "bilstm_tagger")
