"""Plain reference of the K-EXAONE-236B-A23B configuration, cut as the
configuration's file says (layers 0-4, experts 0-15 of 128 held, an eighth of
the vocabulary).

Written from the configuration's `equations` in straightforward `jax.numpy`,
float32 under `highest` matmul precision: no kernel, no sort, no batching
machinery; every held expert in turn is applied to the positions that chose
it (`jnp.nonzero`), and to every position where the caller has not counted;
attention is the plain masked softmax over a `[heads, T, T]` score tensor, a
row at a time. Imports nothing of the program under test and takes
nothing it made: the weights come from the seed (`make_weights`, bfloat16
values, which the builder hands to the program and this file upcasts), the
token rows from the harness.

    x_0 = E[id];  h = x + Attn_l(RMSNorm(x));  x' = h + FFN_l(RMSNorm(h))
    out[t] = log_softmax(RMSNorm(x_L) W_head)_t [id_{t+1}]   (last target: pad)
    Attn_l: q, k, v = x W_q, x W_k, x W_v (64 / 8 / 8 heads of 128); q, k
        RMS-normed over the head, rotary positions on the sliding layers;
        head i reads key/value head i // 8; causal, window 128 where sliding
    FFN_0 = W_down(silu(W_gate x) * W_up x)
    FFN_l = sum_{i in top8(s + b), i held} g_i Expert_i(x) + Shared(x),
        s = sigmoid(x W_r), g_i = 2.5 s_i / sum_{j in top8} s_j

`score` computes a layer at a time over blocks of rows, making that layer's
weights from the seed and dropping them after: one layer's float32 weights
(3.0 GB) are the most it holds. Beside the log-probabilities it returns each
position's routing `margin`: the smallest gap, over the sparse layers, of the
8th and 9th largest `s + b` there. The comparison reads the positions whose
margin is wide as stable: a program that rounds differently picks the same
experts there.

`quant="fp8"` is the control: the same computation with the operands of every
matrix product rounded to float8 (e4m3, one scale a tensor), the nearest
precision below the bfloat16 the configuration states. `fault=` plants what a
broken program would compute (`FAULTS`).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import numpy as np

Spec = Tuple[str, Tuple[int, ...], str]        # (path, shape, kind)

FAULTS = ("window_full",        # a sliding layer reads every earlier key
          "rope_on_full",       # rotary positions on the full layers too
          "no_topk_norm",       # the 8 weights not normalised over the top 8
          "no_shared",          # the shared expert left out
          "experts_16_31")      # experts 16-31 held in place of 0-15


def layer_plan(config) -> List[Tuple[int, bool]]:
    """(window, sparse) of each layer run: window 0 is full attention."""
    n = int(config["num_hidden_layers"])
    return [(int(config["sliding_windows"][i]),
             config["mlp_layer_types"][i] == "sparse") for i in range(n)]


def weight_specs(config) -> List[Spec]:
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    nq, nkv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    vocab, held = int(config["vocab_size"]), int(config["num_experts"])
    router = int(config["num_experts_published"])
    ff, eff = int(config["intermediate_size"]), int(config["moe_intermediate_size"])
    specs: List[Spec] = [("embed/table", (vocab, d), "table")]
    for i, (_, sparse) in enumerate(layer_plan(config)):
        p = f"layer{i}"
        specs += [(f"{p}/attn_norm/scale", (d,), "gain"),
                  (f"{p}/attn/wq", (d, nq * hd), "dense"),
                  (f"{p}/attn/wk", (d, nkv * hd), "dense"),
                  (f"{p}/attn/wv", (d, nkv * hd), "dense"),
                  (f"{p}/attn/wo", (nq * hd, d), "dense"),
                  (f"{p}/attn/q_norm", (hd,), "gain"),
                  (f"{p}/attn/k_norm", (hd,), "gain"),
                  (f"{p}/mlp_norm/scale", (d,), "gain")]
        if sparse:
            specs += [(f"{p}/moe/router", (d, router), "dense"),
                      (f"{p}/moe/router_bias", (router,), "router_bias"),
                      # gate and up side by side: columns [0, eff) are the gate
                      (f"{p}/moe/w1", (held, d, 2 * eff), "experts"),
                      (f"{p}/moe/w2", (held, eff, d), "experts"),
                      (f"{p}/shared/w_gate_up", (d, 2 * eff), "dense"),
                      (f"{p}/shared/w_down", (eff, d), "dense")]
        else:
            specs += [(f"{p}/mlp/w_gate_up", (d, 2 * ff), "dense"),
                      (f"{p}/mlp/w_down", (ff, d), "dense")]
    return specs + [("final_norm/scale", (d,), "gain"),
                    ("head/kernel", (d, vocab), "dense")]


def seed_key(seed: int):
    """A PRNG key from any whole number, also one over 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                              seed // (2 ** 31 - 1))


def _leaf(key, shape, kind: str):
    import jax
    import jax.numpy as jnp

    if kind == "gain":
        return jnp.ones(shape, jnp.bfloat16)
    # fan-in: a matrix's rows, an expert's `[experts, rows, columns]` likewise
    std = {"table": 1.0, "router_bias": 0.01}.get(kind) or 1.0 / math.sqrt(shape[-2])
    w = jax.random.normal(key, shape, jnp.float32) * np.float32(std)
    return w.astype(jnp.bfloat16)


def make_weights(config, seed: int, under: Optional[str] = None
                 ) -> Dict[str, "jax.Array"]:
    """Every weight whose path starts with `under` (all of them without it),
    bfloat16, on the device, a leaf at a time from the seed: a leaf's key is
    its place in `weight_specs`, so a layer made alone equals that layer of
    the whole. Scales (the configuration's `assumed.weights`): norm gains 1,
    embeddings of std 1, every matrix 1/sqrt(fan-in), the router's selection
    bias std 0.01."""
    import jax

    key = seed_key(seed)
    gen = jax.jit(_leaf, static_argnums=(1, 2))
    return {path: gen(jax.random.fold_in(key, i), shape, kind)
            for i, (path, shape, kind) in enumerate(weight_specs(config))
            if under is None or path.startswith(under)}


def _quant(x, quant: Optional[str]):
    """Round to the control's precision: float8 e4m3 with one scale a tensor."""
    import jax.numpy as jnp

    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _einsum(expr: str, a, b, quant: Optional[str]):
    import jax
    import jax.numpy as jnp

    return jnp.einsum(expr, _quant(a, quant), _quant(b, quant),
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, gain, eps: float):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rotary(x, theta: float):
    """Rotary positions over the whole head, `[..., T, heads, hd]`: the two
    halves of the head rotate against each other (the `rotate_half` form)."""
    import jax.numpy as jnp

    t, hd = x.shape[-3], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(config, w, x, window: int, quant, fault):
    """One row `[T, hidden]` through the layer's attention."""
    import jax
    import jax.numpy as jnp

    hd = int(config["head_dim"])
    nq, nkv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    eps, a = float(config["rms_norm_eps"]), config["assumed"]
    t = x.shape[0]
    q = _einsum("td,de->te", x, w["attn/wq"], quant).reshape(t, nq, hd)
    k = _einsum("td,de->te", x, w["attn/wk"], quant).reshape(t, nkv, hd)
    v = _einsum("td,de->te", x, w["attn/wv"], quant).reshape(t, nkv, hd)
    if a["qk_norm"]:
        q, k = rms_norm(q, w["attn/q_norm"], eps), rms_norm(k, w["attn/k_norm"], eps)
    sliding = window > 0
    if (a["rope_layers"] == "sliding" and sliding) or a["rope_layers"] == "all" \
            or fault == "rope_on_full":
        theta = float(config["rope_parameters"]["rope_theta"])
        q, k = rotary(q, theta), rotary(k, theta)
    if fault == "window_full":
        sliding = False
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]
    if sliding:
        seen &= pos[None, :] > pos[:, None] - window
    q = q.reshape(t, nkv, nq // nkv, hd)        # head i reads kv head i // group

    def group(qkv):
        qg, kg, vg = qkv                         # [T, group, hd], [T, hd], [T, hd]
        s = _einsum("qgd,kd->gqk", qg, kg, quant) * np.float32(hd ** -0.5)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return _einsum("gqk,kd->qgd", p, vg, quant)

    o = jax.lax.map(group, (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1)))
    o = o.swapaxes(0, 1).reshape(t, nq * hd)     # [kv, T, group, hd] -> [T, heads * hd]
    return _einsum("te,ed->td", o, w["attn/wo"], quant)


def swiglu(x, w_gate_up, w_down, quant):
    import jax
    import jax.numpy as jnp

    gate, up = jnp.split(_einsum("td,df->tf", x, w_gate_up, quant), 2, axis=-1)
    return _einsum("tf,fd->td", jax.nn.silu(gate) * up, w_down, quant)


def route(config, w, x, quant, fault):
    """`x [N, hidden]` -> (the chosen experts `[N, 8]`, their weights `[N, 8]`,
    each position's gap between the 8th and 9th selection score)."""
    import jax
    import jax.numpy as jnp

    top_k = int(config["num_experts_per_tok"])
    s = jax.nn.sigmoid(_einsum("td,de->te", x, w["moe/router"], quant))
    chosen_by, idx = jax.lax.top_k(s + w["moe/router_bias"], top_k + 1)
    margin = chosen_by[:, top_k - 1] - chosen_by[:, top_k]
    idx = idx[:, :top_k]
    g = jnp.take_along_axis(s, idx, axis=-1)
    if config["norm_topk_prob"] and fault != "no_topk_norm":
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return idx, g * np.float32(config["routed_scaling_factor"]), margin


def experts(config, w, x, idx, g, quant, fault, most: Optional[int] = None):
    """The held experts' part of the layer plus the shared expert, for
    `x [N, hidden]` routed as `idx`, `g` say. Every held expert in turn is
    applied to the positions that chose it, `most` of them at the most (the
    caller has counted; None: every position, the rest weighted 0)."""
    import jax.numpy as jnp

    held = int(config["num_experts"])
    first = held if fault == "experts_16_31" else int(config["first_expert_held"])
    most = x.shape[0] if most is None else most
    y = jnp.zeros_like(x)
    if fault != "no_shared":
        y = swiglu(x, w["shared/w_gate_up"], w["shared/w_down"], quant)
    for e in range(held):
        weight = jnp.sum(jnp.where(idx == first + e, g, 0.0), axis=-1)      # [N]
        (pos,) = jnp.nonzero(weight > 0, size=most, fill_value=0)
        weight = jnp.where(jnp.arange(most) < jnp.sum(weight > 0), weight[pos], 0.0)
        y = y.at[pos].add(weight[:, None] * swiglu(
            x[pos], w["moe/w1"][e], w["moe/w2"][e], quant))
    return y


def attend(config, w, x, window: int, quant, fault):
    """Rows `[R, T, hidden]` -> the rows after the attention sublayer."""
    import jax

    eps = float(config["rms_norm_eps"])
    return x + jax.lax.map(lambda row: attention(
        config, w, rms_norm(row, w["attn_norm/scale"], eps), window, quant, fault), x)


def front(config, w, x, window: int, sparse: bool, quant, fault):
    """Rows `[R, T, hidden]` as far as the router: (the rows after attention,
    the FFN's normed input `[R T, hidden]`, the routing or None)."""
    h = attend(config, w, x, window, quant, fault)
    hn = rms_norm(h, w["mlp_norm/scale"], float(config["rms_norm_eps"]))
    hn = hn.reshape(-1, hn.shape[-1])
    return h, hn, route(config, w, hn, quant, fault) if sparse else None


def back(config, w, h, hn, routing, quant, fault, most: Optional[int] = None):
    """The layer's FFN and residual: (the rows after the layer, their margins)."""
    import jax.numpy as jnp

    if routing is None:
        y = swiglu(hn, w["mlp/w_gate_up"], w["mlp/w_down"], quant)
        return h + y.reshape(h.shape), jnp.full(h.shape[:2], jnp.inf, jnp.float32)
    idx, g, margin = routing
    y = experts(config, w, hn, idx, g, quant, fault, most)
    return h + y.reshape(h.shape), margin.reshape(h.shape[:2])


def layer(config, w, x, window: int, sparse: bool, quant, fault):
    """Rows `[R, T, hidden]` -> (the rows after the layer, their margins)."""
    return back(config, w, *front(config, w, x, window, sparse, quant, fault),
                quant, fault)


def log_probs(config, w, x, ids, quant):
    """One row: the log-probability of the next id at every position."""
    import jax
    import jax.numpy as jnp

    xn = rms_norm(x, w["final_norm/scale"], float(config["rms_norm_eps"]))
    logp = jax.nn.log_softmax(_einsum("td,dv->tv", xn, w["head/kernel"], quant))
    target = jnp.concatenate([ids[1:], jnp.full((1,), int(config["pad_id"]), ids.dtype)])
    return jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]


def _f32(w: Dict[str, "jax.Array"], prefix: str) -> Dict[str, "jax.Array"]:
    import jax.numpy as jnp

    return {p[len(prefix):]: a.astype(jnp.float32) for p, a in w.items()}


def score(config, seed: int, ids: np.ndarray, quant: Optional[str] = None,
          fault: Optional[str] = None, block: int = 8,
          weights: Optional[Dict[str, "jax.Array"]] = None
          ) -> Dict[str, np.ndarray]:
    """{"logprob": [N, T], "margin": [N, T]} float32 of padded token rows
    `[N, T]` int32: a layer at a time, `block` rows at a time. A sparse layer
    stops at the router for the host to count what its busiest expert takes
    (rounded up to 4,096: few sizes, few programs), then every expert runs over that many positions. `weights`, for a test that already holds them all,
    stands in for the seed."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    ids = np.asarray(ids, np.int32)

    def made(under: str):
        have = weights if weights is not None else make_weights(config, seed, under)
        return _f32({p: a for p, a in have.items() if p.startswith(under)}, under)

    def blocks(fn, w, *rows):
        out = [fn(w, *(r[i:i + block] for r in rows))
               for i in range(0, len(ids), block)]
        return jax.tree.map(lambda *parts: jnp.concatenate(parts), *out)

    x = blocks(jax.jit(lambda w, i: w["table"][i]), made("embed/"), ids)
    margin = jnp.full(ids.shape, jnp.inf, jnp.float32)
    programs: Dict[Tuple, object] = {}            # one a kind of layer and size
    for i, (window, sparse) in enumerate(layer_plan(config)):
        def one(w, xb, window=window, sparse=sparse):
            h, hn, routing = programs.setdefault((window, sparse), jax.jit(
                lambda w, xb: front(config, w, xb, window, sparse, quant, fault)))(w, xb)
            most = None
            if sparse:    # what the busiest expert takes, rounded up to 4,096
                most = int(np.bincount(np.asarray(routing[0]).ravel()).max())
                most = min(-(-most // 4096) * 4096, hn.shape[0])
            return programs.setdefault((sparse, most), jax.jit(
                lambda w, h, hn, r: back(config, w, h, hn, r, quant, fault, most)))(
                    w, h, hn, routing)

        x, m = blocks(one, made(f"layer{i}/"), x)
        margin = jnp.minimum(margin, m)
    w = {**made("final_norm/"), **made("head/")}
    w = {"final_norm/scale": w["scale"], "head/kernel": w["kernel"]}
    logp = blocks(jax.jit(lambda w, xb, ib: jax.lax.map(
        lambda r: log_probs(config, w, r[0], r[1], quant), (xb, ib))), w, x, ids)
    return {"logprob": np.asarray(logp), "margin": np.asarray(margin)}


def row_gaps(got: np.ndarray, ref: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """The gap of every real position's log-probability from the reference's,
    `[rows, T]` with NaN at the pads: the difference over the spread (largest
    less smallest) of the reference's log-probabilities over the row's real
    positions, or over the median row's spread, whichever is larger. A value
    that is not finite reads as an infinite gap."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    real = np.arange(ref.shape[1])[None, :] < np.asarray(lengths)[:, None]
    spread = np.where(real, ref, -np.inf).max(axis=1) - np.where(real, ref, np.inf).min(axis=1)
    spread = np.maximum(spread, max(float(np.median(spread)), 1e-30))
    gap = np.abs(got - ref) / spread[:, None]
    return np.where(real, np.where(np.isfinite(gap), gap, np.inf), np.nan)
