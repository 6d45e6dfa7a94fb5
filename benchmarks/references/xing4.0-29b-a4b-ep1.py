"""Plain reference of the Xing4.0-29B-A4B configuration, cut as the
configuration's file says (layers 0-5 of 40, every expert, head and vocabulary
row held).

Written from the configuration's `equations` in straightforward `jax.numpy`,
float32 under `highest` matmul precision: no kernel, no sort, no batching
machinery (what it shares with the first language model's reference it takes
from that file). A token's state is `X [n, d]`, n = `hc_mult` streams; every
sublayer reads and writes it through its hyper-connection (`hyper_maps`,
`mix_in`, `mix_out`); attention is the masked softmax of DeepSeek-V3's latent
attention in its decompressed form, a block of 1,024 queries of one head
against all keys at a time; every expert in turn is applied to the positions
that chose it (`jnp.nonzero`). Imports nothing of the program under test and
takes nothing it made: the weights come from the seed (`make_weights`,
bfloat16 values, which the builder hands to the program and this file
upcasts a layer at a time), the token rows from the harness.

    X_0[i] = E[id] for every stream i
    a sublayer F with its (phi, alpha, b):  u = vec(X)
        m = (u phi) rsqrt(mean(u^2) + eps);  m -> m_pre [n], m_post [n], m_res [n, n]
        H_pre = sigmoid(a_pre m_pre + b_pre);  H_post = 2 sigmoid(a_post m_post + b_post)
        M = exp(clip(a_res m_res + b_res, -30, 30)); 20 times: M /= rowsum(M) + hc_eps;
            M /= colsum(M) + hc_eps;  H_res = M
        y = F(RMSNorm(sum_i H_pre[i] X[i]));  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y
    out[t] = log_softmax(RMSNorm(sum_i X_L[i]) W_head)_t [id_{t+1}]   (last target: pad)
    Attn: c_q = RMSNorm(x W_qa); [q_nope, q_rope] = c_q W_qb (32 heads of 128 + 64)
        [c_kv, k_r] = x W_kva; [k_nope, v] = RMSNorm(c_kv) W_kvb (32 heads of 128 + 128)
        YaRN rotary positions on q_rope and on k_r (one for all heads)
        s = (q_nope . k_nope + q_rope . k_r) 192^-0.5 m^2, m = 0.1 ln(64) + 1; causal
    FFN_0,1 = W_down(silu(W_gate x) * W_up x)
    FFN_l = sum_{i in top4(s + b)} g_i Expert_i(x) + Shared(x),
        s = sigmoid(x W_r), g_i = 2 s_i / sum_{j in top4} s_j

`score` computes four rows at a time, a layer at a time, a row at a time,
making that layer's weights from the seed and dropping them after: one sparse
layer's float32 weights (3.0 GB) are the most it holds beside the four rows'
streams (0.94 GB a row of 16,384).
Beside the log-probabilities it returns each position's routing `margin`: the
smallest gap, over the sparse layers, of the 4th and 5th largest `s + b`
there. The comparison reads the positions whose margin is wide as stable: a
program that rounds differently picks the same experts there.

`quant="fp8"` is the control: the same computation with the operands of every
matrix product rounded to float8 (e4m3, one scale a tensor), the nearest
precision below the bfloat16 the configuration states. `fault=` plants what a
broken program would compute (`FAULTS`).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks.harness import spec

# What the two language models' references share is written once, in the first
# one's file (nothing of the program either): the PRNG key of a seed, the float8
# control's rounding, a matrix product at `highest`, RMSNorm, SwiGLU, the
# DeepSeek-V3 router both configurations have (sigmoid scores, a selection bias
# that chooses, top-k, the margin between the k-th and the next), the upcast of a
# layer's weights, and the comparison's gaps.
_first = spec.bench_module("references", "k-exaone-236b-a23b-ep8")
seed_key, _quant, _einsum, rms_norm = (_first.seed_key, _first._quant, _first._einsum,
                                       _first.rms_norm)
swiglu, route, _f32, row_gaps = _first.swiglu, _first.route, _first._f32, _first.row_gaps

Spec = Tuple[str, Tuple[int, ...], str]        # (path, shape, kind)

FAULTS = ("sinkhorn_1",         # one Sinkhorn iteration instead of hc_sinkhorn_iters
          "static_maps",        # u phi left out: the maps are their biases alone
          "h_post_no_2",        # H_post without its 2
          "streams_first",      # the first stream alone taken, not the sum of the four
          "no_yarn",            # plain theta-10000 frequencies
          "no_mscale",          # the softmax scale without m^2
          "no_rope_key",        # the shared rotary key left out of the score
          "no_topk_norm",       # the 4 weights not normalised over the top 4
          "no_shared")          # the shared expert left out

# Computed on request and NOT a fault: the streams collapsed by their mean. The
# mean is the sum over n and the final RMSNorm divides the n out, so the
# outputs are the same numbers (to rms_norm_eps) and nothing can tell.
NO_FAULTS = ("streams_mean",)

QUERY_BLOCK = 1024              # queries of one head against all keys at a time
POSITION_BLOCK = 2048           # positions whose logits exist at a time


def layer_plan(config) -> List[bool]:
    """Whether each layer run has an expert layer (else the dense SwiGLU)."""
    dense, freq = int(config["first_k_dense_replace"]), int(config["moe_layer_freq"])
    return [i >= dense and i % freq == 0 for i in range(int(config["num_hidden_layers"]))]


def maps_width(config) -> int:
    n = int(config["hc_mult"])
    return n * n + 2 * n


def weight_specs(config) -> List[Spec]:
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    qr, kvr = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    vd, vocab = int(config["v_head_dim"]), int(config["vocab_size"])
    experts, n = int(config["n_routed_experts"]), int(config["hc_mult"])
    ff, eff = int(config["intermediate_size"]), int(config["moe_intermediate_size"])
    shared = eff * int(config["n_shared_experts"])
    specs: List[Spec] = [("embed/table", (vocab, d), "table")]
    for i, sparse in enumerate(layer_plan(config)):
        p = f"layer{i}"
        specs += [(f"{p}/attn_norm/scale", (d,), "gain"),
                  (f"{p}/attn/wq_a", (d, qr), "dense"),
                  (f"{p}/attn/q_norm", (qr,), "gain"),
                  # a head's columns: its 128 content lanes, then its 64 rotary ones
                  (f"{p}/attn/wq_b", (qr, h * (nope + rope)), "dense"),
                  # columns: the 512-wide latent, then the shared rotary key
                  (f"{p}/attn/wkv_a", (d, kvr + rope), "dense"),
                  (f"{p}/attn/kv_norm", (kvr,), "gain"),
                  # a head's columns: its content keys, then its values
                  (f"{p}/attn/wkv_b", (kvr, h * (nope + vd)), "dense"),
                  (f"{p}/attn/wo", (h * vd, d), "dense"),
                  (f"{p}/mlp_norm/scale", (d,), "gain")]
        if sparse:
            specs += [(f"{p}/moe/router", (d, experts), "dense"),
                      (f"{p}/moe/router_bias", (experts,), "router_bias"),
                      # gate and up side by side: columns [0, eff) are the gate
                      (f"{p}/moe/w1", (experts, d, 2 * eff), "experts"),
                      (f"{p}/moe/w2", (experts, eff, d), "experts"),
                      (f"{p}/shared/w_gate_up", (d, 2 * shared), "dense"),
                      (f"{p}/shared/w_down", (shared, d), "dense")]
        else:
            specs += [(f"{p}/mlp/w_gate_up", (d, 2 * ff), "dense"),
                      (f"{p}/mlp/w_down", (ff, d), "dense")]
        for hc in ("attn_hc", "mlp_hc"):
            # phi's columns, and b's entries: pre [n], post [n], res [n, n] row-major
            specs += [(f"{p}/{hc}/phi", (n * d, maps_width(config)), "dense"),
                      (f"{p}/{hc}/alpha", (3,), "gain"),
                      (f"{p}/{hc}/b", (maps_width(config),), f"hc_b{n}")]
    return specs + [("final_norm/scale", (d,), "gain"),
                    ("head/kernel", (d, vocab), "dense")]


def _leaf(key, shape, kind: str):
    import jax
    import jax.numpy as jnp

    if kind == "gain":                 # norm gains, and the maps' alpha
        return jnp.ones(shape, jnp.bfloat16)
    if kind.startswith("hc_b"):        # std 0.5, and +2 on the diagonal of b_res
        n = int(kind[4:])
        w = jax.random.normal(key, shape, jnp.float32) * np.float32(0.5)
        return w.at[2 * n + np.arange(n) * (n + 1)].add(2.0).astype(jnp.bfloat16)
    # fan-in: a matrix's rows, an expert's `[experts, rows, columns]` likewise
    std = {"table": 1.0, "router_bias": 0.01}.get(kind) or 1.0 / math.sqrt(shape[-2])
    w = jax.random.normal(key, shape, jnp.float32) * np.float32(std)
    return w.astype(jnp.bfloat16)


def make_weights(config, seed: int, under: Optional[str] = None
                 ) -> Dict[str, "jax.Array"]:
    """Every weight whose path starts with `under` (all of them without it),
    bfloat16, on the device, a leaf at a time from the seed: a leaf's key is
    its place in `weight_specs`, so a layer made alone equals that layer of
    the whole. Scales (the configuration's `assumed.weights`): gains and the
    maps' alpha 1, embeddings of std 1, every matrix (phi among them)
    1/sqrt(fan-in), the selection bias std 0.01, the maps' b std 0.5 with +2
    on the diagonal of b_res."""
    import jax

    key = seed_key(seed)
    gen = jax.jit(_leaf, static_argnums=(1, 2))
    return {path: gen(jax.random.fold_in(key, i), shape, kind)
            for i, (path, shape, kind) in enumerate(weight_specs(config))
            if under is None or path.startswith(under)}


def yarn_frequencies(config, fault=None) -> np.ndarray:
    """The 32 inverse frequencies of the 64 rotary dims (float64)."""
    dim, theta = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = config["rope_scaling"]
    if fault == "no_yarn":
        return f
    if rs["type"] != "yarn":
        raise ValueError(f"rope scaling {rs['type']!r} has no equations here")
    factor, context = float(rs["factor"]), float(rs["original_max_position_embeddings"])

    def r(x):
        return dim * math.log(context / (2 * math.pi * x)) / (2 * math.log(theta))

    low = max(math.floor(r(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(r(float(rs["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary(config, x, fault=None):
    """Rotary positions over the last dim of `[T, heads, 64]`: the two halves
    rotate against each other (the `rotate_half` form); cos and sin scaled by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim) (1 here)."""
    import jax.numpy as jnp

    rs = config["rope_scaling"]
    by = _mscale(float(rs["factor"]), float(rs["mscale"])) \
        / _mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    t, hd = x.shape[0], x.shape[-1]
    ang = np.arange(t, dtype=np.float64)[:, None] * yarn_frequencies(config, fault)[None, :]
    cos = jnp.asarray(np.cos(ang) * by, jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang) * by, jnp.float32)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(config, w, x, quant, fault):
    """One row `[T, hidden]` through the layer's latent attention."""
    import jax
    import jax.numpy as jnp

    h, eps = int(config["num_attention_heads"]), float(config["rms_norm_eps"])
    kvr = int(config["kv_lora_rank"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    t = x.shape[0]
    c_q = rms_norm(_einsum("td,dr->tr", x, w["attn/wq_a"], quant), w["attn/q_norm"], eps)
    q = _einsum("tr,re->te", c_q, w["attn/wq_b"], quant).reshape(t, h, nope + rope)
    ckv = _einsum("td,dr->tr", x, w["attn/wkv_a"], quant)
    c = rms_norm(ckv[:, :kvr], w["attn/kv_norm"], eps)
    kv = _einsum("tr,re->te", c, w["attn/wkv_b"], quant).reshape(t, h, -1)
    q_rope = rotary(config, q[..., nope:], fault)
    k_r = rotary(config, ckv[:, None, kvr:], fault)[:, 0]       # one for all heads
    rs = config["rope_scaling"]
    m = 1.0 if fault == "no_mscale" else \
        _mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    scale = np.float32((nope + rope) ** -0.5 * m * m)
    blk = min(QUERY_BLOCK, t)
    pad = -t % blk
    pos = jnp.arange(t)

    def head(a):
        qn, qr, kn, v = a                     # [T, 128], [T, 64], [T, 128], [T, 128]

        def block(b):
            qnb, qrb, first = b
            s = _einsum("qd,kd->qk", qnb, kn, quant)
            if fault != "no_rope_key":
                s = s + _einsum("qd,kd->qk", qrb, k_r, quant)
            seen = pos[None, :] <= (first + jnp.arange(blk))[:, None]
            p = jax.nn.softmax(jnp.where(seen, s * scale, -jnp.inf), axis=-1)
            return _einsum("qk,kd->qd", p, v, quant)

        qn, qr = (jnp.pad(z, ((0, pad), (0, 0))).reshape(-1, blk, z.shape[-1])
                  for z in (qn, qr))
        o = jax.lax.map(block, (qn, qr, jnp.arange(qn.shape[0]) * blk))
        return o.reshape(-1, o.shape[-1])[:t]

    o = jax.lax.map(head, (q[..., :nope].swapaxes(0, 1), q_rope.swapaxes(0, 1),
                           kv[..., :nope].swapaxes(0, 1), kv[..., nope:].swapaxes(0, 1)))
    return _einsum("te,ed->td", o.swapaxes(0, 1).reshape(t, -1), w["attn/wo"], quant)


def experts(config, w, x, idx, g, quant, fault, most: Optional[int] = None):
    """Every routed expert's part of the layer plus the shared expert, for
    `x [N, hidden]` routed as `idx`, `g` say. Every expert in turn is applied
    to the positions that chose it, `most` of them at the most (the caller
    has counted; None: every position, the rest weighted 0)."""
    import jax
    import jax.numpy as jnp

    most = x.shape[0] if most is None else most
    y = jnp.zeros_like(x)
    if fault != "no_shared":
        y = swiglu(x, w["shared/w_gate_up"], w["shared/w_down"], quant)

    def one(y, a):
        e, w1, w2 = a
        weight = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)      # [N]
        (pos,) = jnp.nonzero(weight > 0, size=most, fill_value=0)
        weight = jnp.where(jnp.arange(most) < jnp.sum(weight > 0), weight[pos], 0.0)
        return y.at[pos].add(weight[:, None] * swiglu(x[pos], w1, w2, quant)), None

    n = int(config["n_routed_experts"])
    return jax.lax.scan(one, y, (jnp.arange(n), w["moe/w1"], w["moe/w2"]))[0]


def hyper_maps(config, w, hc: str, x, quant, fault):
    """`X [T, n, d]` -> (`H_pre [T, n]`, `H_post [T, n]`, `H_res [T, n, n]`)
    of the sublayer whose hyper-connection is `hc`."""
    import jax
    import jax.numpy as jnp

    n, eps = int(config["hc_mult"]), float(config["rms_norm_eps"])
    u = x.reshape(x.shape[0], -1)
    m = _einsum("tu,uc->tc", u, w[f"{hc}/phi"], quant) \
        * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)
    if fault == "static_maps":
        m = jnp.zeros_like(m)
    alpha, b = w[f"{hc}/alpha"], w[f"{hc}/b"]
    h_pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])
    h_post = (1.0 if fault == "h_post_no_2" else 2.0) \
        * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    a = jnp.clip(alpha[2] * m[:, 2 * n:] + b[2 * n:],
                 float(config["mhc_h_res_clamp_min"]), float(config["mhc_h_res_clamp_max"]))
    res = jnp.exp(a).reshape(-1, n, n)                 # row-major: [out stream, in stream]
    hc_eps = float(config["hc_eps"])
    for _ in range(1 if fault == "sinkhorn_1" else int(config["hc_sinkhorn_iters"])):
        res = res / (jnp.sum(res, axis=2, keepdims=True) + hc_eps)
        res = res / (jnp.sum(res, axis=1, keepdims=True) + hc_eps)
    return h_pre, h_post, res


def mix_in(h_pre, x):
    """The sublayer's input `[T, d]`: `sum_i H_pre[i] X[i]`."""
    import jax.numpy as jnp

    return jnp.sum(h_pre[:, :, None] * x, axis=1)


def mix_out(h_post, h_res, x, y):
    """`X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`."""
    return sum(h_res[:, :, j, None] * x[:, None, j, :] for j in range(x.shape[1])) \
        + h_post[:, :, None] * y[:, None, :]


def front(config, w, x, sparse: bool, quant, fault):
    """One row `X [T, n, d]` as far as the router: (the streams after the
    attention sublayer, the FFN's maps, its normed input `[T, d]`, the routing
    or None)."""
    eps = float(config["rms_norm_eps"])
    h_pre, h_post, h_res = hyper_maps(config, w, "attn_hc", x, quant, fault)
    y = attention(config, w, rms_norm(mix_in(h_pre, x), w["attn_norm/scale"], eps),
                  quant, fault)
    x = mix_out(h_post, h_res, x, y)
    h_pre, h_post, h_res = hyper_maps(config, w, "mlp_hc", x, quant, fault)
    hn = rms_norm(mix_in(h_pre, x), w["mlp_norm/scale"], eps)
    return x, (h_post, h_res), hn, route(config, w, hn, quant, fault) if sparse else None


def back(config, w, x, maps, hn, routing, quant, fault, most: Optional[int] = None):
    """The layer's FFN through its hyper-connection: (the streams after the
    layer, the row's margins)."""
    import jax.numpy as jnp

    if routing is None:
        y = swiglu(hn, w["mlp/w_gate_up"], w["mlp/w_down"], quant)
        return mix_out(*maps, x, y), jnp.full(x.shape[:1], jnp.inf, jnp.float32)
    idx, g, margin = routing
    y = experts(config, w, hn, idx, g, quant, fault, most)
    return mix_out(*maps, x, y), margin


def layer(config, w, x, sparse: bool, quant, fault):
    """One row `X [T, n, d]` -> (the row after the layer, its margins)."""
    return back(config, w, *front(config, w, x, sparse, quant, fault), quant, fault)


def log_probs(config, w, x, ids, quant, fault=None):
    """One row `X [T, n, d]`: the log-probability of the next id at every
    position, the logits a block of positions at a time."""
    import jax
    import jax.numpy as jnp

    x = {"streams_mean": jnp.mean(x, axis=1), "streams_first": x[:, 0]}.get(
        fault, jnp.sum(x, axis=1))
    xn = rms_norm(x, w["final_norm/scale"], float(config["rms_norm_eps"]))
    target = jnp.concatenate([ids[1:], jnp.full((1,), int(config["pad_id"]), ids.dtype)])
    t = x.shape[0]
    blk = min(POSITION_BLOCK, t)
    pad = -t % blk

    def block(a):
        xb, tb = a
        logp = jax.nn.log_softmax(_einsum("td,dv->tv", xb, w["head/kernel"], quant))
        return jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

    out = jax.lax.map(block, (jnp.pad(xn, ((0, pad), (0, 0))).reshape(-1, blk, xn.shape[-1]),
                              jnp.pad(target, (0, pad)).reshape(-1, blk)))
    return out.reshape(-1)[:t]


def score(config, seed: int, ids: np.ndarray, quant: Optional[str] = None,
          fault: Optional[str] = None, block: int = 4,
          weights: Optional[Dict[str, "jax.Array"]] = None) -> Dict[str, np.ndarray]:
    """{"logprob": [N, T], "margin": [N, T]} float32 of padded token rows
    `[N, T]` int32: `block` rows at a time (their streams, 0.94 GB a row of
    16,384, are what is held), a layer at a time, a row at a time. A sparse
    layer stops at the router for the host to count what its busiest expert
    takes (rounded up to a power of two, 2,048 at the least: few sizes, few
    programs), then every expert runs over that many positions. `weights`,
    for a test that already holds them all, stands in for the seed."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS + NO_FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    ids = np.asarray(ids, np.int32)
    n = int(config["hc_mult"])

    def made(under: str):
        have = weights if weights is not None else make_weights(config, seed, under)
        return {p: a for p, a in have.items() if p.startswith(under)}

    programs: Dict[Tuple, object] = {}            # one a kind of layer and size

    def program(key, fn):
        return programs.setdefault(key, jax.jit(fn))

    def rows_through(ids):
        # the embedding rows are gathered from the bfloat16 table, then upcast
        rows = [program("embed", lambda tb, i: jnp.repeat(
            tb[i].astype(jnp.float32)[:, None, :], n, axis=1))(
                made("embed/")["embed/table"], jnp.asarray(r)) for r in ids]
        margins = [jnp.full(ids.shape[1:], jnp.inf, jnp.float32) for _ in ids]
        for i, sparse in enumerate(layer_plan(config)):
            w = _f32(made(f"layer{i}/"), f"layer{i}/")
            to_router = program(("front", sparse), lambda w, x, sparse=sparse: front(
                config, w, x, sparse, quant, fault))
            for r, x in enumerate(rows):
                x, maps, hn, routing = to_router(w, x)
                most = None
                if sparse:    # what the busiest expert takes, a power of two from 2,048
                    busiest = int(np.bincount(np.asarray(routing[0]).ravel()).max())
                    most = min(max(2048, 1 << (busiest - 1).bit_length()), hn.shape[0])
                rest = program(("back", sparse, most), lambda *a, most=most: back(
                    config, *a, quant, fault, most))
                rows[r], m = rest(w, x, maps, hn, routing)
                margins[r] = jnp.minimum(margins[r], m)
            del w
        w = {"final_norm/scale": _f32(made("final_norm/"), "final_norm/")["scale"],
             "head/kernel": _f32(made("head/"), "head/")["kernel"]}
        last = program("head", lambda w, x, i: log_probs(config, w, x, i, quant, fault))
        return (np.stack([np.asarray(last(w, x, jnp.asarray(i))) for x, i in zip(rows, ids)]),
                np.stack([np.asarray(m) for m in margins]))

    parts = [rows_through(ids[at:at + block]) for at in range(0, len(ids), block)]
    return {"logprob": np.concatenate([p[0] for p in parts]),
            "margin": np.concatenate([p[1] for p in parts])}
