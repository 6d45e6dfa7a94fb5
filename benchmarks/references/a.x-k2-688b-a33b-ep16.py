"""Plain reference of the A.X-K2 configuration, cut as the configuration's
file says (layers 0-4 of 61, experts 0-15 of 256, rows 0-20,479 of the
vocabulary: one chip of the sixteen that share a layer).

Written from the configuration's `equations` in straightforward `jax.numpy`,
float32 under `highest` matmul precision: no kernel, no sort of the program's,
no batching machinery (what it shares with the first language model's
reference it takes from that file). A row at a time and a layer at a time;
attention a block of queries at a time: the indexer's scores of the block
against every key, `lax.top_k` over the causal ones, and every head's dense
scores masked to the chosen keys; every held expert in turn is applied to the
positions that chose it (`jnp.nonzero`). Imports nothing of the program under
test and takes nothing it made: the weights come from the seed
(`make_weights`, bfloat16 values, which the builder hands to the program and
this file upcasts a layer at a time), the token rows from the harness.

    x_0 = E[id];  h = x + Attn(N_attn(x));  x' = h + FFN(N_mlp(h))
    N(x) = n sigmoid((n A) B), n = RMSNorm(x)        (rank 16)
    out[t] = log_softmax(RMSNorm(x_L) W_head)_t [id_{t+1}]   (last target: pad)
    Attn: c_q = RMSNorm(x W_qa); [q_nope, q_rope, g] = c_q W_qb (64 heads of 128 + 64 + 128)
        [c_kv, k_r] = x W_kva; [k_nope, v] = RMSNorm(c_kv) W_kvb (64 heads of 128 + 128)
        YaRN rotary positions on q_rope and on k_r (one for all heads)
        q^I = c_q W^I_q (64 heads of 128), k^I = LayerNorm(x W^I_k), rotary on their
        first 64 dims; w = x W^I_w 64^-0.5; I[t, s] = 128^-0.5 sum_j w_j relu(q^I_j . k^I_s)
        S_t = the min(2048, t + 1) causal keys of largest I (ties to the lower position)
        s = (q_nope . k_nope + q_rope . k_r) 192^-0.5 m^2 over S_t, m = 0.1 ln 2 + 1
        o = (P v) sigmoid(g); output o W_o
    FFN_0 = W_down(silu(W_gate x) * W_up x)
    FFN_l = sum_{i chosen and held} g_i Expert_i(x) + Shared(x): s = sigmoid(x W_r)
        over 256; a group of 32 scores the sum of its two largest s + b; the 4 best
        of 8 groups are kept; the 8 largest s + b among their experts are chosen;
        g_i = 2.5 s_i / sum_{chosen} s_j

Beside the log-probabilities `score` returns each position's routing
`margin`: the smallest, over the sparse layers, of the gap between the 8th and
9th `s + b` among the kept groups' experts and the gap between the 4th and
5th group score. The comparison reads the positions whose margin is wide as
stable: a program that rounds differently picks the same groups and experts
there.

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

_first = spec.bench_module("references", "k-exaone-236b-a23b-ep8")
seed_key, _quant, _einsum, rms_norm = (_first.seed_key, _first._quant, _first._einsum,
                                       _first.rms_norm)
swiglu, row_gaps = _first.swiglu, _first.row_gaps

Spec = Tuple[str, Tuple[int, ...], str]        # (path, shape, kind)

FAULTS = ("dense_attention",     # every causal key, no selection
          "topk_1024",           # the top 1,024 keys in place of 2,048
          "index_no_relu",       # the indexer's scores without the ReLU
          "index_w_uniform",     # the index weights w all 64^-0.5
          "no_out_gate",         # the heads' outputs not gated
          "no_gated_norm",       # the sublayers' norms plain RMSNorm
          "routing_no_groups")   # the top 8 of all 256 experts, no group limit

QUERY_BLOCK = 64                # queries whose projections, scores and masks exist at a time
POSITION_BLOCK = 2048           # positions whose logits exist at a time


def layer_plan(config) -> List[bool]:
    """Whether each layer run has an expert layer (else the dense SwiGLU)."""
    dense, freq = int(config["first_k_dense_replace"]), int(config["moe_layer_freq"])
    return [i >= dense and i % freq == 0 for i in range(int(config["num_hidden_layers"]))]


def weight_specs(config) -> List[Spec]:
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    qr, kvr = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    vd, vocab = int(config["v_head_dim"]), int(config["vocab_size"])
    ih, idim = int(config["index_n_heads"]), int(config["index_head_dim"])
    held, router = int(config["n_routed_experts"]), int(config["n_routed_experts_published"])
    ff, eff = int(config["intermediate_size"]), int(config["moe_intermediate_size"])
    rank = int(config["gated_norm_rank"])
    shared = eff * int(config["n_shared_experts"])
    gate = vd if config["attention_output_gate"] else 0

    def norm(p):
        return [(f"{p}/scale", (d,), "gain"), (f"{p}/gate_a", (d, rank), "dense"),
                (f"{p}/gate_b", (rank, d), "dense")]

    specs: List[Spec] = [("embed/table", (vocab, d), "table")]
    for i, sparse in enumerate(layer_plan(config)):
        p = f"layer{i}"
        specs += norm(f"{p}/attn_norm") + [
            (f"{p}/attn/wq_a", (d, qr), "dense"),
            (f"{p}/attn/q_norm", (qr,), "gain"),
            # a head's columns: 128 content lanes, 64 rotary ones, 128 of its gate
            (f"{p}/attn/wq_b", (qr, h * (nope + rope + gate)), "dense"),
            # columns: the 512-wide latent, then the shared rotary key
            (f"{p}/attn/wkv_a", (d, kvr + rope), "dense"),
            (f"{p}/attn/kv_norm", (kvr,), "gain"),
            # a head's columns: its content keys, then its values
            (f"{p}/attn/wkv_b", (kvr, h * (nope + vd)), "dense"),
            (f"{p}/attn/wo", (h * vd, d), "dense"),
            (f"{p}/attn/index_wq", (qr, ih * idim), "dense"),
            (f"{p}/attn/index_wk", (d, idim), "dense"),
            (f"{p}/attn/index_k_scale", (idim,), "gain"),
            (f"{p}/attn/index_k_bias", (idim,), "zero"),
            (f"{p}/attn/index_ww", (d, ih), "dense")] + norm(f"{p}/mlp_norm")
        if sparse:
            specs += [(f"{p}/moe/router", (d, router), "dense"),
                      (f"{p}/moe/router_bias", (router,), "router_bias"),
                      # gate and up side by side: columns [0, eff) are the gate
                      (f"{p}/moe/w1", (held, d, 2 * eff), "experts"),
                      (f"{p}/moe/w2", (held, eff, d), "experts"),
                      (f"{p}/shared/w_gate_up", (d, 2 * shared), "dense"),
                      (f"{p}/shared/w_down", (shared, d), "dense")]
        else:
            specs += [(f"{p}/mlp/w_gate_up", (d, 2 * ff), "dense"),
                      (f"{p}/mlp/w_down", (ff, d), "dense")]
    return specs + [("final_norm/scale", (d,), "gain"),
                    ("head/kernel", (d, vocab), "dense")]


def _leaf(key, shape, kind: str):
    import jax
    import jax.numpy as jnp

    if kind in ("gain", "zero"):
        return (jnp.ones if kind == "gain" else jnp.zeros)(shape, jnp.bfloat16)
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
    the indexer's LayerNorm bias 0, embeddings of std 1, every matrix
    1/sqrt(fan-in), the router's selection bias std 0.01."""
    import jax

    key = seed_key(seed)
    gen = jax.jit(_leaf, static_argnums=(1, 2))
    return {path: gen(jax.random.fold_in(key, i), shape, kind)
            for i, (path, shape, kind) in enumerate(weight_specs(config))
            if under is None or path.startswith(under)}


def yarn(config) -> Tuple[np.ndarray, float]:
    """(the 32 inverse frequencies of the 64 rotary dims, float64; what cos
    and sin are scaled by, mscale(factor, mscale) / mscale(factor,
    mscale_all_dim))."""
    dim, rp = int(config["qk_rope_head_dim"]), config["rope_parameters"]
    theta = float(rp["rope_theta"])
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope type {rp['rope_type']!r} has no equations here")
    factor, context = float(rp["factor"]), float(rp["original_max_position_embeddings"])

    def r(x):
        return dim * math.log(context / (2 * math.pi * x)) / (2 * math.log(theta))

    low = max(math.floor(r(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(r(float(rp["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (f / factor * ramp + f * (1.0 - ramp),
            _mscale(factor, float(rp["mscale"])) / _mscale(factor, float(rp["mscale_all_dim"])))


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary_tables(config, t: int):
    """(cos, sin) `[t, 32]` float32 of positions 0..t-1, computed in float64."""
    import jax.numpy as jnp

    inv, by = yarn(config)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * by, jnp.float32),
            jnp.asarray(np.sin(ang) * by, jnp.float32))


def rotary(x, cos, sin):
    """Rotary positions over the last dim of `[T, heads, 64]` at the tables'
    rows (`[T, 32]`): the two halves rotate against each other (the
    `rotate_half` form)."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    cos, sin = cos[:, None, :], sin[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def norm(config, w, name: str, x, quant, fault):
    """The gated RMSNorm before a sublayer: `n sigmoid((n A) B)`."""
    import jax

    n = rms_norm(x, w[f"{name}/scale"], float(config["rms_norm_eps"]))
    if fault == "no_gated_norm":
        return n
    low = _einsum("td,dr->tr", n, w[f"{name}/gate_a"], quant)
    return n * jax.nn.sigmoid(_einsum("tr,rd->td", low, w[f"{name}/gate_b"], quant))


def index_queries(config, w, c_q, cos, sin, quant):
    """`q^I [T, heads, 128]` of query latents `c_q [T, q_rank]` at the
    positions of the tables' rows."""
    import jax.numpy as jnp

    ih, idim = int(config["index_n_heads"]), int(config["index_head_dim"])
    rope = int(config["qk_rope_head_dim"])
    qi = _einsum("tr,re->te", c_q, w["attn/index_wq"], quant).reshape(-1, ih, idim)
    return jnp.concatenate([rotary(qi[..., :rope], cos, sin), qi[..., rope:]], axis=-1)


def index_keys(config, w, x, cos, sin, quant, fault):
    """(`k^I [T, 128]`, `w [T, heads]` with the scores' 128^-0.5 in it) of
    one row."""
    import jax
    import jax.numpy as jnp

    ih, idim = int(config["index_n_heads"]), int(config["index_head_dim"])
    rope, t = int(config["qk_rope_head_dim"]), x.shape[0]
    k = _einsum("td,de->te", x, w["attn/index_wk"], quant)
    mu = jnp.mean(k, axis=-1, keepdims=True)
    k = (k - mu) * jax.lax.rsqrt(jnp.mean((k - mu) ** 2, axis=-1, keepdims=True)
                                 + float(config["rms_norm_eps"]))
    k = k * w["attn/index_k_scale"] + w["attn/index_k_bias"]
    ki = jnp.concatenate([rotary(k[:, None, :rope], cos, sin)[:, 0], k[:, rope:]], axis=-1)
    if fault == "index_w_uniform":
        wi = jnp.full((t, ih), ih ** -0.5, jnp.float32)
    else:
        wi = _einsum("td,dj->tj", x, w["attn/index_ww"], quant) * np.float32(ih ** -0.5)
    return ki, wi * np.float32(idim ** -0.5)


def attention(config, w, x, quant, fault):
    """One row `[T, hidden]` (the normed input) through the layer's sparse,
    gated latent attention: keys, values and the indexer's keys for the row,
    then a block of queries at a time (their projections, the indexer's
    scores against every key, the selection, the heads' masked softmax, the
    gate)."""
    import jax
    import jax.numpy as jnp

    h, eps = int(config["num_attention_heads"]), float(config["rms_norm_eps"])
    kvr, vd = int(config["kv_lora_rank"]), int(config["v_head_dim"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    topk = 1024 if fault == "topk_1024" else int(config["index_topk"])
    t = x.shape[0]
    cos, sin = rotary_tables(config, t)
    c_q = rms_norm(_einsum("td,dr->tr", x, w["attn/wq_a"], quant), w["attn/q_norm"], eps)
    ckv = _einsum("td,dr->tr", x, w["attn/wkv_a"], quant)
    kv = _einsum("tr,re->te", rms_norm(ckv[:, :kvr], w["attn/kv_norm"], eps),
                 w["attn/wkv_b"], quant).reshape(t, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_r = rotary(ckv[:, None, kvr:], cos, sin)[:, 0]            # one for all heads
    ki, wi = index_keys(config, w, x, cos, sin, quant, fault)
    m = _mscale(float(config["rope_parameters"]["factor"]),
                float(config["rope_parameters"]["mscale_all_dim"]))
    scale = np.float32((nope + rope) ** -0.5 * m * m)
    blk = min(QUERY_BLOCK, t)
    pad = -t % blk
    pos = jnp.arange(t)

    def block(b):
        cq, wib, cs, sn, first = b           # [blk, q_rank], [blk, heads], [blk, 32] x 2
        qg = _einsum("tr,re->te", cq, w["attn/wq_b"], quant).reshape(blk, h, -1)
        qn, qr, g = qg[..., :nope], rotary(qg[..., nope:nope + rope], cs, sn), \
            qg[..., nope + rope:]
        causal = pos[None, :] <= (first + jnp.arange(blk))[:, None]
        if fault == "dense_attention":
            seen = causal
        else:
            s_i = _einsum("qjd,kd->qjk", index_queries(config, w, cq, cs, sn, quant), ki,
                          quant)
            s_i = s_i if fault == "index_no_relu" else jnp.maximum(s_i, 0.0)
            score = jnp.where(causal, jnp.einsum("qjk,qj->qk", s_i, wib,
                                                 precision="highest"), -jnp.inf)
            _, idx = jax.lax.top_k(score, min(topk, t))
            seen = jnp.zeros((blk, t), bool).at[jnp.arange(blk)[:, None], idx].set(True) \
                & causal
        s = (_einsum("qhd,khd->hqk", qn, k_nope, quant)
             + _einsum("qhd,kd->hqk", qr, k_r, quant)) * scale
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        o = _einsum("hqk,khd->qhd", p, v, quant)
        return o if fault == "no_out_gate" else o * jax.nn.sigmoid(g)

    def blocks(z):
        return jnp.pad(z, ((0, pad),) + ((0, 0),) * (z.ndim - 1)).reshape(
            -1, blk, *z.shape[1:])

    o = jax.lax.map(block, (blocks(c_q), blocks(wi), blocks(cos), blocks(sin),
                            jnp.arange((t + pad) // blk) * blk))
    return _einsum("te,ed->td", o.reshape(-1, h * vd)[:t], w["attn/wo"], quant)


def route(config, w, x, quant, fault):
    """`x [N, hidden]` -> (the chosen experts `[N, 8]`, their weights `[N, 8]`,
    each position's margin: the smaller of the 8th-9th gap of `s + b` among
    the kept groups' experts and the 4th-5th gap of the group scores)."""
    import jax
    import jax.numpy as jnp

    top_k, experts = int(config["num_experts_per_tok"]), int(config["n_routed_experts_published"])
    groups, kept = int(config["n_group"]), int(config["topk_group"])
    s = jax.nn.sigmoid(_einsum("td,de->te", x, w["moe/router"], quant))
    by = s + w["moe/router_bias"]
    margin = jnp.full(x.shape[:1], jnp.inf, jnp.float32)
    if fault != "routing_no_groups":
        grouped = by.reshape(-1, groups, experts // groups)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)      # [N, groups]
        ranked, best = jax.lax.top_k(group_score, kept + 1)
        margin = ranked[:, kept - 1] - ranked[:, kept]
        keep = jnp.any(best[:, :kept, None] == jnp.arange(groups), axis=1)
        by = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(by.shape)
    chosen_by, idx = jax.lax.top_k(by, top_k + 1)
    margin = jnp.minimum(margin, chosen_by[:, top_k - 1] - chosen_by[:, top_k])
    idx = idx[:, :top_k]
    g = jnp.take_along_axis(s, idx, axis=-1)
    if config["norm_topk_prob"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return idx, g * np.float32(config["routed_scaling_factor"]), margin


def experts(config, w, x, idx, g, quant, most: Optional[int] = None):
    """The held experts' part of the layer plus the shared expert, for
    `x [N, hidden]` routed as `idx`, `g` say. Every held expert in turn is
    applied to the positions that chose it, `most` of them at the most (the
    caller has counted; None: every position, the rest weighted 0)."""
    import jax
    import jax.numpy as jnp

    held, first = int(config["n_routed_experts"]), int(config["first_expert_held"])
    most = x.shape[0] if most is None else most
    y = swiglu(x, w["shared/w_gate_up"], w["shared/w_down"], quant)

    def one(y, a):
        e, w1, w2 = a
        weight = jnp.sum(jnp.where(idx == first + e, g, 0.0), axis=-1)      # [N]
        (pos,) = jnp.nonzero(weight > 0, size=most, fill_value=0)
        weight = jnp.where(jnp.arange(most) < jnp.sum(weight > 0), weight[pos], 0.0)
        return y.at[pos].add(weight[:, None] * swiglu(x[pos], w1, w2, quant)), None

    return jax.lax.scan(one, y, (jnp.arange(held), w["moe/w1"], w["moe/w2"]))[0]


def front(config, w, x, sparse: bool, quant, fault):
    """One row `[T, hidden]` as far as the router: (the row after attention,
    the FFN's normed input, the routing or None)."""
    h = x + attention(config, w, norm(config, w, "attn_norm", x, quant, fault), quant, fault)
    hn = norm(config, w, "mlp_norm", h, quant, fault)
    return h, hn, route(config, w, hn, quant, fault) if sparse else None


def back(config, w, h, hn, routing, quant, most: Optional[int] = None):
    """The layer's FFN and residual: (the row after the layer, its margins)."""
    import jax.numpy as jnp

    if routing is None:
        y = swiglu(hn, w["mlp/w_gate_up"], w["mlp/w_down"], quant)
        return h + y, jnp.full(h.shape[:1], jnp.inf, jnp.float32)
    idx, g, margin = routing
    return h + experts(config, w, hn, idx, g, quant, most), margin


def log_probs(config, w, x, ids, quant):
    """One row: the log-probability of the next id at every position, the
    logits a block of positions at a time."""
    import jax
    import jax.numpy as jnp

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
          fault: Optional[str] = None,
          weights: Optional[Dict[str, "jax.Array"]] = None) -> Dict[str, np.ndarray]:
    """{"logprob": [N, T], "margin": [N, T]} float32 of padded token rows
    `[N, T]` int32: a layer at a time, a row at a time, making that layer's
    weights from the seed (bfloat16 on the device, upcast inside each
    program) and dropping them after; the rows wait on the host between
    programs, so the device holds one row and one layer. A sparse layer stops
    at the router for the host to count what its busiest held expert takes
    (rounded up to a power of two, 2,048 at the least: few sizes, few
    programs), then every held expert runs over that many positions.
    `weights`, for a test that already holds them all, stands in for the
    seed."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    ids = np.asarray(ids, np.int32)
    held, first = int(config["n_routed_experts"]), int(config["first_expert_held"])

    def made(under: str):
        have = weights if weights is not None else make_weights(config, seed, under)
        return {p[len(under):]: a for p, a in have.items() if p.startswith(under)}

    def up(w):                # float32 inside a program only
        return {p: a.astype(jnp.float32) for p, a in w.items()}

    programs: Dict[Tuple, object] = {}            # one a kind of layer and size

    def program(key, fn):
        return programs.setdefault(key, jax.jit(fn))

    table = made("embed/")["table"]
    rows = [np.asarray(program("embed", lambda tb, i: tb[i].astype(jnp.float32))(
        table, jnp.asarray(r))) for r in ids]
    del table
    margins = [np.full(ids.shape[1:], np.inf, np.float32) for _ in ids]
    for i, sparse in enumerate(layer_plan(config)):
        w = made(f"layer{i}/")
        to_router = program(("front", sparse), lambda w, x, sparse=sparse: front(
            config, up(w), x, sparse, quant, fault))
        for r, x in enumerate(rows):
            h, hn, routing = to_router(w, jnp.asarray(x))
            most = None
            if sparse:    # what the busiest held expert takes, a power of two from 2,048
                local = np.asarray(routing[0]).ravel() - first
                busiest = int(np.bincount(local[(local >= 0) & (local < held)],
                                          minlength=1).max())
                most = min(max(2048, 1 << max(busiest - 1, 0).bit_length()), hn.shape[0])
            rest = program(("back", sparse, most), lambda w, *a, most=most: back(
                config, up(w), *a, quant, most))
            x, m = rest(w, h, hn, routing)
            del h, hn, routing
            rows[r], margins[r] = np.asarray(x), np.minimum(margins[r], np.asarray(m))
        del w
    w = {"final_norm/scale": made("final_norm/")["scale"], "head/kernel": made("head/")["kernel"]}
    last = program("head", lambda w, x, i: log_probs(config, up(w), x, i, quant))
    return {"logprob": np.stack([np.asarray(last(w, jnp.asarray(x), jnp.asarray(i)))
                                 for x, i in zip(rows, ids)]),
            "margin": np.stack(margins)}
