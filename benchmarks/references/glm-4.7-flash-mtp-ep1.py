"""Plain reference of the GLM-4.7-Flash configuration, cut as the
configuration's file says (layers 0-4 of 47, every expert, head and vocabulary
row held, and the multi-token-prediction module).

Written from the configuration's `equations` in straightforward `jax.numpy`,
float32 under `highest` matmul precision: no kernel, no sort of the program's,
no batching machinery (what it shares with the other language models'
references it takes from their files). A row at a time and a layer at a time;
attention a block of queries of one head against all keys at a time, the
logits a block of positions at a time; every expert in turn is applied to the
positions that chose it (`jnp.nonzero`). Imports nothing of the program under
test and takes nothing it made: the weights come from the seed
(`make_weights`, bfloat16 values, which the builder hands to the program and
this file upcasts inside each program), the token rows from the harness.

    x_0 = E[id];  h = x + Attn(RMSNorm(x));  x' = h + FFN(RMSNorm(h))
    out[t] = log_softmax(RMSNorm_f(x_L) W_head)_t [id_{t+1}]   (last target: pad)
    Attn: c_q = RMSNorm(x W_qa); [q_nope, q_rope] = c_q W_qb (20 heads of 192 + 64)
        [c_kv, k_r] = x W_kva; [k_nope, v] = RMSNorm(c_kv) W_kvb (20 heads of 192 + 256)
        rotary positions (theta 1e6) on q_rope and on k_r (one for all heads)
        s = (q_nope . k_nope + q_rope . k_r) 256^-0.5; causal; output (P v) W_o
    FFN_0 = W_down(silu(W_gate x) * W_up x)
    FFN_l = sum_{i in top4(s + b)} g_i Expert_i(x) + Shared(x),
        s = sigmoid(x W_r), g_i = 1.8 s_i / sum_{j in top4} s_j
    MTP: u_t = [RMSNorm_e(E[id_{t+1}]) ; RMSNorm_h(x_L[t])] W_eh  (id_T: pad)
        v = the module's own layer (attention and FFN as above) over u
        mtp[t] = log_softmax(RMSNorm_s(v_t) W_head)_t [id_{t+2}]   (last two: pad)

Beside the two log-probabilities `score` returns each position's routing
`margin` (the smallest gap, over the four sparse layers, of the 4th and 5th
largest `s + b`) and `mtp_margin` (the smaller of `margin` and the module's
own layer's gap there). The comparison reads the positions whose margin is
wide as stable: a program that rounds differently picks the same experts
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

FAULTS = ("mtp_embeds_current",  # the MTP embeds id_t, not id_{t+1}
          "mtp_final_normed",    # the MTP reads the final-normed state
          "mtp_no_hnorm",        # RMSNorm_h left out
          "mtp_targets_next",    # the MTP's targets are id_{t+1}, not id_{t+2}
          "routed_scale_1",      # the routed weights scaled by 1.0, not 1.8
          "no_shared",           # the shared expert left out
          "content_128")         # the content heads cut to their whole tiles (192 -> 128)

QUERY_BLOCK = 1024              # queries of one head against all keys at a time
POSITION_BLOCK = 2048           # positions whose logits exist at a time


def layer_plan(config) -> List[bool]:
    """Whether each layer run has an expert layer (else the dense SwiGLU)."""
    dense = int(config["first_k_dense_replace"])
    return [i >= dense for i in range(int(config["num_hidden_layers"]))]


def layer_specs(config, p: str, sparse: bool) -> List[Spec]:
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    qr, kvr = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    vd, experts = int(config["v_head_dim"]), int(config["n_routed_experts"])
    ff, eff = int(config["intermediate_size"]), int(config["moe_intermediate_size"])
    shared = eff * int(config["n_shared_experts"])
    specs = [(f"{p}/attn_norm/scale", (d,), "gain"),
             (f"{p}/attn/wq_a", (d, qr), "dense"),
             (f"{p}/attn/q_norm", (qr,), "gain"),
             # a head's columns: its 192 content lanes, then its 64 rotary ones
             (f"{p}/attn/wq_b", (qr, h * (nope + rope)), "dense"),
             # columns: the 512-wide latent, then the shared rotary key
             (f"{p}/attn/wkv_a", (d, kvr + rope), "dense"),
             (f"{p}/attn/kv_norm", (kvr,), "gain"),
             # a head's columns: its content keys, then its values
             (f"{p}/attn/wkv_b", (kvr, h * (nope + vd)), "dense"),
             (f"{p}/attn/wo", (h * vd, d), "dense"),
             (f"{p}/mlp_norm/scale", (d,), "gain")]
    if sparse:
        return specs + [(f"{p}/moe/router", (d, experts), "dense"),
                        (f"{p}/moe/router_bias", (experts,), "router_bias"),
                        # gate and up side by side: columns [0, eff) are the gate
                        (f"{p}/moe/w1", (experts, d, 2 * eff), "experts"),
                        (f"{p}/moe/w2", (experts, eff, d), "experts"),
                        (f"{p}/shared/w_gate_up", (d, 2 * shared), "dense"),
                        (f"{p}/shared/w_down", (shared, d), "dense")]
    return specs + [(f"{p}/mlp/w_gate_up", (d, 2 * ff), "dense"),
                    (f"{p}/mlp/w_down", (ff, d), "dense")]


def weight_specs(config) -> List[Spec]:
    d, vocab = int(config["hidden_size"]), int(config["vocab_size"])
    specs: List[Spec] = [("embed/table", (vocab, d), "table")]
    for i, sparse in enumerate(layer_plan(config)):
        specs += layer_specs(config, f"layer{i}", sparse)
    specs += [("final_norm/scale", (d,), "spread"), ("head/kernel", (d, vocab), "dense")]
    if int(config["num_nextn_predict_layers"]):
        specs += [("mtp/enorm/scale", (d,), "spread"), ("mtp/hnorm/scale", (d,), "spread"),
                  # rows: the normed embedding's 2048, then the normed state's
                  ("mtp/eh_proj/kernel", (2 * d, d), "dense")]
        specs += layer_specs(config, "mtp/layer", True)
        specs += [("mtp/norm/scale", (d,), "spread")]
    return specs


def _leaf(key, shape, kind: str):
    import jax
    import jax.numpy as jnp

    if kind == "gain":
        return jnp.ones(shape, jnp.bfloat16)
    if kind == "spread":               # a gain away from 1: uniform in [0.5, 1.5]
        return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5).astype(jnp.bfloat16)
    # fan-in: a matrix's rows, an expert's `[experts, rows, columns]` likewise
    std = {"table": 1.0, "router_bias": 0.01}.get(kind) or 1.0 / math.sqrt(shape[-2])
    w = jax.random.normal(key, shape, jnp.float32) * np.float32(std)
    return w.astype(jnp.bfloat16)


def make_weights(config, seed: int, under: Optional[str] = None
                 ) -> Dict[str, "jax.Array"]:
    """Every weight whose path starts with `under` (all of them without it),
    bfloat16, on the device, a leaf at a time from the seed: a leaf's key is
    its place in `weight_specs`, so a layer made alone equals that layer of
    the whole. Scales (the configuration's `assumed.weights`): the gains
    before the sublayers and on the latents 1, those of the final norm and
    the MTP's three norms uniform in [0.5, 1.5], embeddings of std 1, every
    matrix 1/sqrt(fan-in), the router's selection bias std 0.01."""
    import jax

    key = seed_key(seed)
    gen = jax.jit(_leaf, static_argnums=(1, 2))
    return {path: gen(jax.random.fold_in(key, i), shape, kind)
            for i, (path, shape, kind) in enumerate(weight_specs(config))
            if under is None or path.startswith(under)}


def rotary(config, x):
    """Rotary positions over the last dim of `[T, heads, 64]` (theta
    `rope_theta`, no scaling), the `rotate_half` form."""
    if config.get("rope_scaling"):
        raise ValueError("rope scaling has no equations here")
    return _first.rotary(x, float(config["rope_theta"]))


def attention(config, w, x, quant, fault):
    """One row `[T, hidden]` (the normed input) through a latent attention."""
    import jax
    import jax.numpy as jnp

    h, eps = int(config["num_attention_heads"]), float(config["rms_norm_eps"])
    kvr = int(config["kv_lora_rank"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    content = nope - nope % 128 if fault == "content_128" else nope
    t = x.shape[0]
    c_q = rms_norm(_einsum("td,dr->tr", x, w["attn/wq_a"], quant), w["attn/q_norm"], eps)
    q = _einsum("tr,re->te", c_q, w["attn/wq_b"], quant).reshape(t, h, nope + rope)
    ckv = _einsum("td,dr->tr", x, w["attn/wkv_a"], quant)
    kv = _einsum("tr,re->te", rms_norm(ckv[:, :kvr], w["attn/kv_norm"], eps),
                 w["attn/wkv_b"], quant).reshape(t, h, -1)
    q_rope = rotary(config, q[..., nope:])
    k_r = rotary(config, ckv[:, None, kvr:])[:, 0]              # one for all heads
    scale = np.float32((nope + rope) ** -0.5)
    blk = min(QUERY_BLOCK, t)
    pad = -t % blk
    pos = jnp.arange(t)

    def head(a):
        qn, qr, kn, v = a                     # [T, 192], [T, 64], [T, 192], [T, 256]

        def block(b):
            qnb, qrb, first = b
            s = _einsum("qd,kd->qk", qnb[:, :content], kn[:, :content], quant) \
                + _einsum("qd,kd->qk", qrb, k_r, quant)
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


def route(config, w, x, quant, fault):
    """`x [N, hidden]` -> (the chosen experts `[N, 4]`, their weights `[N, 4]`,
    each position's gap between the 4th and 5th selection score)."""
    import jax
    import jax.numpy as jnp

    top_k = int(config["num_experts_per_tok"])
    s = jax.nn.sigmoid(_einsum("td,de->te", x, w["moe/router"], quant))
    chosen_by, idx = jax.lax.top_k(s + w["moe/router_bias"], top_k + 1)
    margin = chosen_by[:, top_k - 1] - chosen_by[:, top_k]
    idx = idx[:, :top_k]
    g = jnp.take_along_axis(s, idx, axis=-1)
    if config["norm_topk_prob"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    scale = 1.0 if fault == "routed_scale_1" else float(config["routed_scaling_factor"])
    return idx, g * np.float32(scale), margin


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


def front(config, w, x, sparse: bool, quant, fault):
    """One row `[T, hidden]` as far as the router: (the row after attention,
    the FFN's normed input, the routing or None)."""
    eps = float(config["rms_norm_eps"])
    h = x + attention(config, w, rms_norm(x, w["attn_norm/scale"], eps), quant, fault)
    hn = rms_norm(h, w["mlp_norm/scale"], eps)
    return h, hn, route(config, w, hn, quant, fault) if sparse else None


def back(config, w, h, hn, routing, quant, fault, most: Optional[int] = None):
    """The layer's FFN and residual: (the row after the layer, its margins)."""
    import jax.numpy as jnp

    if routing is None:
        y = swiglu(hn, w["mlp/w_gate_up"], w["mlp/w_down"], quant)
        return h + y, jnp.full(h.shape[:1], jnp.inf, jnp.float32)
    idx, g, margin = routing
    return h + experts(config, w, hn, idx, g, quant, fault, most), margin


def next_ids(config, ids, ahead: int):
    """`ids` moved `ahead` positions to the left, the pad id after the row."""
    import jax.numpy as jnp

    return jnp.concatenate([ids[ahead:], jnp.full((ahead,), int(config["pad_id"]), ids.dtype)])


def log_probs(config, gain, head, x, target, quant):
    """One row: the log-probability of `target` at every position under
    `log_softmax(RMSNorm(x) W_head)`, the logits a block of positions at a
    time."""
    import jax
    import jax.numpy as jnp

    xn = rms_norm(x, gain, float(config["rms_norm_eps"]))
    t = x.shape[0]
    blk = min(POSITION_BLOCK, t)
    pad = -t % blk

    def block(a):
        xb, tb = a
        logp = jax.nn.log_softmax(_einsum("td,dv->tv", xb, head, quant))
        return jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

    out = jax.lax.map(block, (jnp.pad(xn, ((0, pad), (0, 0))).reshape(-1, blk, xn.shape[-1]),
                              jnp.pad(target, (0, pad)).reshape(-1, blk)))
    return out.reshape(-1)[:t]


def mtp_input(config, w, table, final_gain, x, ids, quant, fault):
    """One row's input `u [T, hidden]` to the MTP module's layer, from the
    last layer's output `x` (before the final norm) and the row's ids."""
    import jax.numpy as jnp

    eps = float(config["rms_norm_eps"])
    e = table[ids if fault == "mtp_embeds_current" else next_ids(config, ids, 1)]
    if fault == "mtp_final_normed":
        x = rms_norm(x, final_gain, eps)
    hn = x if fault == "mtp_no_hnorm" else rms_norm(x, w["hnorm/scale"], eps)
    joined = jnp.concatenate([rms_norm(e, w["enorm/scale"], eps), hn], axis=-1)
    return _einsum("tc,cd->td", joined, w["eh_proj/kernel"], quant)


def score(config, seed: int, ids: np.ndarray, quant: Optional[str] = None,
          fault: Optional[str] = None,
          weights: Optional[Dict[str, "jax.Array"]] = None) -> Dict[str, np.ndarray]:
    """{"logprob", "mtp_logprob", "margin", "mtp_margin"}, each `[N, T]`
    float32, of padded token rows `[N, T]` int32: a layer at a time, a row at
    a time, making that layer's weights from the seed (bfloat16 on the
    device, upcast inside each program) and dropping them after; the rows
    wait on the host between programs, so the device holds one row and one
    layer. A sparse layer stops at the router for the host to count what its
    busiest expert takes (rounded up to a power of two, 2,048 at the least:
    few sizes, few programs), then every expert runs over that many
    positions. `weights`, for a test that already holds them all, stands in
    for the seed."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    ids = np.asarray(ids, np.int32)

    def made(under: str):
        have = weights if weights is not None else make_weights(config, seed, under)
        return {p[len(under):]: a for p, a in have.items() if p.startswith(under)}

    def up(w):                # float32 inside a program only
        return {p: a.astype(jnp.float32) for p, a in w.items()}

    programs: Dict[Tuple, object] = {}            # one a kind of layer and size

    def program(key, fn):
        return programs.setdefault(key, jax.jit(fn))

    def through(w, rows, sparse: bool, margins):
        """Every row through one layer of weights `w`, in place."""
        to_router = program(("front", sparse), lambda w, x, sparse=sparse: front(
            config, up(w), x, sparse, quant, fault))
        for r, x in enumerate(rows):
            h, hn, routing = to_router(w, jnp.asarray(x))
            most = None
            if sparse:    # what the busiest expert takes, a power of two from 2,048
                busiest = int(np.bincount(np.asarray(routing[0]).ravel()).max())
                most = min(max(2048, 1 << max(busiest - 1, 0).bit_length()), hn.shape[0])
            rest = program(("back", sparse, most), lambda w, *a, most=most: back(
                config, up(w), *a, quant, fault, most))
            x, m = rest(w, h, hn, routing)
            del h, hn, routing
            rows[r], margins[r] = np.asarray(x), np.minimum(margins[r], np.asarray(m))

    table = made("embed/")["table"]
    rows = [np.asarray(program("embed", lambda tb, i: tb[i].astype(jnp.float32))(
        table, jnp.asarray(r))) for r in ids]
    margins = [np.full(ids.shape[1:], np.inf, np.float32) for _ in ids]
    for i, sparse in enumerate(layer_plan(config)):
        through(made(f"layer{i}/"), rows, sparse, margins)
    final = {"gain": made("final_norm/")["scale"], "head": made("head/")["kernel"]}
    last = program("head", lambda w, x, i: log_probs(config, w["gain"].astype(jnp.float32),
                                                     w["head"].astype(jnp.float32), x,
                                                     next_ids(config, i, 1), quant))
    out = {"logprob": np.stack([np.asarray(last(final, jnp.asarray(x), jnp.asarray(i)))
                                for x, i in zip(rows, ids)]),
           "margin": np.stack(margins)}
    # the MTP module: its input from the last layer's rows, its layer, the head
    w = made("mtp/")
    fuse = program("fuse", lambda w, tb, g, x, i: mtp_input(
        config, up(w), tb.astype(jnp.float32), g.astype(jnp.float32), x, i, quant, fault))
    rows = [np.asarray(fuse({k: w[k] for k in ("enorm/scale", "hnorm/scale", "eh_proj/kernel")},
                            table, final["gain"], jnp.asarray(x), jnp.asarray(i)))
            for x, i in zip(rows, ids)]
    del table
    through({p[len("layer/"):]: a for p, a in w.items() if p.startswith("layer/")},
            rows, True, margins)
    ahead = 1 if fault == "mtp_targets_next" else 2
    mtp = program("mtp_head", lambda g, hd, x, i: log_probs(
        config, g.astype(jnp.float32), hd.astype(jnp.float32), x,
        next_ids(config, i, ahead), quant))
    out["mtp_logprob"] = np.stack([np.asarray(mtp(w["norm/scale"], final["head"],
                                                  jnp.asarray(x), jnp.asarray(i)))
                                   for x, i in zip(rows, ids)])
    out["mtp_margin"] = np.stack(margins)
    return out
