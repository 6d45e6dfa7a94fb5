"""Plain reference of the Phi-4-mini-flash-reasoning configuration, whole (32
layers, every row of the vocabulary; `reduced: []`).

Written from the configuration's `equations` in straightforward `jax.numpy`,
float32 under `highest` matmul precision: no kernel, no chunked scan, no
batching machinery (what it shares with the first language model's reference
it takes from that file: the PRNG key of a seed, the float8 control's
rounding, a matrix product at `highest`, SwiGLU, the upcast of a layer's
weights, the comparison's gaps). The selective scan is the recurrence itself,
a `lax.scan` of T steps over `H` (5,120 x 16); attention is the masked softmax
of a block of 1,024 queries of one pair against the keys it may see (all of
them on the full and cross layers, the 512 before the block and the block on
the window layers); the head's logits exist a block of 1,024 positions at a
time. Imports nothing of the program under test and takes nothing it made:
the weights come from the seed (`make_weights`, bfloat16 values, which the
builder hands to the program and this file upcasts a layer at a time), the
token rows from the harness.

    x_0 = E[id];  every layer i: h = x + Mixer_i(LN(x)); x' = h + SwiGLU(LN(h))
    out[t] = log_softmax(LN(x_32) E^T)_t [id_{t+1}]        (last target: pad)
    Mixer_i, L = 32: even i <= 16 Mamba (layer 16 hands on its memory m);
      odd i <= 15 differential attention, window 512; 17 the same, full, its
      K, V handed on; even i >= 18 a Gated Memory Unit on m; odd i >= 19
      differential cross-attention: own queries, layer 17's K, V
    Mamba(u): [xs, z] = u W_in; xc_t = silu(b + sum_j w[:, j] xs_{t-3+j});
      [dl, B_t, C_t] = xc_t W_x; delta_t = softplus(dl W_dt + b_dt);
      H_t = exp(delta_t (x) A) H_{t-1} + (delta_t xc_t) (x) B_t, A = -exp(A_log);
      y_t = H_t C_t + D xc_t; m = y; output (y silu(z)) W_out
    GMU(u) = (m silu(u W_1)) W_2
    Diff(u): q = u W_q + b_q (20 pairs [q1 | q2] of 64 + 64); k, v = u W_kv +
      b_kv (10 pairs [k1 | k2], 10 values V = [v1 | v2] of 128); pair j reads
      pair j // 2; O = softmax(q1 k1^T / 8) V - lam softmax(q2 k2^T / 8) V;
      lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_i, lam_i = 0.8 - 0.6 e^(-0.3 i);
      O <- RMSNorm_128(O) g (1 - lam_i); output [O_0 .. O_19] W_o + b_o

**Departures from the published description**, each the configuration's
`assumed`: the sizes the catalog row lacks (state 16, convolution 4, expand
2, dt rank 160), the memory taken before the gate and with the `D` term, no
positional encoding, random weights. **Pads after a row's last real token
are not computed**: attention is causal and the scan runs forward, so no real
position sees them; a row goes through as its first `length` positions
rounded up to a block of 1,024, and the positions after that read NaN.

`score` goes a layer at a time, a row at a time: a layer's float32 weights
(0.31 GB for a SwiGLU) and the rows' states (0.34 GB a row of 32,768, the
memory 0.67 GB, the shared keys and values 0.34 GB) are what it holds.

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
seed_key, _quant, _einsum = _first.seed_key, _first._quant, _first._einsum
swiglu, _f32, row_gaps = _first.swiglu, _first._f32, _first.row_gaps

Spec = Tuple[str, Tuple[int, ...], str]        # (path, shape, kind)

FAULTS = ("window_511",          # a window layer sees 511 keys, not 512
          "memory_after_gate",   # m taken after the gate: y silu(z)
          "cross_own_keys",      # a cross layer: layer 17's W_kv on its OWN input
          "lambda_init_0",       # lambda_init of layer 0 (0.2) in every layer
          "no_d",                # the D xc term left out of y (and of m)
          "taps_reversed",       # the convolution's four taps in reverse order
          "state_reset_8192")    # the state H set to zero every 8,192 positions

QUERY_BLOCK = 1024              # queries of one pair against their keys at a time
POSITION_BLOCK = 1024           # positions whose logits exist at a time
ROW_BLOCK = 1024                # a row is computed to its length rounded up to this


def layer_plan(config) -> List[str]:
    """Each layer's mixer: mamba, mamba_memory, window, full, gmu or cross."""
    n, every = int(config["num_hidden_layers"]), int(config["mb_per_layer"])
    half = n // 2
    plan = []
    for i in range(n):
        state = i % every == 0
        if i <= half:
            plan.append(("mamba_memory" if i == half else "mamba") if state else "window")
        elif i == half + 1:
            plan.append("full")
        else:
            plan.append("gmu" if state else "cross")
    return plan


def sizes(config) -> Dict[str, int]:
    a, d = config["assumed"], int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    return {"d": d, "heads": heads, "kv": int(config["num_key_value_heads"]),
            "hd": d // heads, "ff": int(config["intermediate_size"]),
            "inner": int(a["mamba_expand"]) * d, "states": int(a["mamba_d_state"]),
            "taps": int(a["mamba_d_conv"]), "rank": int(a["mamba_dt_rank"]),
            "vocab": int(config["vocab_size"]), "window": int(config["sliding_window"])}


def weight_specs(config) -> List[Spec]:
    s = sizes(config)
    d, c, n, r = s["d"], s["inner"], s["states"], s["rank"]
    hq, hkv = s["heads"] * s["hd"], s["kv"] * s["hd"]
    specs: List[Spec] = [("embed/table", (s["vocab"], d), "table")]
    for i, kind in enumerate(layer_plan(config)):
        p = f"layer{i}"
        if kind.startswith("mamba"):
            specs += [(f"{p}/ssm_norm/scale", (d,), "gain"),
                      (f"{p}/ssm_norm/bias", (d,), "bias"),
                      (f"{p}/ssm/w_in", (d, 2 * c), "dense"),        # xs, then z
                      (f"{p}/ssm/conv_w", (c, s["taps"]), "taps"),   # the last meets t
                      (f"{p}/ssm/conv_b", (c,), "bias"),
                      (f"{p}/ssm/w_x", (c, r + 2 * n), "dense"),     # dl, B, C
                      (f"{p}/ssm/w_dt", (r, c), "dense"),
                      (f"{p}/ssm/b_dt", (c,), "dt_bias"),
                      (f"{p}/ssm/a_log", (c, n), "a_log"),
                      (f"{p}/ssm/d", (c,), "gain"),
                      (f"{p}/ssm/w_out", (c, d), "dense")]
        elif kind == "gmu":
            specs += [(f"{p}/gmu_norm/scale", (d,), "gain"),
                      (f"{p}/gmu_norm/bias", (d,), "bias"),
                      (f"{p}/gmu/w1", (d, c), "dense"),
                      (f"{p}/gmu/w2", (c, d), "dense")]
        else:
            specs += [(f"{p}/attn_norm/scale", (d,), "gain"),
                      (f"{p}/attn_norm/bias", (d,), "bias"),
                      (f"{p}/attn/wq", (d, hq), "dense"),
                      (f"{p}/attn/bq", (hq,), "bias")]
            if kind != "cross":      # a key head's columns, then a value head's
                specs += [(f"{p}/attn/wkv", (d, 2 * hkv), "dense"),
                          (f"{p}/attn/bkv", (2 * hkv,), "bias")]
            specs += [(f"{p}/attn/wo", (hq, d), "dense"),
                      (f"{p}/attn/bo", (d,), "bias"),
                      (f"{p}/attn/subln", (2 * s["hd"],), "gain")]
            specs += [(f"{p}/attn/{v}", (s["hd"],), "lambda")
                      for v in ("lq1", "lk1", "lq2", "lk2")]
        specs += [(f"{p}/mlp_norm/scale", (d,), "gain"),
                  (f"{p}/mlp_norm/bias", (d,), "bias"),
                  # gate and up side by side: columns [0, ff) are the gate
                  (f"{p}/mlp/w_gate_up", (d, 2 * s["ff"]), "dense"),
                  (f"{p}/mlp/w_down", (s["ff"], d), "dense")]
    return specs + [("final_norm/scale", (d,), "gain"), ("final_norm/bias", (d,), "bias")]


def _leaf(key, shape, kind: str):
    import jax
    import jax.numpy as jnp

    bf16 = jnp.bfloat16
    if kind == "gain":                 # norm gains, subln's gain, Mamba's D
        return jnp.ones(shape, bf16)
    if kind == "a_log":                # A[c, n] = -(n + 1)
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)),
                                shape).astype(bf16)
    if kind == "dt_bias":              # the inverse softplus of a log-uniform step
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                       * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return (step + jnp.log(-jnp.expm1(-step))).astype(bf16)
    std = {"bias": 0.02, "lambda": 0.1, "table": shape[-1] ** -0.5,
           "taps": shape[-1] ** -0.5}.get(kind) or shape[-2] ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * np.float32(std)).astype(bf16)


def make_weights(config, seed: int, under: Optional[str] = None
                 ) -> Dict[str, "jax.Array"]:
    """Every weight whose path starts with `under` (all of them without it),
    bfloat16, on the device, a leaf at a time from the seed: a leaf's key is
    its place in `weight_specs`, so a layer made alone equals that layer of
    the whole. Scales: the configuration's `assumed.weights`."""
    import jax

    key = seed_key(seed)
    gen = jax.jit(_leaf, static_argnums=(1, 2))
    return {path: gen(jax.random.fold_in(key, i), shape, kind)
            for i, (path, shape, kind) in enumerate(weight_specs(config))
            if under is None or path.startswith(under)}


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def layer_norm(x, w, name: str, eps: float):
    import jax
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w[name + "/scale"] + w[name + "/bias"]


def mamba(config, w, u, quant, fault):
    """One row `[T, hidden]` -> (the mixer's output, the memory `[T, inner]`)."""
    import jax
    import jax.numpy as jnp

    s = sizes(config)
    c, n, r, taps = s["inner"], s["states"], s["rank"], s["taps"]
    t = u.shape[0]
    xz = _einsum("td,de->te", u, w["ssm/w_in"], quant)
    xs, z = xz[:, :c], xz[:, c:]
    tap = w["ssm/conv_w"][:, ::-1] if fault == "taps_reversed" else w["ssm/conv_w"]
    run = jnp.concatenate([jnp.zeros((taps - 1, c), xs.dtype), xs])
    xc = jax.nn.silu(w["ssm/conv_b"] + sum(tap[:, j] * run[j:j + t] for j in range(taps)))
    dbc = _einsum("tc,ce->te", xc, w["ssm/w_x"], quant)
    delta = jax.nn.softplus(_einsum("tr,rc->tc", dbc[:, :r], w["ssm/w_dt"], quant)
                            + w["ssm/b_dt"])
    A = -jnp.exp(w["ssm/a_log"]).T                 # [states, channels], as H is held

    def step(h, a):
        at, d, x, b, cc = a
        if fault == "state_reset_8192":
            h = jnp.where(at % 8192 == 0, 0.0, h)
        h = jnp.exp(d[None, :] * A) * h + (d * x)[None, :] * b[:, None]
        return h, jnp.sum(h * cc[:, None], axis=0)

    # the recurrence itself, a step a time step (eight steps a loop trip: the
    # same steps in the same order, fewer trips of the loop's own overhead)
    _, y = jax.lax.scan(step, jnp.zeros((n, c), jnp.float32),
                        (jnp.arange(t), delta, xc, dbc[:, r:r + n], dbc[:, r + n:]),
                        unroll=8)
    if fault != "no_d":
        y = y + w["ssm/d"] * xc
    gated = y * jax.nn.silu(z)
    return _einsum("tc,cd->td", gated, w["ssm/w_out"], quant), \
        gated if fault == "memory_after_gate" else y


def gated_memory(w, u, m, quant):
    import jax

    return _einsum("tc,cd->td", m * jax.nn.silu(_einsum("td,dc->tc", u, w["gmu/w1"], quant)),
                   w["gmu/w2"], quant)


def keys_values(config, w, u, quant):
    """`u [T, hidden]` -> (k, v) `[T, kv heads x 64]` each."""
    import jax.numpy as jnp

    return tuple(jnp.split(_einsum("td,de->te", u, w["attn/wkv"], quant) + w["attn/bkv"],
                           2, axis=-1))


def diff_attention(config, w, u, kv, lam_init, window: int, quant, fault):
    """One row through differential attention over the keys and values `kv`
    (its own, or layer 17's): a block of queries of one pair at a time."""
    import jax
    import jax.numpy as jnp

    s = sizes(config)
    hd, pairs, kv_pairs = s["hd"], s["heads"] // 2, s["kv"] // 2
    t = u.shape[0]
    if window and fault == "window_511":
        window -= 1
    q = (_einsum("td,de->te", u, w["attn/wq"], quant) + w["attn/bq"]
         ).reshape(t, pairs, 2, hd)
    k = kv[0].reshape(t, kv_pairs, 2, hd)
    v = kv[1].reshape(t, kv_pairs, 2 * hd)
    lam = jnp.exp(jnp.sum(w["attn/lq1"] * w["attn/lk1"])) \
        - jnp.exp(jnp.sum(w["attn/lq2"] * w["attn/lk2"])) + lam_init
    blk = min(QUERY_BLOCK, t)
    assert t % blk == 0
    back = -(-window // blk) * blk if window else 0     # keys kept before a block
    span = blk + back if window else t

    def pair(a):
        qp, kp, vp = a                        # [T, 2, hd], [T, 2, hd], [T, 2 hd]
        kp = jnp.pad(kp, ((back, 0), (0, 0), (0, 0)))
        vp = jnp.pad(vp, ((back, 0), (0, 0)))

        def block(b):
            qb, first = b                     # [blk, 2, hd]
            at = first if window else 0       # in the padded keys: position at - back
            kb = jax.lax.dynamic_slice_in_dim(kp, at, span)
            vb = jax.lax.dynamic_slice_in_dim(vp, at, span)
            kpos = at - back + jnp.arange(span)
            qpos = first + jnp.arange(blk)
            seen = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
            if window:
                seen &= kpos[None, :] > qpos[:, None] - window
            o = []
            for half in range(2):
                sc = _einsum("qd,kd->qk", qb[:, half], kb[:, half], quant) \
                    * np.float32(hd ** -0.5)
                p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
                o.append(_einsum("qk,kd->qd", p, vb, quant))
            return o[0] - lam * o[1]

        o = jax.lax.map(block, (qp.reshape(-1, blk, 2, hd), jnp.arange(t // blk) * blk))
        return o.reshape(t, 2 * hd)

    group = pairs // kv_pairs                 # query pairs 2g, 2g + 1 read pair g
    o = jax.lax.map(pair, (q.swapaxes(0, 1), jnp.repeat(k.swapaxes(0, 1), group, axis=0),
                           jnp.repeat(v.swapaxes(0, 1), group, axis=0)))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + float(config["layer_norm_eps"])) \
        * w["attn/subln"] * (1.0 - lam_init)
    return _einsum("te,ed->td", o.swapaxes(0, 1).reshape(t, -1), w["attn/wo"], quant) \
        + w["attn/bo"]


def layer(config, w, x, kind: str, lam_init, memory, kv, shared_w, quant, fault):
    """One row `[T, hidden]` -> (the row after the layer, the memory and the
    keys and values in the carry after it)."""
    eps = float(config["layer_norm_eps"])
    if kind.startswith("mamba"):
        y, m = mamba(config, w, layer_norm(x, w, "ssm_norm", eps), quant, fault)
        if kind == "mamba_memory":
            memory = m
    elif kind == "gmu":
        y = gated_memory(w, layer_norm(x, w, "gmu_norm", eps), memory, quant)
    else:
        u = layer_norm(x, w, "attn_norm", eps)
        if kind == "cross":
            own = keys_values(config, shared_w, u, quant) if fault == "cross_own_keys" else kv
        else:
            own = keys_values(config, w, u, quant)
            if kind == "full":
                kv = own
        y = diff_attention(config, w, u, own, lam_init,
                           sizes(config)["window"] if kind == "window" else 0, quant, fault)
    h = x + y
    return h + swiglu(layer_norm(h, w, "mlp_norm", eps), w["mlp/w_gate_up"],
                      w["mlp/w_down"], quant), memory, kv


def log_probs(config, w, x, ids, quant):
    """One row `[T, hidden]`: the log-probability of the next id at every
    position, the logits a block of positions at a time, the head the table."""
    import jax
    import jax.numpy as jnp

    xn = layer_norm(x, w, "final_norm", float(config["layer_norm_eps"]))
    t = x.shape[0]
    blk = min(POSITION_BLOCK, t)

    def block(a):
        xb, tb = a
        logp = jax.nn.log_softmax(_einsum("td,vd->tv", xb, w["embed/table"], quant))
        return jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

    return jax.lax.map(block, (xn.reshape(-1, blk, xn.shape[-1]),
                               ids.reshape(-1, blk))).reshape(-1)


def score(config, seed: int, ids: np.ndarray, quant: Optional[str] = None,
          fault: Optional[str] = None,
          weights: Optional[Dict[str, "jax.Array"]] = None) -> Dict[str, np.ndarray]:
    """{"logprob": [N, T]} float32 of padded token rows `[N, T]` int32, NaN
    after a row's computed positions (its length rounded up to `ROW_BLOCK`):
    a layer at a time, a row at a time. `weights`, for a test that already
    holds them all, stands in for the seed."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    ids = np.asarray(ids, np.int32)
    pad, cap = int(config["pad_id"]), ids.shape[1]
    real = [int(np.flatnonzero(r != pad)[-1]) + 1 if (r != pad).any() else 1 for r in ids]
    block = min(ROW_BLOCK, cap)
    upto = [min(cap, -(-n // block) * block) for n in real]
    # the id after each computed position: the row's next, the pad after its last
    target = [np.append(r[1:n], pad if n == cap else r[n]).astype(np.int32)
              for r, n in zip(ids, upto)]

    def made(under: str):
        have = weights if weights is not None else make_weights(config, seed, under)
        return {p: a for p, a in have.items() if p.startswith(under)}

    programs: Dict[Tuple, object] = {}            # one a kind of layer and length

    def program(key, fn):
        return programs.setdefault(key, jax.jit(fn))

    table = made("embed/")["embed/table"]
    rows = [program("embed", lambda tb, i: tb[i].astype(jnp.float32))(
        table, jnp.asarray(r[:n])) for r, n in zip(ids, upto)]
    memory: List = [None] * len(rows)
    kv: List = [None] * len(rows)
    shared_w = None                               # layer 17's, for `cross_own_keys`
    for i, kind in enumerate(layer_plan(config)):
        w = _f32(made(f"layer{i}/"), f"layer{i}/")
        if kind == "full":
            shared_w = {p: w[p] for p in ("attn/wkv", "attn/bkv")}
        init = np.float32(lambda_init(0 if fault == "lambda_init_0" else i))
        through = program(kind, lambda w, x, init, m, c, sw, kind=kind: layer(
            config, w, x, kind, init, m, c, sw, quant, fault))
        for r, x in enumerate(rows):
            rows[r], memory[r], kv[r] = through(
                w, x, init, memory[r], kv[r], shared_w if kind == "cross" else None)
        del w
    w = {"embed/table": table.astype(jnp.float32),
         **{"final_norm/" + p: a for p, a in _f32(made("final_norm/"), "final_norm/").items()}}
    last = program("head", lambda w, x, i: log_probs(config, w, x, i, quant))
    out = np.full(ids.shape, np.nan, np.float32)
    for r, (x, tr) in enumerate(zip(rows, target)):
        out[r, :upto[r]] = np.asarray(last(w, x, jnp.asarray(tr)))
    return {"logprob": out}
