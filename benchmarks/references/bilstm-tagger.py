"""Plain reference of the BiLSTM tagger configuration.

Written from the layer equations of the configuration file (Huang, Xu & Yu
2015, the BI-LSTM model, with the standard LSTM cell and no peepholes) in
straightforward `jax.numpy`, float32 under `highest` matmul precision, no
hoisted projection, no batching machinery. Imports nothing of the program
under test and takes nothing it made: the weights come from the seed
(`make_weights`), the token rows from the harness.

    e_t = E[id_t]
    i, f, g, o = split(e_t Wx + h Wh + b)          each direction
    c = sigmoid(f) c + sigmoid(i) tanh(g);   h = sigmoid(o) tanh(c)
    y_t = [h_fwd,t ; h_bwd,t] W + b                 zero initial state

The backward direction reads the row reversed. A row is the padded row of
the configuration's cap: pad positions are input, as upstream pads to
`maxlen` and scores the whole row, and the comparison reads the real
positions only (`row_gaps`; `logit_gap` is the widest of them).

`logits(..., quant="fp8")` is the control: the same computation with the
operands of every matrix product rounded to float8 (e4m3, one scale a
tensor), the nearest precision below the bfloat16 passes the configuration
states. `fault=` plants what a broken program would compute: `bwd_forward`
runs the backward direction forward, `gates` splits the gates in another
order.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

Spec = Tuple[str, Tuple[int, ...], str]        # (path, shape, kind)

FAULTS = ("bwd_forward", "gates")


def weight_specs(config) -> List[Spec]:
    vocab, emb = int(config["vocab_size"]), int(config["embed_dim"])
    hid, tags = int(config["hidden_size"]), int(config["num_tags"])
    specs: List[Spec] = [("embed/table", (vocab, emb), "table")]
    for d in ("fwd", "bwd"):
        specs += [(f"bilstm/{d}/wx", (emb, 4 * hid), "dense"),
                  (f"bilstm/{d}/wh", (hid, 4 * hid), "dense"),
                  (f"bilstm/{d}/b", (4 * hid,), "gate_bias")]
    return specs + [("tags/kernel", (2 * hid, tags), "dense"),
                    ("tags/bias", (tags,), "bias")]


def seed_key(seed: int):
    """A PRNG key from any whole number, also one over 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                              seed // (2 ** 31 - 1))


def make_weights(config, seed: int) -> Dict[str, "jax.Array"]:
    """Every weight, float32, on the device, in one jitted call from the
    seed. Scales (the configuration's `assumed.weights`): embeddings of unit
    variance, every matrix 1/sqrt(fan-in), so that a gate's pre-activation
    has a spread near 1 and no gate saturates; biases N(0, 0.1), the forget
    gate's (the second quarter: i, f, g, o) one higher."""
    import jax
    import jax.numpy as jnp

    specs = weight_specs(config)

    def gen(key):
        out = {}
        for i, (path, shape, kind) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if kind == "table":
                w = jax.random.normal(k, shape, jnp.float32)
            elif kind == "dense":
                w = jax.random.normal(k, shape, jnp.float32) * np.float32(
                    1.0 / np.sqrt(shape[0]))
            else:  # bias, gate_bias
                w = jax.random.normal(k, shape, jnp.float32) * np.float32(0.1)
                if kind == "gate_bias":
                    h = shape[0] // 4
                    w = w.at[h:2 * h].add(1.0)
            out[path] = w
        return out

    return jax.jit(gen)(seed_key(seed))


def _quant(x, quant: Optional[str]):
    """Round to the control's precision: float8 e4m3 with one scale a tensor."""
    import jax.numpy as jnp

    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _dot(a, b):
    import jax
    import jax.numpy as jnp

    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _lstm(e, w, prefix: str, quant: Optional[str], fault: Optional[str]):
    """One direction over [B, T, E] in the order given: [B, T, H]."""
    import jax
    import jax.numpy as jnp

    wx, wh, b = (w[f"{prefix}/{k}"] for k in ("wx", "wh", "b"))
    wx_q, wh_q = _quant(wx, quant), _quant(wh, quant)   # weights round once

    def cell(carry, e_t):
        h, c = carry
        gates = _dot(_quant(e_t, quant), wx_q) + _dot(_quant(h, quant), wh_q) + b
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        if fault == "gates":
            i, f, g, o = f, i, o, g
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    zeros = jnp.zeros((e.shape[0], wh.shape[0]), jnp.float32)
    _, hs = jax.lax.scan(cell, (zeros, zeros), jnp.swapaxes(e, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


def logits(config, w, ids, quant: Optional[str] = None,
           fault: Optional[str] = None):
    """[B, T] int32 padded rows -> [B, T, tags] float32 logits."""
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    e = w["embed/table"][ids]
    fwd = _lstm(e, w, "bilstm/fwd", quant, fault)
    if fault == "bwd_forward":
        bwd = _lstm(e, w, "bilstm/bwd", quant, fault)
    else:
        bwd = _lstm(e[:, ::-1], w, "bilstm/bwd", quant, fault)[:, ::-1]
    h = jnp.concatenate([fwd, bwd], axis=-1)
    return _dot(_quant(h, quant), _quant(w["tags/kernel"], quant)) + w["tags/bias"]


def tag(config, seed: int, ids: np.ndarray, quant: Optional[str] = None,
        fault: Optional[str] = None, block: int = 512) -> np.ndarray:
    """Logits of padded token rows [N, T] int32, a block of rows at a time."""
    import jax

    w = make_weights(config, seed)
    fwd = jax.jit(lambda w_, x_: logits(config, w_, x_, quant, fault))
    out = []
    for i in range(0, len(ids), block):
        chunk = np.asarray(ids[i:i + block], np.int32)
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate([chunk, np.zeros((pad,) + chunk.shape[1:],
                                                    chunk.dtype)])
        out.append(np.asarray(fwd(w, chunk))[:block - pad])
    return np.concatenate(out)


def row_gaps(got: np.ndarray, ref: np.ndarray, lengths: np.ndarray
             ) -> np.ndarray:
    """The gap of every real position's logits from the reference's, as one
    flat array over the real positions of all rows: the widest difference
    among the position's tags, over the largest absolute reference logit of
    the row's real positions, or of the median row of those given, whichever
    is larger. A row of one or two tokens can have logits that are all
    small (a third of the median row's), and the same absolute error reads
    three times as wide there (PERF.md section 2). A value that is not
    finite reads as an infinite gap."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    real = np.arange(ref.shape[1])[None, :] < np.asarray(lengths)[:, None]
    scale = np.where(real[..., None], np.abs(ref), 0.0).max(axis=(1, 2))
    scale = np.maximum(scale, max(float(np.median(scale)), 1e-30))
    gap = np.abs(got - ref).max(axis=-1) / scale[:, None]
    return np.where(np.isfinite(gap), gap, np.inf)[real]
