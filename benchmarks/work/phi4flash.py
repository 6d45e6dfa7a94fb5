"""Operations and bytes the Phi-4-mini-flash-reasoning scorer needs for one
real token and one batch, from the configuration's shapes and the rows' own
lengths alone: the whole model (32 layers, the whole vocabulary).

FLOPs = 2 x multiply-accumulates of the matrix products a token goes through
(a SwiGLU in each of the 32 layers; W_q, W_kv, W_o of the 9 self-attention
layers and W_q, W_o of the 7 cross layers; W_in, W_x, W_dt, W_out of the 9
Mamba layers; W_1, W_2 of the 7 Gated Memory Units; the tied head) plus the
differential attention cores: two score maps of 64 and two products with the
128-wide value pair, 768 FLOP a (query, key) a pair, 20 pairs: 15,360 a
(query, key) a layer; a window layer's query at position t reads min(512, t +
1) keys, a full or cross layer's t + 1, so a row of n real tokens reads (n +
1) / 2 keys a query there. The selective scan's own arithmetic (some 6 FLOP a
channel a state a step on the vector unit), the convolution, norms, softmax,
gates and the gather are left out. A padded position costs the chip the same
and counts for nothing here, so no share built on this file can pass 100%,
and each reads the same work whatever later implements the padding or the
kernels. At the published widths the products are 6.678 GFLOP a real token,
the head 1.024, the window cores 0.063 and the full and cross cores 2.013 on
a row of 32,768 (9.78 in all; 9.64 over the cell's four rows).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple


def _dims(config) -> Dict[str, int]:
    a, d = config["assumed"], int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    return {"d": d, "heads": heads, "kv": int(config["num_key_value_heads"]),
            "hd": d // heads, "ff": int(config["intermediate_size"]),
            "inner": int(a["mamba_expand"]) * d, "states": int(a["mamba_d_state"]),
            "taps": int(a["mamba_d_conv"]), "rank": int(a["mamba_dt_rank"]),
            "vocab": int(config["vocab_size"]), "window": int(config["sliding_window"])}


def layer_plan(config) -> List[str]:
    n, every = int(config["num_hidden_layers"]), int(config["mb_per_layer"])
    half = n // 2
    return [("mamba" if i % every == 0 else "window") if i <= half
            else "full" if i == half + 1 else ("gmu" if i % every == 0 else "cross")
            for i in range(n)]


def mixer_macs(config, kind: str) -> int:
    """Multiply-accumulates of a mixer's matrix products, a token."""
    s = _dims(config)
    hq, hkv = s["heads"] * s["hd"], s["kv"] * s["hd"]
    return {"mamba": s["d"] * 2 * s["inner"] + s["inner"] * (s["rank"] + 2 * s["states"])
            + s["rank"] * s["inner"] + s["inner"] * s["d"],        # 41,123,840
            "gmu": 2 * s["d"] * s["inner"],                        # 26,214,400
            "cross": 2 * s["d"] * hq,                              # 13,107,200
            }.get(kind, 2 * s["d"] * hq + s["d"] * 2 * hkv)        # 19,660,800


def macs_per_token(config) -> int:
    s = _dims(config)
    return s["d"] * s["vocab"] + sum(3 * s["d"] * s["ff"] + mixer_macs(config, k)
                                     for k in layer_plan(config))


def pair_flops(config) -> int:
    """FLOPs of a layer's cores for one (query, key): 768 a pair, 20 pairs."""
    s = _dims(config)
    return (s["heads"] // 2) * (2 * 2 * s["hd"] + 2 * 2 * 2 * s["hd"])


def keys_seen(config, lengths: Sequence[int]) -> Tuple[float, float]:
    """(query, key) pairs the real tokens of rows of these lengths read, in
    (a window layer, a full or cross layer)."""
    w = _dims(config)["window"]
    window = sum(n * w - w * (w - 1) / 2 if n >= w else n * (n + 1) / 2 for n in lengths)
    return float(window), float(sum(n * (n + 1) / 2 for n in lengths))


def core_flops(config, lengths: Sequence[int]) -> float:
    """FLOPs of the differential cores of every layer for the real tokens."""
    plan = layer_plan(config)
    window, full = keys_seen(config, lengths)
    return pair_flops(config) * (plan.count("window") * window
                                 + (plan.count("full") + plan.count("cross")) * full)


def flops_per_token(config, lengths: Sequence[int]) -> float:
    """Of a real token of rows of these lengths, the mean."""
    return 2.0 * macs_per_token(config) + core_flops(config, lengths) / float(sum(lengths))


def parameters(config) -> int:
    """3,852,562,944 at the published widths: what the program's module holds."""
    s = _dims(config)
    norms = 2 * s["d"]
    total = s["d"] * s["vocab"] + norms
    for kind in layer_plan(config):
        total += 3 * s["d"] * s["ff"] + 2 * norms + mixer_macs(config, kind)
        if kind == "mamba":     # taps, their bias, b_dt, A_log, D
            total += s["inner"] * (s["taps"] + 3 + s["states"])
        elif kind != "gmu":     # b_q, b_o, the four lambda vectors, subln, b_kv
            total += s["heads"] * s["hd"] + s["d"] + 6 * s["hd"] \
                + (0 if kind == "cross" else 2 * s["kv"] * s["hd"])
    return total


def bytes_per_batch(config, tokens: float) -> Dict[str, float]:
    """Bytes one batch holding `tokens` real tokens has to move, at the least:
    every weight once (bfloat16; the table is the head), and for each token
    its int32 id and its float32 log-probability."""
    return {"weights": 2.0 * parameters(config), "input": 4.0 * tokens,
            "output": 4.0 * tokens}


def selective_scan(config, tokens: float) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the nine layers' scans for `tokens` real
    tokens: `xc` and `delta` in and `y` out at 2 bytes a channel, `B_t` and
    `C_t` at 4 (277,632 bytes a token); the FLOPs are the recurrence's
    multiply-adds (6 a channel a state a step). Memory binds by `peaks.py`,
    which has no peak of the vector unit."""
    s = _dims(config)
    layers = layer_plan(config).count("mamba")
    return (6.0 * layers * tokens * s["inner"] * s["states"],
            layers * tokens * (3 * 2.0 * s["inner"] + 2 * 4.0 * s["states"]))


def differential_cores(config, lengths: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, least bytes) of all sixteen layers' cores for the real tokens
    of rows of these lengths; queries and outputs moved once a layer, keys
    and values once a layer that makes its own (bfloat16)."""
    s = _dims(config)
    plan = layer_plan(config)
    tokens = float(sum(lengths))
    hq, hkv = s["heads"] * s["hd"], s["kv"] * s["hd"]
    own = plan.count("window") + plan.count("full")
    moved = 2.0 * tokens * ((own + plan.count("cross")) * 2 * hq + own * 2 * hkv)
    return core_flops(config, lengths), moved
