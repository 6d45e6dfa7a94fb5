"""Operations and bytes the K-EXAONE scorer needs for one real token and one
batch, from the configuration's shapes alone: this chip's share of the
deployment the configuration's file states (5 layers, 16 of 128 experts, an
eighth of the vocabulary).

FLOPs = 2 x multiply-accumulates of the matrix products a token goes through
(the four attention projections, the dense or the shared and routed SwiGLUs,
the router, the head over the vocabulary slice) plus the attention core's two
products (scores, values) at the keys a query reads: `window` on a sliding
layer, (T + 1) / 2 on a full one, averaged over a row of `max_positions`.
Routed visits are counted at their expectation, `top_k x held / experts` a
token a sparse layer (1 here), whatever the router did in a run: a hot expert
earns nothing. Norms, rotary positions, softmax and the gather are left out.
A padded position costs the chip the same and counts for nothing here, so no
share built on this file can pass 100%, and each reads the same work whatever
later implements the padding or the kernels. At the published widths a real
token is 2.742 GFLOP.
"""

from __future__ import annotations

from typing import Dict, List, Tuple


def _dims(config) -> Dict[str, int]:
    return {"d": int(config["hidden_size"]), "hd": int(config["head_dim"]),
            "nq": int(config["num_attention_heads"]),
            "nkv": int(config["num_key_value_heads"]),
            "ff": int(config["intermediate_size"]),
            "eff": int(config["moe_intermediate_size"]),
            "held": int(config["num_experts"]),
            "experts": int(config["num_experts_published"]),
            "top_k": int(config["num_experts_per_tok"]),
            "shared": int(config["num_shared_experts"]),
            "vocab": int(config["vocab_size"]),
            "t": int(config["max_positions"])}


def layer_plan(config) -> List[Tuple[int, bool]]:
    n = int(config["num_hidden_layers"])
    return [(int(config["sliding_windows"][i]),
             config["mlp_layer_types"][i] == "sparse") for i in range(n)]


def expected_visits(config) -> float:
    """Visits to this chip's experts a token makes in one sparse layer."""
    s = _dims(config)
    return s["top_k"] * s["held"] / s["experts"]


def keys_per_query(config, window: int) -> float:
    """Keys a query reads, averaged over the positions of a full row."""
    t = _dims(config)["t"]
    if not window or window >= t:
        return (t + 1) / 2.0
    return window - window * (window - 1) / (2.0 * t)


def attention_core_flops_per_token(config, window: int) -> float:
    s = _dims(config)
    return 4.0 * s["nq"] * s["hd"] * keys_per_query(config, window)


def expert_macs(config) -> int:
    s = _dims(config)
    return 3 * s["d"] * s["eff"]


def macs_per_token(config) -> float:
    """Multiply-accumulates of the weight products one token goes through."""
    s = _dims(config)
    attn = 2 * s["d"] * s["nq"] * s["hd"] + 2 * s["d"] * s["nkv"] * s["hd"]
    total = float(s["d"] * s["vocab"])
    for _, sparse in layer_plan(config):
        total += attn
        if sparse:
            total += s["d"] * s["experts"] + expert_macs(config) * (
                s["shared"] + expected_visits(config))
        else:
            total += 3 * s["d"] * s["ff"]
    return total


def flops_per_token(config) -> float:
    return 2.0 * macs_per_token(config) + sum(
        attention_core_flops_per_token(config, w) for w, _ in layer_plan(config))


def parameters(config, with_table: bool = True) -> int:
    """Parameters this chip holds: 3,712,028,416 at the published widths, of
    them 3,711,959,040 in the weight matrices."""
    s = _dims(config)
    attn = 2 * s["d"] * s["nq"] * s["hd"] + 2 * s["d"] * s["nkv"] * s["hd"] \
        + 2 * s["hd"] + 2 * s["d"]
    total = s["d"] * s["vocab"] * (2 if with_table else 1) + s["d"]
    for _, sparse in layer_plan(config):
        total += attn
        if sparse:
            total += s["d"] * s["experts"] + s["experts"] \
                + expert_macs(config) * (s["shared"] + s["held"])
        else:
            total += 3 * s["d"] * s["ff"]
    return total


def bytes_per_batch(config, tokens: float) -> Dict[str, float]:
    """Bytes one batch holding `tokens` real tokens has to move, at the least:
    every weight but the embedding table once (bfloat16), and for each token
    its int32 id, its row of the table and its float32 log-probability. Not
    XLA's `bytes accessed`, which counts every intermediate."""
    s = _dims(config)
    return {"weights": 2.0 * parameters(config, with_table=False),
            "input": 4.0 * tokens, "table_rows": 2.0 * s["d"] * tokens,
            "output": 4.0 * tokens}


def expert_products(config, tokens: float) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the grouped expert products of one batch: the
    real tokens' expected visits through gate/up and down in every sparse
    layer; the held experts' weights read once, a visit's row read and
    written once (bfloat16)."""
    s = _dims(config)
    layers = sum(1 for _, sparse in layer_plan(config) if sparse)
    visits = tokens * expected_visits(config) * layers
    weights = 2.0 * layers * s["held"] * expert_macs(config)
    rows = 2.0 * visits * (2 * s["d"] + 3 * s["eff"])     # x, gate/up, act, out
    return 2.0 * visits * expert_macs(config), weights + rows


def window_attention(config, tokens: float) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the sliding layers' attention cores of one
    batch at `T x window`: scores and values for the real tokens; queries,
    keys, values and outputs moved once (bfloat16)."""
    s = _dims(config)
    flops = moved = 0.0
    for window, _ in layer_plan(config):
        if window:
            flops += tokens * attention_core_flops_per_token(config, window)
            moved += 2.0 * tokens * s["hd"] * (2 * s["nq"] + 2 * s["nkv"])
    return flops, moved
