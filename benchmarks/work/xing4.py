"""Operations and bytes the Xing4.0 scorer needs for one real token and one
batch, from the configuration's shapes alone: the pipeline stage the
configuration's file states (6 of 40 layers, every expert, the whole
vocabulary).

FLOPs = 2 x multiply-accumulates of the matrix products a token goes through
(the five latent-attention projections, the dense or the shared and routed
SwiGLUs, the router, the two hyper-connection maps a layer, the head) plus the
attention core's two products at the keys a query reads, (T + 1) / 2 averaged
over a row of `max_positions`: scores 192 wide (128 content, 64 rotary),
values 128 wide, 640 FLOP a head a pair. Routed visits are counted at their
expectation, `top_k` a token a sparse layer (every expert is held), whatever
the router did in a run: a hot expert earns nothing. Norms, rotary positions,
softmax, the gather, and the stream mix's multiply-adds (memory-bound work,
counted in bytes by `stream_mix`) are left out. A padded position costs the
chip the same and counts for nothing here, so no share built on this file can
pass 100%, and each reads the same work whatever later implements the padding
or the kernels. At the published widths a real token is 3.134 GFLOP, of it the
attention cores 1.007 (32%) and the head 0.940 (30%).
"""

from __future__ import annotations

from typing import Dict, List, Tuple


def _dims(config) -> Dict[str, int]:
    return {"d": int(config["hidden_size"]), "h": int(config["num_attention_heads"]),
            "qr": int(config["q_lora_rank"]), "kvr": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]), "rope": int(config["qk_rope_head_dim"]),
            "vd": int(config["v_head_dim"]), "ff": int(config["intermediate_size"]),
            "eff": int(config["moe_intermediate_size"]),
            "experts": int(config["n_routed_experts"]),
            "top_k": int(config["num_experts_per_tok"]),
            "shared": int(config["n_shared_experts"]),
            "vocab": int(config["vocab_size"]), "n": int(config["hc_mult"]),
            "t": int(config["max_positions"])}


def layer_plan(config) -> List[bool]:
    dense, freq = int(config["first_k_dense_replace"]), int(config["moe_layer_freq"])
    return [i >= dense and i % freq == 0 for i in range(int(config["num_hidden_layers"]))]


def keys_per_query(config) -> float:
    """Keys a query reads, averaged over the positions of a full row."""
    return (_dims(config)["t"] + 1) / 2.0


def attention_macs(config) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o: 28,409,856 at the published widths."""
    s = _dims(config)
    return s["d"] * s["qr"] + s["qr"] * s["h"] * (s["nope"] + s["rope"]) \
        + s["d"] * (s["kvr"] + s["rope"]) + s["kvr"] * s["h"] * (s["nope"] + s["vd"]) \
        + s["h"] * s["vd"] * s["d"]


def attention_core_flops_per_token(config) -> float:
    s = _dims(config)
    return 2.0 * s["h"] * (s["nope"] + s["rope"] + s["vd"]) * keys_per_query(config)


def expert_macs(config) -> int:
    s = _dims(config)
    return 3 * s["d"] * s["eff"]


def maps_macs(config) -> int:
    """The two maps of a layer: 2 x (n d) x (n^2 + 2n)."""
    s = _dims(config)
    return 2 * s["n"] * s["d"] * (s["n"] * s["n"] + 2 * s["n"])


def macs_per_token(config) -> float:
    """Multiply-accumulates of the weight products one token goes through."""
    s = _dims(config)
    total = float(s["d"] * s["vocab"])
    for sparse in layer_plan(config):
        total += attention_macs(config) + maps_macs(config)
        if sparse:
            total += s["d"] * s["experts"] + expert_macs(config) * (s["shared"] + s["top_k"])
        else:
            total += 3 * s["d"] * s["ff"]
    return total


def flops_per_token(config) -> float:
    return 2.0 * macs_per_token(config) \
        + len(layer_plan(config)) * attention_core_flops_per_token(config)


def parameters(config, with_table: bool = True) -> int:
    """Parameters this chip holds: 4,175,877,700 at the published widths, of
    them 4,175,822,848 in the weight matrices."""
    s = _dims(config)
    width = s["n"] * s["n"] + 2 * s["n"]
    total = s["d"] * s["vocab"] * (2 if with_table else 1) + s["d"]
    for sparse in layer_plan(config):
        total += attention_macs(config) + maps_macs(config) + 2 * (3 + width) \
            + 2 * s["d"] + s["qr"] + s["kvr"]
        if sparse:
            total += s["d"] * s["experts"] + s["experts"] \
                + expert_macs(config) * (s["shared"] + s["experts"])
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


def latent_attention(config, tokens: float) -> Tuple[float, float]:
    """(FLOPs, least bytes) of every layer's attention core of one batch at
    (T + 1) / 2 keys a query for the real tokens; queries (192 a head),
    content keys and values (128 + 128 a head), the shared rotary key and the
    outputs (128 a head) moved once (bfloat16)."""
    s = _dims(config)
    layers = len(layer_plan(config))
    moved = 2.0 * tokens * (s["h"] * (s["nope"] + s["rope"] + s["nope"] + 2 * s["vd"])
                            + s["rope"])
    return layers * tokens * attention_core_flops_per_token(config), layers * moved


def stream_mix(config, tokens: float) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the residual path of one batch: in each of the
    2 sublayers a layer the streams X (n d float32 a token) are read twice
    (for the maps and the sublayer's input, and for the mix) and written
    once, and the sublayer's output y (d float32) is read once. The FLOPs are
    the maps' products and the mix's multiply-adds; memory binds. The same
    bytes whatever dtype or kernel later implements it."""
    s = _dims(config)
    sublayers = 2 * len(layer_plan(config))
    moved = 4.0 * tokens * sublayers * (3 * s["n"] * s["d"] + s["d"])
    flops = tokens * (2.0 * len(layer_plan(config)) * maps_macs(config)
                      + sublayers * 2.0 * s["d"] * (s["n"] + s["n"] * (s["n"] + 1)))
    return flops, moved


def expert_products(config, tokens: float) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the grouped expert products of one batch: the
    real tokens' `top_k` visits through gate/up and down in every sparse
    layer; the 64 experts' weights read once a layer, a visit's row read and
    written once (bfloat16)."""
    s = _dims(config)
    layers = sum(1 for sparse in layer_plan(config) if sparse)
    visits = tokens * s["top_k"] * layers
    weights = 2.0 * layers * s["experts"] * expert_macs(config)
    rows = 2.0 * visits * (2 * s["d"] + 3 * s["eff"])     # x, gate/up, act, out
    return 2.0 * visits * expert_macs(config), weights + rows
