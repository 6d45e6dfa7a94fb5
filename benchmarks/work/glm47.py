"""Operations and bytes the GLM-4.7-Flash scorer needs for one real token and
one batch, from the configuration's shapes alone: the pipeline stage the
configuration's file states (layers 0-4 of 47 and the multi-token-prediction
module, every expert, the whole vocabulary).

FLOPs = 2 x multiply-accumulates of the matrix products a token goes through
(the five latent-attention projections, the dense or the shared and routed
SwiGLUs, the router, the head; for the MTP module `W_eh`, its layer's products
and a second pass over the head) plus the six attention cores' two products
at the keys a query reads, (T + 1) / 2 averaged over a row of
`max_positions`: scores 256 wide (192 content, 64 rotary), values 256 wide,
1,024 FLOP a head a pair. Routed visits are counted at their expectation,
`top_k` a token a sparse layer (every expert is held), whatever the router
did in a run. Norms, rotary positions, softmax and the gathers are left out. A
padded position costs the chip the same and counts for nothing here, so no
share built on this file can pass 100%. At the published widths a real token
is 3.152 GFLOP: the main path 2.195, the MTP module 0.957 (30%); the six
attention cores 1.007 (32%), the two heads 1.269 (40%).
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
            "vocab": int(config["vocab_size"]),
            "mtp": int(config["num_nextn_predict_layers"]),
            "t": int(config["max_positions"])}


def layer_plan(config) -> List[bool]:
    """Whether each attention layer a token goes through has an expert layer:
    the held layers, then the MTP module's."""
    s = _dims(config)
    dense = int(config["first_k_dense_replace"])
    return [i >= dense for i in range(int(config["num_hidden_layers"]))] + [True] * s["mtp"]


def keys_per_query(config) -> float:
    """Keys a query reads, averaged over the positions of a full row."""
    return (_dims(config)["t"] + 1) / 2.0


def attention_macs(config) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o: 21,757,952 at the published widths."""
    s = _dims(config)
    return s["d"] * s["qr"] + s["qr"] * s["h"] * (s["nope"] + s["rope"]) \
        + s["d"] * (s["kvr"] + s["rope"]) + s["kvr"] * s["h"] * (s["nope"] + s["vd"]) \
        + s["h"] * s["vd"] * s["d"]


def attention_core_flops_per_token(config) -> float:
    """167.8 MFLOP at the published widths and a row of 16,384."""
    s = _dims(config)
    return 2.0 * s["h"] * (s["nope"] + s["rope"] + s["vd"]) * keys_per_query(config)


def expert_macs(config) -> int:
    s = _dims(config)
    return 3 * s["d"] * s["eff"]


def ffn_macs(config, sparse: bool) -> int:
    """A layer's FFN: the dense SwiGLU, or the router, the shared expert and
    `top_k` routed ones."""
    s = _dims(config)
    if sparse:
        return s["d"] * s["experts"] + expert_macs(config) * (s["shared"] + s["top_k"])
    return 3 * s["d"] * s["ff"]


def mtp_macs(config) -> int:
    """The MTP module's products but its core: W_eh, its layer, the head."""
    s = _dims(config)
    if not s["mtp"]:
        return 0
    return 2 * s["d"] * s["d"] + attention_macs(config) + ffn_macs(config, True) \
        + s["d"] * s["vocab"]


def macs_per_token(config) -> float:
    """Multiply-accumulates of the weight products one token goes through."""
    s = _dims(config)
    total = float(s["d"] * s["vocab"]) + mtp_macs(config)
    for sparse in layer_plan(config)[:int(config["num_hidden_layers"])]:
        total += attention_macs(config) + ffn_macs(config, sparse)
    return total


def flops_per_token(config) -> float:
    return 2.0 * macs_per_token(config) \
        + len(layer_plan(config)) * attention_core_flops_per_token(config)


def mtp_flops_per_token(config) -> float:
    """The MTP module's share: 957 MFLOP at the published widths."""
    if not _dims(config)["mtp"]:
        return 0.0
    return 2.0 * mtp_macs(config) + attention_core_flops_per_token(config)


def parameters(config, with_table: bool = True) -> int:
    """Parameters this chip holds: 3,904,020,288 at the published widths, of
    them 3,903,979,520 in the weight matrices."""
    s = _dims(config)
    norms = 2 * s["d"] + s["qr"] + s["kvr"]          # the two sublayers' and the latents'
    total = s["d"] * s["vocab"] * (2 if with_table else 1) + s["d"]
    held = s["d"] * s["experts"] + s["experts"] \
        + expert_macs(config) * (s["shared"] + s["experts"])    # router, bias, experts
    for sparse in layer_plan(config):
        total += attention_macs(config) + norms + (held if sparse else ffn_macs(config, False))
    return total + s["mtp"] * (2 * s["d"] * s["d"] + 3 * s["d"])


def bytes_per_batch(config, tokens: float) -> Dict[str, float]:
    """Bytes one batch holding `tokens` real tokens has to move, at the least:
    every weight but the embedding table once (bfloat16), and for each token
    its int32 id, its row of the table for the main path and for the MTP
    module, and its two float32 log-probabilities. Not XLA's `bytes
    accessed`, which counts every intermediate."""
    s = _dims(config)
    return {"weights": 2.0 * parameters(config, with_table=False),
            "input": 4.0 * tokens, "table_rows": 2.0 * s["d"] * tokens * (1 + s["mtp"]),
            "output": 4.0 * tokens * (1 + s["mtp"])}


def latent_attention(config, tokens: float) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the six attention cores of one batch at (T +
    1) / 2 keys a query for the real tokens; queries (256 a head), content
    keys and values (192 + 256 a head), the shared rotary key and the outputs
    (256 a head) moved once (bfloat16)."""
    s = _dims(config)
    layers = len(layer_plan(config))
    moved = 2.0 * tokens * (s["h"] * (s["nope"] + s["rope"] + s["nope"] + 2 * s["vd"])
                            + s["rope"])
    return layers * tokens * attention_core_flops_per_token(config), layers * moved
