"""Operations and bytes the A.X-K2 scorer needs for the real tokens of a
call's rows, from the configuration's shapes and the rows' lengths alone: the
chip the configuration's file states (layers 0-4 of 61, experts 0-15 of 256,
an eighth of the vocabulary).

FLOPs = 2 x multiply-accumulates of the matrix products a token goes through
(the latent attention's five projections with the gates' columns, the
indexer's three, the gated norms' two low-rank products a sublayer, the dense
or the shared and routed SwiGLUs, the router, the head) plus, in every layer,
the indexer at every causal (query, key) pair: 64 heads x 128 x 2 = 16,384
FLOP; and the core at the keys a query reads, `min(t + 1, index_topk)`:
scores 192 wide and values 128 a head, 64 x 320 x 2 = 40,960 FLOP a pair.
Routed visits are counted at their expectation on this chip, `top_k x held /
experts` = 0.5 a token a sparse layer, whatever the router did: a hot expert
earns nothing. The selection (a sort or a search over float32 scores), norms,
rotary positions, softmax and the gates' sigmoids are left out. A padded
position costs the chip the same and counts for nothing here, so no share
built on this file can pass 100%. At the published widths a real token of a
row at the cap is 4.66 GFLOP: the products 2.91, the indexer 1.34, the core
0.41.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

INDEX_PAIR_FLOPS = 16384        # 64 heads x 128 x 2
CORE_PAIR_FLOPS = 40960         # 64 heads x (192 + 128) x 2
LATENT_ROW_BYTES = 1152         # a key's 512-wide latent and 64-wide rotary key, bfloat16


def _dims(config) -> Dict[str, int]:
    return {"d": int(config["hidden_size"]), "h": int(config["num_attention_heads"]),
            "qr": int(config["q_lora_rank"]), "kvr": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]), "rope": int(config["qk_rope_head_dim"]),
            "vd": int(config["v_head_dim"]), "ff": int(config["intermediate_size"]),
            "eff": int(config["moe_intermediate_size"]),
            "held": int(config["n_routed_experts"]),
            "experts": int(config["n_routed_experts_published"]),
            "top_k": int(config["num_experts_per_tok"]),
            "shared": int(config["n_shared_experts"]),
            "ih": int(config["index_n_heads"]), "idim": int(config["index_head_dim"]),
            "topk": int(config["index_topk"]), "rank": int(config["gated_norm_rank"]),
            "vocab": int(config["vocab_size"])}


def layer_plan(config) -> List[bool]:
    dense, freq = int(config["first_k_dense_replace"]), int(config["moe_layer_freq"])
    return [i >= dense and i % freq == 0 for i in range(int(config["num_hidden_layers"]))]


def attention_macs(config) -> int:
    """W_qa, W_qb (with the gates' 128 columns a head), W_kva, W_kvb, W_o:
    113,704,960 at the published widths."""
    s = _dims(config)
    return s["d"] * s["qr"] + s["qr"] * s["h"] * (s["nope"] + s["rope"] + s["vd"]) \
        + s["d"] * (s["kvr"] + s["rope"]) + s["kvr"] * s["h"] * (s["nope"] + s["vd"]) \
        + s["h"] * s["vd"] * s["d"]


def index_macs(config) -> int:
    """W^I_q, W^I_k, W^I_w: 13,959,168."""
    s = _dims(config)
    return s["qr"] * s["ih"] * s["idim"] + s["d"] * s["idim"] + s["d"] * s["ih"]


def norm_macs(config) -> int:
    """The two gated norms' A and B a layer: 458,752."""
    s = _dims(config)
    return 2 * 2 * s["d"] * s["rank"]


def expert_macs(config) -> int:
    s = _dims(config)
    return 3 * s["d"] * s["eff"]


def macs_per_token(config) -> float:
    """Multiply-accumulates of the weight products one token goes through."""
    s = _dims(config)
    visits = s["top_k"] * s["held"] / float(s["experts"])
    total = float(s["d"] * s["vocab"])
    for sparse in layer_plan(config):
        total += attention_macs(config) + index_macs(config) + norm_macs(config)
        if sparse:
            total += s["d"] * s["experts"] + expert_macs(config) * (s["shared"] + visits)
        else:
            total += 3 * s["d"] * s["ff"]
    return total


def pairs(config, lengths: Sequence[int]) -> Tuple[float, float, float]:
    """Of the real tokens of rows of these lengths, in one layer: (causal
    (query, key) pairs the indexer scores, (query, key) pairs the core reads,
    the keys read by queries past position `index_topk`)."""
    k = _dims(config)["topk"]
    causal = sum(n * (n + 1) / 2.0 for n in lengths)
    core = sum(min(n, k) * (min(n, k) + 1) / 2.0 + max(n - k, 0) * float(k) for n in lengths)
    return causal, core, sum(max(n - k, 0) * float(k) for n in lengths)


def flops_per_token(config, lengths: Sequence[int]) -> float:
    """Of a real token of rows of these lengths, the mean."""
    causal, core, _ = pairs(config, lengths)
    layers = len(layer_plan(config))
    return 2.0 * macs_per_token(config) + layers * (
        INDEX_PAIR_FLOPS * causal + CORE_PAIR_FLOPS * core) / float(sum(lengths))


def parameters(config, with_table: bool = True) -> int:
    """Parameters this chip holds: 4,332,741,888 at the published widths, of
    them 4,332,650,496 in the weight matrices."""
    s = _dims(config)
    total = s["d"] * s["vocab"] * (2 if with_table else 1) + s["d"]
    for sparse in layer_plan(config):
        total += attention_macs(config) + index_macs(config) + norm_macs(config) \
            + 2 * s["d"] + s["qr"] + s["kvr"] + 2 * s["idim"]
        if sparse:
            total += s["d"] * s["experts"] + s["experts"] \
                + expert_macs(config) * (s["shared"] + s["held"])
        else:
            total += 3 * s["d"] * s["ff"]
    return total


def bytes_per_batch(config, tokens: float) -> Dict[str, float]:
    """Bytes one batch holding `tokens` real tokens has to move, at the least:
    every weight but the embedding table once (bfloat16), and for each token
    its int32 id, its row of the table and its float32 log-probability."""
    s = _dims(config)
    return {"weights": 2.0 * parameters(config, with_table=False),
            "input": 4.0 * tokens, "table_rows": 2.0 * s["d"] * tokens,
            "output": 4.0 * tokens}


def indexer(config, lengths: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, least bytes) of every layer's `mla_index` for rows of these
    lengths: 16,384 FLOP a causal pair; the queries (64 x 128), the key
    (128), the weights (64, float32) and the selection's bits (a bit a key of
    the row) moved once a token. Compute binds: the weighted ReLU sum on the
    vector unit, for which `peaks.py` has no peak, is not counted."""
    s = _dims(config)
    causal, _, _ = pairs(config, lengths)
    layers = len(layer_plan(config))
    moved = sum(n * (2.0 * s["ih"] * s["idim"] + 2.0 * s["idim"] + 4.0 * s["ih"]
                     + (max(lengths) + 31) // 32 * 4.0) for n in lengths)
    return layers * INDEX_PAIR_FLOPS * causal, layers * moved


def sparse_core(config, lengths: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, least bytes) of every layer's `attn_mla_sparse` for rows of
    these lengths: 40,960 FLOP a (query, selected key) pair at `min(t + 1,
    index_topk)` keys a query; a query past position `index_topk` moves
    1,152 bytes a selected key (its latent row), the least a core that
    gathers its keys would."""
    _, core, past = pairs(config, lengths)
    layers = len(layer_plan(config))
    return layers * CORE_PAIR_FLOPS * core, layers * LATENT_ROW_BYTES * past
