"""Operations and bytes the BiLSTM tagger needs for one real token, from the
configuration's shapes alone.

FLOPs = 2 x multiply-accumulates of the matrix products: each direction's
input product [E, 4H] and recurrent product [H, 4H], and the head's [2H,
tags]. At E 50, H 300, 9 tags that is 1,690,800 a token, 99.4% of it in the
LSTM and 85% in the recurrent products. The gates' element-wise work (about
30 operations a hidden unit) and the embedding gather are left out: XLA
counts them, and `selfcheck/test_bilstm_cell.py` holds the two within a
stated band. A padded position costs the chip the same and counts for
nothing here: shares built on this file read the same work whatever later
implements the padding.
"""

from __future__ import annotations

from typing import Dict


def macs_per_token(config) -> int:
    emb, hid = int(config["embed_dim"]), int(config["hidden_size"])
    tags = int(config["num_tags"])
    return 2 * (emb * 4 * hid + hid * 4 * hid) + 2 * hid * tags


def flops_per_token(config) -> float:
    return 2.0 * macs_per_token(config)


def parameters(config, with_table: bool = True) -> int:
    emb, hid = int(config["embed_dim"]), int(config["hidden_size"])
    tags = int(config["num_tags"])
    lstm = 2 * (emb * 4 * hid + hid * 4 * hid + 4 * hid)
    table = int(config["vocab_size"]) * emb if with_table else 0
    return table + lstm + 2 * hid * tags + tags


def bytes_per_batch(config, tokens: float) -> Dict[str, float]:
    """Bytes one batch holding `tokens` real tokens has to move, at the
    least: every weight but the table once (float32, as the program holds
    them), and for each token its int32 id, its row of the table and its
    float32 logits. Not XLA's `bytes accessed`, which counts every
    intermediate."""
    emb, tags = int(config["embed_dim"]), int(config["num_tags"])
    return {"weights": 4.0 * parameters(config, with_table=False),
            "input": 4.0 * tokens,
            "table_rows": 4.0 * emb * tokens,
            "output": 4.0 * tags * tokens}
