"""Token rows of a traffic file: the one generator every token cell's builder
draws its rows from. A traffic mix is its parameters (`cap`, `lengths`); this
file knows no cell and no configuration.

`lengths`: {"distribution": "lognormal", "median", "sigma", "min",
"rows_at_cap"}. The multiset of lengths is the distribution's quantiles at
(i + 0.5) / rows, rounded, clipped to min..cap, the longest `rows_at_cap`
rows set to the cap: it depends on the parameters and the row count alone.
A seed orders the multiset and draws the ids, so it changes what the rows
hold and never the amount of work.
"""

from __future__ import annotations

import math
import statistics
from typing import Any, Dict, Tuple

import numpy as np


def lengths_multiset(spec: Dict[str, Any], rows: int, cap: int) -> np.ndarray:
    """Real tokens of each of `rows` rows, ascending."""
    if spec["distribution"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['distribution']!r}")
    law = statistics.NormalDist(math.log(float(spec["median"])), float(spec["sigma"]))
    logs = np.array([law.inv_cdf((i + 0.5) / rows) for i in range(rows)])
    n = np.clip(np.rint(np.exp(logs)), int(spec["min"]), cap).astype(np.int64)
    at_cap = int(spec["rows_at_cap"])
    if at_cap:
        n[-at_cap:] = cap
    return n


def padded_rows(traffic: Dict[str, Any], rows: int, vocab: int, pad_id: int,
                seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """([rows, cap] int32 ids, [rows] lengths): ids from 1 to vocab - 1 in a
    row's first `length` positions, `pad_id` after."""
    cap = int(traffic["cap"])
    rng = np.random.default_rng(int(seed))
    lengths = rng.permutation(lengths_multiset(traffic["lengths"], rows, cap))
    ids = rng.integers(1, vocab, (rows, cap), dtype=np.int32)
    ids[np.arange(cap)[None, :] >= lengths[:, None]] = pad_id
    return ids, lengths
