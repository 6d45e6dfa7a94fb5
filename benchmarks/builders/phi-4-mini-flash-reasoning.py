"""Builds the system under test for the Phi-4-mini-flash-reasoning
configuration: the fused `PipelineModel([DNNModel(tokens -> logprob)])` of
the program around its `hybrid_causal_lm`, given the benchmark's seeded
bfloat16 weights and a DataFrame of padded int32 token rows
(`harness/token_rows.py`).

The subject is the K-EXAONE builder's (`builders/k-exaone-236b-a23b-ep8.py`:
the sampling of rows, real tokens as work, `check`) with this configuration's
model, one output column (the model has no expert layer, so no `expert_load`
node: `call` is its own, `counters` the base's less that node) and this cell's comparison;
`README-hybrid-cells.md` says what differs.

`correct` has no router to work around (PERF.md section 2): as `bilstm.tag`'s,
`logprob_gap` is the WIDEST gap of any sampled real position over its row's
spread of reference log-probabilities (or the median row's), at the timed
batch, of what the timed path produced. Beside it `logprob_gap_mean`, the
mean over the same positions: the two mildest planted faults move every
position a little (2.5 times the program's own rounding) and the widest gap
by only 2.2 to 4.4 times, and a mean over 130,000-200,000 positions hardly
varies from seed to seed.

Traffic parameters read here: `batches_per_call`, `partitions`, `cap`,
`lengths`, `check_rows_per_call`, `check_rows_last_call`.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Dict, List

import numpy as np

from benchmarks.harness import spec, token_rows
from benchmarks.harness.check import Compared

_base = spec.bench_module("builders", "k-exaone-236b-a23b-ep8")
_nest, _column_of = _base._nest, _base._column_of

# Set on the chip at batch 1 x 32,768 (my chip runs, PR 40; PERF.md section 2).
# logprob_gap, the widest: lower reading 0.0223, the program's largest over 10
# runs on 10 seeds (0.0120, 0.0148, 0.0150, 0.0160, 0.0162, 0.0167, 0.0168,
# 0.0213, 0.0214, 0.0223; the three of 0.021-0.022 all in a row's first
# quarter); upper reading 0.0498, the smallest of the control and the planted
# faults through the run's own path (`selfcheck/control_on_chip_phi4flash.py`,
# two seeds, three rows each: a cross layer on its own keys 0.0498 / 0.0536,
# window 511 0.0971 / 0.1078, the state reset every 8,192 positions 0.1287 /
# 0.2092, the memory after the gate 0.1735 / 0.1637, the float8 control 0.3024
# / 0.2999, the three others 0.55-0.80). The readings lie 2.2 times apart, so
# the issue's "three times above, half below" cannot both hold; the limit
# stands 1.8 times above the one (a false `correct: false` refuses a PR) and
# 1.25 under the other.
LOGPROB_GAP_LIMIT = 0.04
# logprob_gap_mean: lower reading 0.00304 (0.00227-0.00304 on the 10 seeds);
# upper readings: window 511 0.00686 / 0.00925, a cross layer on its own keys
# 0.00777 / 0.00822, the memory after the gate 0.0305, float8 0.0462 / 0.0559
# (the state reset reads 0.00137 / 0.00174 here: it moves few positions by
# much, and the widest catches it). 1.5 times above the one, 1.5 and 1.7 under
# the two mildest faults.
LOGPROB_GAP_MEAN_LIMIT = 0.0045


def model_of(config, cap: int):
    """The program's scorer for the configuration's file, without weights."""
    from mmlspark_tpu.models.transformer import hybrid_causal_lm

    a = config["assumed"]
    return hybrid_causal_lm(
        seq_len=cap, vocab_size=int(config["vocab_size"]),
        hidden=int(config["hidden_size"]), heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        num_layers=int(config["num_hidden_layers"]),
        dense_hidden=int(config["intermediate_size"]),
        window=int(config["sliding_window"]), mb_per_layer=int(config["mb_per_layer"]),
        d_state=int(a["mamba_d_state"]), d_conv=int(a["mamba_d_conv"]),
        expand=int(a["mamba_expand"]), dt_rank=int(a["mamba_dt_rank"]),
        eps=float(config["layer_norm_eps"]), pad_id=int(config["pad_id"]),
        param_dtype="bfloat16", init=False)


class Subject(_base.Subject):
    def __init__(self, config, traffic, seed: int, chips: List[Any]):
        from mmlspark_tpu.core.dataframe import DataFrame
        from mmlspark_tpu.core.pipeline import PipelineModel
        from mmlspark_tpu.models.dnn_model import DNNModel

        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.reference = spec.bench_module("references", config["reference"])
        cap = int(traffic["cap"])
        if cap != int(config["max_positions"]):
            raise ValueError(f"the mix pads to {cap}, the configuration to "
                             f"{config['max_positions']}")
        batch = int(config["assumed"]["batch_size"])
        self.rows = batch * int(traffic["batches_per_call"])
        self.items_per_call = self.rows
        self.ids, self.lengths = token_rows.padded_rows(
            traffic, self.rows, int(config["vocab_size"]), int(config["pad_id"]),
            self.seed)
        col = _column_of(self.ids)
        self.df = DataFrame.from_dict({"tokens": col},
                                      num_partitions=int(traffic["partitions"]))
        # one batch of the same rows: compiles and loads what a whole call runs
        self._warm_df = DataFrame.from_dict({"tokens": col[:batch]}, num_partitions=1)
        model = dataclasses.replace(
            model_of(config, cap),
            params=_nest(self.reference.make_weights(config, self.seed)))
        self.fused = PipelineModel([
            DNNModel(inputCol="tokens", batchSize=batch, fetchDict={"logprob": "OUTPUT_0"})
            .set_model(model)]).fuse()
        self._pick = np.random.default_rng(self.seed + 1)
        self._stats: List[Any] = []
        self._cache_misses_warm = 0
        self._calls = 0
        self._last = None
        self._finite_of = self._finite = None
        self._load = None            # what the base's `counters` sums: nothing here

    def call(self):
        col = self.fused.transform(self.df).column("logprob")
        self._stats.append(self.fused.last_ingest_stats)
        self._calls += 1
        return col

    def counters(self) -> Dict[str, Any]:
        found = super().counters()
        del found["expert_load"]     # no expert layer, so no such node was fetched
        return found

    def compare(self, idx: np.ndarray, got: np.ndarray) -> List[Compared]:
        """`got [n, cap]`, what came back for the rows `idx`, against the
        reference's log-probabilities of those rows."""
        need, at = np.unique(idx, return_inverse=True)
        ref = self.reference.score(self.config, self.seed, self.ids[need])
        gaps = self.reference.row_gaps(got, ref["logprob"][at], self.lengths[idx])
        real = ~np.isnan(gaps)
        g = gaps[real]
        # where along a row the gap grows (the scan and the full layers carry
        # a difference forward), for the next reader of the limit
        quarters = np.array_split(np.arange(gaps.shape[1]), 4)
        print(f"logprob_gap over {int(real.sum())} real positions of {len(idx)} rows "
              f"({len(need)} distinct): widest {float(g.max())!r}, 99.9th percentile "
              f"{float(np.quantile(g, 0.999))!r}, 99th {float(np.quantile(g, 0.99))!r}, "
              f"mean {float(g.mean())!r}; widest by quarter of the cap: " + ", ".join(
                  repr(float(gaps[:, q][real[:, q]].max())) if real[:, q].any() else "none"
                  for q in quarters), file=sys.stderr)
        return [Compared("logprob_gap", float(g.max()) if len(g) else float("inf"),
                         LOGPROB_GAP_LIMIT),
                Compared("logprob_gap_mean", float(g.mean()) if len(g) else float("inf"),
                         LOGPROB_GAP_MEAN_LIMIT)]


def build(config, traffic, seed: int, chips: List[Any]) -> Subject:
    return Subject(config, traffic, seed, chips)
