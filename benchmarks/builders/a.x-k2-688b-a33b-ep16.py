"""Builds the system under test for the A.X-K2 configuration: the fused
`PipelineModel([DNNModel(tokens -> logprob, expert_load)])` of the program
around its `latent_causal_lm` with the sparse attention's indexer, the
attention output gate, gated norms and group-limited routing, given the
benchmark's seeded bfloat16 weights and a DataFrame of padded int32 token
rows (`harness/token_rows.py`).

The subject is the K-EXAONE builder's (`builders/k-exaone-236b-a23b-ep8.py`:
the call, the sampling of rows, real tokens as work, the counters) with this
configuration's model and this cell's comparison; `README-sparse-cells.md`
says what differs.

`correct` follows `xing4.score`'s statistics (PERF.md section 2): the
REFERENCE marks the positions whose routing is stable (`margin`: the smaller
of the gap between the 8th and 9th selection score among the kept groups'
experts and the gap between the 4th and 5th group score, the smallest over
the four sparse layers, above `MARGIN`), `unstable_share` holds the margin to
account, and compared are the MEAN gap over the stable positions
(`logprob_gap`) and their 99th percentile (`logprob_gap_p99`).

Traffic parameters read here: `batches_per_call`, `partitions`, `cap`,
`lengths`, `check_rows_per_call`, `check_rows_last_call`.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, List

import numpy as np

from benchmarks.harness import spec, token_rows
from benchmarks.harness.check import Compared

_base = spec.bench_module("builders", "k-exaone-236b-a23b-ep8")
_nest, _column_of = _base._nest, _base._column_of

# Set on the chip at batch 1 x 32,768 (my chip runs, PR 42; PERF.md section 2).
# MARGIN: the stable positions' 99th percentile falls from 0.0032-0.0034 over
# every real position to 0.0014-0.0016 at a margin of 0.0005 and 0.0014-0.0015
# at 0.001, and stays there to 0.008: above 0.001 the flips are out. The group
# margin is narrow (a group's score is a sum of two of 32 sigmoids), so a wider
# MARGIN leaves few positions: 9% of them at 0.004.
MARGIN = 0.001
# logprob_gap, the mean over the stable positions: lower reading 0.00045, the
# program's largest (0.00039-0.00045 on five seeds); upper reading 0.00274, the
# smallest of the control and the planted faults through the run's own path
# (`selfcheck/control_on_chip_axk2.py`: routing without groups 0.00274, the
# indexer without its ReLU 0.00330, dense attention 0.00410, the top 1,024
# 0.00425, float8 0.00470, w uniform 0.00560, no output gate 0.0119, no gated
# norm 0.0668). The limit lies 2.4 times above the one and 2.5 times under the
# other.
LOGPROB_GAP_LIMIT = 0.0011
# logprob_gap_p99: lower reading 0.00158 (0.00136-0.00158), upper reading
# 0.0132 (the indexer without its ReLU; dense attention 0.0160, float8 0.0162,
# the others 0.0153-0.218): 2.8 times above, 2.9 under.
LOGPROB_GAP_P99_LIMIT = 0.0045
# unstable_share: 0.448-0.453 at MARGIN on every window read (the router's own
# spread of scores, not the program's); a margin of 0.002 reads 0.70 and fails.
UNSTABLE_SHARE_LIMIT = 0.5
# the margins standard error reads the stable gaps and the unstable share at,
# for whoever sets MARGIN next (-1: every real position)
MARGINS_SHOWN = (-1.0, 0.0005, 0.001, 0.002, 0.004, 0.005, 0.008)


def model_of(config, cap: int):
    """The program's scorer for the configuration's file, without weights."""
    from mmlspark_tpu.models.transformer import latent_causal_lm

    n = int(config["num_hidden_layers"])
    dense, freq = int(config["first_k_dense_replace"]), int(config["moe_layer_freq"])
    rp = config["rope_parameters"]
    return latent_causal_lm(
        seq_len=cap, vocab_size=int(config["vocab_size"]),
        hidden=int(config["hidden_size"]), heads=int(config["num_attention_heads"]),
        q_rank=int(config["q_lora_rank"]), kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]), rope=int(config["qk_rope_head_dim"]),
        v_dim=int(config["v_head_dim"]),
        sparse=[i >= dense and i % freq == 0 for i in range(n)],
        dense_hidden=int(config["intermediate_size"]),
        expert_hidden=int(config["moe_intermediate_size"]),
        num_experts=int(config["n_routed_experts_published"]),
        experts_held=int(config["n_routed_experts"]),
        first_expert=int(config["first_expert_held"]),
        top_k=int(config["num_experts_per_tok"]),
        scoring=config["scoring_func"], norm_topk=bool(config["norm_topk_prob"]),
        scale=float(config["routed_scaling_factor"]),
        shared_experts=int(config["n_shared_experts"]),
        rope_theta=float(rp["rope_theta"]),
        rope_scaling={k: v for k, v in rp.items() if k != "rope_theta"},
        eps=float(config["rms_norm_eps"]), pad_id=int(config["pad_id"]),
        index_heads=int(config["index_n_heads"]), index_dim=int(config["index_head_dim"]),
        index_topk=int(config["index_topk"]),
        out_gate=bool(config["attention_output_gate"] and config["attn_gate_fused"]),
        gated_norm_rank=int(config["gated_norm_rank"]) if config["gated_norm"] else 0,
        n_group=int(config["n_group"]), topk_group=int(config["topk_group"]),
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
            DNNModel(inputCol="tokens", batchSize=batch,
                     fetchDict={"logprob": "OUTPUT_0", "expert_load": "expert_load"})
            .set_model(model)]).fuse()
        self._pick = np.random.default_rng(self.seed + 1)
        self._stats: List[Any] = []
        self._cache_misses_warm = 0
        self._calls = 0
        self._last = None
        self._finite_of = self._finite = None
        self._load = None

    def compare(self, idx: np.ndarray, got: np.ndarray) -> List[Compared]:
        """`got [n, cap]`, what came back for the rows `idx`, against the
        reference's log-probabilities of those rows."""
        need, at = np.unique(idx, return_inverse=True)
        ref = self.reference.score(self.config, self.seed, self.ids[need])
        gaps = self.reference.row_gaps(got, ref["logprob"][at], self.lengths[idx])
        margin = ref["margin"][at]
        real = ~np.isnan(gaps)

        def stable_at(m):
            return real & (margin > m)

        def line(m):
            v = gaps[stable_at(m)]
            if not len(v):
                return f"margin > {m}: no position"
            q = [float(x) for x in np.quantile(v, [0.5, 0.9, 0.99, 0.999])]
            return (f"margin > {m}: {len(v)} positions, unstable share "
                    f"{1.0 - len(v) / float(real.sum())!r}; gaps mean {float(v.mean())!r}, "
                    f"median {q[0]!r}, 90th {q[1]!r}, 99th {q[2]!r}, 99.9th {q[3]!r}, "
                    f"widest {float(v.max())!r}")

        stable = stable_at(MARGIN)
        # the selection bites past position index_topk: the gaps before and after
        topk = int(self.config["index_topk"])
        print(f"logprob_gap over {int(real.sum())} real positions of {len(idx)} rows "
              f"({len(need)} distinct)\n" + "\n".join(line(m) for m in MARGINS_SHOWN)
              + f"\nstable gaps' mean before position {topk}, after: " + ", ".join(
                  repr(float(gaps[:, part][stable[:, part]].mean()))
                  if stable[:, part].any() else "none"
                  for part in (slice(0, topk), slice(topk, None))), file=sys.stderr)
        v = gaps[stable]
        return [Compared("logprob_gap", float(v.mean()) if len(v) else float("inf"),
                         LOGPROB_GAP_LIMIT),
                Compared("logprob_gap_p99",
                         float(np.quantile(v, 0.99)) if len(v) else float("inf"),
                         LOGPROB_GAP_P99_LIMIT),
                Compared("unstable_share", 1.0 - float(stable.sum()) / float(real.sum()),
                         UNSTABLE_SHARE_LIMIT)]


def build(config, traffic, seed: int, chips: List[Any]) -> Subject:
    return Subject(config, traffic, seed, chips)
