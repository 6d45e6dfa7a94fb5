"""Builds the system under test for the Xing4.0 configuration: the fused
`PipelineModel([DNNModel(tokens -> logprob, expert_load)])` of the program
around its `latent_causal_lm`, given the benchmark's seeded bfloat16 weights
and a DataFrame of padded int32 token rows (`harness/token_rows.py`).

The subject is the K-EXAONE builder's (`builders/k-exaone-236b-a23b-ep8.py`:
the call, the sampling of rows, real tokens as work, the counters) with this
configuration's model and this cell's comparison; `README-latent-cells.md`
says what differs.

`correct` under a router that holds every expert (PERF.md section 2). The
REFERENCE marks the positions whose routing is stable (`margin`: the gap of
the 4th and 5th selection score, the smallest over the four sparse layers,
above `MARGIN`), as `README-scorer-cells.md` sets out, and `unstable_share`
holds the margin to account. But here every flip counts (all 64 experts are
held: a flipped position's output differs by a whole expert) and reaches the
row's later positions through attention, which is peaked (the softmax scale
carries m^2 = 2.0): the WIDEST gap over stable positions reads 0.08-0.12 for
the program and 0.15 for the mildest planted fault, so it is no statistic.
Compared are the MEAN gap over the stable positions (`logprob_gap`) and their
99th percentile (`logprob_gap_p99`): a fault that moves every position moves
the first, one that moves one position in a hundred by much moves the second.

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

# Set on the chip at batch 1 x 16,384 (my chip runs, PR 36; PERF.md section 2).
# MARGIN: the stable positions' 99th percentile falls from 0.020-0.025 at a
# margin of 0.002 to 0.0059-0.0104 at 0.004 and 0.0062-0.0070 at 0.008: above
# 0.004 the flips themselves are out and what is left is their wake.
MARGIN = 0.004
# logprob_gap, the mean over the stable positions: lower reading 0.00164, the
# program's largest over 11 runs on 11 seeds (0.00118-0.00164); upper reading
# 0.0106, the smallest of the control and the planted faults on two seeds through
# the run's own path (`selfcheck/control_on_chip_xing4.py`: the first stream
# alone 0.0106 / 0.0137, one Sinkhorn step 0.0137 / 0.0165, the float8 control
# 0.0287 / 0.0312, the others 0.043-0.104). The limit lies 2.9 times above the
# one and 2.3 times under the other.
LOGPROB_GAP_LIMIT = 0.0047
# logprob_gap_p99: lower reading 0.0104 (0.0059-0.0104), upper reading 0.0494
# (the first stream alone 0.0494 / 0.0644; one Sinkhorn step 0.097 / 0.104,
# float8 0.129 / 0.142, the others 0.169-0.338): 2.3 times above, 2.1 under.
LOGPROB_GAP_P99_LIMIT = 0.024
# unstable_share: 0.5616-0.5713 at MARGIN on every seed (the router's own
# spread of scores, not the program's); a margin of 0.005 reads 0.647-0.652
# and fails.
UNSTABLE_SHARE_LIMIT = 0.62
# the margins standard error reads the stable gaps and the unstable share at,
# for whoever sets MARGIN next (-1: every real position)
MARGINS_SHOWN = (-1.0, 0.0005, 0.001, 0.002, 0.004, 0.005, 0.008)


def model_of(config, cap: int):
    """The program's scorer for the configuration's file, without weights."""
    from mmlspark_tpu.models.transformer import latent_causal_lm

    n = int(config["num_hidden_layers"])
    dense, freq = int(config["first_k_dense_replace"]), int(config["moe_layer_freq"])
    return latent_causal_lm(
        seq_len=cap, vocab_size=int(config["vocab_size"]),
        hidden=int(config["hidden_size"]), heads=int(config["num_attention_heads"]),
        q_rank=int(config["q_lora_rank"]), kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]), rope=int(config["qk_rope_head_dim"]),
        v_dim=int(config["v_head_dim"]),
        sparse=[i >= dense and i % freq == 0 for i in range(n)],
        dense_hidden=int(config["intermediate_size"]),
        expert_hidden=int(config["moe_intermediate_size"]),
        num_experts=int(config["n_routed_experts"]),
        experts_held=int(config["n_routed_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        scoring=config["scoring_func"], norm_topk=bool(config["norm_topk_prob"]),
        scale=float(config["routed_scaling_factor"]),
        shared_experts=int(config["n_shared_experts"]),
        rope_theta=float(config["rope_theta"]), rope_scaling=dict(config["rope_scaling"]),
        streams=int(config["hc_mult"]), sinkhorn_iters=int(config["hc_sinkhorn_iters"]),
        hc_eps=float(config["hc_eps"]),
        hc_clamp=(float(config["mhc_h_res_clamp_min"]), float(config["mhc_h_res_clamp_max"])),
        eps=float(config["rms_norm_eps"]), pad_id=int(config["pad_id"]),
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
        # a flip's wake grows along a row (later positions attend to more
        # flipped ones); a fault in the positions would too, and faster
        quarters = np.array_split(np.arange(gaps.shape[1]), 4)
        print(f"logprob_gap over {int(real.sum())} real positions of {len(idx)} rows "
              f"({len(need)} distinct)\n" + "\n".join(line(m) for m in MARGINS_SHOWN)
              + "\nstable gaps' mean by quarter of the cap: " + ", ".join(
                  repr(float(gaps[:, q][stable[:, q]].mean())) if stable[:, q].any()
                  else "none" for q in quarters), file=sys.stderr)
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
