"""Builds the system under test for the GLM-4.7-Flash configuration: the fused
`PipelineModel([DNNModel(tokens -> logprob, mtp_logprob, expert_load)])` of
the program around its `latent_causal_lm` with the multi-token-prediction
module, given the benchmark's seeded bfloat16 weights and a DataFrame of
padded int32 token rows (`harness/token_rows.py`).

The subject is the K-EXAONE builder's (`builders/k-exaone-236b-a23b-ep8.py`:
the call, the sampling of rows, real tokens as work, the counters) with this
configuration's model and a second output carried beside the first:
`README-mtp-cells.md` says what differs. A call's output is, a row, the
program's `[2, cap]` float32 log-probabilities: row 0 the next token's
(`logprob`), row 1 the MTP module's of the token two ahead (`mtp_logprob`).
A row counts as work where both are finite at every real position.

`correct` follows `xing4.score`'s statistics (PERF.md section 2), for each
output: the REFERENCE marks the positions whose routing is stable (`margin`:
the gap of the 4th and 5th selection score, the smallest over the four
sparse layers; for an MTP position the smaller of that and the module's own
layer's gap), `unstable_share` holds the margin to account, and compared are
the MEAN gap over the stable positions and their 99th percentile.

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

# Set on a TPU v5e at batch 1 x 16,384 (PERF.md section 2).
# MARGIN: xing4.score's, for the same router (top-4 of 64 all held, sigmoid
# and a selection bias): the stable positions' 99th percentile falls from
# 0.0182-0.0192 (main) and 0.028-0.034 (MTP) at a margin of 0.0005 to
# 0.0019-0.0020 and 0.0022-0.0025 at 0.002, and stays there to 0.008: above
# 0.002 the flips are out, and 0.004 keeps as much again of room.
MARGIN = 0.004
# logprob_gap, the mean over the stable positions: lower reading 0.000565, the
# program's largest on four seeds (0.000544-0.000565); upper reading 0.0173,
# the smallest of the control and the planted faults that move the main output
# through the run's own path (`selfcheck/control_on_chip_glm47.py`: the content
# heads cut to 128 0.0173, float8 0.0226, the routed scale 1.0 0.0367, no
# shared expert 0.0790). The limit is near their geometric middle: 5.5 times
# above the one, 5.6 times under the other.
LOGPROB_GAP_LIMIT = 0.0031
# logprob_gap_p99: lower reading 0.00200 (0.00186-0.00200), upper 0.107 (the
# content heads cut to 128; float8 0.112, the others 0.122-0.254): 7.5 times
# above, 7.1 under.
LOGPROB_GAP_P99_LIMIT = 0.015
# unstable_share: 0.5638-0.5658 at MARGIN on every seed (the router's own
# spread of scores, not the program's); a margin of 0.005 reads 0.647-0.651
# and fails.
UNSTABLE_SHARE_LIMIT = 0.61
# mtp_logprob_gap: lower reading 0.000646 (0.000587-0.000646); upper 0.0221,
# the smallest of the control and every fault (the content heads 0.0221,
# float8 0.0292, the final-normed state 0.0322, the routed scale 0.0426,
# RMSNorm_h left out 0.0530, no shared expert 0.0865, id_t embedded 0.113,
# targets one ahead 0.144): 5.9 times above, 5.8 under.
MTP_LOGPROB_GAP_LIMIT = 0.0038
# mtp_logprob_gap_p99: lower reading 0.00243 (0.00213-0.00243); upper 0.116
# (the final-normed state; the content heads 0.125, float8 0.133, the others
# 0.143-0.465): 7.0 times above, 6.8 under.
MTP_LOGPROB_GAP_P99_LIMIT = 0.017
# mtp_unstable_share: 0.6447-0.6477 at MARGIN (the smaller of two layers'
# margins); a margin of 0.005 reads 0.728-0.730 and fails.
MTP_UNSTABLE_SHARE_LIMIT = 0.69
# the margins standard error reads the stable gaps and the unstable share at,
# for whoever sets MARGIN next (-1: every real position)
MARGINS_SHOWN = (-1.0, 0.0005, 0.001, 0.002, 0.004, 0.005, 0.008)
OUTPUTS = (("logprob", "margin", ""), ("mtp_logprob", "mtp_margin", "mtp_"))


def model_of(config, cap: int):
    """The program's scorer for the configuration's file, without weights."""
    from mmlspark_tpu.models.transformer import latent_causal_lm

    n, dense = int(config["num_hidden_layers"]), int(config["first_k_dense_replace"])
    return latent_causal_lm(
        seq_len=cap, vocab_size=int(config["vocab_size"]),
        hidden=int(config["hidden_size"]), heads=int(config["num_attention_heads"]),
        q_rank=int(config["q_lora_rank"]), kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]), rope=int(config["qk_rope_head_dim"]),
        v_dim=int(config["v_head_dim"]), sparse=[i >= dense for i in range(n)],
        dense_hidden=int(config["intermediate_size"]),
        expert_hidden=int(config["moe_intermediate_size"]),
        num_experts=int(config["n_routed_experts"]),
        experts_held=int(config["n_routed_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        scoring=config["scoring_func"],
        norm_topk=bool(config["norm_topk_prob"]),
        scale=float(config["routed_scaling_factor"]),
        shared_experts=int(config["n_shared_experts"]),
        rope_theta=float(config["rope_theta"]), rope_scaling=config["rope_scaling"],
        eps=float(config["rms_norm_eps"]), pad_id=int(config["pad_id"]),
        nextn=int(config["num_nextn_predict_layers"]),
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
                     fetchDict={"logprob": "OUTPUT_0", "mtp_logprob": "mtp_logprob",
                                "expert_load": "expert_load"})
            .set_model(model)]).fuse()
        self._pick = np.random.default_rng(self.seed + 1)
        self._stats: List[Any] = []
        self._cache_misses_warm = 0
        self._calls = 0
        self._last = None
        self._finite_of = self._finite = None
        self._load = None

    def warm(self) -> None:
        self._finite_rows(self._both(self.fused.transform(self._warm_df)))
        self._cache_misses_warm = self.fused.fusion_stats()["compile_cache"]["misses"]

    @staticmethod
    def _both(out):
        """A row's two outputs as one `[2, cap]` row: logprob, mtp_logprob."""
        return _column_of(np.stack([np.stack(list(out.column("logprob"))),
                                    np.stack(list(out.column("mtp_logprob")))], axis=1))

    def call(self):
        out = self.fused.transform(self.df)
        col = self._both(out)
        self._stats.append(self.fused.last_ingest_stats)
        self._calls += 1
        load = np.sum(np.stack(list(out.column("expert_load"))), axis=0)
        self._load = load if self._load is None else self._load + load
        return col

    def _finite_rows(self, col) -> np.ndarray:
        """Per row that came back: both outputs finite at every real position
        (a pad position that is not finite fails nothing)."""
        if self._finite_of is not col:
            self._finite = np.array([bool(np.isfinite(np.asarray(v)[..., :k]).all())
                                     for v, k in zip(col, self.lengths)])
            self._finite_of = col
        return self._finite

    def _sample(self, col, n: int, always=()):
        """(row numbers, their `[2, cap]` outputs) of n rows drawn from the
        seed, with `always` among them; a failed row is failed, not wrong."""
        idx = self._pick.choice(len(col), n, replace=False)
        idx = np.unique(np.concatenate([idx, np.asarray(always, np.int64)]))
        idx = idx[self._finite_rows(col)[idx]]
        return idx, np.stack([np.asarray(col[i], np.float32) for i in idx]) \
            if len(idx) else np.empty((0, 2, int(self.traffic["cap"])), np.float32)

    def compare(self, idx: np.ndarray, got: np.ndarray) -> List[Compared]:
        """`got [n, 2, cap]`, what came back for the rows `idx`, against the
        reference's two log-probabilities of those rows."""
        need, at = np.unique(idx, return_inverse=True)
        ref = self.reference.score(self.config, self.seed, self.ids[need])
        compared: List[Compared] = []
        for j, (key, margin_key, prefix) in enumerate(OUTPUTS):
            gaps = self.reference.row_gaps(got[:, j], ref[key][at], self.lengths[idx])
            margin = ref[margin_key][at]
            real = ~np.isnan(gaps)

            def stable_at(m):
                return real & (margin > m)

            def line(m):
                v = gaps[stable_at(m)]
                if not len(v):
                    return f"margin > {m}: no position"
                q = [float(x) for x in np.quantile(v, [0.5, 0.9, 0.99, 0.999])]
                return (f"margin > {m}: {len(v)} positions, unstable share "
                        f"{1.0 - len(v) / float(real.sum())!r}; gaps mean "
                        f"{float(v.mean())!r}, median {q[0]!r}, 90th {q[1]!r}, 99th "
                        f"{q[2]!r}, 99.9th {q[3]!r}, widest {float(v.max())!r}")

            stable = stable_at(MARGIN)
            quarters = np.array_split(np.arange(gaps.shape[1]), 4)
            print(f"{key} gap over {int(real.sum())} real positions of {len(idx)} rows "
                  f"({len(need)} distinct)\n" + "\n".join(line(m) for m in MARGINS_SHOWN)
                  + "\nstable gaps' mean by quarter of the cap: " + ", ".join(
                      repr(float(gaps[:, q][stable[:, q]].mean())) if stable[:, q].any()
                      else "none" for q in quarters), file=sys.stderr)
            v = gaps[stable]
            mean, p99, unstable = {
                "": (LOGPROB_GAP_LIMIT, LOGPROB_GAP_P99_LIMIT, UNSTABLE_SHARE_LIMIT),
                "mtp_": (MTP_LOGPROB_GAP_LIMIT, MTP_LOGPROB_GAP_P99_LIMIT,
                         MTP_UNSTABLE_SHARE_LIMIT)}[prefix]
            compared += [
                Compared(f"{prefix}logprob_gap",
                         float(v.mean()) if len(v) else float("inf"), mean),
                Compared(f"{prefix}logprob_gap_p99",
                         float(np.quantile(v, 0.99)) if len(v) else float("inf"), p99),
                Compared(f"{prefix}unstable_share",
                         1.0 - float(stable.sum()) / float(real.sum()), unstable)]
        return compared


def build(config, traffic, seed: int, chips: List[Any]) -> Subject:
    return Subject(config, traffic, seed, chips)
