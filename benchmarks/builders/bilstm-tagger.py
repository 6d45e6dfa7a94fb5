"""Builds the system under test for the BiLSTM tagger configuration: the
fused `PipelineModel([DNNModel(tokens -> tags)])` of the program around its
`bilstm_tagger`, given the benchmark's seeded weights and a DataFrame of
padded int32 token rows (`harness/token_rows.py`).

A call's output is the program's own output column, fetched to the host: one
[cap, tags] float32 array of logits a row. Work is real tokens: the lengths
of the rows all of whose real positions came back finite, never a padded
position. The benchmark reads the real positions and its sampled rows, and
makes no dense copy of the column: that copy was a fifth of a call (PERF.md
section 6, PR 29).

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

# logit_gap on the chip at batch 8192 (PERF.md section 2): the program's
# largest reading over 14 seeds 0.0155 (0.0185 over every position of a call),
# the fp8 control's smallest over three seeds 0.0727
LOGIT_GAP_LIMIT = 0.04


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, leaf in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


def _column_of(rows: np.ndarray) -> np.ndarray:
    """[n, ...] as an object column of n row views, as a DataFrame holds
    token rows and the program returns a row's logits."""
    col = np.empty(len(rows), dtype=object)
    for i in range(len(rows)):
        col[i] = rows[i]
    return col


class Subject:
    column_of = staticmethod(_column_of)     # for a stand-in for the program

    def __init__(self, config, traffic, seed: int, chips: List[Any]):
        from mmlspark_tpu.core.dataframe import DataFrame
        from mmlspark_tpu.core.pipeline import PipelineModel
        from mmlspark_tpu.models.attention import bilstm_tagger
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
        # the row of each real position, rows in order
        self._row_of_real = np.repeat(np.arange(self.rows), self.lengths)
        self._finite_of = self._finite = None
        col = _column_of(self.ids)
        self.df = DataFrame.from_dict({"tokens": col},
                                      num_partitions=int(traffic["partitions"]))
        # one batch of the same rows: compiles and loads what a whole call runs
        self._warm_df = DataFrame.from_dict({"tokens": col[:batch]}, num_partitions=1)
        model = dataclasses.replace(
            bilstm_tagger(seq_len=cap, vocab_size=int(config["vocab_size"]),
                          embed_dim=int(config["embed_dim"]),
                          hidden=int(config["hidden_size"]),
                          num_tags=int(config["num_tags"])),
            params=_nest(self.reference.make_weights(config, self.seed)))
        self.fused = PipelineModel([
            DNNModel(inputCol="tokens", outputCol="tags", batchSize=batch)
            .set_model(model)]).fuse()
        # the rows whose logits are kept for the comparison: a fresh sample
        # from the seed for every call, and a larger one for the last
        self._pick = np.random.default_rng(self.seed + 1)
        self._stats: List[Any] = []
        self._cache_misses_warm = 0
        self._calls = 0
        self._last = None

    def warm(self) -> None:
        self._finite_rows(self.fused.transform(self._warm_df).column("tags"))
        self._cache_misses_warm = self.fused.fusion_stats()["compile_cache"]["misses"]

    def call(self):
        # the output column as the caller holds it, one [cap, tags] array a
        # row: no copy here; `_finite_rows` and `_sample` read what they need
        col = self.fused.transform(self.df).column("tags")
        self._stats.append(self.fused.last_ingest_stats)
        self._calls += 1
        return col

    def _finite_rows(self, col) -> np.ndarray:
        """Per row that came back: every real position finite. Reads the real
        positions alone, as a caller of a tagger does (a ninth of the
        positions; a pad position that is not finite fails nothing), and
        once an output: `work`, `failed_items` and the sampling all ask, and
        their time is the window's."""
        if self._finite_of is not col:
            n = len(col)
            real = np.concatenate([np.asarray(v)[:k] for v, k in
                                   zip(col, self.lengths)])
            bad = ~np.isfinite(real).all(axis=-1)
            ok = np.ones(n, dtype=bool)
            ok[self._row_of_real[:len(real)][bad]] = False
            self._finite_of, self._finite = col, ok
        return self._finite

    def work(self, col) -> float:
        return float(self.lengths[:len(col)][self._finite_rows(col)].sum())

    def failed_items(self, col) -> int:
        # a row that did not come back, or came back not finite where it is real
        return int(self.rows - len(col) + (~self._finite_rows(col)).sum())

    def _sample(self, col, n: int, always=()):
        """(row numbers, their logits) of n rows drawn from the seed, with
        `always` among them; a failed row is failed, not wrong: not kept."""
        idx = self._pick.choice(len(col), n, replace=False)
        idx = np.unique(np.concatenate([idx, np.asarray(always, np.int64)]))
        idx = idx[self._finite_rows(col)[idx]]
        cap, tags = int(self.traffic["cap"]), int(self.config["num_tags"])
        rows = np.empty((len(idx), cap, tags), np.float32)
        for k, i in enumerate(idx):
            rows[k] = col[i]
        return idx, rows

    def keep(self, col):
        self._last = col             # only the last call's rows are held whole
        # the longest row of the call among the first call's
        longest = [int(np.argmax(self.lengths[:len(col)]))] \
            if self._calls == 1 else []
        return self._sample(col, int(self.traffic["check_rows_per_call"]),
                            longest)

    def counters(self) -> Dict[str, Any]:
        st = self.fused.fusion_stats()
        records = [r for s in self._stats if s is not None for r in s.records]
        return {"ingest_records": records,
                "fallbacks_total": int(st["fallbacks_total"]),
                "program_cache_misses_in_window":
                    int(st["compile_cache"]["misses"]) - self._cache_misses_warm,
                # what the caller sent, and what the program shipped for it
                "real_tokens": self._calls * int(self.lengths.sum()),
                "padded_positions": sum(int(r.bytes_in) for r in records)
                // self.ids.itemsize}

    def free(self) -> None:
        self.fused = None
        self.df = self._warm_df = None
        self._stats.clear()

    def check(self, kept) -> List[Compared]:
        # the last call gives a larger sample; the whole array is dropped here
        samples = list(kept) + [self._sample(
            self._last, int(self.traffic["check_rows_last_call"]))]
        self._last = self._finite_of = self._finite = None
        idx = np.concatenate([i for i, _ in samples])
        if not len(idx):
            return []                # every sampled row failed: nothing compared
        got = np.concatenate([rows for _, rows in samples])
        need, at = np.unique(idx, return_inverse=True)
        ref = self.reference.tag(self.config, self.seed, self.ids[need])
        gaps = self.reference.row_gaps(got, ref[at], self.lengths[idx])
        print(f"logit_gap over {gaps.size} real positions of {len(idx)} rows: "
              f"widest {float(gaps.max())!r}, 99.9th percentile "
              f"{float(np.quantile(gaps, 0.999))!r}, mean {float(gaps.mean())!r}",
              file=sys.stderr)
        return [Compared("logit_gap", float(gaps.max()), LOGIT_GAP_LIMIT)]


def build(config, traffic, seed: int, chips: List[Any]) -> Subject:
    return Subject(config, traffic, seed, chips)
