"""Builds the system under test for the ResNet-50 featurize configuration:
the fused `PipelineModel([ImageTransformer.resize, ImageFeaturizer])` of the
program, given the benchmark's seeded weights and a DataFrame of image rows.

Traffic parameters read here: `batches_per_call`, `partitions`, `source_px`,
`check_rows_per_call`, `check_rows_last_call`.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmarks.harness import spec
from benchmarks.harness.check import Compared

# feature_gap on the chip at batch 2048 (PERF.md section 2): the program reads
# at most 0.0064 over a dozen seeds, the fp8 control at least 0.041 over three
FEATURE_GAP_LIMIT = 0.02


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, leaf in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


class Subject:
    def __init__(self, config, traffic, seed: int, chips: List[Any]):
        from mmlspark_tpu.core.dataframe import DataFrame
        from mmlspark_tpu.core.pipeline import PipelineModel
        from mmlspark_tpu.core.schema import ImageSchema
        from mmlspark_tpu.image.featurizer import ImageFeaturizer
        from mmlspark_tpu.image.stages import ImageTransformer
        from mmlspark_tpu.models.module import FunctionModel
        from mmlspark_tpu.models.resnet import build_resnet

        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.reference = spec.bench_module("references", config["reference"])
        size, ch = int(config["image_size"]), int(config["channels"])
        batch = int(config["assumed"]["batch_size"])
        self.rows = batch * int(traffic["batches_per_call"])
        self.items_per_call = self.rows
        px = int(traffic["source_px"])
        rng = np.random.default_rng(self.seed)
        self.images = rng.integers(0, 256, (self.rows, px, px, ch), dtype=np.uint8)
        col = np.empty(self.rows, dtype=object)
        for i in range(self.rows):
            col[i] = ImageSchema.make(self.images[i], f"img{i}")
        self.df = DataFrame.from_dict({"image": col},
                                      num_partitions=int(traffic["partitions"]))
        # one batch of the same rows: compiles and loads what a whole call runs
        self._warm_df = DataFrame.from_dict({"image": col[:batch]}, num_partitions=1)
        module = build_resnet(int(config["depth"]), int(config["num_classes"]),
                              size, ch, int(config["width"]))
        params = _nest(self.reference.make_weights(config, self.seed))
        model = FunctionModel(
            module=module, params=params, input_shape=(size, size, ch),
            layer_names=["fc", "avgpool", "layer4", "layer3", "layer2",
                         "layer1", "stem"], name=f"resnet{config['depth']}")
        self.fused = PipelineModel([
            ImageTransformer().resize(size, size),
            ImageFeaturizer(scaleFactor=1 / 255., batchSize=batch)
            .set_model(model)]).fuse()
        # the rows whose features are kept for the comparison: a fresh sample
        # from the seed for every call, and a larger one for the last
        self._pick = np.random.default_rng(self.seed + 1)
        self._stats: List[Any] = []
        self._cache_misses_warm = 0
        self._last = None

    def _features(self, out) -> np.ndarray:
        return np.stack([np.asarray(v, dtype=np.float32)
                         for v in out.column("features")])

    def warm(self) -> None:
        self._features(self.fused.transform(self._warm_df))
        self._cache_misses_warm = self.fused.fusion_stats()["compile_cache"]["misses"]

    def call(self):
        out = self.fused.transform(self.df)
        feats = self._features(out)
        self._stats.append(self.fused.last_ingest_stats)
        return feats

    def work(self, feats) -> float:
        return float(len(feats) - self.failed_items(feats))

    def failed_items(self, feats) -> int:
        # a row that did not come back, or came back not finite, failed
        missing = self.rows - len(feats)
        return int(missing + (~np.isfinite(feats).all(axis=1)).sum())

    def keep(self, feats):
        n = int(self.traffic["check_rows_per_call"])
        idx = np.sort(self._pick.choice(min(self.rows, len(feats)), n, replace=False))
        self._last = feats          # only the last call's rows are held whole
        return idx, feats[idx].copy()

    def counters(self) -> Dict[str, Any]:
        st = self.fused.fusion_stats()
        records = [r for s in self._stats if s is not None for r in s.records]
        return {"ingest_records": records,
                "fallbacks_total": int(st["fallbacks_total"]),
                "program_cache_misses_in_window":
                    int(st["compile_cache"]["misses"]) - self._cache_misses_warm}

    def free(self) -> None:
        self.fused = None
        self.df = self._warm_df = None
        self._stats.clear()

    def check(self, kept) -> List[Compared]:
        # the last call gives a larger sample; the whole arrays are dropped here
        n_last = int(self.traffic["check_rows_last_call"])
        idx_last = np.sort(self._pick.choice(len(self._last), n_last, replace=False))
        samples = list(kept) + [(idx_last, self._last[idx_last].copy())]
        self._last = None
        need = np.unique(np.concatenate([idx for idx, _ in samples]))
        ref = self.reference.featurize(self.config, self.seed, self.images[need])
        at = {int(r): k for k, r in enumerate(need)}
        gap = 0.0
        for idx, rows in samples:
            want = ref[[at[int(r)] for r in idx]]
            gap = max(gap, self.reference.feature_gap(rows, want))
        return [Compared("feature_gap", gap, FEATURE_GAP_LIMIT)]


def build(config, traffic, seed: int, chips: List[Any]) -> Subject:
    return Subject(config, traffic, seed, chips)
