"""Boosting engine: objectives, gbdt/rf/dart/goss variants, metrics, persistence.

LGBM_Booster* parity (the surface the reference drives, lightgbm/
LightGBMBooster.scala:21-148, TrainUtils.scala:134-233): iterate trees over
grad/hess of a pluggable objective, evaluate metrics per iteration, early-stop,
serialize to a model string, merge boosters (continued / multi-batch training),
single-row and batched prediction, feature importances.

Grad/hess computation and score updates are jitted; tree growth is tree.py.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core import faults as _faults
from ..obs.trace import close_span, current_batch, open_span, root_span
from ..parallel.mesh import fetch_global

from .binning import BinMapper
from .tree import GrowerConfig, Tree, build_thresholds, grow_tree

MODEL_FORMAT = "mmlspark_tpu.gbdt.v1"


@dataclasses.dataclass
class TrainParams:
    """Native-param-string equivalent (reference lightgbm/TrainParams.scala:1-117)."""

    # regression|regression_l1|quantile|binary|multiclass|lambdarank
    objective: str = "regression"
    boosting_type: str = "gbdt"            # gbdt|rf|dart|goss
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    max_bin: int = 255
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    bagging_seed: int = 3
    feature_fraction: float = 1.0
    feature_fraction_seed: int = 2
    early_stopping_round: int = 0
    num_class: int = 1
    alpha: float = 0.9                     # quantile / huber parameter
    drop_rate: float = 0.1                 # dart
    max_drop: int = 50                     # dart
    uniform_drop: bool = False             # dart
    top_rate: float = 0.2                  # goss
    other_rate: float = 0.1                # goss
    categorical_feature: Tuple[int, ...] = ()
    # categorical SET-split controls (LightGBM cat_smooth / cat_l2 /
    # max_cat_threshold defaults): sorted-by-gradient-statistic category
    # subsets, not ordered-int thresholds
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_threshold: int = 32
    # tree_learner parity (LightGBMParams.scala:13-18). Both values run the
    # exact psum'd-histogram algorithm: voting_parallel is LightGBM's lossy
    # bandwidth optimization for slow networks; exact histograms over ICI
    # strictly dominate (same or better splits at no extra cost here).
    parallelism: str = "data_parallel"
    max_delta_step: float = 0.0            # clamp |leaf value| (0 = off)
    pos_bagging_fraction: float = 1.0      # binary class-aware bagging
    neg_bagging_fraction: float = 1.0
    max_bin_by_feature: Tuple[int, ...] = ()
    # log the TRAIN metric every iteration (isProvideTrainingMetric,
    # TrainUtils.scala:194-230) — also when a validation set is present
    train_metric: bool = False
    metric: str = ""                       # default chosen by objective
    verbosity: int = -1
    seed: int = 0

    def to_string(self) -> str:
        """LightGBM-style 'key=value key=value' param string."""
        return " ".join(f"{k}={v}" for k, v in dataclasses.asdict(self).items())


# ---------------------------------------------------------------------------
# Objectives: per-row grad/hess of the loss wrt raw scores (jitted)
# ---------------------------------------------------------------------------


def _sigmoid(x):
    import jax.numpy as jnp

    return 1.0 / (1.0 + jnp.exp(-x))


def grad_hess(objective: str, scores, labels, weights=None, alpha: float = 0.9,
              groups=None, group_segments=None):
    """Returns (grad, hess) arrays, shape [N] (or [N,K] multiclass)."""
    import jax
    import jax.numpy as jnp

    if objective == "binary":
        p = _sigmoid(scores)
        g = p - labels
        h = jnp.maximum(p * (1.0 - p), 1e-16)
    elif objective == "multiclass":
        p = jax.nn.softmax(scores, axis=-1)
        y = jax.nn.one_hot(labels.astype(jnp.int32), scores.shape[-1])
        g = p - y
        h = jnp.maximum(2.0 * p * (1.0 - p), 1e-16)
    elif objective in ("regression", "regression_l2", "l2", "mean_squared_error"):
        g = scores - labels
        h = jnp.ones_like(scores)
    elif objective in ("regression_l1", "l1", "mae"):
        g = jnp.sign(scores - labels)
        h = jnp.ones_like(scores)
    elif objective == "quantile":
        diff = scores - labels
        g = jnp.where(diff >= 0, 1.0 - alpha, -alpha)
        h = jnp.ones_like(scores)
    elif objective == "huber":
        diff = scores - labels
        g = jnp.clip(diff, -alpha, alpha)
        h = jnp.ones_like(scores)
    elif objective == "poisson":
        g = jnp.exp(scores) - labels
        h = jnp.exp(scores)
    elif objective == "lambdarank":
        return _lambdarank_grad_hess(scores, labels, groups,
                                     segments=group_segments)
    else:
        raise ValueError(f"Unknown objective {objective!r}")
    if weights is not None:
        w = weights if g.ndim == 1 else weights[:, None]
        g, h = g * w, h * w
    return g, h


class GroupSegments:
    """Host-side segmentation of contiguous ``group_ids`` runs, bucketed by
    padded (power-of-two) group size. Computed once per dataset and reused
    every boosting iteration (the group layout never changes)."""

    __slots__ = ("n", "buckets")

    def __init__(self, n, buckets):
        self.n = n
        # buckets: list of (Gb, rows, loc_g, loc_slot, m) — see segment_groups
        self.buckets = buckets


def segment_groups(group_ids) -> GroupSegments:
    """Segment rows into contiguous groups and bucket groups by size class.

    Raises if a group id appears in two non-adjacent runs — that silently
    breaks pairwise ranking, so it must be an error (sort by group first).
    """
    gi = np.asarray(group_ids)
    n = len(gi)
    change = np.nonzero(gi[1:] != gi[:-1])[0] + 1
    starts = np.concatenate([[0], change]).astype(np.int64)
    counts = np.diff(np.concatenate([starts, [n]])).astype(np.int64)
    run_ids = gi[starts]
    if len(np.unique(run_ids)) != len(run_ids):
        raise ValueError(
            "lambdarank requires rows grouped contiguously by group id; a "
            "group id reappears after a different group — sort the dataset "
            "by the group column first")

    by_size: Dict[int, list] = {}
    for g in range(len(starts)):
        c = int(counts[g])
        gb = 1 if c <= 1 else 1 << int(np.ceil(np.log2(c)))
        by_size.setdefault(gb, []).append(g)

    import jax.numpy as jnp

    buckets = []
    for gb, glist in sorted(by_size.items()):
        bcounts = counts[glist]
        rows = np.concatenate(
            [np.arange(starts[g], starts[g] + counts[g]) for g in glist])
        loc_g = np.repeat(np.arange(len(glist), dtype=np.int64), bcounts)
        loc_slot = np.concatenate(
            [np.arange(c, dtype=np.int64) for c in bcounts])
        # store as device arrays: the layout is static across boosting, so the
        # H2D upload of the index arrays happens once, not per iteration
        buckets.append((gb, jnp.asarray(rows, dtype=jnp.int32),
                        jnp.asarray(loc_g, dtype=jnp.int32),
                        jnp.asarray(loc_slot, dtype=jnp.int32), len(glist)))
    return GroupSegments(n, buckets)


# Bound on pairwise-tensor elements materialized at once (f32 [chunk, Gb, Gb];
# 2**24 elements = 64 MB per tensor, ~6 live tensors => a few hundred MB peak).
_LAMBDARANK_PAIR_BUDGET = 1 << 24


@functools.partial(
    __import__("jax").jit, static_argnames=("sigma",))
def _lambdarank_bucket(s_pad, l_pad, valid, sigma: float = 1.0):
    """Pairwise LambdaRank lambdas for one [m, G] padded bucket of groups."""
    import jax.numpy as jnp

    m, G = s_pad.shape
    gains = jnp.where(valid, 2.0 ** l_pad - 1.0, 0.0)
    # within-group rank by current score (invalid slots sort last: score -inf)
    order = jnp.argsort(-s_pad, axis=1)
    rank_of = jnp.zeros((m, G), dtype=jnp.int32)
    rank_of = rank_of.at[jnp.arange(m)[:, None], order].set(
        jnp.broadcast_to(jnp.arange(G, dtype=jnp.int32), (m, G)))
    disc = 1.0 / jnp.log2(rank_of.astype(jnp.float32) + 2.0)
    # ideal DCG per group (labels sorted descending)
    ideal_gains = jnp.sort(gains, axis=1)[:, ::-1]
    idcg = jnp.sum(ideal_gains / jnp.log2(jnp.arange(G, dtype=jnp.float32) + 2.0),
                   axis=1, keepdims=True)
    inv_idcg = jnp.where(idcg > 0, 1.0 / idcg, 0.0)[..., None]

    pair_ok = valid[:, :, None] & valid[:, None, :]
    better = (l_pad[:, :, None] > l_pad[:, None, :]) & pair_ok
    s_diff = jnp.where(pair_ok, s_pad[:, :, None] - s_pad[:, None, :], 0.0)
    rho = 1.0 / (1.0 + jnp.exp(sigma * s_diff))      # P(i beats j but doesn't)
    delta = jnp.abs((gains[:, :, None] - gains[:, None, :])
                    * (disc[:, :, None] - disc[:, None, :])) * inv_idcg
    lam = jnp.where(better, -sigma * rho * delta, 0.0)
    h_pair = jnp.where(better, sigma * sigma * rho * (1 - rho) * delta, 0.0)
    g_pad = jnp.sum(lam, axis=2) - jnp.sum(lam, axis=1)
    h_pad = jnp.sum(h_pair, axis=2) + jnp.sum(h_pair, axis=1)
    return g_pad, h_pad


def _lambdarank_grad_hess(scores, labels, group_ids, sigma: float = 1.0,
                          segments: Optional[GroupSegments] = None):
    """Pairwise LambdaRank with |ΔNDCG| weighting (LightGBM semantics).

    Groups (contiguous ``group_ids`` runs) are bucketed by power-of-two padded
    size, so a few singleton-heavy queries never inflate the padding of the
    rest; within a bucket the [m, G, G] pairwise tensors are materialized at
    most ``_LAMBDARANK_PAIR_BUDGET`` elements at a time (lax.map over group
    chunks), bounding memory at O(chunk * G^2) regardless of dataset size.
    """
    import jax
    import jax.numpy as jnp

    seg = segments if segments is not None else segment_groups(group_ids)
    n = seg.n
    g_out = jnp.zeros(n, dtype=jnp.float32)
    h_out = jnp.full(n, 1e-16, dtype=jnp.float32)

    for gb, rows, loc_g, loc_slot, m in seg.buckets:
        if gb <= 1:
            continue  # singleton groups: no pairs, keep (0, 1e-16)
        chunk = max(1, min(m, _LAMBDARANK_PAIR_BUDGET // (gb * gb)))
        m_pad = (m + chunk - 1) // chunk * chunk
        s_pad = jnp.full((m_pad, gb), -jnp.inf, dtype=jnp.float32)
        l_pad = jnp.zeros((m_pad, gb), dtype=jnp.float32)
        valid = jnp.zeros((m_pad, gb), dtype=bool)
        s_pad = s_pad.at[loc_g, loc_slot].set(scores[rows])
        l_pad = l_pad.at[loc_g, loc_slot].set(labels[rows])
        valid = valid.at[loc_g, loc_slot].set(True)

        nchunks = m_pad // chunk
        if nchunks == 1:
            g_pad, h_pad = _lambdarank_bucket(s_pad, l_pad, valid, sigma)
        else:
            g_pad, h_pad = jax.lax.map(
                lambda t: _lambdarank_bucket(t[0], t[1], t[2] > 0, sigma),
                (s_pad.reshape(nchunks, chunk, gb),
                 l_pad.reshape(nchunks, chunk, gb),
                 valid.reshape(nchunks, chunk, gb).astype(jnp.int8)))
            g_pad = g_pad.reshape(m_pad, gb)
            h_pad = h_pad.reshape(m_pad, gb)
        g_out = g_out.at[rows].set(g_pad[loc_g, loc_slot])
        h_out = h_out.at[rows].set(
            jnp.maximum(h_pad[loc_g, loc_slot], 1e-16))
    return g_out, h_out


def init_score(objective: str, labels: np.ndarray, num_class: int = 1,
               alpha: float = 0.9) -> np.ndarray:
    """Base score before the first tree (BoostFromAverage parity).
    ``alpha`` is the quantile level for the quantile objective."""
    if objective == "binary":
        p = np.clip(labels.mean(), 1e-12, 1 - 1e-12)
        return np.full(1, np.log(p / (1 - p)), dtype=np.float64)
    if objective == "multiclass":
        out = np.zeros(num_class, dtype=np.float64)
        for k in range(num_class):
            p = np.clip((labels == k).mean(), 1e-12, 1 - 1e-12)
            out[k] = np.log(p)
        return out
    if objective in ("regression", "regression_l2", "l2", "huber",
                     "mean_squared_error"):
        return np.full(1, labels.mean(), dtype=np.float64)
    if objective in ("regression_l1", "l1", "mae"):
        return np.full(1, np.median(labels), dtype=np.float64)
    if objective == "quantile":
        return np.full(1, np.quantile(labels, alpha), dtype=np.float64)
    if objective == "poisson":
        return np.full(1, np.log(max(labels.mean(), 1e-12)), dtype=np.float64)
    return np.zeros(1, dtype=np.float64)


# ---------------------------------------------------------------------------
# Metrics (per-iteration eval + early stopping; TrainUtils.scala:194-230)
# ---------------------------------------------------------------------------


def eval_metric(metric: str, scores: np.ndarray, labels: np.ndarray,
                groups: Optional[np.ndarray] = None) -> float:
    eps = 1e-15
    if metric == "binary_logloss":
        p = np.clip(1 / (1 + np.exp(-scores)), eps, 1 - eps)
        return float(-np.mean(labels * np.log(p) + (1 - labels) * np.log(1 - p)))
    if metric == "binary_error":
        return float(np.mean((scores > 0) != (labels > 0.5)))
    if metric == "auc":
        order = np.argsort(scores)
        ranks = np.empty(len(scores))
        ranks[order] = np.arange(1, len(scores) + 1)
        # average ranks for ties
        for v in np.unique(scores):
            m = scores == v
            if m.sum() > 1:
                ranks[m] = ranks[m].mean()
        pos = labels > 0.5
        n_pos, n_neg = pos.sum(), (~pos).sum()
        if n_pos == 0 or n_neg == 0:
            return 0.5
        return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))
    if metric == "multi_logloss":
        e = np.exp(scores - scores.max(axis=1, keepdims=True))
        p = np.clip(e / e.sum(axis=1, keepdims=True), eps, None)
        return float(-np.mean(np.log(p[np.arange(len(labels)),
                                       labels.astype(np.int64)])))
    if metric == "multi_error":
        return float(np.mean(np.argmax(scores, axis=1) != labels))
    if metric in ("l2", "mse"):
        return float(np.mean((scores - labels) ** 2))
    if metric == "rmse":
        return float(np.sqrt(np.mean((scores - labels) ** 2)))
    if metric in ("l1", "mae"):
        return float(np.mean(np.abs(scores - labels)))
    if metric == "ndcg":
        return _ndcg(scores, labels, groups)
    raise ValueError(f"Unknown metric {metric!r}")


def _ndcg(scores, labels, groups, k: int = 10) -> float:
    if groups is None:
        groups = np.zeros(len(scores), dtype=np.int64)
    vals = []
    for gid in np.unique(groups):
        m = groups == gid
        s, l = scores[m], labels[m]
        order = np.argsort(-s)[:k]
        dcg = np.sum((2 ** l[order] - 1) / np.log2(np.arange(len(order)) + 2))
        ideal = np.argsort(-l)[:k]
        idcg = np.sum((2 ** l[ideal] - 1) / np.log2(np.arange(len(ideal)) + 2))
        vals.append(dcg / idcg if idcg > 0 else 1.0)
    return float(np.mean(vals)) if vals else 1.0


_HIGHER_BETTER = {"auc", "ndcg"}


def default_metric(objective: str) -> str:
    return {
        "binary": "binary_logloss",
        "multiclass": "multi_logloss",
        "lambdarank": "ndcg",
        "regression_l1": "l1",
        "l1": "l1",
        "mae": "l1",
        "quantile": "l1",
    }.get(objective, "l2")


# ---------------------------------------------------------------------------
# Booster
# ---------------------------------------------------------------------------


class Booster:
    """Trained model: bin mapper + tree ensemble + objective metadata."""

    def __init__(self, params: TrainParams, bin_mapper: Optional[BinMapper],
                 trees: Optional[List[List[Tree]]] = None,
                 base_score: Optional[np.ndarray] = None,
                 best_iteration: int = -1):
        self.params = params
        self.bin_mapper = bin_mapper
        # trees[i][k]: iteration i, class k (num_class=1 => k=0)
        self.trees: List[List[Tree]] = trees or []
        self.base_score = (base_score if base_score is not None
                           else np.zeros(max(params.num_class, 1)))
        self.best_iteration = best_iteration

    # -- prediction ------------------------------------------------------
    def raw_predict(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        """[N,F] raw features -> [N] or [N,K] raw scores."""
        from .predict import predict_ensemble

        n_iter = num_iteration if num_iteration > 0 else (
            self.best_iteration if self.best_iteration > 0 else len(self.trees))
        n_iter = min(n_iter, len(self.trees))
        k = max(self.params.num_class, 1)
        scores = np.tile(self.base_score, (X.shape[0], 1)).astype(np.float64)
        if n_iter > 0:
            scores += predict_ensemble(
                [self.trees[i] for i in range(n_iter)], X, k)
        return scores[:, 0] if k == 1 else scores

    def predict_proba(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        raw = self.raw_predict(X, num_iteration)
        if self.params.objective == "binary":
            p = 1 / (1 + np.exp(-raw))
            return np.stack([1 - p, p], axis=1)
        if self.params.objective == "multiclass":
            e = np.exp(raw - raw.max(axis=1, keepdims=True))
            return e / e.sum(axis=1, keepdims=True)
        return raw

    # -- introspection (LightGBMBooster.scala feature importance parity) --
    def feature_importances(self, importance_type: str = "split") -> np.ndarray:
        num_f = self.bin_mapper.num_features if self.bin_mapper else 0
        imp = np.zeros(num_f, dtype=np.float64)
        for group in self.trees:
            for tree in group:
                internal = tree.feature >= 0
                if importance_type == "gain":
                    np.add.at(imp, tree.feature[internal], tree.gain[internal])
                else:
                    np.add.at(imp, tree.feature[internal], 1.0)
        return imp

    @property
    def num_total_model(self) -> int:
        return sum(len(g) for g in self.trees)

    # -- persistence (saveNativeModel / LGBM_BoosterMerge parity) ---------
    def to_string(self) -> str:
        return json.dumps({
            "format": MODEL_FORMAT,
            "params": dataclasses.asdict(self.params),
            "base_score": self.base_score.tolist(),
            "best_iteration": self.best_iteration,
            "bin_mapper": self.bin_mapper.to_json() if self.bin_mapper else None,
            "trees": [[t.to_dict() for t in group] for group in self.trees],
        })

    @staticmethod
    def from_string(s: str) -> "Booster":
        d = json.loads(s)
        if d.get("format") != MODEL_FORMAT:
            # explicit check (a bare assert vanishes under `python -O` and a
            # foreign payload would then explode deep inside TrainParams)
            raise ValueError(f"bad model format {d.get('format')!r}; "
                             f"expected {MODEL_FORMAT!r}")
        p = d["params"]
        p["categorical_feature"] = tuple(p.get("categorical_feature", ()))
        p["max_bin_by_feature"] = tuple(p.get("max_bin_by_feature", ()))
        params = TrainParams(**p)
        return Booster(
            params,
            BinMapper.from_json(d["bin_mapper"]) if d["bin_mapper"] else None,
            trees=[[Tree.from_dict(t) for t in group] for group in d["trees"]],
            base_score=np.asarray(d["base_score"], dtype=np.float64),
            best_iteration=d.get("best_iteration", -1),
        )

    def merge(self, other: "Booster") -> "Booster":
        """Append another booster's trees (LGBM_BoosterMerge — multi-batch/continued
        training, LightGBMBase.scala:26-39)."""
        return Booster(self.params, self.bin_mapper or other.bin_mapper,
                       trees=self.trees + other.trees,
                       base_score=self.base_score,
                       best_iteration=-1)


# ---------------------------------------------------------------------------
# Whole-run fused training (one dispatch for ALL boosting iterations)
# ---------------------------------------------------------------------------

# Precomputed bagging-mask budget for the scan path: [iters, N] bool uploaded
# once. Above this, fall back to the per-tree loop.
_SCAN_MASK_BUDGET = 1 << 28


def _now() -> float:
    return time.perf_counter()


@functools.partial(__import__("jax").jit, donate_argnums=(0,))
def _widen_bins(b):
    """uint8 bins -> device-resident int32 (donated: the u8 copy is freed).
    Keeps every downstream kernel on the int32 layout it was built for while
    the host->device transfer ships 1/4 the bytes."""
    import jax.numpy as jnp

    return b.astype(jnp.int32)


def _scan_train_ok(params: TrainParams, objective: str, valid, log,
                   shard_put, checkpoint=None) -> bool:
    """Can this run take the whole-training-in-one-dispatch lax.scan path?

    The scan path removes EVERY per-iteration host round trip (the per-tree
    fused grower still paid one dispatch + one fetch per tree — ~4 blocking
    round trips per iteration end to end). Exclusions: dart (host-side tree
    drop/re-add), lambdarank (grouped grad), validation/early-stopping +
    per-iteration logging (host eval), and sharded inputs (the per-tree
    shard_map grower handles those). GOSS runs in-scan with on-device
    gradient-threshold selection + row compaction (see _train_scan) — the
    sampling is the point of GOSS (LightGBM's speed feature,
    GossStrategy in the reference's underlying engine), so the compacted
    histogram stream is where the row-reduction actually buys time.
    """
    import jax

    if os.environ.get("MMLSPARK_TPU_NO_SCAN_TRAIN", "") not in ("", "0"):
        return False
    if params.boosting_type == "dart":
        return False
    if objective == "lambdarank":
        return False
    if valid is not None or log is not None or params.train_metric:
        return False
    if shard_put is not None:
        return False
    if checkpoint is not None:
        # iteration-level checkpointing needs a per-iteration host boundary;
        # the whole-run scan has none
        return False
    max_nodes = 2 * params.num_leaves - 1
    if max_nodes < 3:
        return False  # num_leaves=1: nothing to grow
    forced = os.environ.get("MMLSPARK_TPU_SCAN_TRAIN", "") not in ("", "0")
    if not forced and jax.default_backend() == "cpu":
        # CPU in-process dispatch is cheap; the host loop keeps exact-f64
        # score accumulation there
        return False
    return True


def _scan_precompute_masks(params: TrainParams, rng, n: int, num_f: int,
                           y: np.ndarray, is_rf: bool):
    """Replicate the host loop's per-iteration RNG draws (bagging mask, then
    feature mask — same order, same generator) for all iterations up front.
    Returns (row_masks [iters,N]|None, feat_masks [iters,F]|None, ok)."""
    iters = params.num_iterations
    bag_cond = ((params.bagging_fraction < 1.0
                 or params.pos_bagging_fraction < 1.0
                 or params.neg_bagging_fraction < 1.0)
                and (is_rf or params.bagging_freq > 0)
                # goss overrides bagging (host-path / LightGBM semantics:
                # the goss selection IS the row mask)
                and params.boosting_type != "goss")
    use_feat = params.feature_fraction < 1.0
    if bag_cond and iters * n > _SCAN_MASK_BUDGET:
        return None, None, False
    row_masks = np.empty((iters, n), dtype=bool) if bag_cond else None
    feat_masks = np.empty((iters, num_f), dtype=bool) if use_feat else None
    bag = np.ones(n, dtype=bool)
    for it in range(iters):
        if bag_cond and it % max(params.bagging_freq, 1) == 0:
            if (params.pos_bagging_fraction < 1.0
                    or params.neg_bagging_fraction < 1.0):
                pos = y > 0.5
                frac = np.where(pos, params.pos_bagging_fraction,
                                params.neg_bagging_fraction)
                bag = rng.random(n) < frac
            else:
                bag = rng.random(n) < params.bagging_fraction
        if bag_cond:
            row_masks[it] = bag
        if use_feat:
            m = np.zeros(num_f, dtype=bool)
            n_feat = max(1, int(num_f * params.feature_fraction))
            m[rng.choice(num_f, size=n_feat, replace=False)] = True
            feat_masks[it] = m
    return row_masks, feat_masks, True


def _train_scan(params: TrainParams, config: GrowerConfig, booster: "Booster",
                mapper: BinMapper, bins_dev, labels, w_dev,
                scores: np.ndarray, n: int, num_f: int, num_bins: int,
                k: int, lr: float, row_masks, feat_masks,
                pad_mask: Optional[np.ndarray] = None,
                cat_args=None) -> None:
    """Run ALL boosting iterations in ONE jitted lax.scan dispatch.

    Each scan step: grad/hess from the running scores, whole-tree growth via
    the fused while_loop grower (Pallas MXU histograms on TPU), on-device f32
    leaf values feeding a Kahan-compensated score update. The stacked tree
    arrays come back in a single fetch; leaf values of the SAVED trees are
    recomputed on host in f64 from the fetched (grad, hess, count) sums —
    the same precision lineage as the per-tree path. The running f32 score
    update uses device-f32 leaf values, so late-tree splits can differ from
    the per-tree path by float rounding (predictions agree to ~1e-5; the
    per-tree path remains available via MMLSPARK_TPU_NO_SCAN_TRAIN=1).

    Replaces ~4 blocking round trips per boosting iteration with one dispatch
    + one fetch for the whole run (the reference's LGBM_BoosterUpdateOneIter
    loop is likewise in-process once entered, TrainUtils.scala:170-233).
    """
    import jax
    import jax.numpy as jnp

    from . import pallas_hist
    from .tree import _grow_tree_device_body

    iters = params.num_iterations
    M = 2 * params.num_leaves - 1
    # same interpret plumbing as tree._grow_tree_device: CPU tests exercise
    # the Pallas kernels (histogram + tier select) in interpreter mode
    interpret = pallas_hist.interpret_mode()
    use_mxu = pallas_hist.use_pallas() or interpret
    objective = params.objective
    alpha = params.alpha

    l1 = np.float32(config.lambda_l1)
    l2 = np.float32(config.lambda_l2)
    msh = np.float32(config.min_sum_hessian_in_leaf)
    mgs = np.float32(config.min_gain_to_split)
    has_fm = feat_masks is not None
    fm_dummy = jnp.zeros(0, dtype=bool)
    if pad_mask is not None and not pad_mask.all():
        if row_masks is not None:
            row_masks = row_masks & pad_mask[None, :]
        ones_mask = jnp.asarray(pad_mask)
    else:
        ones_mask = jnp.ones(n, dtype=bool)
    shrink = np.float32(lr)

    # ----- in-scan GOSS: the histogram kernel streams ~2 MXU cycles per
    # row*feature regardless of masking, so a masked goss subset saves
    # nothing — the win comes from COMPACTING the tree's rows to the
    # selected ~(top_rate+other_rate) fraction at the root, shrinking every
    # histogram/partition pass of the whole tree. Selection is on device
    # and EXACT-COUNT (_exact_topk_mask: bitwise bisection with index
    # tie-break — LightGBM's sorted-GOSS count semantics): exactly top_n
    # |grad| rows plus exactly other_n uniform draws among the rest,
    # amplified by (1-a)/b like the host path, gathered into a
    # static-capacity buffer that by construction can never overflow (the
    # pre-r4 >=-threshold mask truncated in row order on gradient ties).
    # Full-row score routing is recovered by replaying the grown tree's
    # splits over all N rows.
    is_goss = params.boosting_type == "goss"
    if is_goss:
        n_real = int(pad_mask.sum()) if pad_mask is not None else n
        top_n = int(n_real * params.top_rate)
        other_n = int(n_real * params.other_rate)
        sel_budget = max(top_n + other_n, 1)
        goss_cap = min(n, -(-(sel_budget + max(256, sel_budget // 16)) // 512)
                       * 512)
        goss_amp = np.float32((1.0 - params.top_rate)
                              / max(params.other_rate, 1e-12))
        goss_keys = jax.random.split(
            jax.random.PRNGKey(params.seed or params.bagging_seed), iters)

    # ----- bagging/rf row compaction: the same economics as GOSS — the
    # histogram kernel streams ~2 MXU cycles per row*feature regardless of
    # masking, so when host-precomputed bagging masks select a fraction of
    # rows, gathering them to the buffer front shrinks every histogram and
    # partition pass of the whole tree. The capacity is exact on the host
    # (masks are precomputed); full-row score routing is recovered by the
    # same split replay GOSS uses. Gated to a selected fraction <= 0.625
    # (above that, the per-iteration gather + replay eats the stream
    # savings) at real scale; MMLSPARK_TPU_DENSE_BAG_COMPACT=1 forces
    # (tests), MMLSPARK_TPU_NO_DENSE_BAG_COMPACT=1 kills.
    bag_cap = 0
    if (row_masks is not None and not is_goss
            and os.environ.get("MMLSPARK_TPU_NO_DENSE_BAG_COMPACT",
                               "") in ("", "0")):
        forced = os.environ.get("MMLSPARK_TPU_DENSE_BAG_COMPACT",
                                "") not in ("", "0")
        nr = int(pad_mask.sum()) if pad_mask is not None else n
        # cheap gates first: the mask reduction scans up to iters x n bools
        if forced or (jax.default_backend() == "tpu" and nr >= 100_000):
            max_cnt = int(row_masks.sum(axis=1).max())
            if forced or max_cnt / max(nr, 1) <= 0.625:
                bag_cap = min(n, -(-max(max_cnt, 1) // 512) * 512)

    from . import histogram as H

    def _route_full(tree_out):
        """Route ALL n rows through the grown tree (children have larger ids
        than parents, so one in-order replay of the split records is a full
        traversal)."""
        feat = tree_out["feature"]
        tb = tree_out["threshold_bin"]
        dl_ = tree_out["default_left"]
        li = tree_out["left"]
        ri = tree_out["right"]
        cwords = tree_out.get("cat_words")

        def rb(j, nor):
            f = feat[j]
            binrow = jax.lax.dynamic_index_in_dim(
                bins_dev, jnp.maximum(f, 0), axis=0, keepdims=False)
            if cwords is not None:
                new = H.partition_rows_cat(binrow, nor, j, tb[j], dl_[j],
                                           li[j], ri[j], cwords[j])
            else:
                new = H.partition_rows(binrow, nor, j, tb[j], dl_[j], li[j],
                                       ri[j])
            return jnp.where(f >= 0, new, nor)

        return jax.lax.fori_loop(0, tree_out["n_nodes"], rb,
                                 jnp.zeros(n, jnp.int32))

    def body(carry, xs):
        score, comp = carry
        row_mask = xs["rm"] if row_masks is not None else ones_mask
        fmask = xs["fm"] if has_fm else fm_dummy
        g, h = grad_hess(objective, score, labels, w_dev, alpha)
        if is_goss:
            from .sparse import _exact_topk_mask

            g_sel = jnp.abs(g) if g.ndim == 1 else jnp.sum(jnp.abs(g), axis=1)
            not_real = ~ones_mask if pad_mask is not None else None
            is_top = _exact_topk_mask(g_sel, top_n, n, exclude=not_real)
            u = jax.random.uniform(xs["gk"], (n,))
            excl_other = (is_top if not_real is None
                          else (is_top | not_real))
            sel = is_top | _exact_topk_mask(u, other_n, n,
                                            exclude=excl_other)
            amp = jnp.where(is_top, jnp.float32(1.0), goss_amp)
            idx = jnp.nonzero(sel, size=goss_cap, fill_value=0)[0]
            sel_cnt = jnp.sum(sel, dtype=jnp.int32)  # <= goss_cap always
            mask_it = jnp.arange(goss_cap, dtype=jnp.int32) < sel_cnt
            bins_it = jnp.take(bins_dev, idx, axis=1)
            amp_c = jnp.take(amp, idx)
            nor0 = jnp.zeros(goss_cap, jnp.int32)
        elif bag_cap:
            idx = jnp.nonzero(row_mask, size=bag_cap, fill_value=0)[0]
            sel_cnt = jnp.sum(row_mask, dtype=jnp.int32)  # <= bag_cap
            mask_it = jnp.arange(bag_cap, dtype=jnp.int32) < sel_cnt
            bins_it = jnp.take(bins_dev, idx, axis=1)
            nor0 = jnp.zeros(bag_cap, jnp.int32)
        else:
            bins_it, mask_it = bins_dev, row_mask
            nor0 = jnp.zeros(n, jnp.int32)
        outs = []
        for kk in range(k):
            gk = g if g.ndim == 1 else g[:, kk]
            hk = h if h.ndim == 1 else h[:, kk]
            if is_goss:
                gk = jnp.take(gk, idx) * amp_c
                hk = jnp.take(hk, idx) * amp_c
            elif bag_cap:
                gk = jnp.take(gk, idx)
                hk = jnp.take(hk, idx)
            out = _grow_tree_device_body(
                bins_it, gk, hk, mask_it, nor0,
                l1, l2, msh, mgs, fmask,
                num_bins=num_bins, max_nodes=M,
                min_data_in_leaf=config.min_data_in_leaf,
                max_depth=config.max_depth, use_mxu=use_mxu,
                has_feature_mask=has_fm, interpret=interpret,
                cat_args=cat_args)
            rows = out.pop("node_of_row")
            if is_goss or bag_cap:
                rows = _route_full(out)
            sums, feat = out["sums"], out["feature"]
            g_thr = jnp.sign(sums[:, 0]) * jnp.maximum(
                jnp.abs(sums[:, 0]) - l1, 0.0)
            val = jnp.where(feat < 0, -g_thr / (sums[:, 1] + l2), 0.0)
            if config.max_delta_step > 0:
                val = jnp.clip(val, -config.max_delta_step,
                               config.max_delta_step)
            # host-path parity: an unsplit root keeps value 0
            val = val.at[0].set(jnp.where(out["n_nodes"] > 1, val[0], 0.0))
            upd = (val * shrink)[rows]
            if k == 1:
                y_ = upd + comp
                t_ = score + y_
                score, comp = t_, y_ - (t_ - score)
            else:
                s_col, c_col = score[:, kk], comp[:, kk]
                y_ = upd + c_col
                t_ = s_col + y_
                score = score.at[:, kk].set(t_)
                comp = comp.at[:, kk].set(y_ - (t_ - s_col))
            outs.append(out)
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *outs)  # [k, ...]
        return (score, comp), stacked

    score0 = jnp.asarray(scores[:, 0] if k == 1 else scores, dtype=jnp.float32)
    comp0 = jnp.zeros_like(score0)
    xs = None
    if row_masks is not None or has_fm or is_goss:
        xs = {}
        if row_masks is not None:
            xs["rm"] = jnp.asarray(row_masks)
        if has_fm:
            xs["fm"] = jnp.asarray(feat_masks)
        if is_goss:
            xs["gk"] = goss_keys
    # the fit's phases are spans under the call's root (obs/trace.py): the
    # scan whole, one child a chunk with its fetch inside, the host tree build
    obs = current_batch()
    scan_obs = open_span(obs)
    w0, t0 = time.time(), _now()

    # Chunk the scan: bound row*iteration work (and the stacked per-tree
    # outputs) per dispatch; the (score, comp) carry stays device-resident
    # across chunks, so the host cost is one small fetch per chunk. The
    # 6e7 row-iter default dates from an earlier installation whose worker
    # restarted after ~40-60 s of continuous execution (earlier claim, not
    # measured in this round).
    budget = int(os.environ.get("MMLSPARK_TPU_SCAN_CHUNK_ROWS", str(6 * 10**7)))
    ipc = max(1, min(iters, budget // max(n, 1)))
    n_chunks = -(-iters // ipc)

    carry = (score0, comp0)
    host_chunks = []
    done = 0
    while done < iters:
        # EVERY chunk runs the same static length (one compiled program): a
        # short final chunk overgrows up to ipc-1 surplus trees (same xs rows
        # repeated) that are simply dropped below — one tree of wasted
        # compute beats a second multi-second XLA compile
        chunk_obs = open_span(scan_obs)
        wc, tc = time.time(), _now()
        xs_c = None
        if xs is not None:
            idx = np.minimum(np.arange(done, done + ipc), iters - 1)
            xs_c = {k: v[idx] for k, v in xs.items()}
        carry, ys = jax.lax.scan(body, carry, xs_c, length=ipc)
        wf, tf = time.time(), _now()
        host_chunks.append(fetch_global(ys))
        if chunk_obs is not None:
            chunk_obs[0].record_batch("gbdt:fetch", chunk_obs[1], wf,
                                      _now() - tf)
            close_span(chunk_obs, "gbdt:scan_chunk", wc, _now() - tc,
                       rows=n, iterations=ipc)
        done += ipc
    host = jax.tree.map(lambda *c: np.concatenate(c, axis=0), *host_chunks) \
        if len(host_chunks) > 1 else host_chunks[0]
    host = jax.tree.map(lambda a: a[:iters], host)
    close_span(scan_obs, "gbdt:scan", w0, _now() - t0, chunks=n_chunks,
               iterations=iters)
    w0, t0 = time.time(), _now()

    for it in range(iters):
        group: List[Tree] = []
        for kk in range(k):
            nn = int(host["n_nodes"][it, kk])
            feature = host["feature"][it, kk][:nn].astype(np.int32)
            tbin = host["threshold_bin"][it, kk][:nn].astype(np.int32)
            sums = host["sums"][it, kk][:nn].astype(np.float64)
            g_thr = np.sign(sums[:, 0]) * np.maximum(
                np.abs(sums[:, 0]) - config.lambda_l1, 0.0)
            value = np.where(feature < 0,
                             -g_thr / (sums[:, 1] + config.lambda_l2), 0.0)
            if config.max_delta_step > 0:
                value = np.clip(value, -config.max_delta_step,
                                config.max_delta_step)
            value[0] = 0.0 if nn == 1 else value[0]
            cat_sets = cat_words_np = None
            if "cat_words" in host:
                from .tree import cat_sets_from_words

                cat_sets, cat_words_np = cat_sets_from_words(
                    host["cat_words"][it, kk][:nn], feature, mapper)
            threshold = build_thresholds(feature, tbin, cat_sets, mapper)
            group.append(Tree(
                feature=feature,
                threshold=threshold,
                threshold_bin=tbin,
                default_left=host["default_left"][it, kk][:nn].astype(bool),
                left=host["left"][it, kk][:nn].astype(np.int32),
                right=host["right"][it, kk][:nn].astype(np.int32),
                value=value,
                gain=host["gain"][it, kk][:nn].astype(np.float32),
                count=sums[:, 2].astype(np.int32),
                shrinkage=lr,
                weight=sums[:, 1],
                cat_sets=cat_sets,
                cat_bin_words=cat_words_np,
            ))
        booster.trees.append(group)
    if obs is not None:
        obs[0].record_batch("gbdt:trees", obs[1], w0, _now() - t0,
                            trees=iters * k)


# ---------------------------------------------------------------------------
# Training loop
# ---------------------------------------------------------------------------


def _grad_hess_np(objective: str, scores: np.ndarray, labels: np.ndarray,
                  weights: Optional[np.ndarray], alpha: float):
    """Host mirror of grad_hess (same formulas; f32 like the device path —
    the grower consumes f32 anyway; lambdarank is device-only and gated out
    of the native path)."""
    scores = scores.astype(np.float32)
    labels = labels.astype(np.float32)
    if objective == "binary":
        p = 1.0 / (1.0 + np.exp(-scores))
        g = p - labels
        h = np.maximum(p * (1.0 - p), 1e-16)
    elif objective == "multiclass":
        m = scores - scores.max(axis=-1, keepdims=True)
        e = np.exp(m)
        p = e / e.sum(axis=-1, keepdims=True)
        yh = np.zeros_like(p)
        li = labels.astype(np.int64)
        # out-of-range labels get a zero one-hot row (jax.nn.one_hot
        # semantics — the device engine accepts them; fancy indexing
        # would crash or wrap)
        ok = (li >= 0) & (li < p.shape[-1])
        yh[np.nonzero(ok)[0], li[ok]] = 1.0
        g = p - yh
        h = np.maximum(2.0 * p * (1.0 - p), 1e-16)
    elif objective in ("regression", "regression_l2", "l2",
                       "mean_squared_error"):
        g = scores - labels
        h = np.ones_like(scores)
    elif objective in ("regression_l1", "l1", "mae"):
        g = np.sign(scores - labels)
        h = np.ones_like(scores)
    elif objective == "quantile":
        diff = scores - labels
        g = np.where(diff >= 0, 1.0 - alpha, -alpha)
        h = np.ones_like(scores)
    elif objective == "huber":
        g = np.clip(scores - labels, -alpha, alpha)
        h = np.ones_like(scores)
    elif objective == "poisson":
        g = np.exp(scores) - labels
        h = np.exp(scores)
    else:
        raise ValueError(f"Unknown objective {objective!r}")
    if weights is not None:
        w = np.asarray(weights, dtype=np.float32)
        w = w if g.ndim == 1 else w[:, None]
        g, h = g * w, h * w
    return g, h


_NATIVE_PATH_FORCING_ENVS = (
    # envs that force a specific XLA training path: honoring them means the
    # native host engine must stand aside (tests pin paths this way;
    # NO_SCAN_TRAIN explicitly selects the XLA host loop, not this engine)
    "MMLSPARK_TPU_SCAN_TRAIN", "MMLSPARK_TPU_NO_SCAN_TRAIN",
    "MMLSPARK_TPU_FUSED_TREE", "MMLSPARK_TPU_NO_FUSED_TREE",
    "MMLSPARK_TPU_HIST_EXACT")


def _native_train_ok(params: TrainParams, n: int) -> bool:
    """Route this fit to the native C++ host grower?

    The reference's engine is LightGBM's C++ core (TrainUtils.scala:170-233);
    this is its small-N equivalent: below ~MMLSPARK_TPU_NATIVE_TRAIN_MAX
    row*iteration*class work the per-dispatch overhead of any accelerator
    exceeds what one host core does outright (a small fit on the device
    is bound by its dispatches, not by its arithmetic). Large fits keep the
    whole-run lax.scan device path. MMLSPARK_TPU_NATIVE_TRAIN=1 forces,
    =0 disables."""
    env = os.environ.get("MMLSPARK_TPU_NATIVE_TRAIN", "")
    if env in ("0", "false"):
        return False
    if params.categorical_feature or params.objective == "lambdarank":
        return False
    if params.max_bin > 255 or (params.max_bin_by_feature
                                and max(params.max_bin_by_feature) > 255):
        return False
    if any(os.environ.get(e, "") not in ("", "0")
           for e in _NATIVE_PATH_FORCING_ENVS):
        return False
    from .. import native_loader

    if not native_loader.available():
        return False
    if env in ("1", "true", "force"):
        return True
    # size budget FIRST: small fits are native on every backend, so the
    # decision must not initialize the accelerator (the whole point of
    # this engine is that H2D is never touched for them)
    budget = float(os.environ.get("MMLSPARK_TPU_NATIVE_TRAIN_MAX", "2e7"))
    if n * params.num_iterations * max(params.num_class, 1) <= budget:
        return True
    # above budget the device engine is the default — consulting the
    # backend here is free, those fits initialize it anyway
    import jax

    return jax.default_backend() == "cpu"


def _train_native(params: TrainParams, X: np.ndarray, y: np.ndarray,
                  weights, valid, valid_groups, init_scores, init_model,
                  log) -> Optional[Booster]:
    """All-host training loop over the C++ grower (no device arrays at all).

    Mirrors the host-orchestrated loop of train() — same objectives,
    bagging/GOSS/dart/rf selection logic, early stopping, and metric
    logging — with mml_gbdt_grow_tree replacing the XLA tree grower.
    Returns None when this fit cannot run natively (mapper with >256 bins
    inherited from init_model, native lib unavailable at call time)."""
    from .. import native_loader

    n, num_f = X.shape
    k = max(params.num_class, 1)
    objective = params.objective
    rng = np.random.default_rng(params.seed or params.bagging_seed)

    if init_model is not None and init_model.bin_mapper is not None:
        mapper = init_model.bin_mapper
    else:
        mapper = BinMapper.fit(X, params.max_bin, (), seed=params.seed,
                               max_bin_by_feature=params.max_bin_by_feature)
    num_bins = mapper.max_num_bins
    if num_bins > 256:
        return None
    bins_fm = mapper.transform_fm(X, dtype=np.uint8)

    if init_scores is not None:
        base = np.zeros(k, dtype=np.float64)
        scores = np.broadcast_to(
            np.asarray(init_scores, dtype=np.float64).reshape(n, -1),
            (n, k)).copy()
    else:
        base = init_score(objective, np.asarray(y, dtype=np.float64), k,
                          alpha=params.alpha)
        scores = np.tile(base, (n, 1)).astype(np.float64)
    booster = Booster(params, mapper, base_score=base)
    if init_model is not None:
        booster.trees = [list(g) for g in init_model.trees]
        booster.base_score = init_model.base_score
        if init_model.trees:
            scores = init_model.raw_predict(
                X, num_iteration=len(init_model.trees)).reshape(n, -1)

    metric = params.metric or default_metric(objective)
    higher_better = metric in _HIGHER_BETTER
    best_val, best_iter, rounds_no_improve = \
        (-np.inf if higher_better else np.inf), -1, 0
    val_X, val_y = valid if valid is not None else (None, None)

    is_rf = params.boosting_type == "rf"
    is_dart = params.boosting_type == "dart"
    is_goss = params.boosting_type == "goss"
    lr = 1.0 if is_rf else params.learning_rate
    bag_mask = np.ones(n, dtype=bool)
    yv = np.asarray(y, dtype=np.float64)
    wv = np.asarray(weights, dtype=np.float64) if weights is not None else None

    from ..obs.metrics import TrainRecorder

    recorder = TrainRecorder("gbdt_native")
    for it in range(params.num_iterations):
        _it_t0 = _now()
        _faults.fire(_faults.TRAIN_STEP, iteration=it, engine="native")
        dropped: List[int] = []
        if is_dart and booster.trees:
            n_trees = len(booster.trees)
            if params.uniform_drop:
                drop_mask = rng.random(n_trees) < params.drop_rate
                dropped = list(np.where(drop_mask)[0][: params.max_drop])
            else:
                n_drop = min(max(1, int(n_trees * params.drop_rate)),
                             params.max_drop)
                dropped = list(rng.choice(n_trees, size=n_drop,
                                          replace=False))
            for di in dropped:
                for kk in range(k):
                    scores[:, kk] -= _tree_contrib(booster.trees[di][kk], X)

        sc = scores[:, 0] if k == 1 else scores
        g, h = _grad_hess_np(objective, sc, yv, wv, params.alpha)

        row_mask = bag_mask
        if is_goss:
            g_abs = np.abs(g)
            if g_abs.ndim == 2:
                g_abs = g_abs.sum(axis=1)
            top_n = int(n * params.top_rate)
            other_n = int(n * params.other_rate)
            # argpartition: the top-|g| SET is what GOSS needs, not its
            # order — O(n) beats the device path's full argsort here
            # (selection was ~20% of the native 200k GOSS fit)
            part = np.argpartition(-g_abs, max(top_n - 1, 0))
            row_mask = np.zeros(n, dtype=bool)
            row_mask[part[:top_n]] = True
            rest = part[top_n:]
            picked = rng.choice(len(rest), size=min(other_n, len(rest)),
                                replace=False)
            row_mask[rest[picked]] = True
            amplify = (1.0 - params.top_rate) / max(params.other_rate, 1e-12)
            amp = np.ones(n)
            amp[rest] = amplify
            g, h = g * (amp if g.ndim == 1 else amp[:, None]), \
                h * (amp if h.ndim == 1 else amp[:, None])
        elif ((params.bagging_fraction < 1.0
               or params.pos_bagging_fraction < 1.0
               or params.neg_bagging_fraction < 1.0)
              and (is_rf or params.bagging_freq > 0)
              and it % max(params.bagging_freq, 1) == 0):
            if (params.pos_bagging_fraction < 1.0
                    or params.neg_bagging_fraction < 1.0):
                pos = np.asarray(y) > 0.5
                frac = np.where(pos, params.pos_bagging_fraction,
                                params.neg_bagging_fraction)
                bag_mask = rng.random(n) < frac
            else:
                bag_mask = rng.random(n) < params.bagging_fraction
            row_mask = bag_mask

        feature_mask = None
        if params.feature_fraction < 1.0:
            m = np.zeros(num_f, dtype=bool)
            n_feat = max(1, int(num_f * params.feature_fraction))
            m[rng.choice(num_f, size=n_feat, replace=False)] = True
            feature_mask = m

        group: List[Tree] = []
        for kk in range(k):
            gk = np.ascontiguousarray(g if g.ndim == 1 else g[:, kk],
                                      dtype=np.float32)
            hk = np.ascontiguousarray(h if h.ndim == 1 else h[:, kk],
                                      dtype=np.float32)
            res = native_loader.gbdt_grow_tree(
                bins_fm, gk, hk,
                None if row_mask.all() else row_mask, feature_mask,
                num_bins=num_bins, num_leaves=params.num_leaves,
                max_depth=params.max_depth,
                min_data_in_leaf=params.min_data_in_leaf,
                min_sum_hessian=params.min_sum_hessian_in_leaf,
                min_gain_to_split=params.min_gain_to_split,
                lambda_l1=params.lambda_l1, lambda_l2=params.lambda_l2,
                max_delta_step=params.max_delta_step)
            if res is None:
                return None
            feat = res["feature"]
            thr = np.zeros(len(feat), dtype=np.float64)
            for i in np.nonzero(feat >= 0)[0]:
                thr[i] = mapper.bin_upper_value(int(feat[i]),
                                                int(res["threshold_bin"][i]))
            tree = Tree(
                feature=feat, threshold=thr,
                threshold_bin=res["threshold_bin"],
                default_left=res["default_left"], left=res["left"],
                right=res["right"], value=res["value"], gain=res["gain"],
                count=res["count"], weight=res["weight"])
            shrink = lr
            if is_dart and dropped:
                shrink = lr / (len(dropped) + lr)
            tree.shrinkage = shrink
            group.append(tree)
            scores[:, kk] += tree.value[res["leaf_of_row"]] * shrink
        if is_dart and dropped:
            factor = len(dropped) / (len(dropped) + lr)
            for di in dropped:
                for kk in range(k):
                    booster.trees[di][kk].shrinkage *= factor
                    scores[:, kk] += _tree_contrib(booster.trees[di][kk], X)
        booster.trees.append(group)

        if params.train_metric and log:
            tm = eval_metric(metric, scores[:, 0] if k == 1 else scores, yv)
            recorder.metric(f"train_{metric}", tm)
            log(f"[{it + 1}] train {metric}={tm:.6f}")
        if val_X is not None:
            val_scores = booster.raw_predict(
                val_X, num_iteration=len(booster.trees))
            m = eval_metric(metric, val_scores,
                            np.asarray(val_y, dtype=np.float64), valid_groups)
            recorder.metric(f"valid_{metric}", m)
            improved = m > best_val if higher_better else m < best_val
            if improved:
                best_val, best_iter, rounds_no_improve = \
                    m, len(booster.trees), 0
            else:
                rounds_no_improve += 1
            if log:
                log(f"[{it + 1}] valid {metric}={m:.6f}")
            if params.early_stopping_round > 0 \
                    and rounds_no_improve >= params.early_stopping_round:
                booster.best_iteration = best_iter
                if log:
                    log(f"early stopping at iteration {it + 1}, "
                        f"best {best_iter}")
                recorder.step(_now() - _it_t0, examples=n)
                break
        elif log and not params.train_metric and (it + 1) % 10 == 0:
            m = eval_metric(metric, scores[:, 0] if k == 1 else scores, yv)
            log(f"[{it + 1}] train {metric}={m:.6f}")
        recorder.step(_now() - _it_t0, examples=n)

    if is_rf and booster.trees:
        inv = 1.0 / len(booster.trees)
        for gtrees in booster.trees:
            for t in gtrees:
                t.shrinkage = inv
    return booster


def train(params: TrainParams,
          X: np.ndarray, y: np.ndarray,
          weights: Optional[np.ndarray] = None,
          groups: Optional[np.ndarray] = None,
          valid: Optional[Tuple[np.ndarray, np.ndarray]] = None,
          valid_groups: Optional[np.ndarray] = None,
          init_scores: Optional[np.ndarray] = None,
          init_model: Optional[Booster] = None,
          log: Optional[Callable[[str], None]] = None,
          mesh=None, checkpoint=None) -> Booster:
    """``_train`` under the call's root span ``fit`` (obs/trace.py: the
    default recorder's, unless a batch is bound): its phases record as
    ``gbdt:bin_fit``, ``gbdt:bins``, ``gbdt:scan`` (one ``gbdt:scan_chunk``
    a chunk, its ``gbdt:fetch`` inside) and ``gbdt:trees``."""
    attrs = {"rows": len(y), "features": int(X.shape[1]),
             "iterations": int(params.num_iterations)}
    with root_span("fit", attrs):
        return _train(params, X, y, weights, groups, valid, valid_groups,
                      init_scores, init_model, log, mesh, checkpoint)


def _train(params: TrainParams, X: np.ndarray, y: np.ndarray,
           weights: Optional[np.ndarray], groups: Optional[np.ndarray],
           valid: Optional[Tuple[np.ndarray, np.ndarray]],
           valid_groups: Optional[np.ndarray],
           init_scores: Optional[np.ndarray], init_model: Optional[Booster],
           log: Optional[Callable[[str], None]], mesh, checkpoint) -> Booster:
    """Full training: bin, boost, early-stop. Returns a Booster.

    ``mesh``: optional jax Mesh — rows are sharded over the ``data`` axis and the
    histogram scatter becomes a cross-shard reduction (GSPMD inserts the psum):
    the TPU equivalent of LightGBM's socket-ring data-parallel mode
    (TrainUtils.scala:383-418). Rows are padded to a shard multiple with
    zero-hessian padding so they never influence splits (empty-partition
    IgnoreStatus parity, TrainUtils.scala:332-341).

    ``checkpoint``: optional gbdt.checkpoint.CheckpointConfig — atomically
    persists the model + loop state every k iterations and resumes an
    interrupted fit from the last checkpoint, replaying the remaining
    iterations identically to an uninterrupted run (pins the fit to the
    per-iteration host-orchestrated loop; see CheckpointConfig docs).
    """
    if checkpoint is not None:
        from .checkpoint import (check_params_match, load_checkpoint,
                                 save_checkpoint)
    # native C++ host engine for small fits (and CPU-only hosts): decided
    # before ANY device work so H2D is never touched.
    # Checkpointed fits skip it — the native loop keeps its state in C++.
    if mesh is None and groups is None and checkpoint is None \
            and _native_train_ok(params, len(y)):
        nb = _train_native(params, X, y, weights, valid, valid_groups,
                           init_scores, init_model, log)
        if nb is not None:
            return nb

    import jax
    import jax.numpy as jnp

    from .pallas_hist import CHUNK

    # Pad rows so every device array is a CHUNK multiple (the histogram
    # kernel would otherwise jnp.pad inside jit — a whole-array copy that
    # OOMed the 10M-row bench) and, when sharded, a per-shard CHUNK
    # multiple. Padded rows: NaN features (bin 0), zero label/weight,
    # excluded from training via pad_mask (empty-partition IgnoreStatus
    # parity, TrainUtils.scala:332-341).
    shard_put = bins_put = None
    n_shards = 1
    if mesh is not None:
        from ..parallel.mesh import DATA_AXIS, data_sharding

        n_shards = int(mesh.shape.get(DATA_AXIS, 1))
    row_mult = CHUNK * max(n_shards, 1)
    pad = (-len(y)) % row_mult
    if pad:
        X = np.concatenate([X, np.full((pad, X.shape[1]), np.nan)])
        y = np.concatenate([y, np.zeros(pad)])
        if weights is not None:
            weights = np.concatenate([weights, np.zeros(pad)])
        if groups is not None:
            groups = np.concatenate([groups, np.full(pad, -1)])
    if n_shards > 1:
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.mesh import DATA_AXIS, data_sharding

        sharding = data_sharding(mesh)
        shard_put = lambda a: jax.device_put(a, sharding)
        # feature-major bins shard the ROW dim, which is dim 1
        bins_sharding = NamedSharding(mesh, PartitionSpec(None, DATA_AXIS))
        bins_put = lambda a: jax.device_put(a, bins_sharding)
    pad_mask = np.ones(len(y), dtype=bool)
    if pad:
        pad_mask[-pad:] = False

    n, num_f = X.shape
    n_real = int(pad_mask.sum())
    k = max(params.num_class, 1)
    objective = params.objective
    rng = np.random.default_rng(params.seed or params.bagging_seed)
    obs = current_batch()  # the fit's root span (obs/trace.py), or None

    if init_model is not None and init_model.bin_mapper is not None:
        mapper = init_model.bin_mapper
    else:
        w_fit, t_fit = time.time(), _now()
        mapper = BinMapper.fit(X[:n_real], params.max_bin,
                               params.categorical_feature, seed=params.seed,
                               max_bin_by_feature=params.max_bin_by_feature)
        if obs is not None:
            obs[0].record_batch("gbdt:bin_fit", obs[1], w_fit,
                                _now() - t_fit, rows=n_real, features=num_f)
    # the mapper (possibly inherited from init_model with a different max_bin)
    # is the sole authority on bin count — mixing in params.max_bin would corrupt
    # the flat scatter indices in compute_histogram
    num_bins = mapper.max_num_bins
    put = shard_put or jax.device_put
    put_bins = bins_put or jax.device_put
    # feature-major [F, N] device layout (column store, like LightGBM's own
    # Dataset): minor dim rows -> no XLA lane padding (an [N, 28] int32
    # array tiles 28 -> 128 lanes, a 4.6x HBM blowup at 10M rows). Bins ship
    # as uint8 when they fit (4x less H2D — 280 MB vs 1.1 GB at 10M rows
    # through the host link) and widen once on device.
    u8 = num_bins <= 256
    bin_dtype = np.uint8 if u8 else np.int32
    w_bins, t_bins = time.time(), _now()
    if bins_put is None and n * num_f >= 1 << 22:
        # Overlapped bin+ship: the MAIN thread bins columns (the host has
        # one core — a transform pool cannot help) while a single worker
        # thread ships each finished slab (device_put releases the GIL
        # during the transfer, so slab puts ride inside the binning wall
        # clock).
        import queue
        import threading

        slabs: List = [None] * num_f
        slab_q: "queue.Queue" = queue.Queue()
        worker_err: List[BaseException] = []

        def _put_worker():
            while True:
                item = slab_q.get()
                if item is None:
                    return
                fi, arr = item
                try:
                    slabs[fi] = jax.device_put(arr)
                except BaseException as e:  # surface after join, not as a
                    worker_err.append(e)    # confusing stack(None) TypeError
                    return

        th = threading.Thread(target=_put_worker, daemon=True)
        th.start()
        try:
            for f in range(num_f):
                col = mapper.transform_col(f, np.ascontiguousarray(X[:, f]))
                slab_q.put((f, col.astype(bin_dtype)))
        finally:
            slab_q.put(None)
            th.join()
        if worker_err:
            raise worker_err[0]
        bins_dev = jnp.stack(slabs, axis=0)
        if u8:
            bins_dev = _widen_bins(bins_dev)
    else:
        bins_fm = mapper.transform_fm(X, dtype=bin_dtype)
        if u8:
            bins_dev = _widen_bins(put_bins(jnp.asarray(bins_fm)))
        else:
            bins_dev = put_bins(jnp.asarray(bins_fm))
    if obs is not None:
        obs[0].record_batch("gbdt:bins", obs[1], w_bins, _now() - t_bins,
                            rows=n, features=num_f)

    labels = put(jnp.asarray(y, dtype=jnp.float32))
    w_dev = put(jnp.asarray(weights, dtype=jnp.float32)) if weights is not None else None
    g_dev = put(jnp.asarray(groups, dtype=jnp.int32)) if groups is not None else None
    # lambdarank group layout is static across boosting: segment once here
    group_seg = (segment_groups(groups)
                 if groups is not None and objective == "lambdarank" else None)

    if init_scores is not None:
        # per-row init score (initScoreCol): boosting starts from it, but it is
        # NOT part of the serialized model (LightGBM init_score semantics)
        base = np.zeros(k, dtype=np.float64)
        pad_rows = n - len(init_scores)
        init_arr = np.asarray(init_scores, dtype=np.float64).reshape(len(init_scores), -1)
        if pad_rows:
            init_arr = np.concatenate([init_arr, np.zeros((pad_rows, init_arr.shape[1]))])
        scores = np.broadcast_to(init_arr, (n, k)).copy()
    else:
        base = init_score(objective, np.asarray(y[:n_real], dtype=np.float64),
                          k, alpha=params.alpha)
        scores = np.tile(base, (n, 1)).astype(np.float64)
    booster = Booster(params, mapper, base_score=base)
    if init_model is not None:
        booster.trees = [list(g) for g in init_model.trees]
        booster.base_score = init_model.base_score
        if init_model.trees:
            # seed from ALL inherited trees (they are all carried into the merged
            # model), not the early-stopped prefix
            scores = init_model.raw_predict(
                X, num_iteration=len(init_model.trees)).reshape(n, -1)

    metric = params.metric or default_metric(objective)
    higher_better = metric in _HIGHER_BETTER
    best_val = -np.inf if higher_better else np.inf
    best_iter = -1
    rounds_no_improve = 0

    val_X = val_y = None
    if valid is not None:
        val_X, val_y = valid

    config = GrowerConfig(
        num_leaves=params.num_leaves, max_depth=params.max_depth,
        min_data_in_leaf=params.min_data_in_leaf,
        min_sum_hessian_in_leaf=params.min_sum_hessian_in_leaf,
        min_gain_to_split=params.min_gain_to_split,
        lambda_l1=params.lambda_l1, lambda_l2=params.lambda_l2,
        max_delta_step=params.max_delta_step,
        cat_smooth=params.cat_smooth, cat_l2=params.cat_l2,
        max_cat_threshold=params.max_cat_threshold)

    # categorical SET splits (LightGBM num_cat machinery): features flagged
    # categorical split by sorted-gradient-prefix subsets
    cat_args = None
    if params.categorical_feature:
        cat_mask_np = np.zeros(num_f, dtype=bool)
        cat_mask_np[list(params.categorical_feature)] = True
        cat_args = (jnp.asarray(cat_mask_np), np.float32(params.cat_smooth),
                    np.float32(params.cat_l2),
                    np.int32(params.max_cat_threshold))

    is_rf = params.boosting_type == "rf"
    is_dart = params.boosting_type == "dart"
    is_goss = params.boosting_type == "goss"
    lr = 1.0 if is_rf else params.learning_rate
    bag_mask = np.ones(n, dtype=bool)  # persists across iters (bagging_freq reuse)

    # ----- checkpoint resume: restore model + loop state (scores, RNG
    # stream, bagging mask, early-stopping bookkeeping) so iterations
    # start_it..N replay the uninterrupted computation exactly
    start_it = 0
    if checkpoint is not None and checkpoint.resume:
        ck = load_checkpoint(checkpoint.path)
        if ck is not None:
            check_params_match(ck["params"], dataclasses.asdict(params),
                               checkpoint.path)
            restored = Booster.from_string(ck["model"])
            booster.trees = restored.trees
            booster.base_score = restored.base_score
            if ck["scores"].shape != (n, k):
                raise ValueError(
                    f"checkpoint {checkpoint.path!r} scores shape "
                    f"{ck['scores'].shape} does not match this dataset "
                    f"({(n, k)}); resume requires the same data and mesh")
            scores = ck["scores"]
            rng.bit_generator.state = ck["rng_state"]
            bag_mask = ck["bag_mask"].astype(bool)
            best_val = ck["best_val"]
            best_iter = ck["best_iter"]
            rounds_no_improve = ck["rounds_no_improve"]
            start_it = int(ck["iteration"])

    # whole-run fused path: every boosting iteration inside ONE lax.scan
    # dispatch — no per-tree host round trips at all
    if _scan_train_ok(params, objective, valid, log, shard_put, checkpoint):
        row_masks, feat_masks, ok = _scan_precompute_masks(
            params, rng, n, num_f, np.asarray(y), is_rf)
        if ok:
            from ..core.runtime import ensure_compile_cache

            ensure_compile_cache()
            _train_scan(params, config, booster, mapper, bins_dev, labels,
                        w_dev, scores, n, num_f, num_bins, k, lr,
                        row_masks, feat_masks, pad_mask=pad_mask,
                        cat_args=cat_args)
            if is_rf and booster.trees:
                inv = 1.0 / len(booster.trees)
                for gtrees in booster.trees:
                    for t in gtrees:
                        t.shrinkage = inv
            return booster

    # single-device accelerator fast path: keep the running scores ON DEVICE
    # (Kahan-compensated f32 — see _add_leaf_values) and update them from the
    # fused grower's device-resident row routing — no per-iter [N] score
    # upload or row fetch. Dart is excluded (it rewrites scores on host when
    # dropping/re-adding trees), the sharded path is excluded (its grower
    # already returns host rows through the per-shard kernels), and CPU keeps
    # the exact-f64 host accumulation (in-process dispatch is cheap there).
    fast_scores = (shard_put is None and not is_dart
                   and jax.default_backend() != "cpu")
    max_nodes = 2 * params.num_leaves - 1
    score_dev = comp_dev = None
    if fast_scores:
        score_dev = jax.device_put(jnp.asarray(
            scores[:, 0] if k == 1 else scores, dtype=jnp.float32))
        comp_dev = jnp.zeros_like(score_dev)

    def _host_scores():
        if not fast_scores:
            return scores
        s, c = fetch_global((score_dev, comp_dev))
        return (np.asarray(s, dtype=np.float64)
                + np.asarray(c, dtype=np.float64)).reshape(n, -1)

    from ..obs.metrics import TrainRecorder

    recorder = TrainRecorder("gbdt")
    for it in range(start_it, params.num_iterations):
        _it_t0 = _now()
        # chaos seam: a planned fault here simulates preemption mid-train
        # (the last checkpoint is on disk; resume replays from it)
        _faults.fire(_faults.TRAIN_STEP, iteration=it)
        # ----- dart: drop a subset of existing trees from the current scores
        dropped: List[int] = []
        if is_dart and booster.trees:
            n_trees = len(booster.trees)
            if params.uniform_drop:
                drop_mask = rng.random(n_trees) < params.drop_rate
                dropped = list(np.where(drop_mask)[0][: params.max_drop])
            else:
                n_drop = min(max(1, int(n_trees * params.drop_rate)), params.max_drop)
                dropped = list(rng.choice(n_trees, size=n_drop, replace=False))
            for di in dropped:
                for kk in range(k):
                    scores[:, kk] -= _tree_contrib(booster.trees[di][kk], X)

        if not fast_scores:
            score_dev = put(jnp.asarray(scores[:, 0] if k == 1 else scores,
                                        dtype=jnp.float32))
        g, h = grad_hess(objective, score_dev, labels, w_dev, params.alpha,
                         g_dev, group_segments=group_seg)

        # ----- bagging / goss row selection
        row_mask = bag_mask
        if is_goss:
            g_abs = np.abs(np.asarray(fetch_global(g)))
            if g_abs.ndim == 2:
                g_abs = g_abs.sum(axis=1)
            # pad rows sit at the end; goss ranks/samples REAL rows only
            g_abs = g_abs[:n_real]
            top_n = int(n_real * params.top_rate)
            other_n = int(n_real * params.other_rate)
            order = np.argsort(-g_abs)
            row_mask = np.zeros(n, dtype=bool)
            row_mask[order[:top_n]] = True
            rest = order[top_n:]
            picked = rng.choice(len(rest), size=min(other_n, len(rest)), replace=False)
            row_mask[rest[picked]] = True
            amplify = (1.0 - params.top_rate) / max(params.other_rate, 1e-12)
            amp = np.ones(n, dtype=np.float32)
            amp[rest] = amplify
            amp_dev = jnp.asarray(amp)
            g, h = g * (amp_dev if g.ndim == 1 else amp_dev[:, None]), \
                   h * (amp_dev if h.ndim == 1 else amp_dev[:, None])
        elif ((params.bagging_fraction < 1.0
               or params.pos_bagging_fraction < 1.0
               or params.neg_bagging_fraction < 1.0)
              and (is_rf or params.bagging_freq > 0)
              and it % max(params.bagging_freq, 1) == 0):
            # resample every bagging_freq iterations, reuse the subset in between
            if (params.pos_bagging_fraction < 1.0
                    or params.neg_bagging_fraction < 1.0):
                # class-aware bagging (binary): per-class keep fractions
                # (LightGBM pos/neg_bagging_fraction; overrides the uniform
                # fraction like LightGBM does)
                pos = np.asarray(y) > 0.5
                frac = np.where(pos, params.pos_bagging_fraction,
                                params.neg_bagging_fraction)
                bag_mask = rng.random(n) < frac
            else:
                bag_mask = rng.random(n) < params.bagging_fraction
            row_mask = bag_mask

        # ----- feature subsampling
        feature_mask = None
        if params.feature_fraction < 1.0:
            m = np.zeros(num_f, dtype=bool)
            n_feat = max(1, int(num_f * params.feature_fraction))
            m[rng.choice(num_f, size=n_feat, replace=False)] = True
            feature_mask = jnp.asarray(m)

        row_mask &= pad_mask
        mask_dev = put(jnp.asarray(row_mask))
        group: List[Tree] = []
        for kk in range(k):
            gk = g if g.ndim == 1 else g[:, kk]
            hk = h if h.ndim == 1 else h[:, kk]
            tree, leaf_of_row = grow_tree(bins_dev, gk, hk, mask_dev, num_bins,
                                          config, mapper, feature_mask,
                                          device_rows=fast_scores,
                                          cat_args=cat_args)
            shrink = lr
            if is_dart and dropped:
                shrink = lr / (len(dropped) + lr)  # dart normalization
            tree.shrinkage = shrink
            group.append(tree)
            if fast_scores:
                # rows may be host numpy if the grower fell back to the
                # per-split path (memory budget) — device scores either way
                vals = np.zeros(max(max_nodes, len(tree.value)),
                                dtype=np.float32)
                vals[: len(tree.value)] = tree.value * shrink
                score_dev, comp_dev = _add_leaf_values(
                    score_dev, comp_dev, jnp.asarray(vals),
                    jnp.asarray(leaf_of_row), kk if k > 1 else None)
            else:
                scores[:, kk] += tree.value[leaf_of_row] * shrink
        if is_dart and dropped:
            # scale dropped trees and add them back
            factor = len(dropped) / (len(dropped) + lr)
            for di in dropped:
                for kk in range(k):
                    booster.trees[di][kk].shrinkage *= factor
                    scores[:, kk] += _tree_contrib(booster.trees[di][kk], X)
        booster.trees.append(group)

        # ----- eval + early stopping
        if params.train_metric and log:
            host_sc = _host_scores()
            tm = eval_metric(metric, host_sc[:n_real, 0] if k == 1
                             else host_sc[:n_real],
                             np.asarray(y[:n_real], dtype=np.float64),
                             groups[:n_real] if groups is not None else None)
            recorder.metric(f"train_{metric}", tm)
            log(f"[{it + 1}] train {metric}={tm:.6f}")
        if val_X is not None:
            val_scores = booster.raw_predict(val_X, num_iteration=len(booster.trees))
            m = eval_metric(metric, val_scores, np.asarray(val_y, dtype=np.float64),
                            valid_groups)
            recorder.metric(f"valid_{metric}", m)
            improved = m > best_val if higher_better else m < best_val
            if improved:
                best_val, best_iter, rounds_no_improve = m, len(booster.trees), 0
            else:
                rounds_no_improve += 1
            if log:
                log(f"[{it + 1}] valid {metric}={m:.6f}")
            if params.early_stopping_round > 0 \
                    and rounds_no_improve >= params.early_stopping_round:
                booster.best_iteration = best_iter
                if log:
                    log(f"early stopping at iteration {it + 1}, best {best_iter}")
                recorder.step(_now() - _it_t0, examples=n_real)
                break
        elif log and not params.train_metric and (it + 1) % 10 == 0:
            host_sc = _host_scores()[:n_real]
            train_scores = host_sc[:, 0] if k == 1 else host_sc
            m = eval_metric(metric, train_scores,
                            np.asarray(y[:n_real], dtype=np.float64),
                            groups[:n_real] if groups is not None else None)
            log(f"[{it + 1}] train {metric}={m:.6f}")

        # ----- atomic checkpoint every k iterations (and at the end)
        if checkpoint is not None and (
                (it + 1) % max(checkpoint.every_k, 1) == 0
                or it + 1 == params.num_iterations):
            _ck_t0 = _now()
            save_checkpoint(
                checkpoint.path,
                params_dict=dataclasses.asdict(params),
                model_string=booster.to_string(),
                iteration=it + 1,
                scores=_host_scores() if fast_scores else scores,
                rng_state=rng.bit_generator.state,
                bag_mask=bag_mask,
                best_val=best_val, best_iter=best_iter,
                rounds_no_improve=rounds_no_improve)
            recorder.checkpoint(_now() - _ck_t0)
        recorder.step(_now() - _it_t0, examples=n_real)

    if is_rf and booster.trees:
        inv = 1.0 / len(booster.trees)
        for gtrees in booster.trees:
            for t in gtrees:
                t.shrinkage = inv
    return booster


def _tree_contrib(tree: Tree, X: np.ndarray) -> np.ndarray:
    from .predict import predict_single_tree

    return predict_single_tree(tree, X)


@functools.partial(__import__("jax").jit, static_argnames=("kk",))
def _add_leaf_values(score, comp, values, rows, kk=None):
    """On-device score update: score += values[rows] (column kk if multiclass).

    Kahan-compensated: ``comp`` carries the rounding residual of every prior
    add, so small per-tree updates against a large running score are not lost
    to f32 (the accumulated sum keeps ~2x24-bit effective mantissa, standing
    in for the f64 host accumulation of the non-fast path). ``values`` is
    padded to the static max-node count so every tree of a run hits the same
    compiled executable."""
    upd = values[rows]
    if kk is not None:
        s_col, c_col = score[:, kk], comp[:, kk]
        y = upd + c_col
        t = s_col + y
        return (score.at[:, kk].set(t),
                comp.at[:, kk].set(y - (t - s_col)))
    y = upd + comp
    t = score + y
    return t, y - (t - score)
