"""Jitted online linear learner: per-example adaptive SGD / FTRL over hashed features.

Replaces VW's C++ learn loop (driven per-row through JNI at
vw/VowpalWabbitBase.scala:239-258) with a ``lax.scan`` over examples inside one
XLA program: each step gathers the example's weights, computes the loss gradient,
and scatter-updates — the whole pass is one device launch instead of N JNI calls.

Distributed (VW AllReduce spanning-tree parity, VowpalWabbitBase.scala:314-342):
each mesh shard scans its rows independently, then weights are averaged with
``psum`` under ``shard_map`` after every pass — exactly VW's between-pass model
averaging, over ICI instead of driver-rooted TCP.

Sparse rows are padded to a fixed nnz per row; padded slots (index 0, value 0) are
inert because both the gradient and the l2 decay are gated on value != 0, and padded
rows (example weight 0) don't advance the learning-rate clock.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..parallel.mesh import fetch_global


@dataclasses.dataclass
class LearnerConfig:
    num_bits: int = 18
    learning_rate: float = 0.5
    power_t: float = 0.5           # lr decay exponent (VW --power_t)
    initial_t: float = 0.0
    l1: float = 0.0
    l2: float = 0.0
    loss_function: str = "squared"  # squared | logistic | hinge | quantile
    quantile_tau: float = 0.5
    adaptive: bool = True           # AdaGrad per-weight scaling (VW default)
    num_passes: int = 1
    ftrl: bool = False
    ftrl_alpha: float = 0.005
    ftrl_beta: float = 0.1
    seed: int = 0


@dataclasses.dataclass
class SparseDataset:
    """Padded sparse matrix: [N, max_nnz] indices/values (+label/weight)."""

    indices: np.ndarray   # int32 [N, K]
    values: np.ndarray    # float32 [N, K]
    labels: np.ndarray    # float32 [N]
    weights: np.ndarray   # float32 [N]

    @staticmethod
    def from_rows(rows, labels, weights=None, num_bits: int = 18) -> "SparseDataset":
        mask = (1 << num_bits) - 1
        n = len(rows)
        nnz = [0 if r is None else len(r["indices"]) for r in rows]
        k = max(max(nnz, default=1), 1)
        idx = np.zeros((n, k), dtype=np.int32)
        val = np.zeros((n, k), dtype=np.float32)
        for i, r in enumerate(rows):
            if r is None or len(r["indices"]) == 0:
                continue
            m = len(r["indices"])
            idx[i, :m] = (np.asarray(r["indices"], dtype=np.int64) & mask)
            val[i, :m] = r["values"]
        return SparseDataset(
            idx, val,
            np.asarray(labels, dtype=np.float32),
            np.asarray(weights if weights is not None else np.ones(n),
                       dtype=np.float32))


def _loss_grad(loss: str, pred, label, tau: float):
    """dLoss/dPred for the supported VW loss functions."""
    import jax.numpy as jnp

    if loss == "squared":
        return pred - label
    if loss == "logistic":
        # labels in {-1, +1} (VW convention)
        return -label / (1.0 + jnp.exp(label * pred))
    if loss == "hinge":
        return jnp.where(label * pred < 1.0, -label, 0.0)
    if loss == "quantile":
        return jnp.where(pred > label, 1.0 - tau, -tau)
    raise ValueError(f"Unknown loss {loss!r}")


def make_scan_pass(config: LearnerConfig):
    """Build the jitted single-pass scan: (state, dataset) -> (state, example_losses).

    State: (w, g2, t) for adaptive SGD, or (z, n_acc) for FTRL.
    """
    import jax
    import jax.numpy as jnp

    loss = config.loss_function
    tau = config.quantile_tau
    lr = config.learning_rate
    power_t = config.power_t
    l2 = config.l2
    l1 = config.l1

    if config.ftrl:
        def step(state, ex):
            z, n_acc = state
            idx, val, label, wgt = ex
            # FTRL-proximal weight reconstruction for active coords
            zi = z[idx]
            ni = n_acc[idx]
            sign = jnp.sign(zi)
            wi = jnp.where(
                jnp.abs(zi) <= l1, 0.0,
                -(zi - sign * l1) / ((config.ftrl_beta + jnp.sqrt(ni))
                                     / config.ftrl_alpha + l2))
            pred = jnp.sum(wi * val)
            g = _loss_grad(loss, pred, label, tau) * wgt
            gi = g * val
            sigma = (jnp.sqrt(ni + gi * gi) - jnp.sqrt(ni)) / config.ftrl_alpha
            z = z.at[idx].add(gi - sigma * wi)
            n_acc = n_acc.at[idx].add(gi * gi)
            return (z, n_acc), _example_loss(loss, pred, label, tau) * wgt

        def run_pass(state, ds):
            return jax.lax.scan(step, state,
                                (ds["indices"], ds["values"], ds["labels"],
                                 ds["weights"]))
    else:
        def step(state, ex):
            w, g2, t = state
            idx, val, label, wgt = ex
            wi = w[idx]
            pred = jnp.sum(wi * val)
            g = _loss_grad(loss, pred, label, tau) * wgt
            # gate the l2 decay on active slots: padded nnz slots are (index 0,
            # value 0) and must not decay weight bucket 0 / pollute its AdaGrad
            # accumulator
            gi = g * val + l2 * wi * (val != 0)
            # padded rows (example weight 0) must not advance the lr-decay clock
            t = t + (wgt > 0)
            if config.adaptive:
                # VW adaptive: per-weight rate lr * g2^(-power_t)
                g2 = g2.at[idx].add(gi * gi)
                scale = jnp.power(g2[idx] + 1e-16, power_t) + 1e-8
                w = w.at[idx].add(-lr * gi / scale)
            else:
                eta = lr / jnp.power(t + config.initial_t, power_t)
                w = w.at[idx].add(-eta * gi)
            return (w, g2, t), _example_loss(loss, pred, label, tau) * wgt

        def run_pass(state, ds):
            return jax.lax.scan(step, state,
                                (ds["indices"], ds["values"], ds["labels"],
                                 ds["weights"]))

    return jax.jit(run_pass)


def _example_loss(loss: str, pred, label, tau: float):
    import jax.numpy as jnp

    if loss == "squared":
        return 0.5 * (pred - label) ** 2
    if loss == "logistic":
        return jnp.logaddexp(0.0, -label * pred)  # stable for large |margin|
    if loss == "hinge":
        return jnp.maximum(0.0, 1.0 - label * pred)
    if loss == "quantile":
        d = pred - label
        return jnp.where(d > 0, (1 - tau) * d, -tau * d)
    raise ValueError(loss)


@dataclasses.dataclass
class TrainingStats:
    """Per-worker diagnostics (VowpalWabbitBase TrainingStats parity,
    vw/VowpalWabbitBase.scala:29-48)."""

    partition_id: int
    num_examples: int
    total_time_ns: int
    learn_time_ns: int
    average_loss: float
    weighted_example_sum: float


def _ftrl_weights(config: LearnerConfig, z, n_acc):
    """Reconstruct dense weights from FTRL-proximal (z, n) state."""
    import jax.numpy as jnp

    sign = jnp.sign(z)
    return jnp.where(
        jnp.abs(z) <= config.l1, 0.0,
        -(z - sign * config.l1) / ((config.ftrl_beta + jnp.sqrt(n_acc))
                                   / config.ftrl_alpha + config.l2))


def _native_pass_ok(config: LearnerConfig) -> bool:
    """Route single-shard training to the native C++ sequential learner?

    Default on: a sequential per-example update stream is latency-bound on
    an accelerator, exactly like the reference's VW (a C++ core driven
    per row). FTRL and unsupported losses stay on the scan path;
    MMLSPARK_TPU_NATIVE_VW=0 disables (tests pin the scan path with it)."""
    import os

    if os.environ.get("MMLSPARK_TPU_NATIVE_VW", "") in ("0", "false"):
        return False
    if config.ftrl:
        return False
    if config.loss_function not in ("squared", "logistic", "hinge",
                                    "quantile"):
        return False
    from .. import native_loader

    return native_loader.load() is not None


def train_linear(config: LearnerConfig, dataset: SparseDataset,
                 initial_weights: Optional[np.ndarray] = None,
                 mesh=None) -> Tuple[np.ndarray, List[TrainingStats]]:
    """Run ``num_passes`` scan passes; with a mesh, shards scan independently and
    state is psum-averaged between passes (AllReduce spanning-tree parity).

    Optimizer state (AdaGrad accumulators / FTRL z,n) carries across passes.
    """
    import time

    dim = 1 << config.num_bits
    n = len(dataset.labels)
    n_shards = 1
    if mesh is not None:
        from ..parallel.mesh import DATA_AXIS

        n_shards = int(mesh.shape.get(DATA_AXIS, 1))

    if (n_shards == 1 and _native_pass_ok(config)
            and int(np.min(dataset.indices, initial=0)) >= 0
            and int(np.max(dataset.indices, initial=-1)) < dim):
        # native C++ sequential pass (VW's own architecture: a C core doing
        # per-example updates, vw/VowpalWabbitBase.scala:218-305). Sequential
        # SGD is latency-bound on an accelerator (~115k ex/s through the
        # scan vs millions/s on one host core), so the single-shard regime
        # runs on the host; mesh fits keep the psum-averaged scan path.
        # Decided BEFORE any jnp state exists — this branch must never
        # initialize a device or ship the 2^bits weight vector anywhere.
        # Index bounds are validated above: the C kernel indexes raw memory
        # where XLA's scatter would clamp/drop OOB indices (datasets built
        # by from_rows are always masked in-range; hand-built ones may not
        # be and fall through to the scan engine).
        from .. import native_loader

        # FORCED copy: the in-place ctypes update must never alias (and
        # mutate) caller-owned initial_weights (a zero-copy jax-array view
        # is read-only; a caller numpy array would be silently trained on)
        w_np = (np.array(np.asarray(initial_weights), dtype=np.float32)
                if initial_weights is not None
                else np.zeros(dim, dtype=np.float32))
        g2_np = np.zeros(dim, dtype=np.float32)
        t_val = 0.0
        w_sum = float(dataset.weights.sum())
        stats = []
        native_ok = True
        for _ in range(config.num_passes):
            t0 = time.perf_counter_ns()
            res = native_loader.vw_train_pass(
                dataset.indices, dataset.values, dataset.labels,
                dataset.weights, w_np, g2_np, t_val,
                loss=config.loss_function, tau=config.quantile_tau,
                lr=config.learning_rate, power_t=config.power_t,
                initial_t=config.initial_t, l2=config.l2,
                adaptive=config.adaptive)
            dt = time.perf_counter_ns() - t0
            if res is None:
                # the .so (or its symbol) went away between the
                # _native_pass_ok probe and the call — fall through to the
                # jax scan engine below, restarting from initial_weights
                # (mirrors binning.transform_col's bin_column fallback; an
                # assert here would strip under python -O and unpack None)
                native_ok = False
                break
            t_val, loss_sum = res
            stats.append(TrainingStats(0, n, dt, dt,
                                       loss_sum / max(w_sum, 1e-12), w_sum))
        if native_ok:
            return w_np, stats

    import jax
    import jax.numpy as jnp

    w0 = (jnp.asarray(initial_weights, dtype=jnp.float32)
          if initial_weights is not None else jnp.zeros(dim, dtype=jnp.float32))
    if config.ftrl:
        # warm start: choose z so the reconstructed weights equal w0 at n=0
        # (ignores the l1 dead zone — exact for |z| > l1, the active coords)
        z0 = -w0 * (config.ftrl_beta / config.ftrl_alpha + config.l2)
        z0 = jnp.where(z0 != 0, z0 + jnp.sign(z0) * config.l1, 0.0)
        state = (z0, jnp.zeros(dim, dtype=jnp.float32))  # (z, n)
    else:
        state = (w0, jnp.zeros(dim, dtype=jnp.float32), jnp.float32(0.0))

    run_pass = make_scan_pass(config)
    stats: List[TrainingStats] = []

    if n_shards > 1:
        from jax.sharding import PartitionSpec as P

        # version-gated API (moved modules, renamed kwargs): route through
        # the compat shim instead of resolving jax.shard_map here
        from ..parallel.mesh import shard_map_compat as shard_map

        pad = (-n) % n_shards

        def padded(a, fill=0):
            if not pad:
                return a
            cfg = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
            return np.pad(a, cfg, constant_values=fill)

        ds = {
            "indices": padded(dataset.indices),
            "values": padded(dataset.values),       # value 0 => no-op example
            "labels": padded(dataset.labels),
            "weights": padded(dataset.weights, 0),  # weight 0 => no grad
        }

        def shard_pass(state, indices, values, labels, weights):
            local = {"indices": indices, "values": values,
                     "labels": labels, "weights": weights}
            # carry starts replicated but the scan makes it shard-varying:
            # mark it varying up front (vma typing for scan-in-shard_map)
            state = jax.tree.map(
                # analysis: allow J001 -- pinned jax 0.9.0 always has pcast
                lambda s: jax.lax.pcast(s, (DATA_AXIS,), to="varying"),
                state)
            state, losses = run_pass(state, local)
            # between-pass model averaging over the data axis (VW sync point);
            # pmean also restores the replicated (invariant) type for out_specs P()
            state = jax.tree.map(
                lambda s: jax.lax.pmean(s, axis_name=DATA_AXIS), state)
            return state, jax.lax.psum(jnp.sum(losses), axis_name=DATA_AXIS)

        state_spec = jax.tree.map(lambda _: P(), state)
        sharded = jax.jit(shard_map(
            shard_pass, mesh=mesh,
            in_specs=(state_spec, P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                      P(DATA_AXIS)),
            out_specs=(state_spec, P())))

        for _ in range(config.num_passes):
            t0 = time.perf_counter_ns()
            state, loss_sum = sharded(state, ds["indices"], ds["values"],
                                      ds["labels"], ds["weights"])
            # the loss fetch is the sync point: dispatch is asynchronous,
            # so the call above returns at enqueue and timing it alone
            # records ~0 — fetch BEFORE reading the clock. fetch_global:
            # under a multi-PROCESS mesh the replicated loss spans
            # non-addressable devices and a bare float() raises
            loss_host = float(fetch_global(loss_sum))
            dt = time.perf_counter_ns() - t0
            w_sum = float(dataset.weights.sum())
            stats.append(TrainingStats(0, n, dt, dt,
                                       loss_host / max(w_sum, 1e-12),
                                       w_sum))
    else:
        ds = {"indices": jnp.asarray(dataset.indices),
              "values": jnp.asarray(dataset.values),
              "labels": jnp.asarray(dataset.labels),
              "weights": jnp.asarray(dataset.weights)}
        for _ in range(config.num_passes):
            t0 = time.perf_counter_ns()
            state, losses = run_pass(state, ds)
            # fetch-as-sync (see sharded branch): time the execution, not
            # the async enqueue
            loss_host = float(jnp.sum(losses))
            dt = time.perf_counter_ns() - t0
            w_sum = float(dataset.weights.sum())
            stats.append(TrainingStats(0, n, dt, dt,
                                       loss_host / max(w_sum, 1e-12),
                                       w_sum))

    # fetch BEFORE the FTRL weight transform: _ftrl_weights runs eager jnp
    # ops, which raise on non-addressable multi-process state just like a
    # bare np.asarray would
    s0, s1 = fetch_global((state[0], state[1]))
    if config.ftrl:
        w = _ftrl_weights(config, s0, s1)
    else:
        w = s0
    return np.asarray(w), stats


def predict_linear(w: np.ndarray, dataset: SparseDataset) -> np.ndarray:
    """Batched sparse dot product (jitted)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fwd(w, idx, val):
        return jnp.sum(w[idx] * val, axis=1)

    return np.asarray(fwd(jnp.asarray(w), jnp.asarray(dataset.indices),
                          jnp.asarray(dataset.values)))


class LinearLearner:
    """Incremental face of the scan pass: ``partial_fit(rows, labels)``
    folds one mini-batch into persistent optimizer state (the serving
    lifecycle's online-adapter contract; ``train_linear`` keeps its
    whole-pass semantics and native fast path untouched).

    Always the jax scan path, never the native engine — the C++ loop
    keeps its learning-rate clock internal, so its state cannot round-trip
    through a checkpoint bitwise. State (weights + AdaGrad/FTRL
    accumulators + lr clock) carries across calls: replaying the same
    example slices in the same order reproduces the state bitwise, which
    is exactly the online trainer's journal-resume contract.
    """

    def __init__(self, config: Optional[LearnerConfig] = None):
        self.config = config if config is not None else LearnerConfig()
        self._pass = None     # jitted scan, built on first partial_fit
        self._state = None    # (w, g2, t) adaptive/sgd or (z, n) FTRL
        self.examples_seen = 0

    def _ensure_state(self) -> None:
        if self._state is not None:
            return
        import jax.numpy as jnp

        dim = 1 << self.config.num_bits
        if self.config.ftrl:
            self._state = (jnp.zeros(dim, dtype=jnp.float32),
                           jnp.zeros(dim, dtype=jnp.float32))
        else:
            self._state = (jnp.zeros(dim, dtype=jnp.float32),
                           jnp.zeros(dim, dtype=jnp.float32),
                           jnp.float32(0.0))

    def partial_fit(self, rows, labels, weights=None) -> float:
        """One incremental step over ``rows`` (sparse dicts, the
        ``SparseDataset.from_rows`` shape); returns the summed weighted
        example loss of the batch."""
        import jax.numpy as jnp

        self._ensure_state()
        if self._pass is None:
            self._pass = make_scan_pass(self.config)
        ds = SparseDataset.from_rows(rows, labels, weights,
                                     num_bits=self.config.num_bits)
        batch = {"indices": jnp.asarray(ds.indices),
                 "values": jnp.asarray(ds.values),
                 "labels": jnp.asarray(ds.labels),
                 "weights": jnp.asarray(ds.weights)}
        self._state, losses = self._pass(self._state, batch)
        self.examples_seen += int(len(ds.labels))
        return float(jnp.sum(losses))

    @property
    def weights(self) -> np.ndarray:
        """Dense weight vector reconstructed from the current state."""
        self._ensure_state()
        if self.config.ftrl:
            return np.asarray(_ftrl_weights(self.config, self._state[0],
                                            self._state[1]))
        return np.asarray(self._state[0])

    def predict(self, rows) -> np.ndarray:
        ds = SparseDataset.from_rows(rows, np.zeros(len(rows)),
                                     num_bits=self.config.num_bits)
        return predict_linear(self.weights, ds)

    def state_dict(self) -> Dict[str, object]:
        """Exact numpy snapshot of the optimizer state (float32 arrays —
        a serialize/load round-trip continues training bitwise)."""
        self._ensure_state()
        arrs = [np.asarray(s) for s in self._state]
        if self.config.ftrl:
            return {"kind": "ftrl", "z": arrs[0], "n": arrs[1],
                    "examples_seen": self.examples_seen}
        return {"kind": "adaptive", "w": arrs[0], "g2": arrs[1],
                "t": float(arrs[2]), "examples_seen": self.examples_seen}

    def load_state_dict(self, d: Dict[str, object]) -> "LinearLearner":
        import jax.numpy as jnp

        expected = "ftrl" if self.config.ftrl else "adaptive"
        if d.get("kind") != expected:
            raise ValueError(f"state kind {d.get('kind')!r} does not match "
                             f"config ({expected})")
        if self.config.ftrl:
            self._state = (jnp.asarray(d["z"], dtype=jnp.float32),
                           jnp.asarray(d["n"], dtype=jnp.float32))
        else:
            self._state = (jnp.asarray(d["w"], dtype=jnp.float32),
                           jnp.asarray(d["g2"], dtype=jnp.float32),
                           jnp.float32(d["t"]))
        self.examples_seen = int(d.get("examples_seen", 0))
        return self
