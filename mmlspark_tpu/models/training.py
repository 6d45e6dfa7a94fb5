"""Training step: loss, optimizer wiring, mesh-sharded train step.

The reference trains DNNs outside the framework (CNTK models arrive pretrained via
ModelDownloader) and trains heads with LightGBM/VW. The TPU build makes DNN training
first-class because transfer learning *is* the north-star benchmark (BASELINE.md):
a jitted, mesh-sharded train step over (data, fsdp, tensor) axes, scaling-book style
— annotate shardings, let XLA insert the collectives.

  - batch sharded over ("data", "fsdp")    — DP; fsdp axis also feeds batch so FSDP
    all-gathers amortize (standard ZeRO-3 layout).
  - conv kernels sharded cin->fsdp, cout->tensor; dense din->fsdp, dout->tensor.
    Dims not divisible by the axis stay replicated (mesh-agnostic degradation).
  - bf16 activations/matmuls (module layer property), f32 params + optimizer state.
"""

from __future__ import annotations

import dataclasses
import functools
import signal as _signal
import threading
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import numpy as np

from .module import Module, Sequential
from ..core import faults
from ..parallel.mesh import DATA_AXIS, FSDP_AXIS, TENSOR_AXIS


def cross_entropy_loss(logits, labels, mask=None):
    """Mean softmax cross-entropy; labels are int class ids over the leading
    dims. Handles [B, K] logits with [B] labels AND per-token [B, T, K] with
    [B, T] (sequence taggers/LMs) — classes are always the last axis. Padded
    rows/tokens masked out via ``mask`` of the labels' shape."""
    import jax
    import jax.numpy as jnp

    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(
        logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0] - lse
    if mask is not None:
        m = mask.astype(jnp.float32)
        return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)
    return -ll.mean()


def accuracy(logits, labels, mask=None):
    import jax.numpy as jnp

    pred = jnp.argmax(logits, axis=-1)
    hit = (pred == labels).astype(jnp.float32)
    if mask is not None:
        m = mask.astype(jnp.float32)
        return (hit * m).sum() / jnp.maximum(m.sum(), 1.0)
    return hit.mean()


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: Any


def _register_train_state():
    import jax

    jax.tree_util.register_dataclass(
        TrainState, data_fields=["params", "opt_state", "step"], meta_fields=[])


_register_train_state()


def _decay_mask(params):
    """Weight decay only touches matmul/conv kernels — never biases, BN scale/shift,
    or BN moving statistics (decaying `var` toward 0 explodes 1/sqrt(var+eps))."""
    import jax

    return jax.tree.map(lambda leaf: np.ndim(leaf) >= 2, params)


def make_optimizer(learning_rate: float = 0.1, momentum: float = 0.9,
                   weight_decay: float = 0.0):
    import optax

    txs = []
    if weight_decay:
        txs.append(optax.add_decayed_weights(weight_decay, mask=_decay_mask))
    txs.append(optax.sgd(learning_rate, momentum=momentum))
    return optax.chain(*txs)


def _apply_bn_ema(params, stats: Dict[str, Any], momentum: float):
    """Fold batch statistics into the BatchNorm moving mean/var params.

    ``stats`` is keyed by layer path ("stem/bn", "layer1/0/body/bn1", ...); each
    path addresses a nested params dict holding {"mean", "var"}.
    """
    for path, (mean, var) in stats.items():
        node = params
        keys = path.split("/")
        for k in keys[:-1]:
            node = node[k]
        bn = dict(node[keys[-1]])
        bn["mean"] = momentum * bn["mean"] + (1 - momentum) * mean
        bn["var"] = momentum * bn["var"] + (1 - momentum) * var
        node[keys[-1]] = bn
    return params


def make_train_step(module: Module, optimizer, bn_momentum: float = 0.9) -> Callable:
    """Pure (state, batch) -> (state, metrics) step; jit/pjit-ready.

    BatchNorm layers use batch statistics in the forward pass and their moving
    mean/var params are EMA-updated from the same statistics (side-channel via
    ``stats_out``), so eval-mode inference after training is correct.
    """

    def step(state: TrainState, batch: Dict[str, Any]) -> Tuple[TrainState, Dict]:
        import jax
        import optax

        x, y = batch["x"], batch["y"]
        mask = batch.get("mask")

        def loss_fn(params):
            stats: Dict[str, Any] = {}
            if isinstance(module, Sequential):
                logits = module.apply(params, x, train=True, stats_out=stats)
            else:
                logits = module.apply(params, x, train=True)
            return cross_entropy_loss(logits, y, mask), (logits, stats)

        (loss, (logits, stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        if stats:
            params = _apply_bn_ema(jax.tree.map(lambda v: v, params), stats, bn_momentum)
        metrics = {"loss": loss, "accuracy": accuracy(logits, y, mask)}
        return TrainState(params, opt_state, state.step + 1), metrics

    return step


class PreemptionGuard:
    """Turns a preemption signal (SIGTERM — what TPU VMs get on maintenance
    events and spot reclaims) into a flag the training loop polls between
    steps, so the loop checkpoints and exits cleanly instead of dying
    mid-step.

    ``request()`` triggers the same path programmatically (tests, cluster
    agents that learn of preemption out-of-band). Installing the handler only
    works on the main thread; elsewhere the guard silently degrades to the
    programmatic path.
    """

    def __init__(self, signals: Tuple[int, ...] = (_signal.SIGTERM,)):
        self.signals = tuple(signals)
        self._event = threading.Event()
        self._prev: Dict[int, Any] = {}

    def request(self) -> None:
        self._event.set()

    def requested(self) -> bool:
        return self._event.is_set()

    def __enter__(self) -> "PreemptionGuard":
        for sig in self.signals:
            try:
                self._prev[sig] = _signal.signal(
                    sig, lambda *_: self._event.set())
            except ValueError:  # not the main thread
                pass
        return self

    def __exit__(self, *exc) -> None:
        for sig, prev in self._prev.items():
            try:
                _signal.signal(sig, prev)
            except ValueError:
                pass
        self._prev.clear()


@dataclasses.dataclass
class TrainLoopResult:
    state: TrainState
    steps_run: int
    preempted: bool
    last_metrics: Optional[Dict[str, float]]


def _batch_rows(batch) -> Optional[int]:
    """Leading-dim row count of a step batch (tuple/dict/array pytrees);
    None when nothing array-like is found."""
    if isinstance(batch, (tuple, list)) and batch:
        return _batch_rows(batch[0])
    if isinstance(batch, dict) and batch:
        return _batch_rows(next(iter(batch.values())))
    shape = getattr(batch, "shape", None)
    if shape:
        return int(shape[0])
    return None


def run_train_loop(state: TrainState, step_fn: Callable, batches: Iterable,
                   *, checkpoint_path: Optional[str] = None,
                   every_k: int = 100,
                   guard: Optional[PreemptionGuard] = None,
                   resume: bool = True,
                   log: Optional[Callable[[str], None]] = None,
                   registry=None) -> TrainLoopResult:
    """Drive ``step_fn`` over ``batches`` with checkpoint/resume and a
    preemption hook — the DNN counterpart of the GBDT checkpointed fit.

    ``checkpoint_path``: TrainState saved there every ``every_k`` steps and
    on preemption (models.checkpoint/orbax — sharded arrays restore onto
    their original device placement via the live ``state`` as reference).
    ``resume=True`` restores it when present and skips the already-trained
    prefix of ``batches`` by the restored step counter — a deterministic
    (seeded/indexed) batch stream therefore replays the exact uninterrupted
    schedule. ``guard``: a PreemptionGuard polled between steps; when it
    fires, the loop checkpoints once more and returns ``preempted=True``.

    ``registry``: obs MetricsRegistry receiving the per-step series
    (``mmlspark_train_*{engine="dnn"}``: step time, examples/s, loss,
    checkpoint latency); defaults to the process-wide registry so
    ``/_mmlspark/metrics`` scrapes see training progress.
    """
    import time as _time

    from .checkpoint import load_train_state, save_train_state
    from ..core.runtime import ensure_compile_cache
    from ..obs.metrics import TrainRecorder

    ensure_compile_cache()
    recorder = TrainRecorder("dnn", registry=registry)

    def _save_timed(st):
        t0 = _time.perf_counter()
        save_train_state(st, checkpoint_path)
        recorder.checkpoint(_time.perf_counter() - t0)

    start_step = 0
    if checkpoint_path is not None and resume:
        import os

        if os.path.exists(checkpoint_path):
            state = load_train_state(checkpoint_path, like=state)
            start_step = int(np.asarray(state.step))
            if log:
                log(f"resumed from {checkpoint_path} at step {start_step}")

    steps_run = 0
    metrics_out: Optional[Dict[str, float]] = None
    dirty = False  # steps since the last save
    preempted = False
    for i, batch in enumerate(batches):
        if i < start_step:
            continue  # replayed prefix: already folded into the checkpoint
        if guard is not None and guard.requested():
            preempted = True
            break
        faults.fire(faults.TRAIN_STEP, step=i, engine="dnn")
        t_step = _time.perf_counter()
        state, metrics = step_fn(state, batch)
        dur = _time.perf_counter() - t_step
        steps_run += 1
        dirty = True
        metrics_out = metrics
        recorder.step(dur, examples=_batch_rows(batch),
                      loss=(metrics or {}).get("loss"))
        if checkpoint_path is not None and steps_run % max(every_k, 1) == 0:
            _save_timed(state)
            dirty = False
    else:
        if guard is not None and guard.requested():
            preempted = True
    if checkpoint_path is not None and (dirty or preempted):
        _save_timed(state)
    if metrics_out is not None:
        metrics_out = {k: float(v) for k, v in metrics_out.items()}
    return TrainLoopResult(state=state, steps_run=steps_run,
                           preempted=preempted, last_metrics=metrics_out)


def param_sharding_rules(params, mesh):
    """NamedSharding tree: cin->fsdp, cout->tensor for matmul/conv kernels,
    replicate the rest; any non-divisible dim falls back to replicated."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    fsdp = mesh.shape.get(FSDP_AXIS, 1)
    tens = mesh.shape.get(TENSOR_AXIS, 1)

    def rule(leaf):
        shape = leaf.shape
        if len(shape) == 4:  # conv kernel [kh,kw,cin,cout]
            spec = [None, None,
                    FSDP_AXIS if fsdp > 1 and shape[2] % fsdp == 0 else None,
                    TENSOR_AXIS if tens > 1 and shape[3] % tens == 0 else None]
            return NamedSharding(mesh, P(*spec))
        if len(shape) == 2:  # dense kernel [din,dout]
            spec = [FSDP_AXIS if fsdp > 1 and shape[0] % fsdp == 0 else None,
                    TENSOR_AXIS if tens > 1 and shape[1] % tens == 0 else None]
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree.map(rule, params)


def batch_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P((DATA_AXIS, FSDP_AXIS)))


def init_train_state(module: Module, in_shape, optimizer, seed: int = 0,
                     mesh=None) -> TrainState:
    """Initialize params (+opt state); if a mesh is given, place both sharded."""
    import jax

    params, _ = module.init(jax.random.PRNGKey(seed), in_shape)
    if mesh is not None:
        shardings = param_sharding_rules(params, mesh)
        params = jax.device_put(params, shardings)
    opt_state = optimizer.init(params)
    step = np.int32(0)
    return TrainState(params, opt_state, step)


def compile_train_step(module: Module, optimizer, mesh=None):
    """jit the train step. Sharding comes from the *inputs* (GSPMD propagation):
    place state via init_train_state(mesh=...) and batches via batch_sharding(mesh);
    XLA inserts the DP gradient psums / FSDP all-gathers / TP collectives.

    Pass ``mesh`` when training over a multi-device mesh: activations are then
    anchored to the batch sharding via module.activation_sharding — without
    the anchors the XLA SPMD partitioners (Shardy and GSPMD alike) produce
    WRONG conv gradients for channel-sharded kernels at small spatial sizes
    (see activation_sharding's docstring; the equivalence test in
    tests/test_models.py fails by ~1e-1 without this)."""
    import jax

    from ..core.runtime import ensure_compile_cache
    from .module import activation_sharding

    ensure_compile_cache()
    step = make_train_step(module, optimizer)
    if mesh is None:
        return jax.jit(step, donate_argnums=(0,))

    constraint = batch_sharding(mesh)

    def step_anchored(state, batch):
        with activation_sharding(constraint):  # trace-time context
            return step(state, batch)

    return jax.jit(step_anchored, donate_argnums=(0,))
