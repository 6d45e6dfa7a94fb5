"""Pipeline parallelism: GPipe-style microbatch schedule over the ``pipe``
mesh axis.

The reference scales depth-wise only via Spark's row partitioning (all
executors hold the whole model); on TPU, models that exceed one chip's HBM
shard by LAYERS across the ``pipe`` axis. This module implements the classic
collective-permute pipeline (the scaling-book / shard_map-tutorial schedule):

  - stage ``s`` holds segment ``s`` of the layer stack (params stacked with
    a leading [S] dim sharded over ``pipe``);
  - time runs for ``M + S - 1`` ticks; at tick ``t`` every stage applies its
    segment to its current activation, then activations shift one hop to the
    next stage via ``ppermute`` (ICI neighbor traffic only);
  - stage 0 feeds microbatch ``t`` while stage ``S-1`` emits finished
    microbatch ``t-(S-1)`` — the steady state keeps every chip busy; the
    bubble is the usual ``(S-1)/(M+S-1)`` fraction.

``pipeline_apply`` is functional and grad-safe (ppermute has a transpose
rule, so ``jax.grad`` through the pipeline yields the backward schedule).
"""

from __future__ import annotations

from typing import Any, Callable

__all__ = ["pipeline_apply", "stack_stage_params"]


def stack_stage_params(per_stage_params):
    """[params_stage0, params_stage1, ...] (identical treedefs) -> one pytree
    with a leading [S] dim on every leaf — the layout pipeline_apply expects,
    sharded over the pipe axis via P('pipe', ...)."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda *ls: jnp.stack(ls), *per_stage_params)


def pipeline_apply(stage_fn: Callable[[Any, Any], Any], stacked_params,
                   microbatches, axis_name: str, axis_size: int):
    """Run microbatches through the stage pipeline.

    Args:
      stage_fn: ``(stage_params, x) -> y`` for ONE stage segment; activation
        shapes must be identical across stages (uniform residual width).
      stacked_params: pytree with leading [S] dim per leaf; inside shard_map
        each device sees its local [1, ...] slice (S sharded over
        ``axis_name``).
      microbatches: [M, ...] array of microbatch inputs, replicated.
      axis_name/axis_size: the pipe mesh axis and its (static) size.

    Returns [M, ...] outputs (valid on every device after the final psum-
    style broadcast from the last stage).

    Call under ``jax.shard_map`` with ``in_specs=(P('pipe'), P(), ...)``:

        out = shard_map(
            lambda p, xs: pipeline_apply(stage_fn, p, xs, 'pipe', S),
            mesh=mesh, in_specs=(P('pipe'), P()), out_specs=P())(params, xs)
    """
    import jax
    import jax.numpy as jnp

    S = axis_size
    M = microbatches.shape[0]
    stage = jax.lax.axis_index(axis_name)
    local = jax.tree.map(lambda a: a[0], stacked_params)  # [1,...] -> [...]

    # shard_map's vma typing: the scan carry becomes stage-varying
    # analysis: allow J001 -- pinned jax 0.9.0 always has pcast
    microbatches = jax.lax.pcast(microbatches, (axis_name,), to="varying")
    # derived arrays inherit the varying type from microbatches
    state = jnp.zeros_like(microbatches[0])
    outputs = jnp.zeros_like(microbatches)
    shift = [(i, (i + 1) % S) for i in range(S)]

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (clamped; masked out past M)
        feed = microbatches[jnp.minimum(t, M - 1)]
        x = jnp.where(stage == 0, feed, state)
        y = stage_fn(local, x)
        # last stage emits finished microbatch t-(S-1)
        out_idx = t - (S - 1)
        emit = jnp.logical_and(stage == S - 1, out_idx >= 0)
        outputs = jax.lax.cond(
            emit,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(out_idx, 0), 0),
            lambda o: o, outputs)
        # activations hop to the next stage (wraparound hop is ignored by
        # stage 0, which reads fresh microbatches instead)
        state = jax.lax.ppermute(y, axis_name, shift)
        return (state, outputs), None

    (_, outputs), _ = jax.lax.scan(
        tick, (state, outputs), jnp.arange(M + S - 1))
    # broadcast the last stage's collected outputs to every device
    last = jnp.equal(stage, S - 1).astype(outputs.dtype)
    return jax.lax.psum(outputs * last, axis_name)
