"""Pipeline-parallel mesh execution: fused segments resident on disjoint
``pipe``-axis sub-meshes with device-to-device micro-batch streaming.

The mesh has declared a ``pipe`` axis since PR 14 (parallel/mesh.py) that
no execution path used: shardplan shards WITHIN a segment and
mega-dispatch amortizes dispatch, but a deep fused chain (decode ->
featurize -> DNN -> GBDT) still ran its segments serially on the whole
mesh, with every inter-segment tensor bouncing through the host ring.
This module is the missing execution shape:

  - ``build_pipe_plan(nodes, mesh, depth)`` finds the longest run of
    consecutive fused segments whose device outputs the next segment can
    consume DIRECTLY (``chainable``: the handoff columns' final writers
    have no host ``finalize``, the consumer has no host ``prepare``),
    groups the run into <= ``min(depth, pipe)`` contiguous stages
    balanced by ``SegmentCostModel.predict_ms`` (equal-count while
    uncalibrated), and assigns each stage a disjoint sub-mesh split along
    the pipe axis (non-pipe axes preserved, so ``data``/``feature``
    partition specs still compose INSIDE a stage).
  - ``PipeStageSharding`` is the executor-facing placement handle: by
    default a stage runs REPLICATED over its sub-mesh — GSPMD with fully
    replicated in/out shardings compiles the exact single-device program
    onto the stage's devices, so pipelined replies stay BITWISE-identical
    to serial execution. A tuned per-segment spec (``sharding=`` knob)
    resolves against the SUB-mesh and composes as the ``inner`` sharding
    (that path inherits the sharded contract: allclose, not bitwise —
    tests/test_sharding.py).
  - ``PipeRunner`` streams stage-0's padded micro-batches through the
    stage chain with a bounded in-flight window (default ``depth + 1``):
    each micro-batch is dispatched through EVERY stage before the oldest
    in-flight chain is drained, so all stages stay busy after the
    ``S - 1``-tick fill. Inter-stage tensors move device-to-device with a
    resharding ``jax.device_put`` between the stage shardings — never a
    host readback — and each measured handoff feeds the cost model's
    ``pipe_handoff`` collective fit (the transfer term
    ``predict_pipelined_ms`` prices).
  - A stage whose sub-mesh fails mid-stream (the ``pipe.stage_wedge``
    chaos seam, or a real dispatch/handoff failure) raises
    :class:`StageWedged`; the model quarantines the stage's devices
    (``PipeSupervision`` -> ``ReplicaSupervisor.note_stage_wedged``),
    re-plans at depth N-1 on the survivors via ``degrade_after_wedge``,
    and re-runs the in-flight DataFrame — results are bitwise-identical
    either way, so no request is dropped.

Per-partition contracts the streaming path cannot hold (host-prep rows,
dtype-gate rejections, empty partitions) degrade that partition to the
plain serial executor chain — slower, never wrong — mirroring the fused
host fallback. ``parallel/pipeline_parallel.py``'s ``pipeline_apply``
scan stays the shape-uniform TRAINING idiom; inference segments have
per-stage shapes and executables, so this is its per-stage-dispatch
counterpart with in-flight handoff. docs/pipeline_parallel.md.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core import faults
from .mesh import PIPE_AXIS, MeshSpec, make_mesh, replicated_sharding

__all__ = [
    "PIPE_HANDOFF_OP", "StageWedged", "chainable", "chainable_runs",
    "split_segments", "pipe_submeshes", "balance_stages",
    "build_pipe_plan", "PipeStage",
    "PipePlan", "PipeStageSharding", "stage_sharding_for", "PipeRunner",
    "degrade_after_wedge", "PipeSupervision",
]

#: collective-fit key for the measured inter-stage d2d transfer
#: (costmodel.observe_collective / collective_ms)
PIPE_HANDOFF_OP = "pipe_handoff"


class StageWedged(RuntimeError):
    """A pipeline stage's whole sub-mesh failed mid-stream. Unlike a
    single-partition contract violation (which degrades to the serial
    chain), this is a PLACEMENT failure: the model must quarantine the
    stage's devices and re-plan at depth N-1 before re-running."""

    def __init__(self, stage: int, reason: str = ""):
        super().__init__(reason or f"pipeline stage {stage} wedged")
        self.stage = int(stage)


# ---------------------------------------------------------------------------
# plan derivation
# ---------------------------------------------------------------------------


def chainable(prev, nxt) -> bool:
    """Whether ``nxt``'s fused program can consume ``prev``'s DEVICE
    outputs directly — the d2d handoff contract:

      - every external input of ``nxt`` is a device readback of ``prev``
        (not one of its host-demoted columns), so no value must
        round-trip through the host;
      - the FINAL writer of each handoff column has no host ``finalize``
        (the default finalize ships raw arrays, so the device value IS
        the column value bit-for-bit);
      - no stage of ``nxt`` has a host ``prepare`` hook (prep must see
        host rows, which a device-resident handoff never materializes).
    """
    try:
        readback = {k for k, _ in prev.readback_plan(())}
    except Exception:  # noqa: BLE001 — unplannable segment: not chainable
        return False
    avail = readback - set(prev.host_cols)
    ext = list(nxt.external_in_cols)
    if not ext or not set(ext) <= avail:
        return False
    final_writer: Dict[str, Any] = {}
    for dfn in prev.dfns:
        for c in dfn.out_cols:
            final_writer[c] = dfn
    for c in ext:
        writer = final_writer.get(c)
        if writer is None or writer.finalize is not None:
            return False
    return all(dfn.prepare is None for dfn in nxt.dfns)


def chainable_runs(nodes: Sequence[Any]
                   ) -> List[List[Tuple[int, Any]]]:
    """Maximal runs of >= 2 CONSECUTIVE plan nodes that are all fused
    Segments with each adjacent pair chainable — the candidate pipelines
    of a fused plan, as (node index, segment) lists. Shared by
    ``build_pipe_plan`` and the tuner's depth proposal."""
    runs: List[List[Tuple[int, Any]]] = []
    cur: List[Tuple[int, Any]] = []
    for j, node in enumerate(nodes):
        if hasattr(node, "dfns"):
            if cur and cur[-1][0] == j - 1 and chainable(cur[-1][1], node):
                cur.append((j, node))
                continue
            if len(cur) >= 2:
                runs.append(cur)
            cur = [(j, node)]
        else:
            if len(cur) >= 2:
                runs.append(cur)
            cur = []
    if len(cur) >= 2:
        runs.append(cur)
    return runs


def split_segments(nodes: Sequence[Any]) -> List[Any]:
    """The PIPELINE VIEW of a fused plan: every fused Segment is re-cut
    at each clean d2d boundary — the next DeviceFn can head its own
    program (no host ``prepare``) and the handoff columns are
    finalize-free device readbacks of what came before — into maximal
    chainable sub-segments. A single-device plan fuses a whole chain
    into ONE segment because any break there costs a host round-trip; a
    pipeline wants the OPPOSITE cut, so each stage can live on its own
    pipe-axis sub-mesh with tensors moving device-to-device. Serial
    semantics are unchanged: each sub-segment runs the same DeviceFns in
    the same order, and the repo's bitwise contract already holds across
    segment boundaries (fused == unfused per-stage chain). Nodes that
    cannot split pass through unchanged: host stages, single-stage
    segments, and stitched segments (their transpiled finalize shims pin
    host-only columns mid-segment)."""
    out: List[Any] = []
    for node in nodes:
        dfns = getattr(node, "dfns", None)
        if (not dfns or len(dfns) < 2
                or getattr(node, "host_cols", None)):
            out.append(node)
            continue
        out.extend(_split_one(node))
    return out


def _split_one(seg) -> List[Any]:
    """Cut one fused segment at every DeviceFn that can head its own
    program, then re-merge any adjacent pair the ``chainable`` d2d
    contract rejects (a cross-boundary read of an earlier group's
    column, or a boundary writer with a host finalize)."""
    groups: List[List[int]] = [[0]]
    for i in range(1, len(seg.dfns)):
        if seg.dfns[i].prepare is None:
            groups.append([i])
        else:
            groups[-1].append(i)
    if len(groups) == 1:
        return [seg]

    def build(idxs: List[int]):
        sub = type(seg)()
        for i in idxs:
            sub.add(seg.stages[i], seg.dfns[i])
        return sub

    merged = [build(groups[0])]
    gidx = [list(groups[0])]
    for g in groups[1:]:
        sub = build(g)
        if chainable(merged[-1], sub):
            merged.append(sub)
            gidx.append(list(g))
        else:
            gidx[-1].extend(g)
            merged[-1] = build(gidx[-1])
    if len(merged) == 1:
        return [seg]
    return merged


def pipe_submeshes(mesh, depth: int) -> Optional[List[Any]]:
    """Split ``mesh`` into ``depth`` disjoint sub-meshes along the pipe
    axis, preserving every non-pipe axis size — stage i owns pipe
    coordinate group i, and ``data``/``feature`` specs still resolve
    inside each stage. None when the mesh's pipe axis cannot cover
    ``depth`` stages."""
    depth = int(depth)
    shape = dict(getattr(mesh, "shape", {}) or {})
    p = int(shape.get(PIPE_AXIS, 1))
    axes = list(getattr(mesh, "axis_names", ()) or ())
    if depth < 2 or p < depth or PIPE_AXIS not in axes:
        return None
    arr = np.asarray(mesh.devices)
    pipe_idx = axes.index(PIPE_AXIS)
    sizes = {a: int(shape.get(a, 1))
             for a in ("data", "fsdp", "tensor", "seq", "expert")}
    out = []
    for group in np.array_split(np.arange(p), depth):
        sub = np.take(arr, group, axis=pipe_idx)
        # sub keeps the original axis order, so its flat device list
        # reshapes back to exactly this block inside make_mesh
        out.append(make_mesh(MeshSpec(pipe=len(group), **sizes),
                             device_list=list(sub.flat)))
    return out


def balance_stages(costs: Sequence[Optional[float]], depth: int
                   ) -> List[int]:
    """Contiguous stage sizes for a segment run: with a full
    ``predict_ms`` cost vector, minimize the max stage sum (the pipeline
    clock is its slowest stage); with ANY cost unknown, the equal-count
    split — the count-balanced default an uncalibrated model must not
    deviate from."""
    n = len(costs)
    depth = max(1, min(int(depth), n))
    if any(c is None for c in costs):
        return [len(g) for g in np.array_split(np.arange(n), depth)]
    import itertools
    best: Optional[Tuple[int, ...]] = None
    best_max = float("inf")
    for cuts in itertools.combinations(range(1, n), depth - 1):
        bounds = (0,) + cuts + (n,)
        clock = max(sum(float(c) for c in costs[a:b])
                    for a, b in zip(bounds, bounds[1:]))
        if clock < best_max - 1e-12:
            best, best_max = bounds, clock
    if best is None:  # unreachable: depth<=n guarantees one composition
        raise RuntimeError("balance_stages found no contiguous split")
    return [b - a for a, b in zip(best, best[1:])]


@dataclasses.dataclass
class PipeStage:
    """One pipeline stage: a contiguous group of fused segments resident
    on one pipe-axis sub-mesh."""

    index: int
    seg_nodes: Tuple[int, ...]  # plan-node indices of the member segments
    labels: Tuple[str, ...]
    mesh: Any
    predicted_ms: Optional[float] = None

    def device_ids(self) -> List[int]:
        return [int(getattr(d, "id", i)) for i, d in
                enumerate(np.asarray(self.mesh.devices).flat)]

    def describe(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"index": int(self.index),
                               "segments": list(self.labels),
                               "devices": self.device_ids()}
        if self.predicted_ms is not None:
            out["predicted_ms"] = round(float(self.predicted_ms), 4)
        return out


class PipePlan:
    """Placement of one consecutive run of chainable fused segments onto
    pipe-axis sub-mesh stages. ``nodes`` is the PIPELINE VIEW of the
    plan (``split_segments`` — fused segments re-cut at d2d boundaries);
    ``first``/``last`` bound the run inside THAT list (half-open);
    ``stage_of`` maps each member node index to its stage."""

    def __init__(self, stages: Sequence[PipeStage], first: int, last: int,
                 nodes: Optional[Sequence[Any]] = None):
        self.stages = list(stages)
        self.first = int(first)
        self.last = int(last)
        self.nodes = list(nodes) if nodes is not None else None
        self.depth = len(self.stages)
        self.stage_of: Dict[int, int] = {
            n: st.index for st in self.stages for n in st.seg_nodes}

    def describe(self) -> Dict[str, Any]:
        return {"depth": self.depth,
                "stages": [st.describe() for st in self.stages]}


def build_pipe_plan(nodes: Sequence[Any], mesh, depth: int,
                    model=None, batch: Optional[int] = None
                    ) -> Optional["PipePlan"]:
    """Derive a PipePlan from a fused plan: re-cut it into the pipeline
    view (``split_segments``), find the longest (first on tie) run of
    >= 2 consecutive chainable Segment nodes, group it into
    ``min(depth, pipe, run length)`` contiguous stages balanced by
    ``predict_ms`` (equal-count while uncalibrated), and build each
    stage's sub-mesh. The returned plan's ``nodes`` IS that view — the
    executor must run it, not the original plan. None = stay serial: no
    pipe axis to split, no eligible run, or depth < 2 after clamping."""
    if mesh is None:
        return None
    shape = dict(getattr(mesh, "shape", {}) or {})
    p = int(shape.get(PIPE_AXIS, 1))
    if p < 2 or int(depth) < 2:
        return None
    nodes = split_segments(nodes)
    runs = chainable_runs(nodes)
    if not runs:
        return None
    run = max(runs, key=len)
    depth_eff = min(int(depth), p, len(run))
    if depth_eff < 2:
        return None
    submeshes = pipe_submeshes(mesh, depth_eff)
    if submeshes is None:
        return None
    b = int(batch) if batch else run[0][1].batch_size()
    costs: List[Optional[float]] = []
    for _, seg in run:
        ms = None
        if model is not None:
            try:
                if model.calibrated(seg.label):
                    ms = model.predict_ms(seg.label, batch=b)
            except Exception:  # noqa: BLE001 — balance falls back to count
                ms = None
        costs.append(ms)
    sizes = balance_stages(costs, depth_eff)
    stages: List[PipeStage] = []
    k = 0
    for si, size in enumerate(sizes):
        chunk = run[k:k + size]
        chunk_costs = costs[k:k + size]
        k += size
        pred = sum(chunk_costs) \
            if all(c is not None for c in chunk_costs) else None
        stages.append(PipeStage(
            index=si, seg_nodes=tuple(j for j, _ in chunk),
            labels=tuple(seg.label for _, seg in chunk),
            mesh=submeshes[si], predicted_ms=pred))
    return PipePlan(stages, first=run[0][0], last=run[-1][0] + 1,
                    nodes=nodes)


# ---------------------------------------------------------------------------
# stage placement handle
# ---------------------------------------------------------------------------


class PipeStageSharding:
    """Executor-facing placement for one segment of a pipeline stage —
    the same interface SegmentSharding exposes (shardplan.py), so
    ``SegmentExecutor`` needs no pipeline-specific branches.

    Default placement is REPLICATED over the stage's sub-mesh: GSPMD with
    fully replicated in/out shardings degenerates to the original
    single-device program on every stage device, so the pipelined answer
    stays bitwise-identical to serial execution while the stage owns its
    devices. A tuned ``inner`` SegmentSharding (resolved against the
    SUB-mesh) composes on top and carries the sharded (allclose)
    contract."""

    def __init__(self, segment, submesh, stage_index: int, depth: int,
                 inner=None):
        self.segment = segment
        self.mesh = submesh
        self.stage_index = int(stage_index)
        self.depth = int(depth)
        self.inner = inner
        self.device_ids = tuple(
            int(getattr(d, "id", i)) for i, d in
            enumerate(np.asarray(submesh.devices).flat))

    @property
    def shards(self) -> int:
        return self.inner.shards if self.inner is not None else 1

    def cache_key(self) -> Tuple:
        """CompileCache key tail: a stage-resident executable targets THIS
        sub-mesh's devices — key it apart from the single-device program,
        from other stages, and from post-replan placements of the same
        stage index (the device ids pin the exact sub-mesh)."""
        tail = ("pipe", self.stage_index, self.depth, self.device_ids)
        if self.inner is not None:
            return self.inner.cache_key() + tail
        return tail

    def shape_prefix(self) -> str:
        """Decorate the shape key (``pipe=s<i>of<d>;``) so the cost
        model's bucket parser skips stage-resident records generically —
        same contract as ``spec=``/``mega``/``variant`` prefixes."""
        pre = self.inner.shape_prefix() if self.inner is not None else ""
        return f"pipe=s{self.stage_index}of{self.depth};" + pre

    def input_sharding(self, col: str):
        """Placement a handoff column must land in before this stage's
        dispatch (the reshard target of the d2d ``jax.device_put``)."""
        if self.inner is not None:
            sh = self.inner.input_shardings().get(col)
            if sh is not None:
                return sh
        return replicated_sharding(self.mesh)

    def jit_kwargs(self, mega_k: int = 1) -> Dict[str, Any]:
        if self.inner is not None:
            kwargs = dict(self.inner.jit_kwargs(mega_k=mega_k))
            # never donate pipelined inputs: a stage's staged input IS the
            # upstream stage's output buffer, which the drain still reads
            # (collected readbacks) — donation would free it mid-flight
            kwargs.pop("donate_argnums", None)
            return kwargs
        rep = replicated_sharding(self.mesh)
        # a single sharding is a pytree prefix: replicate params and every
        # staged column over the stage sub-mesh
        return {"in_shardings": (rep, rep), "out_shardings": rep}

    def put_params(self, params):
        import jax
        if self.inner is not None:
            return self.inner.put_params(params)
        return jax.device_put(params, replicated_sharding(self.mesh))

    def device_put(self, arrays: Dict[str, Any]):
        """Stage one HOST batch onto the stage sub-mesh — stage 0 of the
        stream only; downstream stages receive device arrays through
        :meth:`reshard`."""
        import jax
        if self.inner is not None:
            return self.inner.device_put(arrays)
        rep = replicated_sharding(self.mesh)
        return {c: jax.device_put(v, rep) for c, v in arrays.items()}

    def reshard(self, arrays: Dict[str, Any]) -> Dict[str, Any]:
        """Device-to-device handoff: move the upstream stage's output
        arrays onto THIS stage's sub-mesh with a resharding
        ``jax.device_put`` — never a host readback."""
        import jax
        return {c: jax.device_put(v, self.input_sharding(c))
                for c, v in arrays.items()}

    def describe(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"stage": self.stage_index,
                               "depth": self.depth,
                               "devices": list(self.device_ids)}
        if self.inner is not None:
            out["spec"] = self.inner.describe()
        return out


def stage_sharding_for(segment, stage: PipeStage, depth: int,
                       spec_name: Optional[str] = None
                       ) -> PipeStageSharding:
    """Build the segment's stage placement, composing its tuned partition
    spec (resolved against the stage SUB-mesh) when one is named and
    resolvable — resolution failure degrades to the replicated (bitwise)
    stage placement, never fails the transform."""
    inner = None
    if spec_name:
        try:
            from .shardplan import sharding_for
            inner = sharding_for(segment, stage.mesh, spec_name)
        except Exception:  # noqa: BLE001 — degrade to replicated stage
            inner = None
    return PipeStageSharding(segment, stage.mesh, stage.index, depth,
                             inner=inner)


# ---------------------------------------------------------------------------
# streaming runner
# ---------------------------------------------------------------------------


class PipeRunner:
    """Streams micro-batches through the pipelined segment chain.

    Stage 0's executor preps/buckets/stages each partition exactly as the
    serial path does (same micro-batch boundaries, same padding); every
    downstream segment consumes its predecessor's DEVICE outputs through
    a synthesized execution state — no host prep, no readback. A bounded
    in-flight window (default ``depth + 1`` chains) keeps every stage
    dispatching while older chains drain. Partitions the streaming
    contract cannot hold run the plain serial executor chain instead.
    """

    def __init__(self, pplan: PipePlan, executors: Sequence[Any],
                 stats: Sequence[Any], cost_model=None,
                 window: Optional[int] = None):
        self.pplan = pplan
        self.execs = list(executors)
        self.stats = list(stats)
        self.cost_model = cost_model
        self.window = max(1, int(window)) if window else pplan.depth + 1
        node_order = sorted(pplan.stage_of)
        #: chain position (0..n_segments-1) -> stage index
        self.seg_stage = [pplan.stage_of[j] for j in node_order]
        self.micro_batches = 0
        self.partitions = 0
        self.serial_parts = 0
        self.busy_s = [0.0] * pplan.depth
        self.handoff_bytes = [0.0] * pplan.depth
        self.handoff_s = [0.0] * pplan.depth
        self.wall_s = 0.0

    # -- public entry ------------------------------------------------------

    def run(self, df):
        import jax

        from ..core.device_stage import FusionUnsupported
        from ..core.fusion import _HostFallback

        t0 = time.perf_counter()
        params = [ex._put_params(jax) for ex in self.execs]
        parts_per_seg: List[List[Dict[str, np.ndarray]]] = \
            [[] for _ in self.execs]
        for part in df.partitions:
            self.partitions += 1
            try:
                outs = self._run_partition(dict(part), params)
                for lst, p in zip(parts_per_seg, outs):
                    lst.append(p)
            except StageWedged:
                raise
            except (_HostFallback, FusionUnsupported):
                # per-partition contract violation: the serial executor
                # chain (with its own host-fallback safety) — bitwise the
                # unpipelined answer, the waste is counted
                self._serial_partition(part, df.schema, parts_per_seg)
        out = df
        for ex, parts in zip(self.execs, parts_per_seg):
            out = ex._overlay(out, parts)
        # the chain overlaps on purpose: per-segment walls would double-
        # count, so the stream's wall lives in the pipeline stats section
        self.wall_s += time.perf_counter() - t0
        return out

    # -- per-partition streaming ------------------------------------------

    def _serial_partition(self, part, schema, parts_per_seg) -> None:
        from ..core.dataframe import DataFrame

        self.serial_parts += 1
        sub = DataFrame([dict(part)], schema.copy())
        for j, ex in enumerate(self.execs):
            sub = ex.run(sub, self.stats[j])
            parts_per_seg[j].extend(sub.partitions)

    def _run_partition(self, part: Dict[str, np.ndarray], params
                       ) -> List[Dict[str, np.ndarray]]:
        from ..core.fusion import _HostFallback

        ex0 = self.execs[0]
        state0 = ex0._prep_partition(part, self.stats[0])
        if state0["n_valid"] <= 0:
            raise _HostFallback("no valid rows to stream")
        states = [state0]
        for ex in self.execs[1:]:
            seg = ex.segment
            readback = seg.readback_plan(ex._transpiled)
            # synthesized state: the segment's inputs arrive device-
            # resident from the upstream stage, so there is no host part,
            # no validity scan and no prepare (the chainable() gate
            # guaranteed none is needed); _emit_partition fills part/
            # valid/n at drain time from the upstream emit
            states.append({
                "part": None, "sub": {}, "ctx": {}, "valid": None,
                "n": None, "n_valid": None,
                "ext": list(seg.external_in_cols),
                "staged_cols": list(seg.external_in_cols),
                "readback": readback,
                "keys": [k for k, _ in readback]})
        steps = [ex._make_step(p, st)
                 for ex, p, st in zip(self.execs, params, states)]
        collected: List[Dict[str, List[np.ndarray]]] = \
            [{k: [] for k in st["keys"]} for st in states]
        inflight: deque = deque()
        first_batch = True
        src, filler = ex0._fill_ahead(state0, self.stats[0])
        try:
            for batch in src:
                chain = self._dispatch_chain(batch, steps, states,
                                             check_gates=first_batch)
                first_batch = False
                self.micro_batches += 1
                inflight.append(chain)
                while len(inflight) > self.window:
                    self._resolve(inflight.popleft(), states, collected)
            while inflight:
                self._resolve(inflight.popleft(), states, collected)
        finally:
            if filler is not None:
                filler.close()
        return self._emit_chain(states, collected)

    def _fire_wedge(self, stage: int) -> None:
        try:
            faults.fire(faults.PIPE_STAGE_WEDGE, stage=int(stage))
        except Exception as e:  # noqa: BLE001 — any armed exc wedges
            raise StageWedged(int(stage), str(e))

    def _dispatch_chain(self, batch, steps, states, check_gates=False):
        """Dispatch one micro-batch through every stage: stage 0 stages
        from host, each stage boundary reshards device-to-device, every
        dispatch is async — the chain returns handles, drained later by
        ``_resolve`` so up to ``window`` chains overlap."""
        from ..parallel.ingest import BatchTiming, timed_stage

        ex0 = self.execs[0]
        s0 = self.seg_stage[0]
        self._fire_wedge(s0)
        staged, timing0 = timed_stage(ex0._put, batch)
        td = time.perf_counter()
        try:
            handle = steps[0](staged)
        except StageWedged:
            raise
        except Exception as e:  # noqa: BLE001 — stage dispatch died
            raise StageWedged(s0, f"stage 0 dispatch failed: {e}")
        now = time.perf_counter()
        timing0.dispatch_s = now - td
        self.busy_s[s0] += now - td
        handles = [handle]
        timings = [timing0]
        env: Dict[str, Any] = dict(zip(states[0]["keys"], handle[0]))
        # a column stage 0 hands through is no output of its program (the
        # host emits it from the rows it staged): downstream reads the
        # staged input itself, which pipelined programs never donate
        for c, src in (states[0].get("host_cols") or {}).items():
            env[c] = staged[0][src]
        m = handle[1]
        for j in range(1, len(self.execs)):
            xs = {c: env[c] for c in states[j]["ext"]}
            sj, sprev = self.seg_stage[j], self.seg_stage[j - 1]
            timing = BatchTiming(rows=int(m))
            if xs:
                lead = next(iter(xs.values()))
                timing.padded_rows = int(np.shape(lead)[0] or 0)
            if sj != sprev:
                self._fire_wedge(sj)
                t1 = time.perf_counter()
                try:
                    xs = self.execs[j].sharding.reshard(xs)
                except StageWedged:
                    raise
                except Exception as e:  # noqa: BLE001 — handoff died
                    raise StageWedged(sj, f"handoff to stage {sj} "
                                          f"failed: {e}")
                dt = time.perf_counter() - t1
                nbytes = float(sum(int(getattr(v, "nbytes", 0) or 0)
                                   for v in xs.values()))
                self.handoff_s[sj] += dt
                self.handoff_bytes[sj] += nbytes
                timing.h2d_s = dt  # the stage's ingest IS the d2d handoff
                timing.bytes_in = int(nbytes)
                if self.cost_model is not None and nbytes > 0:
                    obs = getattr(self.cost_model, "observe_collective",
                                  None)
                    if callable(obs):
                        try:
                            obs(PIPE_HANDOFF_OP, nbytes, dt)
                        except Exception:  # noqa: BLE001 — obs-only
                            pass
            if check_gates:
                self._check_gates(j, xs)
            t2 = time.perf_counter()
            try:
                hj = steps[j]((xs, m))
            except StageWedged:
                raise
            except Exception as e:  # noqa: BLE001 — stage dispatch died
                raise StageWedged(sj, f"stage {sj} dispatch failed: {e}")
            now = time.perf_counter()
            timing.dispatch_s = now - t2
            self.busy_s[sj] += now - t2
            handles.append(hj)
            timings.append(timing)
            env.update(zip(states[j]["keys"], hj[0]))
        return handles, timings

    def _check_gates(self, j: int, xs: Dict[str, Any]) -> None:
        """First-micro-batch contract check for a downstream segment: the
        same ``accepts`` dtype gates its serial prep would evaluate on
        materialized rows, evaluated on the device arrays' row view
        (batched leading dim stripped). A failing gate degrades the
        partition to the serial chain — bitwise the unpipelined answer,
        which runs the IDENTICAL gate on host rows."""
        from ..core.fusion import _HostFallback

        ex = self.execs[j]
        probes = {c: {"dtype": np.dtype(v.dtype),
                      "ndim": max(0, int(np.ndim(v)) - 1),
                      "sparse": False, "mixed": False}
                  for c, v in xs.items()}
        for dfn, stage in zip(ex.segment.dfns, ex.segment.stages):
            mine = {c: probes[c] for c in dfn.in_cols if c in probes}
            if mine and dfn.accepts is not None and not dfn.accepts(mine):
                raise _HostFallback(
                    f"{type(stage).__name__} dtype gate (pipelined)")

    def _resolve(self, chain, states, collected) -> None:
        """Drain the oldest in-flight chain: block in stage order (each
        residual wait is that stage's un-hidden compute) and collect every
        segment's readbacks."""
        from ..parallel.ingest import _block_ready

        handles, timings = chain
        for j, (st, handle, timing) in enumerate(zip(states, handles,
                                                     timings)):
            sj = self.seg_stage[j]
            t0 = time.perf_counter()
            _block_ready(handle)
            t1 = time.perf_counter()
            timing.compute_s = t1 - t0
            outs = self.execs[j]._fetch(handle)
            t2 = time.perf_counter()
            timing.readback_s = t2 - t1
            self.busy_s[sj] += t2 - t0
            self.stats[j].record(timing)
            for k, y in zip(st["keys"], outs):
                collected[j][k].append(y)

    def _emit_chain(self, states, collected) -> List[Dict[str, np.ndarray]]:
        """Finalize the chain bottom-up exactly as the serial path would:
        each segment's emit runs over its predecessor's emitted partition,
        with validity collapsing after any ``drop_invalid`` segment (the
        rows are GONE from the downstream frame, so downstream emits see a
        fully valid shorter partition)."""
        outs: List[Dict[str, np.ndarray]] = []
        cur_part = states[0]["part"]
        cur_n = states[0]["n"]
        cur_valid = states[0]["valid"]
        n_valid = states[0]["n_valid"]
        for j, ex in enumerate(self.execs):
            st = states[j]
            if j > 0:
                st["part"] = cur_part
                st["n"] = cur_n
                st["valid"] = cur_valid
                st["n_valid"] = n_valid
            out_part = ex._emit_partition(st, collected[j])
            outs.append(out_part)
            if any(d.drop_invalid for d in ex.segment.dfns) \
                    and n_valid < cur_n:
                cur_n = n_valid
                cur_valid = np.ones(n_valid, dtype=bool)
            cur_part = out_part
        return outs

    # -- stats surface -----------------------------------------------------

    def stats_dict(self, requeues: Optional[Dict[int, int]] = None,
                   replans: int = 0) -> Dict[str, Any]:
        """The ``fusion_stats()["pipeline"]`` section (absent entirely
        when no pipe plan ran). Busy/bubble numbers are honest host-side
        CPU measurements of this run — occupancy evidence, not a device
        profile."""
        wall = max(self.wall_s, 1e-9)
        mb = self.micro_batches
        s = self.pplan.depth
        bubble = (s - 1) / (mb + s - 1) if mb > 0 else 0.0
        stages = []
        for st in self.pplan.stages:
            i = st.index
            d = st.describe()
            d["busy_ms"] = round(self.busy_s[i] * 1e3, 3)
            d["busy_ratio"] = round(min(1.0, self.busy_s[i] / wall), 4)
            d["handoff_bytes"] = int(self.handoff_bytes[i])
            d["handoff_ms"] = round(self.handoff_s[i] * 1e3, 3)
            d["requeues"] = int((requeues or {}).get(i, 0))
            stages.append(d)
        return {"depth": s, "window": self.window, "micro_batches": mb,
                "partitions": self.partitions,
                "serial_fallback_partitions": self.serial_parts,
                "bubble_ratio": round(bubble, 4),
                "handoff_bytes": int(sum(self.handoff_bytes)),
                "handoff_ms": round(sum(self.handoff_s) * 1e3, 3),
                "wall_ms": round(wall * 1e3, 3),
                "replans": int(replans),
                "stages": stages}


# ---------------------------------------------------------------------------
# failure handling / supervision
# ---------------------------------------------------------------------------


def degrade_after_wedge(mesh, pplan: PipePlan, stage_index: int
                        ) -> Tuple[Any, int]:
    """(surviving mesh, new depth) after ``stage_index`` wedged: drop the
    stage's devices, rebuild a ``pipe = depth - 1`` mesh over the
    survivors when they divide evenly, else a flat data mesh at depth 1
    (serial execution on the survivors). (None, 1) when nothing
    survives."""
    dead = {id(d) for d in
            np.asarray(pplan.stages[int(stage_index)].mesh.devices).flat}
    survivors = [d for d in np.asarray(mesh.devices).flat
                 if id(d) not in dead]
    if not survivors:
        return None, 1
    new_depth = int(pplan.depth) - 1
    if new_depth >= 2 and len(survivors) % new_depth == 0:
        try:
            return make_mesh(
                MeshSpec(data=len(survivors) // new_depth,
                         pipe=new_depth),
                device_list=survivors), new_depth
        except Exception:  # noqa: BLE001 — fall through to flat mesh
            pass
    return make_mesh(MeshSpec(data=len(survivors)),
                     device_list=survivors), 1


class PipeSupervision:
    """Extends shard-group quarantine (shardplan.MeshSupervision) to
    pipeline stages: registers each stage's flat device-index group with
    the supervisor, and on a wedged stage quarantines its devices
    (``ReplicaSupervisor.note_stage_wedged``), degrades the mesh, and
    re-arms the model at depth N-1 — the model then re-runs the in-flight
    DataFrame on the surviving sub-meshes, bitwise-identical, no request
    dropped."""

    def __init__(self, fused, mesh, supervisor=None):
        self.fused = fused
        self.mesh0 = mesh
        self.mesh = mesh
        self.supervisor = supervisor
        self.replans = 0
        self.depth: Optional[int] = None
        self._registered = False
        if fused is not None:
            fused._pipe_wedge_handler = self.on_stage_wedge
            fused._pipe_supervision = self
            if hasattr(fused, "set_mesh"):
                fused.set_mesh(mesh)

    def register(self, pplan: PipePlan) -> None:
        """Hand the plan's stage device groups (flat indices into the
        ORIGINAL mesh) to the supervisor, mirroring set_shard_groups."""
        self.depth = pplan.depth
        if self.supervisor is None:
            return
        setter = getattr(self.supervisor, "set_pipe_stages", None)
        if not callable(setter):
            return
        devs = list(np.asarray(self.mesh0.devices).flat)
        groups = []
        for st in pplan.stages:
            members = [i for i, d in enumerate(devs)
                       if any(d is sd for sd in
                              np.asarray(st.mesh.devices).flat)]
            groups.append(members)
        setter(groups)
        self._registered = True

    def on_stage_wedge(self, pplan: PipePlan, stage_index: int):
        """The model's wedge callback: quarantine, degrade, re-arm."""
        if not self._registered:
            self.register(pplan)
        if self.supervisor is not None:
            noter = getattr(self.supervisor, "note_stage_wedged", None)
            if callable(noter):
                noter(int(stage_index))
        new_mesh, new_depth = degrade_after_wedge(self.mesh, pplan,
                                                  stage_index)
        self.mesh = new_mesh
        self.depth = new_depth
        self.replans += 1
        if self.fused is not None:
            if hasattr(self.fused, "set_mesh"):
                self.fused.set_mesh(new_mesh)
            if hasattr(self.fused, "set_tuning"):
                self.fused.set_tuning(pipe_depth=new_depth)
        return new_mesh

    def describe(self) -> Dict[str, Any]:
        from .shardplan import mesh_topology
        return {"topology": mesh_topology(self.mesh),
                "original": mesh_topology(self.mesh0),
                "depth": self.depth, "replans": self.replans}
