"""Sharding planner: cost-model-planned partition specs for fused segments.

Every fused segment (core/fusion.py) compiles for ONE device and serving
replicas are data-parallel over ``jax.local_devices()`` only — the single
biggest untouched scaling axis in ROADMAP. This module opens it, in the
spirit of Automap and "A Learned Performance Model for TPUs" (PAPERS.md):
partition specs are DERIVED from the stage graph and CHOSEN by the cost
model, never hand-annotated.

  - ``candidates(segment, mesh)`` derives the candidate partitionings a
    segment admits: batch-dim data parallelism over the mesh's ``data``
    axis by default (every external input shards its leading dim — always
    legal for the row-independent fused programs the planner builds), plus
    a model/feature-dim candidate over the ``tensor`` axis where every
    DeviceFn in the segment DECLARES a shardable feature dim
    (``DeviceFn.shard_dims``). Candidates are descriptions (no jax import)
    so the Tuner can enumerate them host-side.
  - ``sharding_for(segment, mesh, name)`` resolves a candidate into a
    ``SegmentSharding``: the ``NamedSharding``s for inputs/params/outputs
    (built over ``make_mesh()`` meshes via the parallel/mesh.py helpers,
    the single owner J001 enforces; jax 0.9.0), the pjit kwargs with
    ``donate_argnums`` on the ring-staged inputs, and the sharded
    ``device_put`` the executor stages batches through.
  - ``measure_collectives(mesh)`` times real all-reduce / all-gather
    probes over the mesh (via ``shard_map_compat``) and feeds the cost
    model's α·bytes collective term — ``choose_sharding`` then prices a
    candidate as flops/shards + α·bytes and becomes a journaled,
    one-step-rollback Tuner knob (core/tune.py).
  - ``shard_groups(mesh)`` / ``submesh_excluding(mesh, devices)`` /
    ``MeshSupervision`` make the PR 10 supervisor mesh-aware: a wedged
    chip quarantines its SHARD GROUP (every device sharing its data-axis
    slice — the model-parallel group it computes with), and the fused
    model re-plans onto the surviving submesh.

Unsharded stays bitwise-identical: with no mesh set (or a 1-shard
candidate) ``sharding_for`` returns None and the executor runs the exact
PR 13 code path — enforced by tests/test_sharding.py.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core import faults
from .mesh import (DATA_AXIS, TENSOR_AXIS, MeshSpec, data_sharding,
                   make_mesh, replicated_sharding, shard_map_compat)

__all__ = ["ShardCandidate", "SegmentSharding", "MeshSupervision",
           "candidates", "sharding_for", "tuner_candidates",
           "measure_collectives", "shard_groups", "group_of",
           "submesh_excluding", "donation_supported", "mesh_topology",
           "split_csr_rows", "ragged_allgather_bytes"]

#: candidate partitioning names (the values of the ``sharding`` tuner knob)
SPEC_DATA = "data"
SPEC_FEATURE = "feature"
SPEC_CSR_ROW = "csr_row"


# ---------------------------------------------------------------------------
# Candidate derivation (host-side: no jax import)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardCandidate:
    """One partitioning a segment admits over a mesh.

    ``in_dims`` maps each external input column to the array dim sharded
    over ``axis`` (None = replicated input); ``out_dim`` is the dim device
    outputs stay sharded on (None = replicated outputs — XLA inserts the
    reduce/gather). ``collective`` names the dominant collective the cost
    model prices (``all_gather`` for data-parallel readback, ``all_reduce``
    for feature-sharded partial results)."""

    name: str
    axis: str
    shards: int
    in_dims: Tuple[Tuple[str, Optional[int]], ...]
    out_dim: Optional[int]
    collective: str

    def describe(self) -> Dict[str, Any]:
        return {"name": self.name, "axis": self.axis, "shards": self.shards,
                "in_dims": dict(self.in_dims), "out_dim": self.out_dim,
                "collective": self.collective}


def candidates(segment, mesh) -> List[ShardCandidate]:
    """Candidate partitionings for one fused Segment over ``mesh``.

    Data parallelism (shard every external input's batch dim over the
    ``data`` axis) is always derived when the axis has >1 devices: fused
    programs are row-independent by the DeviceFn contract, so batch-dim
    sharding is legal by construction. A feature/model-dim candidate over
    the ``tensor`` axis is derived only when EVERY DeviceFn in the segment
    declares a shardable dim for each of its external inputs
    (``DeviceFn.shard_dims``) — GSPMD keeps it correct either way, but an
    undeclared stage gives the cost model nothing to price, so the planner
    does not propose it."""
    out: List[ShardCandidate] = []
    ext = list(segment.external_in_cols)
    shape = dict(getattr(mesh, "shape", {}) or {})
    n_data = int(shape.get(DATA_AXIS, 1))
    if n_data > 1 and ext:
        out.append(ShardCandidate(
            name=SPEC_DATA, axis=DATA_AXIS, shards=n_data,
            in_dims=tuple((c, 0) for c in ext), out_dim=0,
            collective="all_gather"))
    if n_data > 1 and ext and any(
            getattr(dfn, "sparse_fn", None) is not None
            and getattr(dfn, "sparse_cols", ()) for dfn in segment.dfns):
        # row-split CSR over the data axis: each shard takes a contiguous
        # row range of the CSR triple (rebased indptr + its nnz slice) —
        # per-shard nnz is RAGGED, so the readback gather pads to the
        # ragged max and the cost model prices it from the nnz term
        # (``nnz_bytes``), not the dense N·F bytes
        out.append(ShardCandidate(
            name=SPEC_CSR_ROW, axis=DATA_AXIS, shards=n_data,
            in_dims=tuple((c, 0) for c in ext), out_dim=0,
            collective="all_gather"))
    n_tensor = int(shape.get(TENSOR_AXIS, 1))
    if n_tensor > 1 and ext:
        dims: Dict[str, int] = {}
        ok = True
        written: set = set()
        for dfn in segment.dfns:
            decl = getattr(dfn, "shard_dims", None) or {}
            for c in dfn.in_cols:
                if c in written:
                    continue  # internal input: sharding propagates to it
                if c not in decl:
                    ok = False
                    break
                dims[c] = int(decl[c])
            if not ok:
                break
            written |= set(dfn.out_cols)
        if ok and set(dims) >= set(ext):
            out.append(ShardCandidate(
                name=SPEC_FEATURE, axis=TENSOR_AXIS, shards=n_tensor,
                in_dims=tuple((c, dims[c]) for c in ext), out_dim=None,
                collective="all_reduce"))
    return out


def candidate_for(segment, mesh, name: str) -> Optional[ShardCandidate]:
    for cand in candidates(segment, mesh):
        if cand.name == str(name):
            return cand
    return None


def tuner_candidates(segment, mesh, model=None, batch: Optional[int] = None
                     ) -> List[Dict[str, Any]]:
    """Candidate descriptions in the shape ``SegmentCostModel.
    choose_sharding`` prices: ``{name, shards, op, collective_bytes}``.
    ``collective_bytes`` comes from the harvested XLA memory analysis
    (output bytes for the data candidate's readback gather / the feature
    candidate's partial-result reduce); 0 when unharvested — the collective
    term then prices as free and only the flops/shards division differs."""
    out: List[Dict[str, Any]] = []
    label = getattr(segment, "label", str(segment))
    for cand in candidates(segment, mesh):
        nbytes = 0.0
        if model is not None:
            fn = getattr(model, "segment_bytes", None)
            if callable(fn):
                try:
                    nbytes = float(fn(label, "output_bytes") or 0.0)
                except Exception:  # noqa: BLE001 — estimate only
                    nbytes = 0.0
        if cand.name == SPEC_CSR_ROW and model is not None and batch:
            # the csr_row gather moves the RAGGED per-shard nnz payload,
            # not dense rows: price it from the fitted nnz term when the
            # model has one (falls back to the dense output estimate)
            fn = getattr(model, "nnz_bytes", None)
            if callable(fn):
                try:
                    nb = fn(label, int(batch))
                    if nb is not None:
                        nbytes = float(nb)
                except Exception:  # noqa: BLE001 — estimate only
                    pass
        out.append({"name": cand.name, "shards": cand.shards,
                    "op": cand.collective, "collective_bytes": nbytes})
    return out


def split_csr_rows(indptr, indices, values, shards: int
                   ) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Row-split one CSR column into ``shards`` contiguous row ranges —
    the host side of the ``csr_row`` partition spec. Each shard gets a
    REBASED indptr (``ip[0] == 0``) plus exactly its rows' (indices,
    values) slice, so per-shard nnz is ragged. Concatenating the shards'
    predictions in order is bitwise the unsplit prediction: row splitting
    never reorders or duplicates entries (tests/test_sparse_e2e.py)."""
    indptr = np.asarray(indptr, dtype=np.int64)
    indices = np.asarray(indices)
    values = np.asarray(values)
    n = len(indptr) - 1
    shards = max(1, int(shards))
    bounds = [round(i * n / shards) for i in range(shards + 1)]
    out: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
    for lo, hi in zip(bounds, bounds[1:]):
        base = int(indptr[lo])
        end = int(indptr[hi])
        ip = (indptr[lo:hi + 1] - base).astype(np.int32)
        out.append((ip, np.asarray(indices[base:end], dtype=np.int32),
                    np.asarray(values[base:end], dtype=np.float32)))
    return out


def ragged_allgather_bytes(nnz_per_shard: Sequence[int],
                           rows_per_shard: Optional[Sequence[int]] = None
                           ) -> float:
    """All-gather payload for a ragged row-split CSR batch. all_gather is
    rectangular, so every shard's (indices, values) pair pads to the
    ragged max nnz before the gather — the term the cost model fits
    against measured collective seconds, and why a skewed nnz
    distribution erodes the csr_row spec's win even when total nnz is
    small."""
    nnz = [int(x) for x in nnz_per_shard]
    if not nnz:
        return 0.0
    pad = max(max(nnz), 1)
    bytes_iv = len(nnz) * pad * 8.0  # i32 indices + f32 values per slot
    rows = sum(int(r) for r in (rows_per_shard or []))
    return bytes_iv + (rows + len(nnz)) * 4.0  # + rebased indptr slices


# ---------------------------------------------------------------------------
# Runtime sharding handle (executor-facing)
# ---------------------------------------------------------------------------


def donation_supported(mesh) -> bool:
    """Whether pjit input donation buys anything on this mesh's platform.
    CPU backends ignore donation with a per-compile warning — noise, not
    signal — so donation is gated to non-CPU platforms unless
    ``MMLSPARK_SHARD_DONATE=1`` forces it (the bench/CI knob that keeps
    the donate path exercised on forced-host-device meshes)."""
    if os.environ.get("MMLSPARK_SHARD_DONATE", "") == "1":
        return True
    try:
        dev = next(iter(np.asarray(mesh.devices).flat))
        return str(getattr(dev, "platform", "cpu")) != "cpu"
    except Exception:  # noqa: BLE001 — unknown platform: don't donate
        return False


class SegmentSharding:
    """Resolved sharding state for one SegmentExecutor: the NamedShardings,
    pjit kwargs, and sharded staging for one (segment, candidate, mesh).

    Every jax.sharding object is built lazily through the parallel/mesh.py
    helpers (``data_sharding`` / ``replicated_sharding`` — the surface
    J001 allows; jax 0.9.0). ``device_put`` is the chip-wedge chaos
    seam: ``mesh.chip_wedge`` (core/faults.py) fires per staged batch on
    the SHARDED path only, so injected wedges never perturb the unsharded
    bitwise-parity contract."""

    def __init__(self, segment, mesh, candidate: ShardCandidate):
        self.segment = segment
        self.mesh = mesh
        self.candidate = candidate
        self._in_shardings: Optional[Dict[str, Any]] = None

    @property
    def spec_name(self) -> str:
        return self.candidate.name

    @property
    def shards(self) -> int:
        return int(self.candidate.shards)

    @property
    def axis(self) -> str:
        return self.candidate.axis

    def cache_key(self) -> Tuple:
        """CompileCache key component: a sharded executable must never be
        confused with the single-device one for the same batch shape."""
        return ("spec", self.candidate.name, self.candidate.axis,
                self.shards)

    def shape_prefix(self) -> str:
        """Cost-record shape-key prefix. Deliberately unparseable by
        ``bucket_of_shape`` (like the mega prefix): a sharded executable's
        per-chip flops must not fold into the single-device analytic
        table."""
        return f"spec={self.candidate.name}{self.shards};"

    def _sharding_of(self, dim: Optional[int]):
        if dim is None:
            return replicated_sharding(self.mesh)
        if dim == 0:
            return data_sharding(self.mesh, self.candidate.axis)
        from jax.sharding import NamedSharding, PartitionSpec

        spec = [None] * dim + [self.candidate.axis]
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def input_shardings(self) -> Dict[str, Any]:
        if self._in_shardings is None:
            self._in_shardings = {c: self._sharding_of(dim)
                                  for c, dim in self.candidate.in_dims}
        return dict(self._in_shardings)

    def output_sharding(self):
        return self._sharding_of(self.candidate.out_dim)

    def jit_kwargs(self, mega_k: int = 1) -> Dict[str, Any]:
        """pjit kwargs for the fused program ``fn(params_tuple, cols)``:
        replicated params (pytree-prefix sharding), per-column input
        shardings, the candidate's output sharding, and ``donate_argnums``
        on the ring-staged input dict (argnum 1) — params are NEVER donated
        (they serve every batch). ``mega_k`` > 1 shapes the kwargs for the
        K-tuple-of-dicts mega signature."""
        ins = self.input_shardings()
        cols = tuple(dict(ins) for _ in range(mega_k)) if mega_k > 1 \
            else ins
        kwargs: Dict[str, Any] = {
            "in_shardings": (replicated_sharding(self.mesh), cols),
            "out_shardings": self.output_sharding(),
        }
        if donation_supported(self.mesh):
            kwargs["donate_argnums"] = (1,)
        return kwargs

    def put_params(self, params):
        import jax

        return jax.device_put(params, replicated_sharding(self.mesh))

    def device_put(self, arrays: Dict[str, np.ndarray]) -> Dict[str, Any]:
        """Stage one host batch sharded over the mesh — each column lands
        pre-split across the candidate axis (the slot/deposit staging path
        feeds this the same pre-padded buffers as the single-device put).
        Fires the ``mesh.chip_wedge`` injection point first: an armed delay
        wedges this dispatch (the watchdog's mesh-level prey), an armed
        raise simulates a chip dropping out mid-stage."""
        import jax

        faults.fire(faults.MESH_CHIP_WEDGE)
        ins = self.input_shardings()
        return {c: jax.device_put(v, ins.get(c))
                for c, v in arrays.items()}

    def describe(self) -> Dict[str, Any]:
        return {"spec": self.candidate.name, "axis": self.candidate.axis,
                "shards": self.shards,
                "collective": self.candidate.collective,
                "donate": donation_supported(self.mesh)}


def sharding_for(segment, mesh, name: Optional[str]
                 ) -> Optional[SegmentSharding]:
    """Resolve a tuned sharding knob value into a SegmentSharding, or None
    when it must stay unsharded: no mesh, an unknown/unsupported candidate,
    or a 1-shard axis (a 1-device mesh IS the unsharded path — the
    bitwise-identity contract)."""
    if mesh is None or not name:
        return None
    cand = candidate_for(segment, mesh, name)
    if cand is None or cand.shards <= 1:
        return None
    return SegmentSharding(segment, mesh, cand)


# ---------------------------------------------------------------------------
# Collective probes (the α·bytes calibration source)
# ---------------------------------------------------------------------------


def measure_collectives(mesh, sizes: Sequence[int] = (1 << 14, 1 << 18),
                        repeats: int = 3, model=None,
                        axis: Optional[str] = None) -> List[Dict[str, Any]]:
    """Time real all-reduce / all-gather collectives over the mesh's data
    axis at each payload size (bytes), optionally feeding the cost model's
    ``observe_collective``. Returns the probe records. The probes run via
    ``shard_map_compat`` (parallel/mesh.py) so the measured path is the
    same jax-version-gated machinery the sharded executables use; compile
    time is excluded (one warmup call per (op, size))."""
    import jax
    from jax.sharding import PartitionSpec

    shape = dict(getattr(mesh, "shape", {}) or {})
    if axis is None:
        axis = DATA_AXIS if int(shape.get(DATA_AXIS, 1)) > 1 else \
            max(shape, key=lambda a: shape[a])
    n = int(shape.get(axis, 1))
    if n <= 1:
        return []
    records: List[Dict[str, Any]] = []

    def reduce_fn(a):
        return jax.lax.psum(a, axis)

    def gather_fn(a):
        return jax.lax.all_gather(a, axis, tiled=True)

    for op, body in (("all_reduce", reduce_fn), ("all_gather", gather_fn)):
        for size in sizes:
            elems = max(n, (int(size) // 4 // n) * n)
            x = np.zeros(elems, dtype=np.float32)
            # check_vma off: the all_gather output IS replicated over the
            # axis, but shard_map cannot statically infer that
            fn = shard_map_compat(body, mesh=mesh,
                                  in_specs=PartitionSpec(axis),
                                  out_specs=PartitionSpec(),
                                  check_vma=False)
            jitted = jax.jit(fn)
            jax.block_until_ready(jitted(x))  # compile outside the timing
            t0 = time.perf_counter()
            for _ in range(max(1, int(repeats))):
                jax.block_until_ready(jitted(x))
            seconds = (time.perf_counter() - t0) / max(1, int(repeats))
            rec = {"op": op, "axis": axis, "shards": n,
                   "bytes": elems * 4, "seconds": seconds}
            records.append(rec)
            if model is not None:
                feed = getattr(model, "observe_collective", None)
                if callable(feed):
                    feed(op, elems * 4, seconds)
    return records


# ---------------------------------------------------------------------------
# Mesh-aware supervision: shard groups + submesh re-planning
# ---------------------------------------------------------------------------


def shard_groups(mesh) -> List[List[int]]:
    """Flat-device-index groups that fail TOGETHER: all devices sharing one
    data-axis coordinate (the model-parallel slice a chip computes with —
    when one chip wedges, every partial result in its slice is lost, so
    the whole slice quarantines, not one replica). For a pure data-parallel
    mesh each group is a single device."""
    devs = np.asarray(mesh.devices)
    arr = np.arange(devs.size).reshape(devs.shape)
    axes = list(mesh.axis_names)
    if DATA_AXIS in axes:
        arr = np.moveaxis(arr, axes.index(DATA_AXIS), 0)
    n = arr.shape[0]
    return [[int(i) for i in row] for row in arr.reshape(n, -1)]


def group_of(mesh, device_index: int) -> List[int]:
    """The shard group (flat device indices) containing ``device_index``."""
    idx = int(device_index)
    for grp in shard_groups(mesh):
        if idx in grp:
            return grp
    raise ValueError(f"device index {device_index} not in mesh")


def submesh_excluding(mesh, exclude_devices: Sequence[Any]):
    """A fresh data-parallel mesh over the surviving devices (None when
    none survive). The survivors re-plan as pure data parallelism — the
    safe shape any device count supports; the tuner re-derives richer
    specs once the replacement capacity arrives."""
    dead = set(id(d) for d in exclude_devices)
    survivors = [d for d in np.asarray(mesh.devices).flat
                 if id(d) not in dead]
    if not survivors:
        return None
    return make_mesh(MeshSpec(data=len(survivors)), device_list=survivors)


def mesh_topology(mesh) -> str:
    """Canonical topology string (axis names + sizes + device kind) — the
    persistent compile cache folds this into its environment fingerprint so
    a sharded ``.mmlc`` executable never warm-loads onto a different mesh
    shape (serving/fleet/cache.py)."""
    if mesh is None:
        return "none"
    try:
        shape = dict(mesh.shape)
        axes = ",".join(f"{a}={int(shape[a])}" for a in mesh.axis_names)
        dev = next(iter(np.asarray(mesh.devices).flat))
        kind = getattr(dev, "device_kind", None) or \
            getattr(dev, "platform", "unknown")
        return f"{axes};kind={kind}"
    except Exception:  # noqa: BLE001 — a weird mesh still fingerprints
        return "unknown"


class MeshSupervision:
    """Glue from replica-level supervision to mesh-level repair: owns the
    mesh a FusedPipelineModel shards over, registers the shard groups with
    a ReplicaSupervisor (one supervised index per mesh device), and on a
    wedge quarantines the group + re-plans the model onto the surviving
    submesh (pure data parallelism over the survivors).

    ``on_wedge(device_index)`` is idempotent per group and returns the new
    mesh (None when no devices survive — the model then runs unsharded,
    which is always correct)."""

    def __init__(self, fused, mesh, supervisor=None):
        self.fused = fused
        self.mesh0 = mesh
        self.mesh = mesh
        self.supervisor = supervisor
        self._failed: List[Any] = []
        self.replans = 0
        if supervisor is not None:
            setter = getattr(supervisor, "set_shard_groups", None)
            if callable(setter):
                setter(shard_groups(mesh))
        if fused is not None and hasattr(fused, "set_mesh"):
            fused.set_mesh(mesh)

    def failed_devices(self) -> List[Any]:
        return list(self._failed)

    def on_wedge(self, device_index: int):
        """A chip wedged: quarantine its whole shard group and re-plan the
        fused model over the surviving submesh."""
        group = group_of(self.mesh0, device_index)
        devs = np.asarray(self.mesh0.devices).flat
        fresh = [devs[i] for i in group
                 if not any(devs[i] is f for f in self._failed)]
        if not fresh:
            return self.mesh  # whole group already quarantined: no-op
        self._failed.extend(fresh)
        if self.supervisor is not None:
            wedge = getattr(self.supervisor, "note_wedged", None)
            if callable(wedge):
                wedge(int(device_index))
        sub = submesh_excluding(self.mesh0, self._failed)
        self.mesh = sub
        self.replans += 1
        if self.fused is not None and hasattr(self.fused, "set_mesh"):
            self.fused.set_mesh(sub)
        return sub

    def describe(self) -> Dict[str, Any]:
        return {"topology": mesh_topology(self.mesh),
                "original": mesh_topology(self.mesh0),
                "failed_devices": len(self._failed),
                "replans": self.replans}
