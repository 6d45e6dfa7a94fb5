"""Device-resident pipeline fusion: adjacent stages -> one XLA program.

``PipelineModel.transform`` executes stage-by-stage through host numpy:
every boundary between two device-capable stages pays a D2H readback, a
host re-batching pass, and a fresh H2D upload. This module removes those
boundaries (the TVM argument, arXiv:1802.04799, applied to SparkML-style
Transformer chains):

  - ``plan(stages, schema)`` partitions a fitted stage list into maximal
    runs of device-capable stages (``stage.device_fn(schema)`` — see
    core/device_stage.py) plus host stages. A host-only stage splits a
    segment; a ``terminal`` device stage (one whose outputs finalize on
    host, e.g. GBDT's f64 objective transforms) ends one.
  - ``Segment`` composes its stages' device fns into ONE jittable program:
    batches stack once, ride the TransferRing (parallel/ingest.py — uint8
    wire in, H2D on the prefetch thread, one dispatch, one readback), and
    every executable is cached in the shared CompileCache keyed by
    (segment, bucketed batch shape, dtype).
  - ``FusedPipelineModel`` is the drop-in runner ``PipelineModel.fuse()``
    returns. Fused output is BITWISE-IDENTICAL to the unfused chain: device
    fns carry only provably-exact ops; anything host-flavored runs in the
    stages' prepare/finalize hooks using the unfused code paths, and any
    partition the contract cannot hold for (ragged rows, sparse rows,
    nulls into NaN-filling stages, unsupported dtypes) falls back to the
    host path per partition — never a wrong answer, never a failure.

Batch bucketing mirrors parallel/batching.py (power-of-two buckets) so a
segment compiles O(log n) shapes; `fusion_stats()` exposes the segment
layout, per-segment ingest decomposition, compile-cache hit rate, and any
fallbacks taken.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.scopes import scope
from ..obs.trace import (batch_span, close_span, current_batch, open_span,
                         root_span)
from . import profiling
from .dataframe import DataFrame
from .device_stage import CompileCache, DeviceFn, FusionUnsupported, compile_cache
from .pipeline import PipelineModel, Transformer
from .runtime import ensure_compile_cache
from .schema import Schema


class _HostFallback(Exception):
    """Internal: this partition (or segment) must run the unfused path."""


# ---------------------------------------------------------------------------
# Planning
# ---------------------------------------------------------------------------


class HostStage:
    """Plan node: a stage executed through its normal transform()."""

    __slots__ = ("stage",)

    def __init__(self, stage: Transformer):
        self.stage = stage

    @property
    def label(self) -> str:
        return type(self.stage).__name__

    def describe(self) -> Dict[str, Any]:
        return {"kind": "host", "stages": [self.label]}


class Segment:
    """Plan node: a maximal run of device-capable stages fused into one
    compiled program per (batch shape, dtype) signature."""

    def __init__(self):
        self.stages: List[Transformer] = []
        self.dfns: List[DeviceFn] = []
        # stage names the plan kept a segment OPEN across: their terminal
        # host finalize is transpiled (DeviceFn.device_finalize) so the
        # boundary they would force disappears (the compiler-search stitch,
        # docs/compiler_search.md); empty for every plan produced without
        # the stitch knob
        self.stitched: List[str] = []
        # out_cols of stitched stages: they materialize only on HOST at
        # finalize time, so no later in-segment stage may consume them
        self.host_cols: set = set()

    # -- construction ----------------------------------------------------
    def add(self, stage: Transformer, dfn: DeviceFn) -> None:
        self.stages.append(stage)
        self.dfns.append(dfn)

    def mark_stitched(self, stage: Transformer, dfn: DeviceFn) -> None:
        """Record that the segment continues PAST this terminal stage: its
        f64 host-finalize reductions are transpiled to a device shim
        (``device_finalize``), so downstream device stages keep consuming
        the segment's device-resident columns instead of paying the
        readback + ``rows_to_batch`` re-batch + H2D round-trip a segment
        break costs. The stage's finalized output columns stay host-only
        (``host_cols``) — a later stage reading them still splits."""
        self.stitched.append(type(stage).__name__)
        self.host_cols |= set(dfn.out_cols)

    def can_accept(self, dfn: DeviceFn) -> bool:
        if not self.dfns:
            return True
        # a stitched terminal stage's columns exist only on host: a reader
        # cannot join the device program
        if set(dfn.in_cols) & self.host_cols:
            return False
        written = self.written_cols - self.host_cols
        internal_in = set(dfn.in_cols) & written
        if internal_in and not dfn.internal_ok:
            return False
        # a prepare hook may only own external inputs no earlier stage reads
        if dfn.prepare is not None:
            earlier_ext = {c for d in self.dfns for c in d.in_cols
                           if c not in written and c not in self.host_cols}
            if set(dfn.in_cols) & earlier_ext:
                return False
        return True

    # -- derived layout --------------------------------------------------
    @property
    def written_cols(self) -> set:
        return {c for d in self.dfns for c in d.out_cols}

    @property
    def external_in_cols(self) -> List[str]:
        ext: List[str] = []
        written: set = set()
        for d in self.dfns:
            for c in d.in_cols:
                if c not in written and c not in ext:
                    ext.append(c)
            written |= set(d.out_cols)
        return ext

    @property
    def key(self) -> Tuple:
        return tuple(d.key for d in self.dfns)

    @property
    def label(self) -> str:
        return "+".join(type(s).__name__ for s in self.stages)

    @property
    def heavy(self) -> bool:
        return any(d.heavy for d in self.dfns)

    def readback_plan(self, transpiled: Tuple[int, ...] = ()
                      ) -> List[Tuple[str, int]]:
        """(env key, writer dfn index) pairs the executor reads back: each
        column at its FINAL value plus every internal ``__`` key — plus,
        for dfn indices in ``transpiled``, the extra outputs their
        ``device_finalize`` computes on device."""
        final_writer: Dict[str, int] = {}
        for i, d in enumerate(self.dfns):
            for c in d.out_cols:
                final_writer[c] = i
        out: List[Tuple[str, int]] = []
        for i, d in enumerate(self.dfns):
            for k in d.device_outputs:
                if k.startswith("__") or final_writer.get(k) == i:
                    out.append((k, i))
            if i in transpiled:
                out.extend((k, i) for k in d.device_finalize_outputs)
        return out

    def host_emit_plan(self) -> Dict[str, str]:
        """{out col: in col} for the columns this segment can emit from
        the host rows it staged instead of reading them back: the writer
        declares the column a ``passthrough`` of an input that is
        segment-external at that stage (no earlier in-segment writer:
        what was staged IS what the stage saw), and it is the column's
        final writer. Whether a partition takes the host copy is the
        executor's to decide from how the column shipped
        (``SegmentExecutor._host_emit_cols``); ``readback_plan`` keeps
        listing the column — it stays a device value of the program for
        whoever chains onto the segment."""
        final_writer = {c: i for i, d in enumerate(self.dfns)
                        for c in d.out_cols}
        out: Dict[str, str] = {}
        written: set = set()
        for i, d in enumerate(self.dfns):
            for c, src in d.passthrough.items():
                if (c in d.device_outputs and final_writer.get(c) == i
                        and src in d.in_cols and src not in written):
                    out[c] = src
            written |= set(d.out_cols)
        return out

    def batch_size(self) -> int:
        for s in self.stages:
            if s.has_param("batchSize") and s.get("batchSize"):
                return int(s.get("batchSize"))
        return 256

    def ring_depth(self) -> int:
        for s in self.stages:
            if s.has_param("ringDepth") and s.get("ringDepth"):
                return int(s.get("ringDepth"))
        return 2

    def describe(self) -> Dict[str, Any]:
        out = {"kind": "fused",
               "stages": [type(s).__name__ for s in self.stages],
               "in_cols": self.external_in_cols,
               "out_cols": sorted(self.written_cols),
               # the output nodes a batch's program hands back, in order
               "fetched": [k for k, _ in self.readback_plan()],
               "batch_size": self.batch_size()}
        if self.stitched:  # key absent on unstitched plans: describe parity
            out["stitched"] = list(self.stitched)
        host_emit = self.host_emit_plan()
        if host_emit:  # columns emitted from the staged host rows
            out["host_emit"] = sorted(host_emit)
        return out


def plan(stages: Sequence[Transformer], schema: Schema,
         cost_model=None,
         fuse_overrides: Optional[Dict[str, bool]] = None,
         stitch_overrides: Optional[Dict[str, bool]] = None) -> List[Any]:
    """Partition a fitted stage chain into HostStage / Segment plan nodes.

    Walks the chain threading the schema through ``transform_schema``; each
    stage offers a DeviceFn via ``device_fn(schema)`` (None = host-only).
    Segments that carry no heavy stage are demoted to host stages — a
    device round-trip for column plumbing alone is a loss.

    ``cost_model`` (core/costmodel.py SegmentCostModel) upgrades that
    demotion heuristic to a PREDICTED fuse-vs-host comparison:
    ``fuse_decision(label)`` returning True keeps a light segment fused,
    False demotes it, None (uncalibrated / no host measurements) falls back
    to the heuristic — so plans from an uncalibrated model are
    bitwise-identical to the default. ``fuse_overrides`` ({label: bool},
    the Tuner's applied knob — also how its calibration probe force-fuses
    a light candidate to measure its device cost) wins over both.

    ``stitch_overrides`` ({terminal stage class name: bool}) is the
    compiler-search stitch knob: a ``terminal`` stage normally CLOSES its
    segment — its finalize runs f64 host reductions whose outputs nothing
    downstream can consume on device, so the next device stage pays a
    readback + ``rows_to_batch`` host re-batch + H2D round-trip. When the
    stage declares the transpiled shim (``stitchable`` +
    ``device_finalize``/``finalize_stitched``) and its override is True —
    or, with no override, ``cost_model.stitch_decision(segment label,
    stage name)`` prices the merge as beating the measured round-trip it
    removes (None while uncalibrated: cold-start plans stay
    bitwise-identical) — the segment stays OPEN across the shim:
    downstream device stages keep consuming the segment's device-resident
    columns, while the stage's own finalized columns stay host-only (a
    later reader of those still splits). Every per-partition host
    fallback gate is unchanged either way.
    """
    nodes: List[Any] = []
    cur: Optional[Segment] = None

    def stitch_across(seg: Segment, stage: Transformer,
                      dfn: DeviceFn) -> bool:
        if not (dfn.stitchable and dfn.device_finalize is not None
                and dfn.finalize_stitched is not None):
            return False
        name = type(stage).__name__
        if stitch_overrides is not None and name in stitch_overrides:
            return bool(stitch_overrides[name])
        if cost_model is not None:
            try:
                decision = cost_model.stitch_decision(seg.label, name)
            except Exception:  # defensive: a model bug must not kill plan
                decision = None
            return bool(decision)
        return False

    def keep_fused(seg: Segment) -> bool:
        if fuse_overrides is not None and seg.label in fuse_overrides:
            return bool(fuse_overrides[seg.label])
        if seg.heavy:
            return True
        if cost_model is not None:
            try:
                decision = cost_model.fuse_decision(seg.label)
            except Exception:  # defensive: a model bug must not kill plan
                decision = None
            if decision is not None:
                return decision
        return False

    def close():
        nonlocal cur
        if cur is not None:
            if keep_fused(cur):
                nodes.append(cur)
            else:
                nodes.extend(HostStage(s) for s in cur.stages)
            cur = None

    for stage in stages:
        dfn: Optional[DeviceFn] = None
        try:
            dfn = stage.device_fn(schema)
        except FusionUnsupported:
            dfn = None
        except Exception:  # defensive: a probing failure must not kill transform
            dfn = None
        if dfn is None:
            close()
            nodes.append(HostStage(stage))
        else:
            if cur is not None and not cur.can_accept(dfn):
                close()
            if cur is None:
                cur = Segment()
            cur.add(stage, dfn)
            if dfn.terminal:
                if stitch_across(cur, stage, dfn):
                    # transpiled shim: the segment stays open — downstream
                    # device stages keep riding this device program
                    cur.mark_stitched(stage, dfn)
                else:
                    close()
        try:
            schema = stage.transform_schema(schema.copy())
        except Exception:
            schema = schema  # schema-opaque stage: keep going with what we have
    close()
    return nodes


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------


def _stack_col(col: np.ndarray, allow_sparse: bool, stats=None) -> np.ndarray:
    """Valid-subset column -> dense [n, ...] array, preserving the wire
    dtype (uint8 pixels stay uint8); f64/i64 narrow exactly like the
    unfused Minibatcher's stack_rows(float32)/device ingestion do. A
    densified sparse column books its waste into ``stats``
    (``IngestStats.note_densify``): the dense bytes materialized vs the
    CSR bytes the same rows actually hold."""
    from ..parallel.batching import densify_sparse, is_sparse_row, sparse_width

    if col.dtype != object:
        arr = np.asarray(col)
    else:
        probe = next((v for v in col if v is not None), None)
        if probe is None:
            arr = np.zeros((len(col), 0), dtype=np.float32)
        elif is_sparse_row(probe):
            if not allow_sparse:
                raise _HostFallback("sparse rows")
            width = sparse_width(col)
            if width > (1 << 22):
                raise _HostFallback(f"sparse width {width} too large")
            arr = densify_sparse(col, width, dtype=np.float32)
            if stats is not None:
                nnz = sum(len(np.atleast_1d(v["values"]))
                          for v in col if v is not None)
                # CSR bytes: f32 values + i32 indices per nnz, i32 indptr
                nnz_bytes = nnz * 8 + (len(col) + 1) * 4
                stats.note_densify(arr.nbytes, nnz_bytes)
        else:
            rows = [np.asarray(v) for v in col]
            shapes = {r.shape for r in rows}
            if len(shapes) > 1:
                raise _HostFallback(f"ragged rows {sorted(shapes)}")
            arr = np.stack(rows)
    if arr.dtype == np.float64:
        arr = arr.astype(np.float32)
    elif arr.dtype == np.int64:
        arr = arr.astype(np.int32)
    elif arr.dtype == object:
        raise _HostFallback("non-array object rows")
    return np.ascontiguousarray(arr)


def _probe_info(col: np.ndarray) -> Dict[str, Any]:
    """Classify one column for the runtime dtype gates. Scans EVERY
    non-null row for sparseness — a partition whose first row is dense but
    a later row sparse (or vice versa) must read as ``mixed`` and take the
    clean host fallback, not mis-classify off row 0 and crash the stack."""
    from ..parallel.batching import is_sparse_row

    if col.dtype != object:
        return {"dtype": col.dtype, "ndim": col.ndim - 1, "sparse": False,
                "mixed": False}
    probe = None
    n_sparse = n_rows = 0
    for v in col:
        if v is None:
            continue
        if probe is None:
            probe = v
        n_rows += 1
        if is_sparse_row(v):
            n_sparse += 1
    if probe is None:
        return {"dtype": None, "ndim": None, "sparse": False, "mixed": False}
    if n_sparse:
        return {"dtype": np.dtype(np.float32), "ndim": 1, "sparse": True,
                "mixed": n_sparse != n_rows}
    arr = np.asarray(probe)
    return {"dtype": arr.dtype, "ndim": arr.ndim, "sparse": False,
            "mixed": False}


def _csr_from_rows(col: np.ndarray, width: int
                   ) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Sparse object column -> (indptr i32 [n+1], indices i32 [nnz],
    values f32 [nnz]). Semantics match ``densify_sparse`` exactly so the
    CSR path stays bitwise-equal to the densify path: indices >= width
    drop (VW masking), duplicate indices keep the LAST value (numpy fancy
    assignment), explicit zeros stay (they densify to the 0.0 fill), and
    per-row indices sort ascending (the gather kernel's key order). None
    = ineligible (a negative index — only hostile producers emit those;
    the caller densifies instead)."""
    indptr = np.zeros(len(col) + 1, dtype=np.int32)
    idx_parts: List[np.ndarray] = []
    val_parts: List[np.ndarray] = []
    for i, v in enumerate(col):
        if v is None:
            indptr[i + 1] = indptr[i]
            continue
        idx = np.atleast_1d(np.asarray(v["indices"], dtype=np.int64))
        vals = np.atleast_1d(np.asarray(v["values"], dtype=np.float32))
        if idx.size and int(idx.min()) < 0:
            return None
        keep = idx < width
        idx, vals = idx[keep], vals[keep]
        order = np.argsort(idx, kind="stable")
        idx, vals = idx[order], vals[order]
        if idx.size > 1:
            last = np.ones(idx.size, dtype=bool)
            last[:-1] = idx[1:] != idx[:-1]
            idx, vals = idx[last], vals[last]
        idx_parts.append(idx.astype(np.int32))
        val_parts.append(vals)
        indptr[i + 1] = indptr[i] + idx.size
    indices = np.concatenate(idx_parts) if idx_parts \
        else np.zeros(0, dtype=np.int32)
    values = np.concatenate(val_parts) if val_parts \
        else np.zeros(0, dtype=np.float32)
    return indptr, indices, values


def _join(batches: List[np.ndarray]) -> np.ndarray:
    """A partition's fetched batches as ONE fresh array: the copy a numeric
    column and a stage's own ``finalize`` need, and nothing else."""
    return np.concatenate(batches, axis=0) if batches \
        else np.zeros((0,), dtype=np.float32)


def _default_finalize(outs: Dict[str, Any], ctx: Dict) -> Dict[str, np.ndarray]:
    """Outputs -> partition columns, for a writer with no ``finalize`` of
    its own. An output is one array, or the list of a partition's fetched
    batches as they came back: 1-D stays a numeric column; [n, ...] becomes
    an object column of per-row VIEWS (DNN output parity), each of the
    batch it was read back in: nothing joined, nothing copied, so a row is
    read-only like its batch and keeps it alive."""
    cols: Dict[str, np.ndarray] = {}
    for name, out in outs.items():
        if isinstance(out, np.ndarray) and out.ndim <= 1:
            cols[name] = out
            continue
        batches = [out] if isinstance(out, np.ndarray) else out
        obj = np.empty(sum(len(b) for b in batches), dtype=object)
        i = 0
        for b in batches:
            for row in b:
                obj[i] = row
                i += 1
        cols[name] = obj
    return cols


def _part_rows(part: Dict[str, np.ndarray]) -> int:
    return len(next(iter(part.values()))) if part else 0


class _Part:
    """One partition on its way through a call. ``own`` is the binding of
    the ``partition`` span it is prepared under; ``_prepare`` fills in
    ``state`` (with ``step``, the dispatch closure made from it, and
    ``batches``, how many it will hand the device) or ``fallback`` (why it
    goes to the host instead; a refusal at ``put`` or at dispatch sets it
    later) or ``error`` (anything else: raised where the partition is
    handed out). The ring path counts what came back in ``drained`` and
    keeps it in ``collected``."""

    __slots__ = ("index", "part", "own", "entered", "state", "fallback",
                 "error", "step", "batches", "collected", "drained")

    def __init__(self, index: int, part: Dict[str, np.ndarray], own):
        self.index, self.part, self.own = index, part, own
        self.entered = False
        self.state: Optional[Dict[str, Any]] = None
        self.fallback: Optional[str] = None
        self.error: Optional[BaseException] = None
        self.step = None
        self.batches = self.drained = 0
        self.collected: Dict[str, List[np.ndarray]] = {}


class _CallerSpans:
    """The calling thread's ``partition`` spans on the ring path. One ring
    carries a call's partitions, and with a ring two deep the caller
    dispatches partition k+1's first batch BEFORE it drains partition k's
    last: its work for two partitions interleaves. So a ``partition`` span
    there is a STRETCH, an interval in which the caller works for one
    partition (prepares it, waits for, dispatches and drains its batches,
    emits it); a partition that shares a ring has two or more, the first
    under the binding it was prepared under (``_Part.own``). Stretches
    never overlap and every span the caller records lies inside one, so
    self time and idle attribution by innermost span still add up
    (benchmarks/harness/spans.py)."""

    def __init__(self, own):
        self._own = own             # the segment's open span; None = off
        self._at: Optional[Tuple[_Part, Any, float]] = None

    def enter(self, rec: _Part, w0: Optional[float] = None):
        """Binding of ``rec``'s stretch from wall time ``w0`` (now) on:
        the one that is open, else a new one after closing another
        partition's."""
        if self._own is None:
            return None
        if self._at is not None and self._at[0] is rec:
            return self._at[1]
        w0 = time.time() if w0 is None else w0
        self.leave(w0)
        own = open_span(self._own) if rec.entered else rec.own
        rec.entered = True
        self._at = (rec, own, w0)
        return own

    def leave(self, w1: Optional[float] = None) -> None:
        """Close the open stretch (at wall time ``w1``, else now)."""
        if self._at is None:
            return
        rec, own, w0 = self._at
        self._at = None
        w1 = time.time() if w1 is None else w1
        close_span(own, "partition", w0, w1 - w0,
                   rows=_part_rows(rec.part), part=rec.index)


class SegmentExecutor:
    """Runs one Segment over a DataFrame: its partitions as a stream
    through ONE TransferRing a call (partition k+1 prepared and staged
    while partition k computes), with compile-cache-backed fused
    executables."""

    def __init__(self, segment: Segment, cache: Optional[CompileCache] = None,
                 buckets: Optional[Tuple[int, ...]] = None,
                 cost_model=None, slot_pool=None, mega_k: int = 1,
                 sharding=None, kernel_variants=None, stitch=None,
                 layout: Optional[str] = None):
        self.segment = segment
        self.cache = cache if cache is not None else compile_cache()
        self.fallbacks: List[str] = []
        # device -> dispatches whose outputs landed there (placement is an
        # observable: fusion_stats()["devices"])
        self.out_devices: Dict[str, int] = {}
        self._device = None  # set by _put_params on the unsharded path
        # column -> bytes this run emitted from staged host rows instead of
        # reading them back (fusion_stats()["host_emit"])
        self.host_emit: Dict[str, int] = {}
        # bytes of fetched batches this run copied into one array at emit
        # (fusion_stats()["joined_bytes"]): 0 where every writer's rows are
        # views of the batch they came back in
        self.joined_bytes = 0
        # batches this run has handed to the device so far: the spans'
        # ``batch`` (an executor serves one run of one call)
        self._batch_no = 0
        # cost-aware bucket SET for short batches (auto-tuner knob; None =
        # the power-of-two default — bitwise-identical cold start)
        self.buckets = tuple(sorted(buckets)) if buckets else None
        # cost model fed by host-fallback timings (the fuse-vs-host term)
        self.cost_model = cost_model
        # pre-allocated H2D staging slots (parallel/ingest.py SlotPool);
        # None = the legacy allocating path, bitwise-identical
        self.slot_pool = slot_pool
        # K-step mega-dispatch factor for the submit path (auto-tuner knob,
        # core/costmodel.py choose_mega_k); 1 = today's per-batch dispatch
        self.mega_k = max(1, int(mega_k or 1))
        # mesh sharding (parallel/shardplan.py SegmentSharding, auto-tuner
        # knob via costmodel.choose_sharding); None = the single-device
        # path, byte-for-byte today's code
        self.sharding = sharding
        # compiler-search knobs (docs/compiler_search.md), both default OFF:
        # kernel_variants maps this segment's shape bucket (or "*") to a
        # core/kernels.py variant id activated around the trace, and stitch
        # ({stage class name: bool}) enables each stage's transpiled
        # `device_finalize` in place of the host `finalize` numeric path
        kv: Dict[Any, str] = {}
        for k, v in (kernel_variants or {}).items():
            if not v:
                continue
            try:
                kv[int(k)] = str(v)
            except (TypeError, ValueError):
                kv["*"] = str(v)
        self.kernel_variants = kv
        self.stitch = {str(k): bool(v) for k, v in (stitch or {}).items()}
        # sparse layout knob (auto-tuner via costmodel.choose_layout):
        # "csr" stages capable sparse columns as (indptr, indices, values)
        # triples instead of densifying; None = the densify path, byte-
        # for-byte today's code (docs/sparse.md)
        self.layout = str(layout) if layout else None
        # transpiled finalizers: every stage the PLAN stitched the segment
        # across, plus any stage the stitch knob names directly (a terminal
        # segment tail with no downstream to merge — the transpile alone
        # still moves its f64 reductions onto the device program)
        self._transpiled: Tuple[int, ...] = tuple(
            i for i, (s, d) in enumerate(zip(segment.stages, segment.dfns))
            if d.device_finalize is not None
            and d.finalize_stitched is not None
            and (type(s).__name__ in segment.stitched
                 or self.stitch.get(type(s).__name__)))
        # `stitch=` shape prefix: transpiled-shim programs decorate their
        # cost records so bucket_of_shape skips them (costmodel.py), like
        # mega{k};/spec=
        names = tuple(dict.fromkeys(
            type(segment.stages[i]).__name__ for i in self._transpiled))
        self._stitch_pre = f"stitch={','.join(names)};" if names else ""
        # the transpiled program differs under the SAME seg.key: key apart
        self._stitch_tail: Tuple = \
            (("stitch", self._transpiled),) if self._transpiled else ()

    def _cost_attrs(self) -> Dict[str, Any]:
        """XLA cost attrs for this segment's trace spans (mean per-batch
        flops/bytes across compiled shape buckets; empty when the backend
        reported none) — a traced p99 spike carries its cost context."""
        cost = self.cache.segment_cost(self.segment.label)
        if not cost:
            return {}
        out: Dict[str, Any] = {}
        for k in ("flops", "bytes_accessed", "peak_memory_bytes"):
            if k in cost:
                out[k] = round(cost[k], 1)
        return out

    # -- host path -------------------------------------------------------
    def _host_partition(self, part: Dict[str, np.ndarray], schema: Schema,
                        obs=None) -> List[Dict[str, np.ndarray]]:
        sub = DataFrame([dict(part)], schema.copy())
        n = _part_rows(part)
        for s in self.segment.stages:
            t0 = time.perf_counter()
            with batch_span(obs, f"host:{type(s).__name__}", rows=n):
                sub = s.transform(sub)
            if self.cost_model is not None and n > 0:
                # the measured HOST side of the fuse-vs-host comparison
                self.cost_model.observe_host(
                    type(s).__name__, time.perf_counter() - t0, n)
        return sub.partitions

    def _put_params(self, jax, obs=None):
        """``_place_params`` under a ``put_params`` span: the weights are
        re-placed on every call, and the span says what that costs."""
        if obs is None:
            return self._place_params(jax)
        params = tuple(d.params for d in self.segment.dfns)
        by_dtype: Dict[str, int] = {}
        for leaf in jax.tree_util.tree_leaves(params):
            name = str(getattr(leaf, "dtype", type(leaf).__name__))
            by_dtype[name] = by_dtype.get(name, 0) + int(getattr(leaf, "nbytes", 0))
        # ``dtypes``: the bytes placed under each dtype, as they were handed
        # in (placement casts nothing: bfloat16 weights stay bfloat16)
        with batch_span(obs, "put_params", bytes=sum(by_dtype.values()),
                        dtypes=",".join(f"{k}={v}" for k, v in sorted(by_dtype.items()))):
            return self._place_params(jax)

    def _place_params(self, jax):
        """Stage-params placement: replicated over the mesh when sharded;
        otherwise on the ONE device this run dispatches to — the innermost
        ``jax.default_device`` (ReplicaSet pins one per replica), else the
        first local device. Staged batches and the executable's cache key
        follow the same device: the ring's put thread does not inherit the
        caller's default-device context, and an AOT executable is bound to
        the device it was lowered for."""
        params = tuple(d.params for d in self.segment.dfns)
        if self.sharding is not None:
            return self.sharding.put_params(params)
        dev = jax.config.jax_default_device
        if dev is None or isinstance(dev, str):
            dev = jax.local_devices(backend=dev)[0]
        self._device = dev
        return jax.device_put(params, dev)

    # -- fused path ------------------------------------------------------
    def run(self, df: DataFrame, stats) -> DataFrame:
        import jax

        seg = self.segment
        # the bound batch's trace binding (a serving batch's, else the
        # call's root on the default recorder, else None); ``own`` is this
        # segment's open span, which everything below records under
        own = open_span(current_batch())
        t_wall, t0 = time.time(), time.perf_counter()
        params_dev = self._put_params(jax, own)
        out_parts = self._ring_partitions(df, params_dev, stats, own)
        with batch_span(own, "overlay"):
            out = self._overlay(df, out_parts)
        close_span(own, f"segment:{seg.label}", t_wall,
                   time.perf_counter() - t0, **self._cost_attrs())
        return out

    def _overlay(self, df: DataFrame, out_parts: List[Dict[str, np.ndarray]]
                 ) -> DataFrame:
        """Overlay the chained stage schema onto the produced partitions,
        inferring any column a stage's transform_schema didn't declare."""
        chained = df.schema.copy()
        for s in self.segment.stages:
            try:
                chained = s.transform_schema(chained)
            except Exception:
                pass
        inferred = DataFrame(out_parts)
        types = {name: chained.types.get(name, inferred.schema.types[name])
                 for name in inferred.schema.names}
        meta = {k: v for k, v in chained.metadata.items() if k in types}
        return DataFrame(out_parts, Schema(types, meta))

    def _prep_partition(self, part: Dict[str, np.ndarray], stats=None,
                        obs=None, ahead: bool = False) -> Dict[str, Any]:
        """``_prep_state`` under a ``prepare`` span (``obs``: the
        partition's open span; ``ahead``: on the look-ahead thread, beside
        the partition before it)."""
        with batch_span(obs, "prepare", rows=_part_rows(part),
                        ahead=int(ahead)) as own:
            return self._prep_state(part, stats, own)

    def _prepare(self, rec: _Part, params_dev, stats,
                 ahead: bool = False) -> None:
        """Prepare one partition into its record, on whichever thread
        calls: the state, its dispatch closure and batch count, or why it
        falls back; any other exception is kept for ``_prepared`` to raise
        where the partition is handed out."""
        try:
            state = self._prep_partition(dict(rec.part), stats, rec.own,
                                         ahead)
            rec.collected = {k: [] for k in state["keys"]}
            if state["n_valid"] > 0:
                rec.step = self._make_step(params_dev, state)
                rec.batches = self._num_batches(state)
            rec.state = state
        except (_HostFallback, FusionUnsupported) as e:
            rec.fallback = str(e)
        except BaseException as e:  # noqa: BLE001 - re-raised at hand-out
            rec.error = e
        stats.note_partition(ahead)

    def _prepared(self, df: DataFrame, params_dev, stats, own):
        """The call's partitions as a stream of prepared records, in order
        (``own``: the segment's open span, under which each gets the
        binding of its ``partition`` span). The first is prepared on the
        thread that asks for it; every later one on a thread of its own
        (``partition-prep``) that starts when the one before it is handed
        out, so its masks, ``prepare`` hooks and stack run while that one's
        batches are filled, staged, dispatched and drained. The look-ahead
        is ONE partition and no knob: the next thread starts only when
        this one's partition has been taken, and a thread blocks on
        nothing, so there is none to release when a call is abandoned.
        Only the record handed out and the one ahead are held here.
        Shared by ``run`` (the ring's producer pulls the stream) and
        ``submit_run``."""
        recs = (_Part(i, part, open_span(own))
                for i, part in enumerate(df.partitions))
        rec, ahead = next(recs, None), None
        while rec is not None:
            if ahead is None:
                self._prepare(rec, params_dev, stats)
            else:
                ahead.join()
            if rec.error is not None:
                raise rec.error
            nxt, ahead = next(recs, None), None
            if nxt is not None:
                ahead = threading.Thread(
                    target=self._prepare, name="partition-prep", daemon=True,
                    args=(nxt, params_dev, stats, True))
                ahead.start()
            yield rec
            rec = nxt

    def _prep_state(self, part: Dict[str, np.ndarray], stats,
                    obs) -> Dict[str, Any]:
        """Host-side prep for one partition — validity masks, per-stage
        prepare hooks, dtype/sparse/null gates, dense stacking — everything
        up to (but excluding) device dispatch. Raises _HostFallback when the
        fused contract cannot hold; returns the execution state shared by
        the blocking ring path (``_ring_partitions``) and the non-blocking
        submit path (``submit_run``)."""
        seg = self.segment
        ext = seg.external_in_cols
        for c in ext:
            if c not in part:
                raise _HostFallback(f"missing column {c!r}")
        n = len(part[ext[0]]) if ext else 0

        # nulls into a NaN-filling stage cannot propagate-as-null: host path
        for dfn in seg.dfns:
            if dfn.null_policy != "fallback":
                continue
            for c in dfn.in_cols:
                if c in ext and part[c].dtype == object and \
                        any(v is None for v in part[c]):
                    raise _HostFallback(f"nulls in {c!r}")

        valid = np.ones(n, dtype=bool)
        for c in ext:
            col = part[c]
            if col.dtype == object:
                valid &= np.array([v is not None for v in col], dtype=bool)
        sub = {c: part[c][valid] for c in ext}
        ctx: Dict[str, Any] = {}

        # host prep (segment-external inputs only): the unfused per-row
        # code. A column an EARLIER in-segment stage writes is internal to
        # this stage even when it shares the external column's name — its
        # value arrives device-resident, so prepare must not touch it.
        written: set = set()
        for dfn, stage in zip(seg.dfns, seg.stages):
            if dfn.prepare is not None:
                mine = {c: sub[c] for c in dfn.in_cols
                        if c in sub and c not in written}
                if mine:
                    # what the hook leaves under "span_attrs" (the image
                    # stages: how the column was resized) rides on its span
                    own = open_span(obs)
                    w0, t0 = time.time(), time.perf_counter()
                    try:
                        sub.update(dfn.prepare(mine, ctx))
                    finally:
                        close_span(own, f"prepare:{type(stage).__name__}",
                                   w0, time.perf_counter() - t0,
                                   rows=int(valid.sum()),
                                   **ctx.pop("span_attrs", {}))
            written |= set(dfn.out_cols)
        # prep can null rows (decode failures): shrink validity like dropNa
        n_valid = int(valid.sum())
        if n_valid:
            keep = np.ones(n_valid, dtype=bool)
            for c in ext:
                col = sub[c]
                if col.dtype == object:
                    keep &= np.array([v is not None for v in col], dtype=bool)
            if not keep.all():
                sub = {c: v[keep] for c, v in sub.items()}
                for k, v in list(ctx.items()):
                    if isinstance(v, np.ndarray) and len(v) == n_valid:
                        ctx[k] = v[keep]
                idx = np.flatnonzero(valid)
                valid = np.zeros(n, dtype=bool)
                valid[idx[keep]] = True
                n_valid = int(valid.sum())

        # runtime dtype gates. A mixed sparse/dense column (first row dense,
        # later rows sparse or vice versa) can satisfy no stacking contract:
        # clean host fallback instead of a mis-classified crash downstream.
        probes = {c: _probe_info(sub[c]) for c in ext}
        mixed = sorted(c for c, p in probes.items() if p.get("mixed"))
        if mixed:
            raise _HostFallback(f"mixed sparse/dense rows in {mixed}")
        csr_cols = self._csr_capable(probes)
        # density term (costmodel.observe_nnz): fed for EVERY sparse
        # external column — including ones about to take the reject_sparse
        # host fallback — so choose_layout can calibrate while the layout
        # knob is still off. Observation only; outputs are untouched.
        if self.cost_model is not None and n_valid > 0:
            from ..parallel.batching import sparse_width

            for c in ext:
                if probes[c]["sparse"]:
                    nnz = sum(len(np.atleast_1d(v["values"]))
                              for v in sub[c] if v is not None)
                    self.cost_model.observe_nnz(
                        seg.label, n_valid, nnz, sparse_width(sub[c]))
        for dfn, stage in zip(seg.dfns, seg.stages):
            mine = {c: probes[c] for c in dfn.in_cols if c in probes}
            if mine and dfn.reject_sparse and any(
                    p["sparse"] for c2, p in mine.items()
                    if c2 not in csr_cols):
                raise _HostFallback("sparse rows")
            if mine and dfn.accepts is not None and not dfn.accepts(mine):
                raise _HostFallback(f"{type(stage).__name__} dtype gate")

        readback = seg.readback_plan(self._transpiled)
        state: Dict[str, Any] = {
            "part": part, "sub": sub, "ctx": ctx, "valid": valid, "n": n,
            "n_valid": n_valid, "ext": ext, "staged_cols": list(ext),
            "readback": readback, "keys": [k for k, _ in readback]}
        if n_valid > 0:
            allow_sparse = all(not d.reject_sparse for d in seg.dfns)
            dense: Dict[str, np.ndarray] = {}
            deposit: Dict[str, List[np.ndarray]] = {}
            csr: Dict[str, Tuple] = {}
            w0, t0 = time.time(), time.perf_counter()
            for c in ext:
                if c in csr_cols:
                    triple = self._stage_csr(sub[c], stats)
                    if triple is not None:
                        csr[c] = triple
                        continue
                    # ineligible / injected sparse.stage fault: accounted
                    # densify fallback — bitwise-equal to the dense path
                    dense[c] = _stack_col(sub[c], True, stats=stats)
                    continue
                rows = self._deposit_rows(sub[c]) \
                    if self.slot_pool is not None else None
                if rows is not None:
                    # slot-eligible: the stack deferred to _batches, which
                    # fills a pre-allocated SlotPool buffer directly (the
                    # one host copy); everything else stacks here as before
                    deposit[c] = rows
                else:
                    dense[c] = _stack_col(sub[c], allow_sparse, stats=stats)
            if obs is not None:
                # a deposit column's stack is deferred to ``fill``: only
                # what was materialized here counts in ``bytes``
                nbytes = sum(a.nbytes for a in dense.values()) + sum(
                    a.nbytes for t in csr.values() for a in t[:3])
                obs[0].record_batch("stack", obs[1], w0,
                                    time.perf_counter() - t0,
                                    rows=n_valid, bytes=nbytes)
            state["dense"] = dense
            state["deposit"] = deposit
            if csr:
                state["csr"] = csr
                staged: List[str] = []
                for c in ext:
                    if c in csr:
                        staged += [f"{c}:indptr", f"{c}:indices",
                                   f"{c}:values", f"{c}:width"]
                    else:
                        staged.append(c)
                state["staged_cols"] = staged
            host_cols = self._host_emit_cols(probes, dense, deposit)
            if host_cols:
                # not program outputs at all: never fetched, never
                # concatenated; _emit_columns takes them from ``sub``
                state["host_cols"] = host_cols
                state["keys"] = [k for k in state["keys"]
                                 if k not in host_cols]
        return state

    def _host_emit_cols(self, probes: Dict[str, Dict[str, Any]],
                        dense: Dict[str, np.ndarray],
                        deposit: Dict[str, List[np.ndarray]]
                        ) -> Dict[str, str]:
        """{out col: in col} this partition emits from ``state["sub"]``:
        the segment's handed-through columns (``Segment.host_emit_plan``)
        whose input shipped dense in its own dtype — a slot deposit, or a
        stack that neither narrowed (f64 -> f32, i64 -> i32) nor densified
        (CSR triples are in neither dict) — so the host rows are, byte for
        byte, what the device would hand back. Anything else reads back
        as before."""
        return {c: src for c, src in self.segment.host_emit_plan().items()
                if src in deposit or (
                    src in dense and not probes[src]["sparse"]
                    and dense[src].dtype == probes[src]["dtype"])}

    def _csr_capable(self, probes: Dict[str, Dict[str, Any]]) -> set:
        """External columns eligible for CSR staging: the layout knob says
        "csr" for this segment, the column's rows are (uniformly) sparse,
        and EVERY consuming stage declares the capability
        (``DeviceFn.sparse_cols`` + ``sparse_fn``). The CSR x sharding
        combination is explicitly gated off — sharded segments keep the
        densify path (shardplan's row-split CSR spec is priced host-side
        only for now, docs/sparse.md)."""
        if self.layout != "csr" or self.sharding is not None:
            return set()
        out = set()
        for c, p in probes.items():
            if not p["sparse"] or p.get("mixed"):
                continue
            consumers = [d for d in self.segment.dfns if c in d.in_cols]
            if consumers and all(c in d.sparse_cols
                                 and d.sparse_fn is not None
                                 for d in consumers):
                out.add(c)
        return out

    def _stage_csr(self, col: np.ndarray, stats=None) -> Optional[Tuple]:
        """One sparse column -> (indptr, indices, values, width), or None
        to take the accounted densify fallback (zero-width column, an i32
        composite-key overflow, a negative index, or an injected
        ``sparse.stage`` fault)."""
        from ..parallel.batching import sparse_width

        from . import faults

        width = sparse_width(col)
        # the gather kernel's composite keys are row*width + index in i32
        if width <= 0 or self.segment.batch_size() * width >= (1 << 31):
            return None
        try:
            faults.fire(faults.SPARSE_STAGE)
        except faults.InjectedFault:
            return None
        triple = _csr_from_rows(col, width)
        if triple is None:
            return None
        indptr, indices, values = triple
        if stats is not None:
            stats.note_csr(int(indptr[-1]) * 8 + indptr.nbytes,
                           len(col) * width * 4)
        return indptr, indices, values, width

    @staticmethod
    def _deposit_rows(col: np.ndarray) -> Optional[List[np.ndarray]]:
        """Rows eligible for slot deposit: an object column of uniform,
        dense ndarray rows whose dtype ships as-is (no f64->f32 / i64->i32
        narrowing and no sparse densify — those transforms need their own
        allocation), so filling the staging slot IS the single host copy.
        Every fallback decision is made HERE, before any generator runs on
        a ring thread. None = take ``_stack_col`` (the copying path)."""
        if col.dtype != object or len(col) == 0:
            return None
        rows = list(col)
        first = rows[0]
        if not isinstance(first, np.ndarray):
            return None
        shape, dt = first.shape, first.dtype
        if dt == object or dt in (np.dtype(np.float64), np.dtype(np.int64)):
            return None
        for r in rows[1:]:
            if not isinstance(r, np.ndarray) or r.shape != shape \
                    or r.dtype != dt:
                return None
        return rows

    def _batches(self, state: Dict[str, Any], stats=None, obs=None,
                 batch0: int = 0):
        """Padded/bucketed Batch stream over the partition's dense arrays.
        Each batch's build is one ``fill`` span under ``obs``, on whichever
        thread runs this generator (the slot filler's, else the ring's
        producer's).

        Deposit-eligible columns (``state["deposit"]``) fill a pre-allocated
        SlotPool buffer in place — stack + pad collapse into one copy into
        the reusable H2D staging slot; slot contention (acquire timeout)
        falls back to the allocating path with an accounted copy
        (``IngestStats.note_copy``)."""
        from ..parallel.batching import Batch, next_bucket, pad_batch
        from ..parallel.ingest import rows_to_batch

        batch_size = self.segment.batch_size()
        dense, ext = state["dense"], state["ext"]
        deposit = state.get("deposit") or {}
        csr = state.get("csr") or {}
        n_valid = state["n_valid"]
        # sharded over the mesh's data axis: every padded batch must split
        # evenly across the shards, so targets round UP to a shard multiple
        # (the pad rows are masked out at readback exactly like bucket pad)
        shards = self.sharding.shards if self.sharding is not None else 1
        for batch, start in enumerate(range(0, n_valid, batch_size), batch0):
            w0, t0 = (time.time(), time.perf_counter()) \
                if obs is not None else (0.0, 0.0)
            stop = min(start + batch_size, n_valid)
            m = stop - start
            target = batch_size if m == batch_size \
                else min(next_bucket(m, buckets=self.buckets), batch_size)
            if shards > 1:
                target = -(-target // shards) * shards
            arrays = {c: pad_batch(dense[c][start:stop], target)
                      for c in dense}
            for c, (indptr, indices, values, width) in csr.items():
                # CSR window slice: rebase the indptr to this window and pad
                # row-wise by REPEATING the last offset (pad rows are empty),
                # nnz-wise to a power-of-two bucket with zeros. Padded nnz
                # entries resolve to row `target` in the gather kernel's
                # composite-key space (key >= target*width), past every real
                # query — they can never alias a live cell. docs/sparse.md.
                base = int(indptr[start])
                nnz_b = int(indptr[stop]) - base
                ip = (indptr[start:stop + 1] - base).astype(np.int32)
                if m < target:
                    ip = np.pad(ip, (0, target - m), mode="edge")
                nnz_pad = next_bucket(max(1, nnz_b))
                idx = np.pad(np.asarray(indices[base:base + nnz_b],
                                        dtype=np.int32),
                             (0, nnz_pad - nnz_b))
                val = np.pad(np.asarray(values[base:base + nnz_b],
                                        dtype=np.float32),
                             (0, nnz_pad - nnz_b))
                arrays[f"{c}:indptr"] = ip
                arrays[f"{c}:indices"] = idx
                arrays[f"{c}:values"] = val
                arrays[f"{c}:width"] = np.asarray(width, dtype=np.int32)
            lease = None
            if deposit:
                spec = {c: ((target,) + rows[0].shape, rows[0].dtype)
                        for c, rows in deposit.items()}
                lease = self.slot_pool.acquire(spec, stats=stats) \
                    if self.slot_pool is not None else None
                if lease is not None:
                    lease.fill_begin()
                    for c, rows in deposit.items():
                        buf = lease.arrays[c]
                        rows_to_batch(rows[start:stop], out=buf,
                                      stats=stats)
                        if m < target:
                            buf[m:] = 0  # pad parity with pad_batch zeros
                        arrays[c] = buf
                    lease.fill_end()
                    if stats is not None:
                        stats.note_deposit()
                else:
                    for c, rows in deposit.items():
                        arrays[c] = pad_batch(
                            rows_to_batch(rows[start:stop], stats=stats),
                            target)
                    if stats is not None:
                        stats.note_copy()
            # analysis: allow D001 -- host-side validity mask, never shipped
            mask = np.zeros(target, dtype=bool)
            mask[:m] = True
            if obs is not None:
                obs[0].record_batch(
                    "fill", obs[1], w0, time.perf_counter() - t0, batch=batch,
                    bytes=sum(a.nbytes for a in arrays.values()))
            yield Batch(arrays, mask, m, staging=lease, counts=self._counts(arrays))

    def _counts(self, arrays: Dict[str, np.ndarray]) -> Dict[str, int]:
        """What the segment's stages count in a batch of their inputs
        (``DeviceFn.count``), each stage over the columns it reads."""
        counts: Dict[str, int] = {}
        for d in self.segment.dfns:
            if d.count is not None and all(c in arrays for c in d.in_cols):
                counts.update(d.count({c: arrays[c] for c in d.in_cols}))
        return counts

    def _put(self, batch):
        import jax

        if self.sharding is None:
            return (jax.device_put(batch.arrays, self._device),
                    batch.num_valid)
        # sharded staging: each column lands pre-split across the mesh's
        # candidate axis. A failure here (a chip dropping out mid-stage —
        # the mesh.chip_wedge chaos seam) degrades this PARTITION to the
        # host fallback: slower, never wrong.
        try:
            return self.sharding.device_put(batch.arrays), batch.num_valid
        except Exception as e:  # noqa: BLE001 — any stage fault demotes
            raise FusionUnsupported(f"mesh stage failure: {e}")

    @staticmethod
    def _sig_of(x, ext) -> Tuple:
        """Shape signature of one staged input dict (CompileCache key)."""
        return tuple((c, tuple(np.shape(x[c])), str(x[c].dtype))
                     for c in ext)

    @staticmethod
    def _shape_key_of(sig) -> str:
        return ";".join(f"{c}={'x'.join(str(d) for d in shp)}:{dt}"
                        for c, shp, dt in sig)

    def _variant_for(self, sig) -> Optional[str]:
        """Kernel-variant id active for one shape signature: the tuned
        per-bucket entry (bucket = leading dim of the first staged input),
        falling back to the ``"*"`` wildcard; None = built-in default."""
        kv = self.kernel_variants
        if not kv:
            return None
        vid = None
        if sig and sig[0][1]:
            vid = kv.get(int(sig[0][1][0]))
        if vid is None:
            vid = kv.get("*")
        return vid

    def _program_tail(self, state: Dict[str, Any]
                      ) -> Tuple[Tuple, str, frozenset]:
        """What tells this state's program from the segment's plain one:
        (CompileCache key tail, shape-key prefix, CSR-staged columns)."""
        csr_cols = frozenset(state.get("csr") or ())
        sh = self.sharding
        # a sharded executable is a DIFFERENT program (GSPMD-partitioned,
        # collectives inserted): key it apart from the single-device one,
        # and prefix the shape key so the cost model's bucket parser skips
        # sharded records (their per-chip flops would skew the
        # single-device analytic table)
        key_tail = (sh.cache_key(),) if sh is not None \
            else (("device", self._device.id),)
        key_tail = key_tail + self._stitch_tail
        shape_pre = (sh.shape_prefix() if sh is not None else "") + \
            self._stitch_pre
        if csr_cols:
            # a CSR-staged program traces sparse_fn bodies over the wire
            # triple: key it apart, and prefix the shape key so
            # bucket_of_shape skips its cost records (the nnz bucket is
            # data- not batch-shaped)
            key_tail = key_tail + (("layout", "csr"),)
            shape_pre = "layout=csr;" + shape_pre
        host_cols = state.get("host_cols")
        if host_cols:
            # a program that leaves its handed-through columns out has
            # fewer outputs under the SAME seg.key and signature: key it
            # apart (the persistent tier's content address follows)
            key_tail = key_tail + (("host_emit", tuple(sorted(host_cols))),)
        return key_tail, shape_pre, csr_cols

    def _program_key(self, key_tail: Tuple, shape_pre: str, sig: Tuple,
                     k: int = 1) -> Tuple[Tuple, str, Optional[str]]:
        """(CompileCache key, shape key, kernel-variant id) of the K-step
        program for one shape signature, from ``_program_tail``'s key tail
        and shape prefix. A kernel variant is a DIFFERENT compiled program
        for the same (segment, signature), and a K-step program holds K
        batches' worth of flops: each is keyed apart and decorates the
        shape key (``variant=<id>;``, ``mega<K>;``) so the cost model's
        bucket_of_shape skips its cost record. K=1 adds nothing to
        either."""
        vid = self._variant_for(sig)
        if vid:
            key_tail = key_tail + (("variant", vid),)
            shape_pre = f"variant={vid};" + shape_pre
        if k > 1:
            key_tail = (("mega", k),) + key_tail
            shape_pre = f"{shape_pre}mega{k};"
        return ((self.segment.key, sig) + key_tail,
                shape_pre + self._shape_key_of(sig), vid)

    def _make_step(self, params_dev, state: Dict[str, Any]):
        """Dispatch closure: staged batch -> (device outputs, num_valid).
        Non-blocking (jax dispatch is async); executables come from the
        shared CompileCache keyed by (segment, shape signature)."""
        seg, keys = self.segment, state["keys"]
        staged_cols = state.get("staged_cols") or state["ext"]
        key_tail, shape_pre, csr_cols = self._program_tail(state)

        def step(staged):
            x, m = staged
            key, shape, vid = self._program_key(
                key_tail, shape_pre, self._sig_of(x, staged_cols))

            def build():
                # a CompileCache miss: the build is a child of the open
                # ``dispatch`` span the caller bound around this step
                with batch_span(current_batch(), "compile",
                                label=seg.label, shape=shape):
                    return self._build(params_dev, x, keys, variant=vid,
                                       csr_cols=csr_cols)

            compiled = self.cache.get(key, build, label=seg.label,
                                      shape=shape)
            with profiling.annotate(f"fused:{seg.label}"):
                ys = compiled(params_dev, x)
            self._note_devices(ys)
            return ys, m

        return step

    def _make_mega_step(self, params_dev, state: Dict[str, Any], k: int):
        """K-step dispatch closure: a list of K same-signature staged
        batches -> tuple of K output tuples, through ONE compiled call."""
        seg, keys = self.segment, state["keys"]
        staged_cols = state.get("staged_cols") or state["ext"]
        key_tail, shape_pre, csr_cols = self._program_tail(state)

        def mega(group):
            xs = [x for (x, _m), _t in group]
            key, shape, vid = self._program_key(
                key_tail, shape_pre, self._sig_of(xs[0], staged_cols), k)
            compiled = self.cache.get(
                key,
                lambda: self._build(params_dev, xs[0], keys, k=k,
                                    variant=vid, csr_cols=csr_cols),
                label=seg.label, shape=shape)
            cols_seq = tuple({c: x[c] for c in staged_cols} for x in xs)
            with profiling.annotate(f"fused:{seg.label}:mega{k}"):
                outs = compiled(params_dev, cols_seq)
            for ys in outs:
                self._note_devices(ys)
            return outs

        return mega

    def _note_devices(self, ys) -> None:
        for d in (ys[0].devices() if ys else ()):
            self.out_devices[str(d)] = self.out_devices.get(str(d), 0) + 1

    @staticmethod
    def _fetch(handle):
        ys, m = handle
        return tuple(np.asarray(y)[:m] for y in ys)

    def _fill_ahead(self, state: Dict[str, Any], stats, obs=None):
        """Batch source for one partition: the plain generator, wrapped in
        a background fill thread when slot deposit is active — slot N+1
        fills while slot N transfers (the paired-buffer overlap; the
        SlotPool's two buffers per bucket pace the lookahead). Returns
        (iterator, closer)."""
        src = self._batches(state, stats, obs, self._batch_no)
        if not state.get("deposit"):
            return src, None
        from ..parallel.batching import DevicePrefetcher

        filler = DevicePrefetcher(src, depth=1, name="slot-fill")
        return iter(filler), filler

    def _ring_partitions(self, df: DataFrame, params_dev, stats, own
                         ) -> List[Dict[str, np.ndarray]]:
        """Every partition of the call through ONE ring, emitted in order.
        The ring's producer pulls ``_batch_stream``: partition k+1 is
        prepared, filled and staged while partition k computes. This
        thread dispatches and drains in order and settles a partition
        (``emit``, or the host path for one that fell back) as soon as
        nothing of it or of an earlier one is left in the ring. What rides
        with each batch is its partition's record, so a batch is stepped
        with ITS partition's program, and a refusal at ``put`` or at
        dispatch demotes that partition alone: its other batches are
        dropped, the ring goes on."""
        from ..parallel.ingest import TransferRing

        seg = self.segment
        caller = _CallerSpans(own)
        arrived: deque = deque()    # records the stream has reached, in order
        out_parts: List[Dict[str, np.ndarray]] = []

        def settle() -> None:
            while arrived and arrived[0].drained == arrived[0].batches:
                rec = arrived.popleft()
                if rec.fallback is None:
                    out_parts.append(self._emit_partition(
                        rec.state, rec.collected, caller.enter(rec)))
                    continue
                caller.enter(rec)   # its span, had it none on this thread
                caller.leave()
                self.fallbacks.append(f"{seg.label}: {rec.fallback}")
                out_parts.extend(
                    self._host_partition(rec.part, df.schema, own))

        def put(batch):
            rec = batch.owner
            if rec.fallback is None:
                try:
                    return self._put(batch) + (rec,)
                except FusionUnsupported as e:
                    rec.fallback = str(e)
            return None, batch.num_valid, rec

        def step(staged):
            x, m, rec = staged
            if rec.fallback is None:
                try:
                    return rec.step((x, m)) + (rec,)
                except FusionUnsupported as e:
                    rec.fallback = str(e)
            return None, m, rec

        def fetch(handle):
            ys, m, rec = handle
            dropped = ys is None or rec.fallback is not None
            return rec, None if dropped else self._fetch((ys, m))

        def obs_of(x, w0):
            return x.owner.own if w0 is None else caller.enter(x[-1], w0)

        prepared = self._prepared(df, params_dev, stats, own)
        fillers: List[Any] = []     # the stream's slot filler, to close
        ring = None
        try:
            # the first partition: prepared here, its stretch begun with it
            w0 = time.time() if own is not None else None
            first = next(prepared, None)
            if first is None:
                return out_parts
            caller.enter(first, w0)
            if first.batches or len(df.partitions) > 1:
                src = self._batch_stream(itertools.chain((first,), prepared),
                                         stats, arrived, fillers)
                ring = TransferRing(
                    src, put=put, step=step, fetch=fetch,
                    depth=seg.ring_depth(), stats=stats, obs=own,
                    obs_of=obs_of if own is not None else None)
                del first
                for rec, out in ring:
                    rec.drained += 1
                    if out is not None:
                        for k, y in zip(rec.state["keys"], out):
                            rec.collected[k].append(y)
                    settle()
            else:                   # nothing for a ring to carry
                arrived.append(first)
            settle()
        finally:
            if ring is not None:
                ring.close()
            for filler in fillers:
                filler.close()
            caller.leave()
        return out_parts

    def _batch_stream(self, prepared, stats, arrived: deque,
                      fillers: List[Any]):
        """The ring's source: every partition's batches in turn, each
        carrying its record (``Batch.owner``). A batch never spans two
        partitions, and a partition that fell back at ``prepare`` or has
        no valid row passes with none. ``arrived`` tells the caller which
        partitions the stream has reached; ``fillers`` holds the open slot
        filler, which the caller closes if it abandons the ring."""
        for rec in prepared:
            arrived.append(rec)
            if not rec.batches:
                continue
            src, filler = self._fill_ahead(rec.state, stats, rec.own)
            self._batch_no += rec.batches
            fillers[:] = [filler] if filler is not None else []
            for batch in src:
                batch.owner = rec
                yield batch

    def _num_batches(self, state: Dict[str, Any]) -> int:
        return -(-state["n_valid"] // self.segment.batch_size())

    def submit_run(self, df: DataFrame, stats):
        """Non-blocking segment execution: prep + H2D-stage + DISPATCH every
        partition's batches now, hand the device-resident handles to the
        returned zero-arg ``resolve()`` which performs readback + finalize
        (the serving executor runs it on its dedicated readback thread).
        ``resolve()`` output is bitwise-identical to ``run()``.

        Host-fallback partitions (ragged/sparse/null/dtype violations)
        execute synchronously at submit time — never a wrong answer."""
        import jax

        from ..parallel.ingest import timed_dispatch, timed_stage

        seg = self.segment
        # this segment's open span under the serving batch's trace binding
        # (None when nothing is bound): closed by resolve()
        own = open_span(current_batch())
        wall0 = time.perf_counter()
        t_wall = time.time()
        params_dev = self._put_params(jax, own)
        mega_k = max(1, int(self.mega_k or 1))
        pendings: List[Tuple[str, Any, Any, Any, int]] = []
        prepared = self._prepared(df, params_dev, stats, own)
        while True:
            # a partition's span covers what happens to it NOW (the wait
            # for its prepare, fill, h2d, dispatch); its drain and emit
            # record under the same span from resolve(), after it closed
            pw0, pt0 = time.time(), time.perf_counter()
            rec = next(prepared, None)
            if rec is None:
                break
            p_own = rec.own
            try:
                if rec.fallback is not None:
                    raise _HostFallback(rec.fallback)
                handles = []
                b0 = self._batch_no
                if rec.batches:
                    state, step = rec.state, rec.step
                    src, filler = self._fill_ahead(state, stats, p_own)
                    self._batch_no += rec.batches
                    try:
                        if mega_k <= 1:
                            # K=1: today's stage-then-dispatch loop,
                            # verbatim — bitwise-identical by construction
                            for b, batch in enumerate(src, b0):
                                staged, timing = timed_stage(
                                    self._put, batch, obs=p_own, batch=b)
                                handle = timed_dispatch(
                                    step, staged, timing, p_own, b)
                                handles.append((handle, timing))
                        else:
                            staged_it = (
                                timed_stage(self._put, batch, obs=p_own,
                                            batch=b)
                                for b, batch in enumerate(src, b0))
                            self._dispatch_mega(staged_it, params_dev,
                                                state, step, mega_k,
                                                handles, p_own, b0)
                    finally:
                        if filler is not None:
                            filler.close()
                pendings.append(("device", rec.state, handles, p_own, b0))
            except (_HostFallback, FusionUnsupported) as e:
                self.fallbacks.append(f"{seg.label}: {e}")
                pendings.append(
                    ("host", self._host_partition(rec.part, df.schema, own),
                     None, None, 0))
            finally:
                close_span(p_own, "partition", pw0,
                           time.perf_counter() - pt0,
                           rows=_part_rows(rec.part), part=rec.index)

        def resolve() -> DataFrame:
            from ..parallel.ingest import (_block_ready, _tree_nbytes,
                                           record_drain)

            out_parts: List[Dict[str, np.ndarray]] = []
            for kind, payload, handles, p_own, b0 in pendings:
                if kind == "host":
                    out_parts.extend(payload)
                    continue
                state = payload
                collected: Dict[str, List[np.ndarray]] = {
                    k: [] for k in state["keys"]}
                for b, (handle, timing) in enumerate(handles, b0):
                    w0 = time.time() if p_own is not None else 0.0
                    t0 = time.perf_counter()
                    _block_ready(handle)
                    t1 = time.perf_counter()
                    timing.compute_s = t1 - t0
                    out = self._fetch(handle)
                    timing.readback_s = time.perf_counter() - t1
                    stats.record(timing)
                    if p_own is not None:
                        record_drain(p_own, timing, w0, b, _tree_nbytes(out))
                    for k, y in zip(state["keys"], out):
                        collected[k].append(y)
                out_parts.append(
                    self._emit_partition(state, collected, p_own))
            stats.add_wall(time.perf_counter() - wall0)
            with batch_span(own, "overlay"):
                out_df = self._overlay(df, out_parts)
            close_span(own, f"segment:{seg.label}", t_wall,
                       time.perf_counter() - wall0, **self._cost_attrs())
            return out_df

        return resolve

    def _dispatch_mega(self, staged_it, params_dev, state: Dict[str, Any],
                       step, k: int, handles, obs=None,
                       batch0: int = 0) -> None:
        """Dispatch staged batches in SLIDING K-step groups: pull from the
        (lazily staging) iterator, and the moment K consecutive
        same-signature batches are staged, run them through the compiled
        K-step program (one Python-level dispatch for K micro-batches) and
        DROP the staged-input references — at most K staged inputs are
        alive at once, matching the ring/K=1 paths' bounded in-flight
        memory instead of staging a whole partition up front. Runs shorter
        than K (signature change or end of stream) dispatch singly through
        the ordinary step — the SAME per-batch executable as K=1, so
        outputs are identical either way. The measured mega dispatch time
        is split evenly across the K timings (the amortization the
        bottleneck attribution shows), with ``timing.mega_k`` tagging the
        share so the cost model can de-amortize it."""
        from ..parallel.ingest import timed_dispatch

        ext = state.get("staged_cols") or state["ext"]
        mega = self._make_mega_step(params_dev, state, k)

        def flush(group):
            b = batch0 + len(handles)
            if len(group) == k:
                w0 = time.time()
                td = time.perf_counter()
                outs = mega(group)
                share = (time.perf_counter() - td) / k
                if obs is not None:
                    # ONE dispatch carried K batches: one span, its first
                    # batch and K beside it
                    obs[0].record_batch("dispatch", obs[1], w0, share * k,
                                        batch=b, mega_k=k)
                for (staged, timing), ys in zip(group, outs):
                    timing.dispatch_s = share
                    timing.mega_k = k
                    handles.append(((ys, staged[1]), timing))
            else:
                for j, (staged, timing) in enumerate(group, b):
                    handle = timed_dispatch(step, staged, timing, obs, j)
                    handles.append((handle, timing))

        group: List[Any] = []
        sig0 = None
        for item in staged_it:
            sig = self._sig_of(item[0][0], ext)
            if group and sig != sig0:
                flush(group)
                group = []
            if not group:
                sig0 = sig
            group.append(item)
            if len(group) == k:
                flush(group)
                group = []
        if group:
            flush(group)

    def _emit_partition(self, state: Dict[str, Any],
                        collected: Dict[str, List[np.ndarray]],
                        obs=None) -> Dict[str, np.ndarray]:
        """``_emit_columns`` under an ``emit`` span (``obs``: the
        partition's span): ``host_cols`` columns came from the staged host
        rows, ``host_bytes`` bytes were not read back for them,
        ``joined_bytes`` bytes of fetched batches were copied into one
        array (0 where every writer's rows are views of their batch)."""
        own = open_span(obs)
        w0, t0 = time.time(), time.perf_counter()
        host = self._host_columns(state)
        joined = 0
        try:
            out_part, joined = self._emit_columns(state, collected, own, host)
            self.joined_bytes += joined
            return out_part
        finally:
            close_span(own, "emit", w0, time.perf_counter() - t0,
                       rows=state["n"], host_cols=len(host),
                       host_bytes=sum(a.nbytes for a in host.values()),
                       joined_bytes=joined)

    def _host_columns(self, state: Dict[str, Any]) -> Dict[str, np.ndarray]:
        """The partition's handed-through columns (``state["host_cols"]``)
        as [n_valid, ...] arrays over the rows that were SHIPPED —
        ``state["sub"]``, the valid rows in order; never a SlotPool
        lease's buffer (the next batch reuses it) nor the padded batch.
        Rows that are views of one block (what a column resize makes) come
        as one view of it, nothing copied; else they are stacked."""
        from ..parallel.ingest import rows_to_batch

        host: Dict[str, np.ndarray] = {}
        for c, src in (state.get("host_cols") or {}).items():
            col = state["sub"][src]
            host[c] = rows_to_batch(list(col)) if col.dtype == object \
                else col
            self.host_emit[c] = self.host_emit.get(c, 0) + host[c].nbytes
        return host

    def _emit_columns(self, state: Dict[str, Any],
                      collected: Dict[str, List[np.ndarray]],
                      obs, host: Dict[str, np.ndarray]
                      ) -> Tuple[Dict[str, np.ndarray], int]:
        """Fetched batches (and ``host``, the columns that never left) ->
        finalized partition columns (per writer stage, scattered over the
        validity mask) and the bytes joined on the way: a writer with no
        ``finalize`` of its own is emitted from its batches as they came
        back (``_default_finalize``)."""
        seg = self.segment
        part, ctx = state["part"], state["ctx"]
        valid, n, n_valid = state["valid"], state["n"], state["n_valid"]
        # fetched: the list of batches; handed through: one array
        full: Dict[str, Any] = {**collected, **host}

        # finalize per writer stage (stage order), scatter into the partition
        by_writer: Dict[int, Dict[str, Any]] = {}
        for k, i in state["readback"]:
            by_writer.setdefault(i, {})[k] = full[k]
        out_part = dict(part)
        transpiled = set(self._transpiled)
        joined = 0
        for i, dfn in enumerate(seg.dfns):
            outs = by_writer.get(i)
            if outs is None:
                continue
            if n_valid == 0:
                cols = {c: np.empty(0, dtype=object) for c in dfn.out_cols}
            else:
                # transpiled finalize: the numeric reductions already ran
                # on device (device_finalize); that host shim only shapes
                # the readbacks into columns
                own = dfn.finalize_stitched if i in transpiled \
                    else dfn.finalize
                # a finalize of the stage's own takes whole-partition
                # arrays, and a 1-D output is one numeric column: those
                # batches are joined. Any other output stays the batches
                # it came back in (its rows will be views of them)
                for k, v in list(outs.items()):
                    if isinstance(v, list) and (
                            own is not None or not v or v[0].ndim <= 1):
                        outs[k] = _join(v)
                        joined += outs[k].nbytes
                with batch_span(
                        obs, f"finalize:{type(seg.stages[i]).__name__}",
                        rows=n_valid):
                    cols = (own or _default_finalize)(outs, ctx)
            for c in dfn.out_cols:
                if c not in cols:
                    continue
                col = cols[c]
                if n_valid == n:
                    out_part[c] = col
                else:
                    scat = np.empty(n, dtype=object)
                    scat[np.flatnonzero(valid)] = col
                    out_part[c] = scat
        if any(d.drop_invalid for d in seg.dfns) and n_valid < n:
            out_part = {k: v[valid] for k, v in out_part.items()}
        return out_part, joined

    def _trace_stages(self, params_tuple, cols: Dict[str, Any],
                      csr_cols: frozenset) -> Dict[str, Any]:
        """The fused body: every stage's ``fn`` in order over one batch's
        staged columns, returning the final env. A stage whose input
        column was CSR-staged (``csr_cols``) traces its ``sparse_fn`` body
        over the wire-triple env keys instead of ``fn`` — the only point
        where the two bodies diverge. A declared ``passthrough`` is held
        to its word here: the output must BE the array the stage was
        given, or the build fails (never a stale column on the host)."""
        seg = self.segment
        transpiled = set(self._transpiled)
        env = dict(cols)
        for i, (dfn, p) in enumerate(zip(seg.dfns, params_tuple)):
            given = {c: env.get(src) for c, src in dfn.passthrough.items()}
            # the device-side twin of ``prepare:<Stage>`` / ``finalize:<Stage>``
            with scope(type(seg.stages[i]).__name__):
                if dfn.sparse_fn is not None and csr_cols & set(dfn.in_cols):
                    env.update(dfn.sparse_fn(p, env))
                else:
                    env.update(dfn.fn(p, env))
                for c, src in dfn.passthrough.items():
                    if given[c] is None or env.get(c) is not given[c]:
                        raise ValueError(
                            f"{type(seg.stages[i]).__name__} declares {c!r} a "
                            f"passthrough of {src!r}, but its device fn "
                            f"returned another value")
                if i in transpiled:
                    env.update(dfn.device_finalize(p, env))
        return env

    def _build(self, params_dev, x: Dict[str, Any], keys: List[str],
               k: int = 1, variant: Optional[str] = None,
               csr_cols: frozenset = frozenset()):
        """AOT-compile the fused program for one shape signature. A kernel
        ``variant`` id is activated around the trace (core/kernels.py) so
        variant-aware call sites resolve it as a static parameter.

        ``k`` > 1 is the K-step mega program: K replicas of the per-batch
        body traced over a K-tuple of same-shape input dicts in one
        callable, so one Python dispatch executes K queued micro-batches.
        Each replica's ops are exactly the per-batch program's, so
        per-batch outputs match K=1."""
        import jax

        from . import kernels as _kernels

        # the K=1 callable's NAME is read outside the program: its
        # executable is the module ``jit_fused``, by which the benchmark's
        # device-trace readers find it (benchmarks/layer_metrics)
        def fused(params_tuple, cols):
            env = self._trace_stages(params_tuple, cols, csr_cols)
            return tuple(env[kk] for kk in keys)

        def fused_k(params_tuple, cols_seq):
            return tuple(fused(params_tuple, cols) for cols in cols_seq)

        # sharded: pjit with the planner's NamedShardings (replicated
        # params, per-column input specs, donated ring-staged inputs) —
        # GSPMD partitions the program and inserts the collectives
        jit_kwargs = self.sharding.jit_kwargs(mega_k=k) \
            if self.sharding is not None else {}
        jitted = jax.jit(fused if k == 1 else fused_k, **jit_kwargs)
        spec = {c: jax.ShapeDtypeStruct(tuple(np.shape(v)),
                                        np.asarray(v).dtype
                                        if not hasattr(v, "dtype") else v.dtype)
                for c, v in x.items()}
        specs = spec if k == 1 else tuple(dict(spec) for _ in range(k))
        # no catch: a Mosaic refusal or a VMEM/HBM OOM must surface, not
        # be retried as a lazy jit
        with _kernels.activate(variant):
            return jitted.lower(params_dev, specs).compile()


# ---------------------------------------------------------------------------
# FusedPipelineModel
# ---------------------------------------------------------------------------


class FusedPipelineModel(PipelineModel):
    """PipelineModel whose transform executes the fused plan.

    Fusion is an EXECUTION STRATEGY, not a persisted artifact: save() writes
    a plain PipelineModel (load + ``.fuse()`` to re-fuse), and the class is
    kept out of the stage registry (``_abstract``).
    """

    _abstract = True

    def __init__(self, stages=None, cache: Optional[CompileCache] = None,
                 cost_model=None, slot_staging: bool = True, **kwargs):
        super().__init__(stages, **kwargs)
        self._cache = cache if cache is not None else compile_cache()
        self._plans: Dict[Tuple, List[Any]] = {}
        self._seg_stats: Dict[str, Any] = {}
        # segment label -> {column: bytes} the last transform emitted from
        # staged host rows instead of reading back
        self._host_emit: Dict[str, Dict[str, int]] = {}
        self._joined_bytes: Dict[str, int] = {}
        self._last_fallbacks: List[str] = []
        # cumulative since construction (replicas share one model across
        # threads; the per-call fields above are last-writer-wins)
        self._totals_lock = threading.Lock()
        self._fallback_total = 0
        self._out_devices: Dict[str, int] = {}
        self._last_plan: Optional[List[Any]] = None
        # auto-tuning state (core/tune.py Tuner drives these): a cost model
        # feeding plan()'s fuse-vs-host comparison + host-stage timings,
        # per-segment bucket-set overrides, fuse overrides, and per-segment
        # K-step mega-dispatch factors. All default OFF: an untuned model
        # plans, buckets, and dispatches bitwise-identically.
        self._cost_model = cost_model
        self._bucket_overrides: Dict[str, Tuple[int, ...]] = {}
        self._fuse_overrides: Dict[str, bool] = {}
        self._mega_k_overrides: Dict[str, int] = {}
        # compiler-search knobs (docs/compiler_search.md): per-segment
        # {bucket: kernel variant id} and per-stage-name stitch flags.
        # Both default OFF — cold start is bitwise-identical.
        self._variant_overrides: Dict[str, Dict[Any, str]] = {}
        self._stitch_overrides: Dict[str, bool] = {}
        # pod-scale sharding (parallel/shardplan.py): the mesh segments may
        # shard over (set_mesh / MeshSupervision) and the per-segment spec
        # overrides (tuner knob via costmodel.choose_sharding). Both
        # default OFF — no mesh or no override = the single-device path.
        self._shard_mesh = None
        self._sharding_overrides: Dict[str, str] = {}
        self._seg_sharding: Dict[str, Any] = {}
        # sparse layout knob (docs/sparse.md): per-segment-label "csr"
        # stages capable sparse columns as wire triples (tuner knob via
        # costmodel.choose_layout). Default OFF — densify, bitwise today.
        self._layout_overrides: Dict[str, str] = {}
        # pipeline-parallel depth knob (parallel/pipeplan.py, tuner knob
        # via costmodel.choose_pipe_depth): > 1 places a chainable segment
        # run on disjoint pipe-axis sub-meshes and streams micro-batches
        # through them. Default OFF (None) — serial, bitwise today.
        self._pipe_depth: Optional[int] = None
        self._pipe_stats: Optional[Dict[str, Any]] = None
        self._pipe_replans = 0
        self._pipe_requeues: Dict[int, int] = {}
        self._pipe_wedge_handler = None
        self._pipe_supervision = None
        # pre-allocated H2D staging (parallel/ingest.py SlotPool), shared
        # across segments/executors; ``slot_staging=False`` pins the legacy
        # allocating path (the bench A/B arm)
        self.slot_staging = bool(slot_staging)
        self._slot_pool = None

    def fuse(self) -> "FusedPipelineModel":
        return self

    def set_tuning(self, buckets: Optional[Dict[str, Tuple[int, ...]]] = None,
                   fuse: Optional[Dict[str, bool]] = None,
                   cost_model=None,
                   mega_k: Optional[Dict[str, int]] = None,
                   sharding: Optional[Dict[str, str]] = None,
                   kernel_variants: Optional[Dict[str, Dict[Any, str]]] = None,
                   stitch: Optional[Dict[str, bool]] = None,
                   layout: Optional[Dict[str, str]] = None,
                   pipe_depth: Optional[int] = None) -> None:
        """Apply tuned knobs (Tuner.apply): per-segment-label bucket sets,
        fuse-vs-demote overrides, per-segment K-step mega-dispatch factors,
        per-segment partition-spec names (sharding over the ``set_mesh``
        mesh), per-segment kernel-variant maps ({label: {bucket|"*":
        variant id}}), per-stage-name stitch flags, the pipeline depth
        (``pipe_depth`` > 1 streams a chainable segment run over pipe-axis
        sub-meshes; <= 1 clears), and/or the cost model itself. Passing
        None leaves a knob unchanged; passing {} clears it. Cached plans
        are invalidated (compiled executables survive in the
        CompileCache)."""
        if pipe_depth is not None:
            self._pipe_depth = int(pipe_depth) \
                if int(pipe_depth) > 1 else None
        if kernel_variants is not None:
            self._variant_overrides = {
                str(k): dict(v) for k, v in kernel_variants.items() if v}
        if stitch is not None:
            self._stitch_overrides = {str(k): bool(v)
                                      for k, v in stitch.items()}
        if buckets is not None:
            self._bucket_overrides = {
                str(k): tuple(sorted(int(b) for b in v))
                for k, v in buckets.items()}
        if fuse is not None:
            self._fuse_overrides = {str(k): bool(v)
                                    for k, v in fuse.items()}
        if mega_k is not None:
            self._mega_k_overrides = {str(k): max(1, int(v))
                                      for k, v in mega_k.items()}
        if sharding is not None:
            self._sharding_overrides = {str(k): str(v)
                                        for k, v in sharding.items() if v}
        if layout is not None:
            self._layout_overrides = {str(k): str(v)
                                      for k, v in layout.items() if v}
        if cost_model is not None:
            self._cost_model = cost_model
        self._plans.clear()

    def set_mesh(self, mesh) -> None:
        """Attach (or, with None, detach) the device mesh segments may
        shard over. The mesh alone changes nothing — a segment shards only
        when a ``sharding`` override names a spec for its label (the
        tuner's journaled, rollback-able decision). MeshSupervision calls
        this with the surviving submesh after a shard-group quarantine."""
        self._shard_mesh = mesh
        self._seg_sharding.clear()
        self._plans.clear()

    @property
    def shard_mesh(self):
        return self._shard_mesh

    @property
    def cost_model(self):
        return self._cost_model

    @property
    def compile_cache(self) -> CompileCache:
        """The executable cache this model's segments compile into — the
        attachment point for the fleet's persistent tier."""
        return self._cache

    def attach_persistent_cache(self, tier, warm: bool = True
                                ) -> Dict[str, int]:
        """Fleet hook (serving/fleet/cache.py): hang the persistent tier
        under this model's CompileCache and (by default) AOT-warm —
        preload every compatible persisted executable NOW, so the first
        request for a previously-seen (segment, bucket) signature is a
        memory hit with zero jit compiles. Harvested cost records from
        the fleet's entries (including cost-only ones) feed the cost
        model, so planning starts calibrated on a fresh pod."""
        self._cache.attach_persistent(tier)
        stats = tier.warm(self._cache) if warm else \
            {"warmed": 0, "costs_only": 0, "skipped": 0, "errors": 0}
        if self._cost_model is not None:
            harvested = tier.harvested_costs()
            if harvested:
                try:
                    self._cost_model.ingest_costs(harvested)
                except Exception:  # noqa: BLE001 — warm costs best-effort
                    pass
        return stats

    @property
    def mega_k_max(self) -> int:
        """Largest active K-step dispatch factor (1 when untuned). Serving's
        DispatchWatchdog scales its budget by this so a K-batch mega-dispatch
        is not mistaken for a hang."""
        return max(self._mega_k_overrides.values(), default=1)

    def _get_slot_pool(self):
        if not self.slot_staging:
            return None
        if self._slot_pool is None:
            from ..parallel.ingest import SlotPool
            self._slot_pool = SlotPool()
        return self._slot_pool

    def _plan_for(self, schema: Schema) -> List[Any]:
        key = tuple(schema.types.items())
        if key not in self._plans:
            self._plans[key] = plan(
                self._stages, schema.copy(), cost_model=self._cost_model,
                fuse_overrides=self._fuse_overrides or None,
                stitch_overrides=self._stitch_overrides or None)
        return self._plans[key]

    def _sharding_for(self, node: Segment):
        """Resolve the segment's tuned spec name into a SegmentSharding
        (None = unsharded: no mesh, no override, an unsupported candidate
        or a 1-shard axis). A resolution error propagates."""
        name = self._sharding_overrides.get(node.label)
        if self._shard_mesh is None or not name:
            self._seg_sharding.pop(node.label, None)
            return None
        from ..parallel.shardplan import sharding_for

        sh = sharding_for(node, self._shard_mesh, name)
        if sh is None:
            self._seg_sharding.pop(node.label, None)
        else:
            self._seg_sharding[node.label] = sh.describe()
        return sh

    def _make_executor(self, node: Segment,
                       stage_sharding=None) -> SegmentExecutor:
        """The one construction of a SegmentExecutor from the tuned knobs.
        ``stage_sharding`` is the stage placement of a pipelined run and
        becomes the executor's sharding; mega-dispatch is then forced off
        (the stream IS the dispatch amortization) and the CSR layout is
        excluded by ``_pipe_plan_for``."""
        pipelined = stage_sharding is not None
        return SegmentExecutor(
            node, self._cache,
            buckets=self._bucket_overrides.get(node.label),
            cost_model=self._cost_model,
            slot_pool=self._get_slot_pool(),
            mega_k=1 if pipelined
            else self._mega_k_overrides.get(node.label, 1),
            sharding=stage_sharding if pipelined
            else self._sharding_for(node),
            kernel_variants=self._variant_overrides.get(node.label),
            stitch=self._stitch_overrides or None,
            layout=None if pipelined
            else self._layout_overrides.get(node.label))

    def _absorb(self, ex: SegmentExecutor) -> None:
        """Fold one finished executor's fallbacks, host-emitted columns,
        joined bytes and output placement into the last-run and cumulative
        stats."""
        self._last_fallbacks.extend(ex.fallbacks)
        self._host_emit[ex.segment.label] = dict(ex.host_emit)
        self._joined_bytes[ex.segment.label] = ex.joined_bytes
        with self._totals_lock:
            self._fallback_total += len(ex.fallbacks)
            for d, n in ex.out_devices.items():
                self._out_devices[d] = self._out_devices.get(d, 0) + n

    def _host_node(self, node: HostStage, df: DataFrame) -> DataFrame:
        """Run one host plan node, feeding its wall time to the cost model
        (the measured host side of fuse-vs-demote) when tuning is on."""
        n = sum(_part_rows(p) for p in df.partitions)
        t0 = time.perf_counter()
        with batch_span(current_batch(), f"host:{node.label}", rows=n):
            out = node.stage.transform(df)
        if self._cost_model is None:
            return out
        if n > 0:
            self._cost_model.observe_host(
                node.label, time.perf_counter() - t0, n)
        return out

    def _run_node(self, node, df: DataFrame) -> DataFrame:
        """Run one plan node serially: a Segment through a fresh executor
        and its own IngestStats, a HostStage on the host."""
        if not isinstance(node, Segment):
            return self._host_node(node, df)
        from ..parallel.ingest import IngestStats

        stats = self._seg_stats[node.label] = IngestStats()
        ex = self._make_executor(node)
        df = ex.run(df, stats)
        self._absorb(ex)
        return df

    def transform(self, df: DataFrame, fused: bool = True) -> DataFrame:
        if not fused:
            return PipelineModel.transform(self, df)
        # one trace a call: with no serving batch bound, the call's root
        # span opens on the default recorder (obs/trace.py) and is bound
        # for the call, so every site below records under it
        attrs = {"rows": sum(_part_rows(p) for p in df.partitions),
                 "partitions": len(df.partitions)}
        with root_span("transform", attrs):
            ensure_compile_cache()
            nodes = self._plan_for(df.schema)
            attrs["segments"] = sum(isinstance(n, Segment) for n in nodes)
            return self._transform_nodes(df, nodes)

    def _transform_nodes(self, df: DataFrame, nodes: List[Any]) -> DataFrame:
        self._last_plan = nodes
        self._seg_stats = {}
        self._host_emit = {}
        self._joined_bytes = {}
        self._last_fallbacks = []
        self._pipe_stats = None
        pplan = self._pipe_plan_for(nodes)
        if pplan is not None:
            from ..parallel.pipeplan import StageWedged

            try:
                return self._transform_pipelined(df, nodes, pplan)
            except StageWedged as e:
                # a stage's sub-mesh died mid-stream: quarantine it,
                # re-plan at depth N-1 on the survivors, and re-run the
                # in-flight DataFrame — bitwise-identical either way, so
                # no request is dropped (depth strictly decreases, so the
                # recursion is bounded by the original depth)
                self._pipe_replan_after_wedge(pplan, e.stage)
                return self.transform(df, fused=True)
        for node in nodes:
            df = self._run_node(node, df)
        return df

    def _pipe_plan_for(self, nodes: List[Any]):
        """Resolve the pipe_depth knob into a PipePlan (None = serial:
        knob off/<= 1, no mesh or no chainable run; a planning error
        propagates). An
        active CSR layout override keeps the plan serial: wire triples
        are staged per-partition on host, which the device-resident
        handoff never materializes (the same explicit exclusion as
        ``_csr_capable``'s sharding gate)."""
        depth = self._pipe_depth
        if not depth or depth <= 1 or self._shard_mesh is None \
                or self._layout_overrides:
            return None
        from ..parallel.pipeplan import build_pipe_plan

        pplan = build_pipe_plan(nodes, self._shard_mesh, depth,
                                model=self._cost_model)
        if pplan is not None and self._pipe_supervision is not None:
            try:
                self._pipe_supervision.register(pplan)
            except Exception:  # noqa: BLE001 — registration best-effort
                pass
        return pplan

    def _transform_pipelined(self, df: DataFrame, nodes: List[Any],
                             pplan) -> DataFrame:
        """Execute the plan with its chainable run pipelined: nodes
        before and after the run go through the ordinary serial loop;
        the run's segments stream micro-batches across their stage
        sub-meshes (parallel/pipeplan.py PipeRunner). StageWedged
        escapes to transform(), which re-plans and re-runs. The plan
        indices refer to the PIPELINE VIEW (``split_segments`` re-cut the
        fused chain at d2d boundaries), so that view is what runs —
        serial semantics are identical node-for-node."""
        from ..parallel.ingest import IngestStats
        from ..parallel.pipeplan import PipeRunner, stage_sharding_for

        if pplan.nodes is not None:
            nodes = pplan.nodes
        for node in nodes[:pplan.first]:
            df = self._run_node(node, df)
        execs, stats = [], []
        for offset, node in enumerate(nodes[pplan.first:pplan.last]):
            stage = pplan.stages[pplan.stage_of[pplan.first + offset]]
            sh = stage_sharding_for(
                node, stage, pplan.depth,
                spec_name=self._sharding_overrides.get(node.label))
            if sh.inner is not None:
                self._seg_sharding[node.label] = sh.inner.describe()
            seg_stats = IngestStats()
            self._seg_stats[node.label] = seg_stats
            stats.append(seg_stats)
            execs.append(self._make_executor(node, stage_sharding=sh))
        runner = PipeRunner(pplan, execs, stats,
                            cost_model=self._cost_model)
        df = runner.run(df)
        for ex in execs:
            self._absorb(ex)
        self._pipe_stats = runner.stats_dict(
            requeues=self._pipe_requeues, replans=self._pipe_replans)
        for node in nodes[pplan.last:]:
            df = self._run_node(node, df)
        return df

    def _pipe_replan_after_wedge(self, pplan, stage_index: int) -> None:
        """Quarantine a wedged stage and re-arm at depth N-1: through the
        registered supervision hook (PipeSupervision — supervisor
        quarantine + mesh degrade) when one is attached, else the local
        degrade. N-1 == 1 clears the knob (serial on the survivors)."""
        self._pipe_replans += 1
        self._pipe_requeues[int(stage_index)] = \
            self._pipe_requeues.get(int(stage_index), 0) + 1
        handler = self._pipe_wedge_handler
        if handler is not None:
            try:
                handler(pplan, int(stage_index))
                return
            except Exception:  # noqa: BLE001 — fall back to local replan
                pass
        from ..parallel.pipeplan import degrade_after_wedge

        mesh, depth = degrade_after_wedge(self._shard_mesh, pplan,
                                          stage_index)
        self.set_mesh(mesh)
        self.set_tuning(pipe_depth=depth if depth > 1 else 1)

    def transform_submit(self, df: DataFrame):
        """Non-blocking transform: run host stages and all but a TRAILING
        fused segment now; the trailing segment's batches are H2D-staged and
        dispatched (device-resident, jax async dispatch) and the returned
        zero-arg ``resolve()`` performs readback + finalize.
        ``transform_submit(df)()`` is bitwise-identical to ``transform(df)``
        — the serving executor uses this split to fulfill replies from its
        dedicated readback thread while the next batch dispatches."""
        from ..parallel.ingest import IngestStats

        ensure_compile_cache()
        nodes = self._plan_for(df.schema)
        self._last_plan = nodes
        self._seg_stats = {}
        self._host_emit = {}
        self._joined_bytes = {}
        self._last_fallbacks = []
        # the submit split stays serial: its contract is a single trailing
        # dispatched segment, not a stream (pipeline stats never linger)
        self._pipe_stats = None
        tail = nodes[-1] if nodes and isinstance(nodes[-1], Segment) else None
        body = nodes[:-1] if tail is not None else nodes
        for node in body:
            df = self._run_node(node, df)
        if tail is None:
            out = df
            return lambda: out
        stats = IngestStats()
        self._seg_stats[tail.label] = stats
        ex = self._make_executor(tail)
        resolve = ex.submit_run(df, stats)

        def done() -> DataFrame:
            out = resolve()
            self._absorb(ex)
            return out

        return done

    # -- stats surface (bench + serving /_mmlspark/stats) -----------------
    @property
    def last_ingest_stats(self):
        """Aggregated ingest decomposition across fused segments of the most
        recent transform (None before the first / when nothing fused)."""
        from ..parallel.ingest import IngestStats

        if not self._seg_stats:
            return None
        agg = IngestStats()
        for s in self._seg_stats.values():
            agg.merge(s)
        return agg

    def fusion_stats(self) -> Dict[str, Any]:
        """Segment layout + per-segment ingest + compile-cache counters +
        XLA cost records and the roofline attribution built from them
        (obs/perf.py): measured-vs-bound per segment with a dominant
        bottleneck label. Cost/roofline sections are empty (never failing)
        when the backend reports no cost analysis."""
        nodes = self._last_plan or []
        per_segment = {label: s.summary()
                       for label, s in self._seg_stats.items()}
        costs = self._cache.costs()
        try:
            from ..obs.perf import attribute_segments

            roofline = attribute_segments(
                per_segment, costs,
                sharding=self._seg_sharding or None,
                cost_model=self._cost_model,
                layout=self._layout_overrides or None)
        except Exception:  # noqa: BLE001 — attribution must not break stats
            roofline = {}
        out = {
            "segments": [n.describe() for n in nodes],
            "n_fused_segments": sum(isinstance(n, Segment) for n in nodes),
            "per_segment": per_segment,
            # per segment of the last transform: the columns emitted from
            # the staged host rows and the bytes not read back for them
            # (0 where the mechanism never engaged)
            "host_emit": {label: {"cols": sorted(cols),
                                  "bytes": sum(cols.values())}
                          for label, cols in self._host_emit.items()},
            # per segment of the last transform: the bytes of fetched
            # batches that emit copied into one array (a writer with its
            # own finalize, a 1-D output); 0 where rows are views of the
            # batch they came back in
            "joined_bytes": dict(self._joined_bytes),
            "fallbacks": list(self._last_fallbacks),
            "fallbacks_total": self._fallback_total,
            "devices": dict(self._out_devices),
            "compile_cache": self._cache.stats(),
            "segment_costs": costs,
            "roofline": roofline,
        }
        if (self._bucket_overrides or self._fuse_overrides
                or self._mega_k_overrides or self._sharding_overrides
                or self._variant_overrides or self._stitch_overrides
                or self._layout_overrides):
            out["tuning"] = {
                "buckets": {k: list(v)
                            for k, v in self._bucket_overrides.items()},
                "fuse": dict(self._fuse_overrides),
                "mega_k": dict(self._mega_k_overrides),
                "sharding": dict(self._sharding_overrides)}
            # new knobs appear only when set: stats payload parity with
            # plans tuned before the compiler-search knobs existed
            if self._variant_overrides:
                out["tuning"]["kernel_variants"] = {
                    label: {str(b): v for b, v in kv.items()}
                    for label, kv in self._variant_overrides.items()}
            if self._stitch_overrides:
                out["tuning"]["stitch"] = dict(self._stitch_overrides)
            if self._layout_overrides:
                out["tuning"]["layout"] = dict(self._layout_overrides)
        stitched: Dict[str, List[str]] = {}
        for n in nodes:
            if not isinstance(n, Segment):
                continue
            names = list(n.stitched)
            names += [type(s).__name__
                      for s, d in zip(n.stages, n.dfns)
                      if d.device_finalize is not None
                      and d.finalize_stitched is not None
                      and self._stitch_overrides.get(type(s).__name__)]
            if names:
                stitched[n.label] = list(dict.fromkeys(names))
        if stitched:  # key absent when nothing stitched: payload parity
            out["stitched"] = stitched
        if self._seg_sharding:
            from ..parallel.shardplan import mesh_topology

            out["sharding"] = {
                "mesh": mesh_topology(self._shard_mesh),
                "segments": {k: dict(v)
                             for k, v in self._seg_sharding.items()}}
        if self._slot_pool is not None:
            out["slot_pool"] = self._slot_pool.stats()
        if self._pipe_stats:  # key absent when no pipe plan ran: parity
            out["pipeline"] = dict(self._pipe_stats)
        return out

    @property
    def last_fusion_stats(self) -> Dict[str, Any]:
        return self.fusion_stats()

    def save(self, path: str, overwrite: bool = True) -> None:
        PipelineModel(self._stages).save(path, overwrite=overwrite)
