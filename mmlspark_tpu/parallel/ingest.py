"""Unified device-ingest layer: uint8 wire format + transfer ring + stats.

The framework's data plane. Earlier claim, not measured in this round: the
flagship featurize path computed ~44x faster per call than end to end —
the DataFrame -> device ingest path, not XLA compute, was the bottleneck.
Two structural fixes live here:

  - **uint8 on the wire** (``PreprocessSpec``): the host stops doing
    ``astype(float32) * scale`` (+ layout transpose) per image; batches ship
    in their decoded dtype (uint8 pixels = 4x fewer H2D bytes) and the
    cast/scale/transpose runs INSIDE the consumer's jitted forward, where
    XLA fuses it with the first conv's bf16 cast for free.
  - **transfer ring** (``TransferRing``): a configurable number of in-flight
    batches replaces ad-hoc double buffering. H2D runs on a background
    thread (overlapping the previous batch's compute), up to ``depth``
    dispatched steps stay in flight, and results drain in order. Every
    stage is timed per batch into an ``IngestStats`` object, so the
    e2e-vs-per-call gap is a first-class measured quantity.

Consumers: DNNModel (models/dnn_model.py) for the DataFrame eval path,
DeviceEnsemble (gbdt/predict.py) for chunked GBDT scoring, and the fused
segments of core/fusion.py. The ring is generic — anything shaped
``host batches -> stage -> dispatch -> readback`` can ride it.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from ..core import faults
from .batching import DevicePrefetcher


# ---------------------------------------------------------------------------
# PreprocessSpec: host preprocessing moved into the compiled forward
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PreprocessSpec:
    """Device-side preprocessing fused into a jitted forward.

    Describes what the host USED to do to each row before batching —
    ``astype(float32) * scale + offset`` and an optional per-row axes
    transpose (NHWC -> NCHW for ONNX imports) — so the wire carries the raw
    decoded dtype and the work runs on device, inside jit. Hashable, so
    compiled-forward caches can key on it.

    ``transpose`` is the PER-ROW axes permutation (e.g. ``(2, 0, 1)`` for
    HWC -> CHW); the batched device op shifts it past the leading batch dim.
    ``dtype``: compute dtype after the cast (float32 unless doing f64
    numerics experiments).
    """

    scale: float = 1.0
    offset: float = 0.0
    transpose: Optional[Tuple[int, ...]] = None
    dtype: str = "float32"

    def __post_init__(self):
        if self.transpose is not None:
            object.__setattr__(self, "transpose",
                               tuple(int(a) for a in self.transpose))

    @property
    def is_identity(self) -> bool:
        return (self.scale == 1.0 and self.offset == 0.0
                and self.transpose is None and self.dtype == "float32")

    def cache_key(self) -> Tuple:
        """Pure-literal tuple form for compile-cache keys. The persistent
        fleet tier (serving/fleet/cache.py) round-trips keys through
        ``repr``/``ast.literal_eval`` — a dataclass repr would survive
        repr but not the (deliberately eval-free) parse, demoting warm-up
        from AOT-by-name to lazy-at-first-request."""
        return ("PreprocessSpec", float(self.scale), float(self.offset),
                self.transpose, self.dtype)

    def _batch_axes(self, ndim: int) -> Tuple[int, ...]:
        perm = self.transpose
        if perm is None or len(perm) != ndim - 1:
            raise ValueError(
                f"transpose {perm} does not match per-row rank {ndim - 1}")
        return (0,) + tuple(a + 1 for a in perm)

    def apply_device(self, x):
        """Batched [B, ...] device op, trace-safe under jit."""
        import jax.numpy as jnp

        dt = getattr(jnp, self.dtype)
        y = x.astype(dt)
        if self.scale != 1.0:
            y = y * dt(self.scale)
        if self.offset != 0.0:
            y = y + dt(self.offset)
        if self.transpose is not None:
            y = jnp.transpose(y, self._batch_axes(y.ndim))
        return y

    def apply_host(self, x: np.ndarray) -> np.ndarray:
        """Numpy reference of ``apply_device`` on a [B, ...] batch — the
        numerical-parity oracle (uint8 -> f32 cast and an f32 multiply are
        exact, so host and device agree bitwise) and the fallback for
        consumers that never reach a device."""
        dt = np.dtype(self.dtype).type
        y = x.astype(dt)
        if self.scale != 1.0:
            y = y * dt(self.scale)
        if self.offset != 0.0:
            y = y + dt(self.offset)
        if self.transpose is not None:
            y = np.transpose(y, self._batch_axes(y.ndim))
        return y

    def apply_host_row(self, img: np.ndarray) -> np.ndarray:
        """Per-row host application (the legacy featurizer prep path)."""
        return self.apply_host(img[None])[0]


# ---------------------------------------------------------------------------
# IngestStats: per-batch ingest decomposition
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BatchTiming:
    """Wall-clock decomposition of one batch through the ring (seconds).

    ``queue_s``  — consumer wait for the prefetched batch (producer-bound
                   time: decode/stack upstream plus H2D not yet hidden).
    ``h2d_s``    — host->device transfer, measured ON the producer thread
                   (device_put + block-until-ready), so it overlaps compute.
    ``dispatch_s`` — host cost of enqueueing the compiled step (async).
    ``compute_s``  — residual wait for the step's outputs at drain time
                   (0 when compute fully hid behind later batches' ingest).
    ``readback_s`` — device->host fetch of the outputs.
    ``bytes_in`` — wire bytes shipped for this batch.
    ``rows``     — valid rows in the batch.
    ``padded_rows`` — the static bucket size the batch was padded to (0 =
                   unpadded/unknown); ``padded_rows - rows`` is pure
                   pad-waste compute, the cost-model term the bucket
                   auto-tuner (core/costmodel.py) minimizes.
    ``mega_k``   — dispatch-amortization group size this batch rode (1 =
                   plain per-batch dispatch). When > 1, ``dispatch_s`` is
                   the per-batch SHARE of one K-step mega dispatch;
                   ``dispatch_s * mega_k`` recovers the per-Python-call
                   fixed cost the cost model's ``choose_mega_k`` needs —
                   without the tag, an active K>1 makes dispatch look
                   cheap, the tuner proposes K=1, and K oscillates.
    ``counts``   — what the program's work depends on in the batch, counted
                   on the host from what was shipped (``Batch.counts``: a
                   causal scorer's ``attn_query_blocks`` and
                   ``attn_live_blocks``); empty where nothing counted.
    """

    queue_s: float = 0.0
    h2d_s: float = 0.0
    dispatch_s: float = 0.0
    compute_s: float = 0.0
    readback_s: float = 0.0
    bytes_in: int = 0
    rows: int = 0
    padded_rows: int = 0
    mega_k: int = 1
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)


class IngestStats:
    """Accumulates ``BatchTiming`` rows plus ring wall time; ``summary()``
    renders the e2e decomposition ``fusion_stats()`` and the serving stats
    endpoint surface. Safe to share across sequential ring runs (partitions of one
    transform accumulate into one object)."""

    def __init__(self):
        self.records: List[BatchTiming] = []
        self.wall_s: float = 0.0
        # ring slot occupancy (dispatched-but-undrained steps): configured
        # depth + running mean/max of observed fill, so "is the ring ever
        # actually full?" is a scraped gauge instead of a rerun experiment
        self.ring_depth: int = 0
        # rings built, the partitions a fused call prepared for them, and
        # how many of those a look-ahead thread prepared while an earlier
        # partition was still in the ring (core/fusion.py: one ring a call;
        # counts, so they do not depend on timing)
        self.rings: int = 0
        self.partitions: int = 0
        self.partitions_ahead: int = 0
        self._occ_sum: int = 0
        self._occ_n: int = 0
        self._occ_max: int = 0
        # pad-waste per bucket: {padded size: [batches, real rows]} — the
        # measured term behind mmlspark_batch_pad_ratio{bucket=} and the
        # cost model's bucket chooser (assumed-waste becomes measured-waste)
        self._pad: Dict[int, List[int]] = {}
        # deposit accounting (docs/ingest.md): batches staged zero-alloc
        # into SlotPool slots vs batches that took the accounted copying
        # fallback (mmlspark_ingest_deposits_total / _copies_total)
        self.deposits: int = 0
        self.copies: int = 0
        # rows_to_batch outcome split: spanning zero-copy views vs stacked
        # copies (mmlspark_ingest_zero_copy_batches_total / _copied_...)
        self.zero_copy_batches: int = 0
        self.copied_batches: int = 0
        # per-slot double-buffer decomposition: fill / transfer seconds and
        # the measured fill<->transfer overlap between paired buffers
        self.slot_fill_s: float = 0.0
        self.slot_transfer_s: float = 0.0
        self.slot_overlap_s: float = 0.0
        self.slot_transfers: int = 0
        # sparse-layout accounting (docs/sparse.md): bytes a densify
        # materialized vs the CSR bytes the same rows would have shipped
        # (mmlspark_ingest_densified_bytes_total / _densify_ratio), and the
        # CSR-through counterpart (bytes actually staged as triples vs the
        # dense-equivalent bytes avoided). All zero — and absent from
        # summary() — until sparse data is seen.
        self.densified_bytes: int = 0
        self.densify_nnz_bytes: int = 0
        self.densifies: int = 0
        self.csr_nnz_bytes: int = 0
        self.csr_dense_bytes: int = 0
        self.csr_batches: int = 0

    def record(self, t: BatchTiming) -> None:
        self.records.append(t)
        if t.padded_rows > 0:
            self.note_padding(t.padded_rows, t.rows)

    def note_padding(self, bucket: int, rows: int) -> None:
        """Count one batch padded to ``bucket`` static rows with ``rows``
        real ones (callable directly by batchers outside the ring)."""
        acc = self._pad.setdefault(int(bucket), [0, 0])
        acc[0] += 1
        acc[1] += int(rows)

    def add_wall(self, seconds: float) -> None:
        self.wall_s += seconds

    def note_ring(self, depth: int) -> None:
        """One ring built, ``depth`` deep."""
        self.ring_depth = max(self.ring_depth, int(depth))
        self.rings += 1

    def note_partition(self, ahead: bool) -> None:
        """One partition prepared for the device path; ``ahead``: by the
        look-ahead thread, beside the partition before it."""
        self.partitions += 1
        self.partitions_ahead += bool(ahead)

    def note_occupancy(self, in_flight: int) -> None:
        n = int(in_flight)
        self._occ_sum += n
        self._occ_n += 1
        self._occ_max = max(self._occ_max, n)

    def note_deposit(self) -> None:
        """One batch staged in place into a pre-allocated slot."""
        self.deposits += 1

    def note_copy(self) -> None:
        """One batch that took the accounted copying fallback (deposit
        ineligible: dtype narrowing, ragged rows, slot contention, or a
        transfer fault) — the ``mmlspark_ingest_copies_total`` counter."""
        self.copies += 1

    def note_batch_copy(self, zero_copy: bool) -> None:
        """rows_to_batch outcome: spanning zero-copy view vs stacked copy."""
        if zero_copy:
            self.zero_copy_batches += 1
        else:
            self.copied_batches += 1

    def note_densify(self, densified_bytes: int, nnz_bytes: int) -> None:
        """One sparse column densified on the host path: the dense bytes it
        materialized vs the CSR bytes the same rows hold — the measured
        waste the layout knob exists to remove."""
        self.densified_bytes += int(densified_bytes)
        self.densify_nnz_bytes += int(nnz_bytes)
        self.densifies += 1

    def note_csr(self, nnz_bytes: int, dense_bytes: int) -> None:
        """One batch staged as a CSR triple: the triple's actual bytes vs
        the dense-equivalent bytes the densify path would have shipped."""
        self.csr_nnz_bytes += int(nnz_bytes)
        self.csr_dense_bytes += int(dense_bytes)
        self.csr_batches += 1

    def note_slot(self, fill_s: float, transfer_s: float,
                  overlap_s: float) -> None:
        """One slot cycle: host fill seconds, H2D transfer seconds, and the
        measured overlap between this transfer and the paired buffer's
        concurrent fill (double-buffering effectiveness, per slot)."""
        self.slot_fill_s += float(fill_s)
        self.slot_transfer_s += float(transfer_s)
        self.slot_overlap_s += float(overlap_s)
        self.slot_transfers += 1

    def merge(self, other: "IngestStats") -> None:
        """Fold another stats object in (segment aggregation)."""
        self.records.extend(other.records)
        self.wall_s += other.wall_s
        self.ring_depth = max(self.ring_depth, other.ring_depth)
        self.rings += other.rings
        self.partitions += other.partitions
        self.partitions_ahead += other.partitions_ahead
        self._occ_sum += other._occ_sum
        self._occ_n += other._occ_n
        self._occ_max = max(self._occ_max, other._occ_max)
        for bucket, (batches, rows) in other._pad.items():
            acc = self._pad.setdefault(bucket, [0, 0])
            acc[0] += batches
            acc[1] += rows
        self.deposits += other.deposits
        self.copies += other.copies
        self.zero_copy_batches += other.zero_copy_batches
        self.copied_batches += other.copied_batches
        self.slot_fill_s += other.slot_fill_s
        self.slot_transfer_s += other.slot_transfer_s
        self.slot_overlap_s += other.slot_overlap_s
        self.slot_transfers += other.slot_transfers
        self.densified_bytes += other.densified_bytes
        self.densify_nnz_bytes += other.densify_nnz_bytes
        self.densifies += other.densifies
        self.csr_nnz_bytes += other.csr_nnz_bytes
        self.csr_dense_bytes += other.csr_dense_bytes
        self.csr_batches += other.csr_batches

    @property
    def num_batches(self) -> int:
        return len(self.records)

    def _pad_summary(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        padding: Dict[str, Any] = {}
        tot_real = tot_padded = 0
        for bucket in sorted(self._pad):
            batches, real = self._pad[bucket]
            padded = batches * bucket
            tot_real += real
            tot_padded += padded
            padding[str(bucket)] = {
                "batches": batches, "rows": real, "padded": padded,
                # fraction of the bucket's compute spent on pad rows
                "pad_ratio": round(1 - real / padded, 4) if padded
                else None}
        out["padding"] = padding
        if tot_padded:
            out["pad_ratio"] = round(1 - tot_real / tot_padded, 4)
        return out

    def _staging_summary(self) -> Dict[str, Any]:
        """Rings / partitions / deposit / zero-copy / slot-overlap section
        (only populated keys, so summaries without staging activity are
        unchanged)."""
        out: Dict[str, Any] = {}
        if self.rings:
            out["rings"] = self.rings
        if self.partitions:
            out["partitions"] = self.partitions
            out["partitions_ahead"] = self.partitions_ahead
        if self.deposits or self.copies:
            out["slot_deposits"] = self.deposits
            out["fallback_copies"] = self.copies
        if self.zero_copy_batches or self.copied_batches:
            out["zero_copy_batches"] = self.zero_copy_batches
            out["copied_batches"] = self.copied_batches
        if self.slot_transfers:
            out["slot_fill_s"] = round(self.slot_fill_s, 6)
            out["slot_transfer_s"] = round(self.slot_transfer_s, 6)
            out["slot_overlap_s"] = round(self.slot_overlap_s, 6)
            # fraction of transfer time hidden behind the paired buffer's
            # fill (1.0 = every transfer fully overlapped a fill)
            out["slot_overlap_ratio"] = round(
                self.slot_overlap_s / self.slot_transfer_s, 4) \
                if self.slot_transfer_s > 0 else None
        if self.densifies:
            out["densifies"] = self.densifies
            out["densified_bytes"] = self.densified_bytes
            out["densify_nnz_bytes"] = self.densify_nnz_bytes
            # dense bytes materialized per CSR byte the rows actually hold
            # (the layout knob's headroom; 1.0 = densify was free)
            out["densify_ratio"] = round(
                self.densified_bytes / self.densify_nnz_bytes, 4) \
                if self.densify_nnz_bytes > 0 else None
        if self.csr_batches:
            out["csr_batches"] = self.csr_batches
            out["csr_nnz_bytes"] = self.csr_nnz_bytes
            out["csr_dense_bytes"] = self.csr_dense_bytes
        return out

    def summary(self) -> Dict[str, Any]:
        if not self.records:
            out = {"n_batches": 0}
            if self._pad:
                out.update(self._pad_summary())
            out.update(self._staging_summary())
            return out
        cols = {f: float(sum(getattr(r, f) for r in self.records))
                for f in ("queue_s", "h2d_s", "dispatch_s", "compute_s",
                          "readback_s")}
        total_bytes = int(sum(r.bytes_in for r in self.records))
        rows = int(sum(r.rows for r in self.records))
        serial = sum(cols.values())
        n = len(self.records)
        out: Dict[str, Any] = {
            "n_batches": n,
            "rows": rows,
            "bytes": total_bytes,
            "wall_s": round(self.wall_s, 6),
            # < 1.0 means the ring hid ingest behind compute (and vice
            # versa); 1.0 = fully serial pipeline
            "overlap_ratio": round(self.wall_s / serial, 4) if serial > 0
            else None,
            "h2d_gbps": round(total_bytes / cols["h2d_s"] / 1e9, 4)
            if cols["h2d_s"] > 0 else None,
        }
        if self.ring_depth > 0:
            out["ring_depth"] = self.ring_depth
            if self._occ_n > 0:
                out["ring_occupancy_mean"] = round(
                    self._occ_sum / self._occ_n, 4)
                out["ring_occupancy_max"] = self._occ_max
        if self._pad:
            out.update(self._pad_summary())
        out.update(self._staging_summary())
        for f, v in cols.items():
            out[f] = round(v, 6)
            out[f"{f[:-2]}_ms_per_batch"] = round(v / n * 1e3, 4)
        return out


def _root_exporter(a: np.ndarray):
    """The object that OWNS an array view's memory: walk the ``.base``
    chain to the final ndarray, and through a memoryview to its exporter
    (``decode_frame`` views are frombuffer-over-memoryview-slice; the slice
    keeps the WHOLE exporter alive, which is what makes a spanning strided
    view over sibling slices memory-safe)."""
    b = a
    while isinstance(b, np.ndarray) and b.base is not None:
        b = b.base
    if isinstance(b, memoryview):
        try:
            return b.obj
        except Exception:  # noqa: BLE001 — released/exotic memoryview
            return b
    return b


def _spanning_view(arrs: List[np.ndarray], shape: Tuple[int, ...],
                   ) -> Optional[np.ndarray]:
    """Zero-copy [B, ...] view when the rows sit at a CONSTANT pointer
    stride inside one live buffer; None otherwise.

    Two layouts qualify: adjacent rows (stride == row nbytes — a whole
    batch shipped in one frame column, or journal replay of a concatenated
    region) and rows spanning multiple PIPELINED FRAMES of one connection
    buffer (stride > row nbytes: equal-size frames back-to-back put each
    frame's payload at payload+header intervals). The second layout is
    only taken when every row resolves to the SAME root exporter object —
    rows from unrelated buffers must never be bridged by pointer
    arithmetic, no matter how adjacent they happen to land."""
    nb = arrs[0].nbytes
    if len(arrs) < 2 or not nb \
            or not all(a.flags["C_CONTIGUOUS"] for a in arrs):
        return None
    try:
        ptrs = [a.__array_interface__["data"][0] for a in arrs]
    except (KeyError, TypeError):
        return None
    stride = ptrs[1] - ptrs[0]
    if stride < nb or any(p != ptrs[0] + i * stride
                          for i, p in enumerate(ptrs)):
        return None
    if stride > nb:
        root = _root_exporter(arrs[0])
        if any(_root_exporter(a) is not root for a in arrs[1:]):
            return None
    # one spanning view over the shared buffer; arrs[0] rides along as
    # .base so the underlying memory stays alive
    return np.lib.stride_tricks.as_strided(
        arrs[0], shape=(len(arrs),) + shape,
        strides=(stride,) + arrs[0].strides)


def rows_to_batch(rows, out: Optional[np.ndarray] = None,
                  stats: Optional["IngestStats"] = None) -> np.ndarray:
    """Per-row arrays -> one contiguous [B, ...] batch for H2D staging.

    The binary-wire ingest path: ``decode_frame`` hands each request's
    payload back as a zero-copy VIEW over its body bytes, and this is the
    single host copy that remains — the batch stack that doubles as the
    transfer ring's staging buffer (uint8 on the wire, cast/scale on
    device via PreprocessSpec).

    Fast path: when the rows sit at one constant stride over ONE live
    buffer (a client shipped a whole batch in one frame column, journal
    replay of a concatenated region, or pipelined equal-size frames of one
    connection), the batch is a strided view — zero copies end-to-end.
    Otherwise ``np.stack``. Rows must agree on shape and dtype (ragged
    batches stay on the per-row host path).

    ``out``: slot-fill mode — a pre-allocated [cap, ...] staging slot
    (SlotPool buffer) receiving the rows in place; returns ``out[:B]``.
    ``stats``: optional IngestStats receiving the zero-copy vs copied
    batch counters.

    A fused segment that re-enters the device after a terminal host
    finalize pays this re-batch per boundary crossing; the cross-segment
    stitch (docs/compiler_search.md) removes that call entirely for
    stitched plans — downstream stages ride the segment's device-resident
    columns, so this path only runs where a genuine host boundary
    remains."""
    arrs = [np.asarray(r) for r in rows]
    if not arrs:
        raise ValueError("rows_to_batch needs at least one row")
    shape, dt = arrs[0].shape, arrs[0].dtype
    for a in arrs[1:]:
        if a.shape != shape or a.dtype != dt:
            raise ValueError(
                f"ragged batch: {a.shape}/{a.dtype} vs {shape}/{dt}")
    if out is not None:
        # slot-fill: rows land in the caller's slot — stack + pad collapse
        # into this ONE copy (the H2D staging buffer is the destination)
        if out.dtype != dt or tuple(out.shape[1:]) != shape \
                or len(out) < len(arrs):
            raise ValueError(
                f"slot [{len(out)}]{out.shape[1:]}/{out.dtype} cannot "
                f"receive batch [{len(arrs)}]{shape}/{dt}")
        view = _spanning_view(arrs, shape) if len(arrs) > 1 else None
        if view is not None:
            out[:len(arrs)] = view  # one bulk memcpy
        else:
            for i, a in enumerate(arrs):
                out[i] = a
        if stats is not None:
            stats.note_batch_copy(zero_copy=False)
        return out[:len(arrs)]
    if len(arrs) == 1:
        if arrs[0].flags["C_CONTIGUOUS"]:
            if stats is not None:
                stats.note_batch_copy(zero_copy=True)
            return arrs[0][None]
        if stats is not None:
            stats.note_batch_copy(zero_copy=False)
        return np.ascontiguousarray(arrs[0])[None]
    view = _spanning_view(arrs, shape)
    if view is not None:
        if stats is not None:
            stats.note_batch_copy(zero_copy=True)
        return view
    if stats is not None:
        stats.note_batch_copy(zero_copy=False)
    return np.stack(arrs)


# ---------------------------------------------------------------------------
# SlotPool: pre-allocated, double-buffered H2D staging slots
# ---------------------------------------------------------------------------


class _SlotBucket:
    """Paired pre-allocated buffers for one (column, batch shape, dtype)
    bucket. Two buffers = double buffering: one fills while the sibling
    transfers. ``fills`` holds this bucket's recent completed fill
    intervals — a transfer's overlap is measured against its OWN bucket's
    sibling fills only, never against unrelated leases elsewhere in the
    shared pool. ``tick`` is the pool's LRU clock value at last use."""

    __slots__ = ("bufs", "free", "fills", "tick")

    def __init__(self, shape: Tuple[int, ...], dtype, n: int):
        self.bufs = [np.zeros(shape, dtype=dtype) for _ in range(n)]
        self.free = list(range(n))
        self.fills: deque = deque(maxlen=8)
        self.tick = 0

    @property
    def nbytes(self) -> int:
        return sum(b.nbytes for b in self.bufs)


class SlotLease:
    """One acquired staging slot: a pre-allocated ``[cap, ...]`` buffer per
    deposit column of one batch. Lifecycle: ``fill_begin``/``fill_end``
    around the host fill, then ``transfer_begin``/``transfer_end`` driven
    by ``timed_stage`` around the H2D transfer — ``transfer_end`` records
    the fill/transfer/overlap decomposition into IngestStats and returns
    the buffers to the pool. ``release()`` is the idempotent abandon path
    (a faulted transfer frees the buffers without recording a cycle; the
    slot content is simply overwritten on reuse, never read). A weakref
    finalizer backstops release: a lease dropped on any abort path (queue
    drain, injected fault, watchdog kill) still returns its buffers to the
    shared, never-replenished pool instead of shrinking it forever."""

    __slots__ = ("arrays", "_pool", "_held", "_stats", "_fill", "_tx0",
                 "_done", "_finalizer", "__weakref__")

    def __init__(self, pool: "SlotPool", held: List[Tuple[Tuple, int]],
                 arrays: Dict[str, np.ndarray], stats):
        import weakref

        self.arrays = arrays
        self._pool = pool
        self._held = held
        self._stats = stats
        self._fill = (0.0, 0.0)
        self._tx0: Optional[float] = None
        self._done = False
        self._finalizer = weakref.finalize(self, pool._release, held)

    def fill_begin(self) -> None:
        self._fill = (time.perf_counter(), 0.0)

    def fill_end(self) -> None:
        self._fill = (self._fill[0], time.perf_counter())
        self._pool._note_fill(self._held, self._fill)

    def transfer_begin(self) -> None:
        self._tx0 = time.perf_counter()

    def transfer_end(self) -> None:
        tx1 = time.perf_counter()
        tx0 = self._tx0 if self._tx0 is not None else tx1
        if self._stats is not None:
            fill_s = max(0.0, self._fill[1] - self._fill[0])
            self._stats.note_slot(fill_s, tx1 - tx0,
                                  self._pool._overlap(self._held, tx0, tx1))
        self.release()

    def release(self) -> None:
        if self._done:
            return
        self._done = True
        # the finalizer IS the release (calling it runs pool._release once
        # and detaches, so a later GC never double-frees)
        self._finalizer()


class SlotPool:
    """Pre-allocated, shape-bucket-keyed H2D staging slots with PAIRED
    buffers per bucket (tentpole piece 2 of the single-copy ingress path,
    docs/ingest.md).

    ``acquire(spec)`` hands out a ``SlotLease`` over one buffer per
    requested column, keyed by (column, full batch shape, dtype) — the
    shape-bucket key, so every padded bucket size reuses its own slot
    instead of allocating per batch. Each bucket holds
    ``buffers_per_bucket`` (default 2) buffers: while buffer A is in H2D
    transfer, buffer B fills — the per-slot overlap is MEASURED (lease
    transfer intervals intersected with concurrent fill intervals) and
    reported through ``IngestStats.note_slot``.

    ``acquire`` is all-or-nothing under one condition variable (no partial
    holds, no lock-order deadlocks) and returns None instead of blocking
    past ``acquire_timeout_s`` — callers fall back to the accounted
    copying path (``IngestStats.note_copy``), so slot contention degrades
    to today's behavior instead of stalling the ring.

    Buffer ALLOCATION happens outside the lock (a 256MB ``np.zeros`` must
    not stall every concurrent acquire/release), and total pool memory is
    bounded by ``max_total_bytes``: inserting a new bucket first evicts
    least-recently-used fully-free buckets, and when no room can be made
    the acquire returns None (copy-path fallback) instead of growing
    without limit across the distinct shapes a long-lived server sees."""

    def __init__(self, buffers_per_bucket: int = 2,
                 max_slot_bytes: int = 1 << 28,
                 max_total_bytes: int = 1 << 31,
                 acquire_timeout_s: float = 2.0):
        import threading

        self._nbuf = max(1, int(buffers_per_bucket))
        self._max_bytes = int(max_slot_bytes)
        self._max_total = int(max_total_bytes)
        self._timeout = float(acquire_timeout_s)
        self._cv = threading.Condition()
        self._buckets: Dict[Tuple, _SlotBucket] = {}
        self._tick = 0          # LRU clock (monotonic acquire counter)
        self._evictions = 0

    def _missing_buckets(self, keys: Dict[str, Tuple],
                         spec: Dict[str, Tuple[Tuple[int, ...], Any]]
                         ) -> Optional[List[Tuple]]:
        """Under self._cv: keys not yet backed by a bucket, as (key, shape,
        dtype, nbytes) build specs. None when any slot exceeds the per-slot
        byte cap (caller falls back to the copying path)."""
        missing = []
        for col, key in keys.items():
            if key in self._buckets:
                continue
            shape, dtype = spec[col]
            nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
            if nbytes <= 0 or nbytes > self._max_bytes:
                return None
            missing.append((key, tuple(int(d) for d in shape), dtype,
                            nbytes))
        return missing

    def _make_room(self, need: int, protect: frozenset) -> bool:
        """Under self._cv: evict LRU fully-free buckets until ``need`` more
        bytes fit under ``max_total_bytes``. False when in-use buckets pin
        the pool above the cap (leased buffers are never evicted — a stale
        release into a re-created bucket is guarded, but yanking live
        buffers is not recoverable). ``protect``: keys the CURRENT acquire
        needs — evicting a sibling bucket of the same spec would ping-pong
        build/evict forever."""
        total = sum(b.nbytes for b in self._buckets.values())
        while total + need > self._max_total:
            victim_key, victim = None, None
            for key, b in self._buckets.items():
                if key not in protect and len(b.free) == len(b.bufs) and \
                        (victim is None or b.tick < victim.tick):
                    victim_key, victim = key, b
            if victim is None:
                return False
            del self._buckets[victim_key]
            total -= victim.nbytes
            self._evictions += 1
        return True

    def acquire(self, spec: Dict[str, Tuple[Tuple[int, ...], Any]],
                stats=None,
                timeout: Optional[float] = None) -> Optional[SlotLease]:
        """``spec``: {column: (full batch shape INCLUDING the leading
        padded cap, dtype)}. Returns a SlotLease, or None on timeout /
        uncacheable shape / a full pool (caller copies and accounts it)."""
        if not spec:
            return None
        deadline = time.perf_counter() + (
            self._timeout if timeout is None else float(timeout))
        keys = {}
        for col in sorted(spec):
            shape, dtype = spec[col]
            keys[col] = (col, tuple(int(d) for d in shape),
                         np.dtype(dtype).str)
        while True:
            with self._cv:
                missing = self._missing_buckets(keys, spec)
                if missing is None:
                    return None
                if not missing:
                    lease = self._try_grab(keys, stats)
                    if lease is not None:
                        return lease
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0 or not self._cv.wait(remaining):
                        return None
                    continue
            # allocate OUTSIDE the lock: np.zeros of a 256MB slot must not
            # stall concurrent acquire/release on the shared pool
            built = [(key, _SlotBucket(shape, dtype, self._nbuf))
                     for key, shape, dtype, _ in missing]
            protect = frozenset(keys.values())
            with self._cv:
                for key, bucket in built:
                    if key in self._buckets:
                        continue  # racing thread built it first; drop ours
                    if not self._make_room(bucket.nbytes, protect):
                        return None
                    self._buckets[key] = bucket
                self._cv.notify_all()
            # loop: grab under the lock now that the buckets exist

    def _try_grab(self, keys: Dict[str, Tuple],
                  stats) -> Optional[SlotLease]:
        """Under self._cv: all-or-nothing lease over one free buffer per
        key. None when any bucket has no free buffer (or two columns
        collapse onto one bucket)."""
        buckets = {col: self._buckets[key] for col, key in keys.items()}
        if not all(b.free for b in buckets.values()) or \
                len({id(b) for b in buckets.values()}) != len(buckets):
            return None
        self._tick += 1
        held = []
        arrays = {}
        for col, key in keys.items():
            bucket = buckets[col]
            bucket.tick = self._tick
            idx = bucket.free.pop()
            held.append((key, idx))
            arrays[col] = bucket.bufs[idx]
        return SlotLease(self, held, arrays, stats)

    def _release(self, held: List[Tuple[Tuple, int]]) -> None:
        with self._cv:
            for key, idx in held:
                bucket = self._buckets.get(key)
                if bucket is not None and idx not in bucket.free:
                    bucket.free.append(idx)
            self._cv.notify_all()

    def _note_fill(self, held: List[Tuple[Tuple, int]],
                   interval: Tuple[float, float]) -> None:
        """Record a completed fill on the lease's OWN buckets only: overlap
        must measure this bucket-pair's double buffering, not unrelated
        leases elsewhere in the shared pool."""
        with self._cv:
            for key, _idx in held:
                bucket = self._buckets.get(key)
                if bucket is not None:
                    bucket.fills.append(interval)

    def _overlap(self, held: List[Tuple[Tuple, int]],
                 tx0: float, tx1: float) -> float:
        """Seconds of [tx0, tx1] overlapped by sibling fills in the lease's
        own buckets (a lease's own fill ends before its transfer begins, so
        it contributes zero by construction). Multi-column leases record
        one identical interval per bucket — deduped so it counts once."""
        with self._cv:
            fills = {f for key, _idx in held
                     for f in getattr(self._buckets.get(key), "fills", ())}
        return sum(max(0.0, min(tx1, f1) - max(tx0, f0))
                   for f0, f1 in fills)

    def stats(self) -> Dict[str, Any]:
        with self._cv:
            buckets = len(self._buckets)
            buffers = sum(len(b.bufs) for b in self._buckets.values())
            nbytes = sum(b.nbytes for b in self._buckets.values())
            evictions = self._evictions
        return {"buckets": buckets, "buffers": buffers, "bytes": nbytes,
                "max_total_bytes": self._max_total,
                "evictions": evictions}


def _tree_rows(item: Any) -> int:
    """Valid rows in a batch: Batch.num_valid when present, else the leading
    dim of a raw array batch."""
    nv = getattr(item, "num_valid", None)
    if nv is not None:
        return int(nv)
    shape = getattr(item, "shape", None)
    if shape:
        return int(shape[0])
    return 0


def _tree_padded(item: Any) -> int:
    """Padded (bucket) size of a batch: ``len(mask)`` of a
    parallel.batching.Batch (mask length == static batch size), 0 when the
    item carries no padding information (raw arrays are unpadded)."""
    mask = getattr(item, "mask", None)
    if mask is not None and getattr(item, "num_valid", None) is not None:
        try:
            return int(len(mask))
        except TypeError:
            return 0
    return 0


def _tree_nbytes(item: Any) -> int:
    """Total nbytes of arrays inside an arbitrary batch structure."""
    if hasattr(item, "nbytes"):
        return int(item.nbytes)
    if hasattr(item, "arrays"):  # parallel.batching.Batch
        return _tree_nbytes(item.arrays)
    if isinstance(item, dict):
        return sum(_tree_nbytes(v) for v in item.values())
    if isinstance(item, (list, tuple)):
        return sum(_tree_nbytes(v) for v in item)
    return 0


def timed_stage(put: Optional[Callable], item: Any,
                obs: Optional[tuple] = None,
                batch: Optional[int] = None) -> Tuple[Any, "BatchTiming"]:
    """Stage one host batch toward the device with ingest accounting: fires
    the INGEST_H2D chaos seam, runs ``put`` (the H2D transfer), blocks until
    the staged arrays are device-resident, and returns (staged, timing) with
    ``h2d_s`` filled. The single staging primitive shared by TransferRing's
    producer thread and the serving executor's fused submit path
    (core/fusion.py ``SegmentExecutor.submit_run``).

    ``obs``: optional (Tracer, sampled contexts) pair — the serving batch's
    trace binding (obs.trace.current_batch), captured by the CALLER on the
    transform thread because this often runs on the ring's producer thread,
    which does not inherit the contextvar. When set, the H2D transfer is
    recorded as an ``h2d`` span on every traced request in the batch
    (``batch``: the batch's ordinal in the call, when the caller counts)."""
    timing = BatchTiming(bytes_in=_tree_nbytes(item), rows=_tree_rows(item),
                         padded_rows=_tree_padded(item),
                         counts=dict(getattr(item, "counts", None) or {}))
    # slot-staged batches (SlotPool) carry their lease: the transfer window
    # is recorded for the per-slot overlap metric and the buffer returns to
    # the pool the moment the staged arrays are device-resident
    slot = getattr(item, "staging", None)
    t_wall = time.time()
    t0 = time.perf_counter()
    if slot is not None:
        slot.transfer_begin()
    try:
        # chaos seam: an injected delay here shows up in h2d_s (slow link),
        # an injected exception surfaces at the consumer (transfer failure)
        faults.fire(faults.INGEST_H2D, rows=timing.rows,
                    nbytes=timing.bytes_in)
        staged = put(item) if put is not None else item
        if slot is not None and _h2d_aliases_host():
            # CPU backends alias aligned host buffers on device_put: the
            # "device" array IS the slot. Releasing the slot then would let
            # the next fill corrupt a pending dispatch. A device-side copy
            # (this backend's stand-in for the DMA real accelerators do)
            # makes the staged value independent before the slot returns.
            staged = _device_copy(staged)
        _block_ready(staged)
    except BaseException:
        if slot is not None:
            # abandon: free the buffers without recording a cycle — the
            # slot is reused (overwritten) later, its content never read
            slot.release()
        raise
    timing.h2d_s = time.perf_counter() - t0
    if slot is not None:
        slot.transfer_end()
    if obs is not None:
        tracer, ctxs = obs
        attrs = {"bytes": timing.bytes_in, "rows": timing.rows}
        if batch is not None:
            attrs["batch"] = batch
        tracer.record_batch("h2d", ctxs, t_wall, timing.h2d_s, **attrs)
    return staged, timing


# ---------------------------------------------------------------------------
# TransferRing
# ---------------------------------------------------------------------------


class TransferRing:
    """N-slot host->device->compute->host pipeline over an iterator of
    batches, draining results IN ORDER.

    Stage contract (each arbitrary pytrees between stages):

      - ``put(item)``    host batch -> staged device input. Runs on the
                         prefetch thread, so its H2D overlaps the consumer's
                         dispatch/drain; the ring additionally blocks the
                         producer thread until the staged arrays are ready,
                         which (a) makes ``h2d_s`` a real transfer time and
                         (b) paces the producer at link speed instead of
                         queueing unbounded device memory.
      - ``step(staged)`` dispatch the compiled computation; returns a handle
                         (device arrays + any metadata). Must not block —
                         jax dispatch is async.
      - ``fetch(handle)`` blocking readback -> the item the ring yields.

    ``depth`` bounds dispatched-but-undrained steps (the old hardwired
    2-deep ``in_flight`` list generalized); ``prefetch`` bounds staged
    batches waiting between put and step (defaults to ``depth``).

    One ring may carry the batches of several owners one after another (a
    fused call's partitions, core/fusion.py): what differs by owner rides in
    the values themselves, which the ring never looks into, and ``obs_of``
    names each batch's trace binding.

    Replaces the reference's background-thread batcher pair
    (stages/Batchers.scala:12-160) as the single overlap primitive shared by
    DNN eval, GBDT scoring, and bench. Iterate once; ``close()`` (idempotent,
    called by ``__iter__``'s finally) releases the producer thread mid-stream
    without stranding it on the bounded queue.
    """

    def __init__(self, it: Iterator, put: Optional[Callable] = None,
                 step: Optional[Callable] = None,
                 fetch: Optional[Callable] = None,
                 depth: int = 2, prefetch: Optional[int] = None,
                 stats: Optional[IngestStats] = None,
                 obs: Optional[tuple] = None, batch0: int = 0,
                 obs_of: Optional[Callable] = None):
        if depth <= 0:
            raise ValueError("depth must be positive")
        self.depth = depth
        self.stats = stats if stats is not None else IngestStats()
        if hasattr(self.stats, "note_ring"):
            self.stats.note_ring(depth)
        self._step = step if step is not None else (lambda x: x)
        self._fetch = fetch if fetch is not None else _default_fetch
        self._user_put = put

        # the trace binding the ring's phases record under: the caller's
        # open span (``obs``: a fused partition's), else the bound batch's,
        # captured HERE (the ring is built on the transform thread, inside
        # obs.trace.batch_context); the producer thread the prefetcher
        # spawns would see an empty context. Every phase is clocked once:
        # the reads that fill BatchTiming are the span's too. ``batch0`` is
        # the call's batches before this ring's first (spans' ``batch``).
        # ``obs_of(x, w0)`` takes the binding's place where batches of more
        # than one owner ride the ring: asked on the producer thread for a
        # host item about to be staged (``w0`` None), and on the consumer
        # for a staged batch or a handle whose phase began at wall time
        # ``w0`` (the wait for it, its drain).
        from ..obs.trace import current_batch

        obs = obs if obs is not None else current_batch()
        self._traced = obs is not None or obs_of is not None
        self._obs_of = obs_of if obs_of is not None \
            else (lambda x, w0=None: obs)
        self._batch0 = int(batch0)
        staged_no = itertools.count(self._batch0)
        self._prefetch = DevicePrefetcher(
            it, put=lambda item: timed_stage(put, item,
                                             obs=self._obs_of(item, None),
                                             batch=next(staged_no)),
            depth=max(1, prefetch or depth))

    def close(self) -> None:
        self._prefetch.close()

    def __iter__(self):
        inflight: "deque" = deque()
        src = iter(self._prefetch)
        batch = self._batch0
        wall0 = time.perf_counter()
        try:
            while True:
                w0 = time.time() if self._traced else 0.0
                tq = time.perf_counter()
                try:
                    staged, timing = next(src)
                except StopIteration:
                    break
                timing.queue_s = time.perf_counter() - tq
                watcher = None
                obs = self._obs_of(staged, w0)
                if obs is not None:
                    obs[0].record_batch("queue", obs[1], w0, timing.queue_s,
                                        batch=batch)
                handle = timed_dispatch(self._step, staged, timing, obs, batch)
                if obs is not None:
                    watcher = watch_in_flight(obs, handle, batch)
                inflight.append((handle, timing, batch, watcher))
                batch += 1
                if hasattr(self.stats, "note_occupancy"):
                    self.stats.note_occupancy(len(inflight))
                if len(inflight) >= self.depth:
                    yield self._drain(inflight)
            while inflight:
                yield self._drain(inflight)
        finally:
            self.stats.add_wall(time.perf_counter() - wall0)
            self.close()

    def _drain(self, inflight: "deque"):
        handle, timing, batch, watcher = inflight.popleft()
        w0 = time.time() if self._traced else 0.0
        obs = self._obs_of(handle, w0)
        t0 = time.perf_counter()
        _block_ready(handle)
        t1 = time.perf_counter()
        timing.compute_s = t1 - t0
        out = self._fetch(handle)
        timing.readback_s = time.perf_counter() - t1
        self.stats.record(timing)
        if obs is not None:
            record_drain(obs, timing, w0, batch, _tree_nbytes(out))
        if watcher is not None:
            # the batch was ready a readback ago: its watcher has recorded,
            # or is about to; no span of a call lands after the call
            watcher.join()
        return out


def timed_dispatch(step: Callable, staged: Any, timing: BatchTiming,
                   obs: Optional[tuple], batch: int) -> Any:
    """One dispatch, clocked once into ``timing.dispatch_s`` and — under a
    trace binding — into a ``dispatch`` span that is bound around the step,
    so a build on a CompileCache miss records as its child. Shared by the
    ring and the fused submit path."""
    if obs is None:
        td = time.perf_counter()
        handle = step(staged)
        timing.dispatch_s = time.perf_counter() - td
        return handle
    from ..obs.trace import batch_context, close_span, open_span

    own = open_span(obs)
    w0 = time.time()
    td = time.perf_counter()
    with batch_context(*own):
        handle = step(staged)
    timing.dispatch_s = time.perf_counter() - td
    close_span(own, "dispatch", w0, timing.dispatch_s, batch=batch)
    return handle


def watch_in_flight(obs: tuple, handle: Any, batch: int):
    """An ``in_flight`` span for one dispatched batch: from now until its
    outputs are ready, as a short-lived thread of its own sees it (it only
    sleeps in block-until-ready, off the GIL). The consumer looks at a batch
    when it drains it, which with a ring two deep is after the NEXT batch's
    wait and the PREVIOUS batch's readback — long after the device finished;
    ``compute_wait`` then reads 0 and says nothing of when. This span's end
    is the one host-clock instant that follows the device's last operation
    of the batch closely, which is what lets a device trace be laid on the
    spans' clock (benchmarks/harness/spans.py). Only under a trace binding;
    a failing step is the drain's to raise, not this thread's. Returns the
    thread, which the drain joins."""
    import threading

    w0, p0 = time.time(), time.perf_counter()

    def wait():
        try:
            _block_ready(handle)
        except BaseException:  # noqa: BLE001 - surfaces at the drain
            return
        obs[0].record_batch("in_flight", obs[1], w0,
                            time.perf_counter() - p0, batch=batch)

    watcher = threading.Thread(target=wait, daemon=True, name="device-watch")
    watcher.start()
    return watcher


def record_drain(obs: tuple, timing: BatchTiming, w0: float, batch: int,
                 nbytes: int) -> None:
    """The drain's two phases as spans, from the clock reads that filled
    ``timing``: ``compute_wait`` (block-until-ready, from ``w0``) and
    ``readback`` right behind it. Shared by the ring and the fused submit
    path's ``resolve()``."""
    tracer, ctxs = obs
    tracer.record_batch("compute_wait", ctxs, w0, timing.compute_s,
                        batch=batch)
    tracer.record_batch("readback", ctxs, w0 + timing.compute_s,
                        timing.readback_s, batch=batch, bytes=nbytes)


#: lazily probed: does this backend's device_put ALIAS aligned host numpy
#: buffers instead of copying? (jax CPU does, real accelerators do not)
_H2D_ALIASES: Optional[bool] = None


def _h2d_aliases_host() -> bool:
    """One-shot probe of the default backend: stage an aligned buffer,
    mutate the host side, and see whether the device value changed. True
    means slot buffers must be device-copied before reuse."""
    global _H2D_ALIASES
    if _H2D_ALIASES is None:
        import sys

        jax = sys.modules.get("jax")
        if jax is None:
            _H2D_ALIASES = False
        else:
            try:
                # analysis: allow D001 -- one-shot probe, not per batch
                raw = np.zeros(1024 + 16, dtype=np.float32)
                off = (-raw.ctypes.data // 4) % 16  # 64-byte-align the view
                probe = raw[off:off + 512]
                dev = jax.block_until_ready(jax.device_put(probe))
                probe[0] = 1.0
                _H2D_ALIASES = bool(np.asarray(dev)[0] == 1.0)
            except Exception:  # noqa: BLE001 — assume the unsafe answer
                _H2D_ALIASES = True
    return _H2D_ALIASES


def _device_copy(tree: Any) -> Any:
    """Device-side copy of every jax array in ``tree`` (structure
    preserved) — detaches staged values from the host slot they may
    alias."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return tree

    def one(v):
        if isinstance(v, jax.Array):
            return v.copy()
        return v

    return jax.tree_util.tree_map(
        one, tree, is_leaf=lambda v: isinstance(v, jax.Array))


def _block_ready(tree: Any) -> Any:
    """Wait for every jax array in ``tree``; no-op for host-only values
    (keeps the ring usable before jax is imported / with numpy stages)."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return tree
    return jax.block_until_ready(tree)


def _default_fetch(handle: Any) -> Any:
    """Readback: device arrays -> numpy, structure preserved."""
    import sys

    jax = sys.modules.get("jax")

    def one(v):
        if jax is not None and isinstance(v, jax.Array):
            return np.asarray(v)
        return v

    if isinstance(handle, tuple):
        return tuple(one(v) for v in handle)
    if isinstance(handle, list):
        return [one(v) for v in handle]
    if isinstance(handle, dict):
        return {k: one(v) for k, v in handle.items()}
    return one(handle)
