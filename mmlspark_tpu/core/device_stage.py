"""Device-stage contract: how a pipeline stage joins a fused XLA program.

PR 1 measured the flagship featurize path at ~11.5k images/sec per-call but
~260 end-to-end: the stage-BOUNDARY cost (D2H readback, host re-batching,
fresh H2D) dominated, not XLA compute. Operator fusion across stage
boundaries is the standard fix (TVM, arXiv:1802.04799); this module defines
the contract a stage implements to participate:

    stage.device_fn(schema) -> Optional[DeviceFn]

A ``DeviceFn`` describes the stage as a jittable column program plus the
host-side shims the fused executor (core/fusion.py) needs at segment edges:

  - ``fn(params, env)``      the traceable body: reads batched [B, ...]
                             arrays out of ``env`` (a dict keyed by column
                             name), returns the dict of columns it writes.
                             Raise ``FusionUnsupported`` at TRACE time when
                             the incoming shapes/dtypes rule fusion out —
                             the executor falls back to the host path.
  - ``prepare(cols, ctx)``   host per-row prep applied only to SEGMENT-
                             EXTERNAL inputs (struct -> array conversion,
                             decode, host-exact ops like resize whose f64
                             arithmetic cannot be reproduced bitwise on
                             device). MUST reuse the unfused code path so
                             fused == unfused stays bitwise.
  - ``finalize(outs, ctx)``  host per-partition post-processing of the
                             stage's device outputs after readback (rebuild
                             image structs, f64 casts, objective transforms)
                             — again the exact unfused code.

The bitwise contract: everything placed in ``fn`` must be provably exact
between the host numpy implementation and XLA — value-preserving moves
(crop/flip/transpose/concat), exact casts (uint8 -> f32), identical
elementwise IEEE arithmetic, or literally the same traced jaxpr (NN
forwards, the GBDT forest kernels). Anything else belongs in ``prepare``/
``finalize`` where the unfused host code runs unchanged.

``CompileCache`` is the shared executable cache for fused segments, keyed by
(segment identity, bucketed batch shape, dtypes) with hit/miss/compile-time
counters — the per-shape cost visibility of the TPU performance-model work
(arXiv:2008.01040) applied to fused programs.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple


class FusionUnsupported(Exception):
    """A stage cannot join (or continue) a fused segment for the observed
    schema/shapes/dtypes. Raised at plan or trace time; the executor falls
    back to the unfused host path for the segment, never failing the
    transform."""


@dataclasses.dataclass
class DeviceFn:
    """One stage's slice of a fused device program (see module docstring).

    ``key`` must be hashable and identify the traced computation (stage
    class, column names, op list, model identity...): it keys the shared
    compile cache together with the batch shape signature.

    ``device_outputs``: the env keys the executor reads back for this stage
    (defaults to ``out_cols``); internal keys (prefixed ``__``) let a stage
    compute raw device values that only ``finalize`` consumes — e.g. the
    GBDT forest scores, finalized into probability/prediction columns in
    f64 on host. A stage with internal outputs is ``terminal``: nothing
    downstream can consume its finalized columns on device.

    ``null_policy``: "propagate" = rows with a null input produce null
    outputs (DNN semantics); "fallback" = nulls in this stage's external
    inputs force the segment onto the host path (stages whose host code
    gives nulls a value, e.g. the assembler's NaN fill).
    """

    key: Tuple
    in_cols: Tuple[str, ...]
    out_cols: Tuple[str, ...]
    fn: Callable[[Any, Dict[str, Any]], Dict[str, Any]]
    params: Any = None
    prepare: Optional[Callable] = None
    finalize: Optional[Callable] = None
    device_outputs: Optional[Tuple[str, ...]] = None
    accepts: Optional[Callable] = None   # ({col: probe_row}) -> bool
    null_policy: str = "propagate"
    reject_sparse: bool = True
    drop_invalid: bool = False
    # fn can consume input produced by an upstream device stage in the same
    # segment (False when `prepare` does host work fn cannot replicate —
    # the planner then starts a new segment at this stage)
    internal_ok: bool = True
    terminal: bool = False
    # heavy = worth a device round-trip on its own (NN forward, forest
    # kernel); a segment of only light stages executes on the host path
    heavy: bool = False
    # Optional model/feature-dim sharding declaration for the pod-scale
    # planner (parallel/shardplan.py): {input col: array dim (batch = 0)
    # that may shard over the mesh's tensor axis}. Batch-dim data
    # parallelism needs no declaration (always legal — fn is row
    # independent by contract); a feature-dim candidate is only DERIVED
    # for a segment when every stage declares one for its external inputs.
    shard_dims: Optional[Dict[str, int]] = None
    # --- compiler-search capability flags (docs/compiler_search.md) ------
    # stitchable: this TERMINAL stage's host finalize shim is transpiled
    # (device_finalize below), so the planner may keep the segment OPEN
    # across it — downstream device stages keep consuming the segment's
    # device-resident columns instead of paying the readback +
    # `rows_to_batch` re-batch + H2D round-trip a terminal close costs —
    # when the stitch knob + calibrated cost model approve. The stage's own
    # finalized columns stay host-only; a later reader of those splits.
    stitchable: bool = False
    # device_finalize: jittable replacement for the numeric part of
    # `finalize` — (params, env) -> extra device outputs (named by
    # `device_finalize_outputs`) traced into the SAME fused program when
    # the stitch knob enables it; `finalize_stitched(outs, ctx)` is the
    # host shim that builds the final columns from those readbacks.
    # `finalize_tolerance` DECLARES the allowed numeric deviation vs the
    # host `finalize` path (None would claim bitwise — the transpiled f64
    # reductions run in f32 on device, so they must declare a tolerance).
    device_finalize: Optional[Callable] = None
    device_finalize_outputs: Tuple[str, ...] = ()
    finalize_stitched: Optional[Callable] = None
    finalize_tolerance: Optional[float] = None
    # --- sparse capability (docs/sparse.md) ------------------------------
    # sparse_cols: input columns this stage can consume as a CSR triple
    # instead of a densified [B, F] matrix. For a capable column ``c`` the
    # executor stages four env keys — ``{c}:indptr`` (i32 [B+1]),
    # ``{c}:indices`` (i32 [nnz_pad]), ``{c}:values`` (f32 [nnz_pad]) and
    # ``{c}:width`` (i32 scalar) — and calls ``sparse_fn`` in place of
    # ``fn``. The CSR path is opt-in per segment (the tuner's journaled
    # ``layout`` knob); with the knob off, a capable stage still takes the
    # densify path, so declaring the capability alone changes nothing.
    sparse_cols: Tuple[str, ...] = ()
    # sparse_fn(params, env): the traceable CSR body — must produce outputs
    # bitwise-equal (or within the kernel's declared tolerance) to ``fn``
    # over the densified equivalent of the same triple.
    sparse_fn: Optional[Callable] = None
    # --- handed-through columns (docs/pipeline_fusion.md) ----------------
    # passthrough: {out col: in col} for every output that ``fn`` returns
    # as the VERY array it was given (``env[in col]``, untouched: the work
    # was `prepare`'s). The declaration is checked while the program is
    # traced — a stage whose ``fn`` returns anything else for that column
    # fails the build. Where the input was staged from host rows as they
    # are, the executor then emits the column from those rows and leaves
    # it out of the program's outputs: nothing is read back to rebuild
    # bytes the host still holds. Later in-segment stages read the column
    # on the device as before.
    passthrough: Optional[Dict[str, str]] = None

    def __post_init__(self):
        self.in_cols = tuple(self.in_cols)
        self.out_cols = tuple(self.out_cols)
        self.passthrough = dict(self.passthrough or {})
        self.sparse_cols = tuple(self.sparse_cols)
        if self.device_outputs is None:
            self.device_outputs = self.out_cols
        else:
            self.device_outputs = tuple(self.device_outputs)
        self.device_finalize_outputs = tuple(self.device_finalize_outputs)


#: thread ident -> CompileCache builds in flight on that thread. The serving
#: watchdog reads it (``building_in``): an XLA compile of a new shape bucket
#: takes tens of seconds on a TPU and must not be taken for a hung dispatch.
_BUILDING: Dict[int, int] = {}
_BUILDING_LOCK = threading.Lock()


def building_in(thread_ident: int) -> bool:
    """True while that thread is inside a ``CompileCache`` build."""
    with _BUILDING_LOCK:
        return _BUILDING.get(thread_ident, 0) > 0


# every cache alive, weakly: ``live_caches()``
_LIVE_CACHES: "weakref.WeakSet[CompileCache]" = weakref.WeakSet()
_LIVE_LOCK = threading.Lock()


class CompileCache:
    """Shared fused-executable cache with hit/miss/compile-time counters
    and per-(segment, shape-bucket) XLA cost records.

    Key: (segment key, bucketed batch shape+dtype signature). Value: the
    compiled callable. AOT compilation (jit -> lower -> compile) is timed so
    ``compile_time_s`` measures XLA work, not the first batch's compute.

    At miss time the freshly-compiled executable's ``cost_analysis()`` /
    ``memory_analysis()`` are harvested (obs/perf.py ``extract_cost`` —
    getattr-gated, every absence degrades to "no record") and stored under
    the human-readable ``(label, shape)`` pair the caller passes, feeding
    the ``mmlspark_segment_cost_*`` families and the roofline report.

    Concurrency contract: counter updates AND cost capture happen under the
    cache lock in one acquisition, so a concurrent ``stats()`` scrape never
    sees a torn hits/misses/compile_time_s triple. ``reset()`` bumps a
    generation counter; a build that a reset raced still installs its
    (valid) executable but does NOT book its miss/compile-time/cost into
    the post-reset counters — cleared stats never mix epochs.

    Eviction contract: the cache is a bounded LRU — a hit refreshes the
    entry, an insert past ``capacity`` evicts the least-recently-used one
    (a long-running server with many shape buckets previously grew compiled
    executables forever under insertion-order eviction). Evicting an entry
    also drops its harvested cost record, so ``costs()`` only ever
    describes executables that are actually resident; ``evictions`` counts
    drops (exposed as ``mmlspark_segment_cache_evictions_total``).
    ``capacity`` defaults from ``MMLSPARK_SEGMENT_CACHE_CAP`` when unset.

    Persistent tier (serving/fleet/cache.py): ``attach_persistent`` hangs a
    second, cross-process tier under the miss path. A memory miss first
    asks the tier for a deserialized executable (no compile, no
    miss/compile-time accounting — the tier keeps its own hit/miss/error
    counters); only a two-tier miss runs ``builder``, after which the
    fresh executable is offered back to the tier best-effort. With no tier
    attached (the default) every code path and counter is exactly the
    pre-fleet behavior.
    """

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            import os

            try:
                capacity = int(os.environ.get(
                    "MMLSPARK_SEGMENT_CACHE_CAP", "256"))
            except ValueError:
                capacity = 256
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self._capacity = int(capacity)
        self._entries: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()
        self._gen = 0
        self._costs: Dict[Tuple[str, str], Dict[str, Any]] = {}
        # entry key -> its cost-record key, so eviction can drop the record
        self._cost_key: Dict[Tuple, Tuple[str, str]] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compile_time_s = 0.0
        # optional cross-process second tier (duck-typed: load/store/stats;
        # serving/fleet/cache.py PersistentCompileCache). None = single-tier.
        self._persistent: Optional[Any] = None
        with _LIVE_LOCK:
            _LIVE_CACHES.add(self)

    @property
    def capacity(self) -> int:
        return self._capacity

    def attach_persistent(self, tier: Optional[Any]) -> None:
        """Hang a persistent tier under the miss path (None detaches). The
        tier must be exception-free: ``load`` returns ``(fn, cost)`` or
        ``None``; ``store`` is fire-and-forget."""
        with self._lock:
            self._persistent = tier

    @property
    def persistent(self) -> Optional[Any]:
        with self._lock:
            return self._persistent

    def preload(self, key: Tuple, fn: Any, label: Optional[str] = None,
                shape: Optional[str] = None,
                cost: Optional[Dict[str, Any]] = None) -> bool:
        """Install a deserialized executable WITHOUT miss/compile-time
        accounting — the persistent tier's pod-start AOT warm path. Returns
        False when the key is already resident (warm never clobbers a live
        entry)."""
        with self._lock:
            if key in self._entries:
                return False
            while len(self._entries) >= self._capacity:
                self._evict_lru_locked()
                self.evictions += 1
            self._entries[key] = fn
            if label is not None:
                self._costs[(str(label), str(shape))] = dict(cost or {})
                self._cost_key[key] = (str(label), str(shape))
            return True

    def set_capacity(self, capacity: int) -> None:
        """Re-bound the cache; shrinking evicts LRU entries immediately."""
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        with self._lock:
            self._capacity = int(capacity)
            while len(self._entries) > self._capacity:
                self._evict_lru_locked()
                self.evictions += 1

    def _evict_lru_locked(self) -> None:
        """Drop the least-recently-used entry (dict order = LRU order:
        hits re-insert at the end) and its cost record. Lock held; the
        caller books ``evictions`` under the same acquisition."""
        key = next(iter(self._entries))
        self._entries.pop(key)
        ck = self._cost_key.pop(key, None)
        if ck is not None:
            self._costs.pop(ck, None)

    def get(self, key: Tuple, builder: Callable[[], Any],
            label: Optional[str] = None,
            shape: Optional[str] = None) -> Any:
        with self._lock:
            if key in self._entries:
                self.hits += 1
                # LRU refresh: move to the end of the dict's order
                fn = self._entries.pop(key)
                self._entries[key] = fn
                return fn
            gen = self._gen
            tier = self._persistent
        if tier is not None:
            # second-tier probe OUTSIDE the lock (deserializing an AOT
            # executable does real I/O). A tier hit installs with NO
            # miss/compile accounting: nothing compiled.
            loaded = tier.load(key, label=label, shape=shape)
            if loaded is not None:
                fn, pcost = loaded
                with self._lock:
                    if key not in self._entries:
                        while len(self._entries) >= self._capacity:
                            self._evict_lru_locked()
                            self.evictions += 1
                        self._entries[key] = fn
                        if self._gen == gen and label is not None:
                            self._costs[(str(label), str(shape))] = dict(
                                pcost or {})
                            self._cost_key[key] = (str(label), str(shape))
                    return self._entries[key]
        # build OUTSIDE the lock: XLA compiles can take seconds and other
        # segments/threads must not serialize behind them
        ident = threading.get_ident()
        with _BUILDING_LOCK:
            _BUILDING[ident] = _BUILDING.get(ident, 0) + 1
        t0 = time.perf_counter()
        try:
            fn = builder()
        finally:
            with _BUILDING_LOCK:
                _BUILDING[ident] -= 1
                if not _BUILDING[ident]:
                    del _BUILDING[ident]
        dt = time.perf_counter() - t0
        cost = None
        if label is not None:
            from ..obs.perf import extract_cost

            cost = extract_cost(fn)
        rec = dict(cost or {})
        rec["compile_s"] = round(dt, 6)
        with self._lock:
            stale = self._gen != gen  # reset() raced the build
            if not stale:
                self.misses += 1
                self.compile_time_s += dt
                if label is not None:
                    self._costs[(str(label), str(shape))] = dict(rec)
            if key not in self._entries:
                while len(self._entries) >= self._capacity:
                    self._evict_lru_locked()
                    self.evictions += 1
                self._entries[key] = fn
                if not stale and label is not None:
                    self._cost_key[key] = (str(label), str(shape))
            out = self._entries[key]
        if tier is not None and not stale and out is fn:
            # offer the fresh executable to the persistent tier, outside
            # every lock: store is best-effort and must never block or
            # fail the serving path (the tier swallows its own errors)
            tier.store(key, fn, cost=rec, label=label, shape=shape)
        return out

    def clear(self) -> None:
        with self._lock:
            self._gen += 1
            self._entries.clear()
            self._costs.clear()
            self._cost_key.clear()
            self.hits = 0
            self.misses = 0
            self.evictions = 0
            self.compile_time_s = 0.0

    #: reset() is clear() — the name the obs layer documents
    reset = clear

    @property
    def entries(self) -> int:
        with self._lock:
            return len(self._entries)

    def resident(self) -> List[Tuple[str, Any]]:
        """(segment label, executable) of every resident entry that was
        built or loaded under a label: what ``obs.scopes.programs()`` reads
        the scope maps from, when asked."""
        with self._lock:
            return [(self._cost_key[key][0], fn)
                    for key, fn in self._entries.items()
                    if key in self._cost_key]

    def costs(self) -> Dict[str, Dict[str, Dict[str, Any]]]:
        """{segment label: {shape bucket: cost record}} — flops /
        bytes_accessed / peak_memory_bytes / compile_s per compiled
        executable (whatever subset the backend reported)."""
        with self._lock:
            out: Dict[str, Dict[str, Dict[str, Any]]] = {}
            for (label, shape), rec in self._costs.items():
                out.setdefault(label, {})[shape] = dict(rec)
            return out

    def segment_cost(self, label: str) -> Optional[Dict[str, float]]:
        """Mean per-batch cost across this segment's compiled shape buckets
        (span attrs + quick attribution), or None when nothing recorded."""
        with self._lock:
            recs = [r for (lab, _), r in self._costs.items() if lab == label]
        if not recs:
            return None
        out: Dict[str, float] = {"shape_buckets": float(len(recs))}
        for k in ("flops", "bytes_accessed", "peak_memory_bytes"):
            vals = [r[k] for r in recs if isinstance(r.get(k), (int, float))]
            if vals:
                out[k] = sum(vals) / len(vals)
        return out

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            total = self.hits + self.misses
            out = {
                "entries": len(self._entries),
                "capacity": self._capacity,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "hit_rate": round(self.hits / total, 4) if total else None,
                "compile_time_s": round(self.compile_time_s, 6),
            }
            tier = self._persistent
        if tier is not None:
            # tier stats OUTSIDE the cache lock (the tier takes its own);
            # the key is absent entirely when no tier is attached, so the
            # fleet=False stats payload is byte-identical to pre-fleet
            try:
                out["persistent"] = tier.stats()
            except Exception as e:  # noqa: BLE001 — stats must not raise
                out["persistent"] = {"error": str(e)}
        return out


_GLOBAL_CACHE = CompileCache()


def live_caches() -> List[CompileCache]:
    """Every CompileCache alive in this process (the shared one, a model's
    own, a replica's), so that one place can list the resident programs."""
    with _LIVE_LOCK:
        return list(_LIVE_CACHES)


def compile_cache() -> CompileCache:
    """The process-wide fused-executable cache (shared across pipelines and
    the serving loop, so warm executables survive re-planning)."""
    return _GLOBAL_CACHE
