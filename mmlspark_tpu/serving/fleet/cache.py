"""Persistent, content-addressed compile cache — the cross-process tier
under the in-process LRU ``CompileCache`` (core/device_stage.py).

Why: every new replica/pod recompiles every (segment, bucket) signature
from scratch, so at fleet scale every scale-out event is a self-inflicted
compile-latency storm. TVM's answer (PAPERS.md) is to ship the tuned,
compiled artifact to new workers instead of re-learning it per worker;
this module is that answer for fused XLA executables.

Entry format (one file per signature, ``<digest>.mmlc``)::

    MAGIC (6 bytes) | header length (8 bytes, big-endian) | header JSON
    | payload (pickled ``serialize_executable.serialize`` triple, or
      empty for cost-only entries)

The content key (``content_key``) is a sha256 over the canonical repr of
the in-process cache key — (segment graph key, shape-bucket signature,
dtypes) — joined with the environment fingerprint (jax version, backend,
format version). Anything that changes what XLA would compile changes the
digest, so a foreign-version entry is simply never looked up AND is
rejected again at load time by the header fingerprint (defense in depth:
a digest collision or a hand-copied file still can't smuggle a stale
executable in).

Degradation contract (chaos-tested, tests/test_faults.py):

  - a truncated / corrupted / foreign-version / unpicklable entry
    degrades to an accounted recompile (``load_errors`` counter, never a
    crash);
  - a store failure (full volume, readonly mount, injected fault) never
    blocks or fails the serving path (``store_errors`` counter);
  - an executable that this jax cannot serialize falls back to persisting
    only the harvested cost record and the live tuner knobs
    (``kind="costs"``), which still warm the cost model — the planner and
    tuner start calibrated even when the executable itself can't travel.

Fault points: ``compilecache.load`` / ``compilecache.store``
(core/faults.py) fire before the read and the atomic write respectively.
"""

from __future__ import annotations

import ast
import errno
import hashlib
import io
import json
import logging
import os
import pickle
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ...core import faults
from . import objstore as _objstore

_LOG = logging.getLogger(__name__)

#: on-disk format version — bump on any layout change; mismatched entries
#: are skipped (never parsed further)
FORMAT = 2
MAGIC = b"MMLC1\n"
_HEADER_LEN_BYTES = 8
#: entry file suffix (mmlspark compiled)
SUFFIX = ".mmlc"


def _canon(obj: Any) -> str:
    """Deterministic textual form of a cache key: primitives and (nested)
    tuples/lists render via repr, anything else via its type+repr — stable
    across processes for the primitive-only keys fusion actually builds."""
    return repr(obj)


def env_fingerprint(mesh: Any = None) -> Dict[str, Any]:
    """What must match for a persisted executable to be loadable here:
    jax/jaxlib version, the default backend, the device count, and the
    MESH TOPOLOGY (axis names + sizes + device kind) executables shard
    over. A GSPMD-partitioned executable hard-codes its mesh shape — warm
    loading one onto a different mesh would dispatch garbage, so the
    topology is part of the content address: a mismatched entry is simply
    never found (clean miss -> recompile), not detected after the fact.
    Import-gated — without jax the fingerprint still exists (cost-only
    entries remain usable).

    ``mesh``: the mesh the owning model shards over; when None the ambient
    ``MeshContext`` (parallel/mesh.py) is consulted, falling back to
    ``"none"`` (the single-device fingerprint, unchanged semantics)."""
    fp: Dict[str, Any] = {"format": FORMAT}
    try:
        import jax

        fp["jax"] = str(jax.__version__)
        fp["backend"] = str(jax.default_backend())
        fp["devices"] = int(jax.device_count())
    except Exception:  # noqa: BLE001 — host-only installs still fingerprint
        fp["jax"] = "none"
        fp["backend"] = "none"
        fp["devices"] = 0
    try:
        if mesh is None:
            from ...parallel.mesh import MeshContext

            mesh = MeshContext.current()
        from ...parallel.shardplan import mesh_topology

        fp["mesh"] = mesh_topology(mesh)
    except Exception:  # noqa: BLE001 — no mesh machinery: single-device
        fp["mesh"] = "none"
    try:
        shape = dict(getattr(mesh, "shape", {}) or {})
        p = int(shape.get("pipe", 1))
        if p > 1:
            # pipelined executables compile per-STAGE on a pipe sub-mesh
            # (parallel/pipeplan.py pipe_submeshes): a stage keeps every
            # non-pipe axis and owns a slice of the pipe axis, so the
            # layout a stage executable hard-codes is (non-pipe shape,
            # pipe extent). Folding that in makes a different pipe layout
            # a clean counted miss. The key exists ONLY when the mesh has
            # a pipe axis to split: every non-pipe fingerprint — and so
            # every pre-pipeline content address — stays byte-identical.
            fp["pipe_submesh"] = ";".join(
                f"{a}={int(shape.get(a, 1))}"
                for a in ("data", "fsdp", "tensor", "seq", "expert")
            ) + f";pipe={p}"
    except Exception:  # noqa: BLE001 — shape-less mesh object
        pass
    return fp


def content_key(key: Any, fp: Optional[Dict[str, Any]] = None) -> str:
    """sha256 content hash of (cache key, environment fingerprint) — the
    entry's filename stem. The in-process key already encodes the segment
    graph identity, the bucketed batch shape, and the dtypes (core/fusion
    ``(seg.key, sig)``); the fingerprint folds in jax/backend/format."""
    fp = fp if fp is not None else env_fingerprint()
    h = hashlib.sha256()
    h.update(_canon(key).encode("utf-8"))
    h.update(json.dumps(fp, sort_keys=True).encode("utf-8"))
    return h.hexdigest()


def _serialize_executable(fn: Any) -> Optional[bytes]:
    """Pickle the AOT executable's portable triple plus the ids of the
    devices it was compiled for, or None when ``fn`` is not a serializable
    executable (the cache also holds plain callables)."""
    from jax.experimental import serialize_executable as se

    try:
        triple = se.serialize(fn)
        device_ids = [d.id for d in fn.runtime_executable().local_devices()]
        return pickle.dumps(triple + (device_ids,))
    except Exception:  # noqa: BLE001 — unserializable executable
        return None


def _deserialize_executable(payload: bytes) -> Any:
    """Load the executable onto the devices it was compiled for:
    ``deserialize_and_load`` defaults to EVERY device of the backend, so a
    one-device executable would otherwise expect one shard per device."""
    import jax
    from jax.experimental import serialize_executable as se

    serialized, in_tree, out_tree, device_ids = pickle.loads(payload)
    by_id = {d.id: d for d in jax.devices()}
    return se.deserialize_and_load(
        serialized, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


class PersistentCompileCache:
    """Directory-backed second tier for ``CompileCache`` (one file per
    signature; the directory is the shared volume / object-store mount).

    ``write=False`` makes the tier read-only (consume a fleet-shared
    cache without contributing — e.g. canary pods). ``knobs_provider``
    (a zero-arg callable returning a dict) snapshots the live tuner knobs
    into every stored entry, so a cost-only entry still carries the tuned
    configuration to the next pod.

    Thread contract: counters live under ``_lock``; file I/O and
    (de)serialization always run OUTSIDE it.
    """

    def __init__(self, path: str, write: bool = True,
                 knobs_provider: Optional[Callable[[], dict]] = None,
                 mesh: Any = None, store: Any = None):
        self.path = str(path)
        self.write = bool(write)
        self.knobs_provider = knobs_provider
        #: optional object-store backend (fleet/objstore.py): entry and
        #: snapshot I/O route through ``store.put``/``store.get`` instead
        #: of the local directory — same format, same degrade contract
        self._store = _objstore.make_store(store)
        # ``mesh`` pins the topology the fingerprint carries (the owning
        # model's shard mesh); default resolves the ambient MeshContext
        self._fp = env_fingerprint(mesh=mesh)
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self.store_skips = 0      # already present / not serializable+empty
        self.costs_only = 0       # entries persisted/loaded without payload
        self.load_errors = 0
        self.store_errors = 0
        self.write_degrades = 0   # ENOSPC flips to accounted read-only
        self.snapshots = 0        # knob-shipping snapshots written
        self._enospc_logged = False
        self._last_snapshot_blob: Optional[bytes] = None
        self.load_s = 0.0
        self.store_s = 0.0
        #: cost records recovered from cost-only entries at warm time:
        #: {label: {shape: record}} — SegmentCostModel.ingest_costs shape
        self._cost_records: Dict[str, Dict[str, Dict[str, Any]]] = {}
        #: last knobs dict seen in a warmed entry (newest mtime wins)
        self.loaded_knobs: Optional[Dict[str, Any]] = None
        if self.write and self._store is None:
            try:
                os.makedirs(self.path, exist_ok=True)
            except OSError:
                # unwritable mount: degrade to read-only, don't crash the
                # server constructor
                self.write = False

    # -- entry I/O ---------------------------------------------------------

    def _file_for(self, digest: str) -> str:
        return os.path.join(self.path, digest + SUFFIX)

    def _load_blob(self, name: str) -> Optional[bytes]:
        """One object's raw bytes by flat name (``<digest>.mmlc`` or the
        snapshot key) — via the object store when attached, else the local
        directory. ``None`` when absent; backend errors raise (accounted
        by the caller, degrading to recompile)."""
        if self._store is not None:
            return self._store.get(name)
        try:
            with open(os.path.join(self.path, name), "rb") as fh:
                return fh.read()
        except FileNotFoundError:
            return None

    def _write_blob(self, name: str, blob: bytes) -> None:
        if self._store is not None:
            self._store.put(name, blob)
        else:
            faults.atomic_write_bytes(os.path.join(self.path, name), blob)

    def _has_entry(self, name: str) -> bool:
        if self._store is not None:
            return self._store.has(name)
        return os.path.exists(os.path.join(self.path, name))

    def _entry_names(self) -> List[str]:
        if self._store is not None:
            try:
                return sorted(self._store.list(SUFFIX))
            except Exception:  # noqa: BLE001 — unlistable remote tier
                return []
        try:
            return sorted(n for n in os.listdir(self.path)
                          if n.endswith(SUFFIX))
        except OSError:
            return []

    def _read_entry(self, path: str
                    ) -> Tuple[Dict[str, Any], Optional[bytes]]:
        """Parse one entry by path -> (header, payload or None). Raises on
        any corruption; callers account and degrade."""
        blob = self._load_blob(os.path.basename(path))
        if blob is None:
            raise FileNotFoundError(path)
        return self._parse_entry(blob)

    def _parse_entry(self, blob: bytes
                     ) -> Tuple[Dict[str, Any], Optional[bytes]]:
        buf = io.BytesIO(blob)
        if buf.read(len(MAGIC)) != MAGIC:
            raise ValueError("bad magic")
        hlen = int.from_bytes(buf.read(_HEADER_LEN_BYTES), "big")
        if hlen <= 0 or hlen > len(blob):
            raise ValueError("bad header length")
        header = json.loads(buf.read(hlen).decode("utf-8"))
        payload = buf.read()
        if header.get("kind") == "exec":
            want = header.get("payload_sha256")
            if want != hashlib.sha256(payload).hexdigest():
                raise ValueError("payload digest mismatch (truncated?)")
        else:
            payload = None
        for k, v in self._fp.items():
            if header.get("env", {}).get(k) != v:
                raise ValueError(
                    f"environment mismatch on {k!r}: entry "
                    f"{header.get('env', {}).get(k)!r} != local {v!r}")
        return header, payload

    def _write_entry(self, path: str, header: Dict[str, Any],
                     payload: bytes) -> None:
        hjson = json.dumps(header, sort_keys=True).encode("utf-8")
        blob = MAGIC + len(hjson).to_bytes(_HEADER_LEN_BYTES, "big") \
            + hjson + payload
        self._write_blob(os.path.basename(path), blob)

    def _note_write_failure(self, e: BaseException) -> None:
        """Account one failed write; ENOSPC additionally flips the tier to
        read-only (logged once) — a full cache volume must never crash or
        spam the serving loop (docs/faults.md disk-full contract)."""
        with self._lock:
            self.store_errors += 1
            if getattr(e, "errno", None) != errno.ENOSPC:
                return
            self.write = False
            self.write_degrades += 1
            logged = self._enospc_logged
            self._enospc_logged = True
        if not logged:
            _LOG.warning("persistent compile-cache volume full (ENOSPC): "
                         "degrading to read-only mode")

    # -- the CompileCache tier protocol ------------------------------------

    def load(self, key: Any, label: Optional[str] = None,
             shape: Optional[str] = None
             ) -> Optional[Tuple[Any, Optional[Dict[str, Any]]]]:
        """Look the live key up in the persistent tier. Returns
        ``(executable, cost_record)`` on a hit, None on miss OR any error
        (corruption, version skew, injected fault) — the caller recompiles
        and the failure is an accounted counter, never an exception."""
        digest = content_key(key, self._fp)
        name = digest + SUFFIX
        t0 = time.perf_counter()
        try:
            faults.fire(faults.COMPILECACHE_LOAD, key=digest, label=label)
            blob = self._load_blob(name)
            if blob is None:
                with self._lock:
                    self.misses += 1
                return None
            header, payload = self._parse_entry(blob)
            if header.get("kind") != "exec" or payload is None:
                # cost-only entry: nothing to execute, but the harvested
                # cost still warms the model
                self._absorb_costs(header)
                with self._lock:
                    self.costs_only += 1
                    self.misses += 1
                return None
            fn = _deserialize_executable(payload)
        except Exception as e:  # noqa: BLE001 — degrade to recompile
            _LOG.warning("persistent compile-cache load failed for %s: %s",
                         digest[:12], e)
            with self._lock:
                self.load_errors += 1
                self.misses += 1
            return None
        dt = time.perf_counter() - t0
        with self._lock:
            self.hits += 1
            self.load_s += dt
        return fn, header.get("cost")

    def store(self, key: Any, fn: Any,
              cost: Optional[Dict[str, Any]] = None,
              label: Optional[str] = None,
              shape: Optional[str] = None) -> bool:
        """Persist one freshly-compiled executable (or, when it can't
        serialize, its cost record + live knobs). Fire-and-forget: every
        failure is a counter, never an exception into the serving path."""
        if not self.write:
            return False
        digest = content_key(key, self._fp)
        name = digest + SUFFIX
        t0 = time.perf_counter()
        try:
            faults.fire(faults.COMPILECACHE_STORE, key=digest, label=label)
            if self._has_entry(name):
                with self._lock:
                    self.store_skips += 1
                return False
            payload = _serialize_executable(fn)
            kind = "exec" if payload is not None else "costs"
            knobs = None
            if self.knobs_provider is not None:
                try:
                    knobs = self.knobs_provider()
                except Exception:  # noqa: BLE001 — knobs are best-effort
                    knobs = None
            header = {
                "kind": kind,
                "env": dict(self._fp),
                "key_repr": _canon(key),
                "label": label,
                "shape": shape,
                "cost": dict(cost or {}) or None,
                "knobs": knobs,
                "payload_sha256": hashlib.sha256(
                    payload).hexdigest() if payload is not None else None,
            }
            self._write_entry(self._file_for(digest), header, payload or b"")
        except Exception as e:  # noqa: BLE001 — never block serving
            _LOG.warning("persistent compile-cache store failed for %s: %s",
                         digest[:12], e)
            self._note_write_failure(e)
            return False
        dt = time.perf_counter() - t0
        with self._lock:
            self.stores += 1
            self.store_s += dt
            if kind == "costs":
                self.costs_only += 1
        return True

    # -- pod-start AOT warm -------------------------------------------------

    def warm(self, cache: Any, limit: Optional[int] = None
             ) -> Dict[str, int]:
        """Preload every compatible persisted executable into the
        in-process ``CompileCache`` (``cache.preload`` — no miss/compile
        accounting), so a fresh replica's first request for a
        previously-seen signature is a plain memory hit with zero jit
        compiles. Cost-only entries warm ``harvested_costs()`` /
        ``loaded_knobs`` instead. Every per-entry failure is counted and
        skipped — a corrupted fleet cache can only make warm-up smaller,
        never fail pod start."""
        out = {"warmed": 0, "costs_only": 0, "skipped": 0, "errors": 0}
        names = self._entry_names()
        for name in names:
            if limit is not None and out["warmed"] >= limit:
                break
            try:
                faults.fire(faults.COMPILECACHE_LOAD, key=name)
                blob = self._load_blob(name)
                if blob is None:
                    out["skipped"] += 1
                    continue
                header, payload = self._parse_entry(blob)
                if header.get("kind") != "exec" or payload is None:
                    self._absorb_costs(header)
                    out["costs_only"] += 1
                    continue
                key = self._key_of(header)
                if key is None:
                    # non-literal key: not warmable by name, but still
                    # lazily loadable at get() time (digest from live key)
                    out["skipped"] += 1
                    continue
                fn = _deserialize_executable(payload)
                if cache.preload(key, fn, label=header.get("label"),
                                 shape=header.get("shape"),
                                 cost=header.get("cost")):
                    out["warmed"] += 1
                else:
                    out["skipped"] += 1
                self._absorb_costs(header)
            except Exception as e:  # noqa: BLE001 — warm must not fail start
                _LOG.warning("skipping persisted entry %s: %s", name, e)
                with self._lock:
                    self.load_errors += 1
                out["errors"] += 1
        return out

    @staticmethod
    def _key_of(header: Dict[str, Any]) -> Optional[Any]:
        """Reconstruct the in-process cache key from its stored canonical
        repr. Only literal keys (tuples/strings/numbers — what fusion
        builds) round-trip; anything else returns None."""
        try:
            key = ast.literal_eval(header.get("key_repr") or "")
        except (ValueError, SyntaxError):
            return None
        return key

    def _absorb_costs(self, header: Dict[str, Any]) -> None:
        """Fold one entry's cost record / knobs into the warm-time side
        channels the cost model and tuner consume."""
        label, shape = header.get("label"), header.get("shape")
        cost = header.get("cost")
        with self._lock:
            if label and shape and isinstance(cost, dict):
                self._cost_records.setdefault(
                    str(label), {})[str(shape)] = dict(cost)
            knobs = header.get("knobs")
            if isinstance(knobs, dict) and knobs:
                self.loaded_knobs = dict(knobs)

    def harvested_costs(self) -> Dict[str, Dict[str, Dict[str, Any]]]:
        """{label: {shape: cost record}} recovered from persisted entries
        — the ``SegmentCostModel.ingest_costs`` shape, so a fresh pod's
        cost model starts calibrated from the fleet's measurements."""
        with self._lock:
            return {lab: {shp: dict(rec) for shp, rec in by.items()}
                    for lab, by in self._cost_records.items()}

    # -- knob shipping (fleet/objstore.py snapshot format) ------------------

    def put_snapshot(self, knobs: Optional[Dict[str, Any]] = None,
                     capacity_plan: Optional[Dict[str, Any]] = None) -> bool:
        """Ship the live tuning state: one canonical-JSON snapshot of the
        tuner's ``KnobSet`` and the controller's capacity plan, stored
        alongside the executables. Byte-identical snapshots are skipped
        (safe to call on every plan tick); failures degrade exactly like
        entry stores — accounted, ENOSPC flips read-only, never a raise."""
        if not self.write:
            return False
        blob = _objstore.snapshot_blob(knobs=knobs,
                                       capacity_plan=capacity_plan,
                                       env=dict(self._fp))
        with self._lock:
            if blob == self._last_snapshot_blob:
                return False
        try:
            self._write_blob(_objstore.SNAPSHOT_KEY, blob)
        except Exception as e:  # noqa: BLE001 — never block serving
            _LOG.warning("knob-snapshot store failed: %s", e)
            self._note_write_failure(e)
            return False
        with self._lock:
            self._last_snapshot_blob = blob
            self.snapshots += 1
        return True

    def load_snapshot(self) -> Optional[Dict[str, Any]]:
        """The shipped tuning snapshot (``{"knobs": ..., "capacity_plan":
        ..., "env": ...}``), or None when absent/corrupt/foreign-format —
        the pod then simply relearns, the PR 13 degrade contract."""
        try:
            blob = self._load_blob(_objstore.SNAPSHOT_KEY)
        except Exception as e:  # noqa: BLE001 — degrade to relearning
            _LOG.warning("knob-snapshot load failed: %s", e)
            with self._lock:
                self.load_errors += 1
            return None
        snap = _objstore.parse_snapshot(blob)
        if blob is not None and snap is None:
            with self._lock:
                self.load_errors += 1
        return snap

    # -- introspection ------------------------------------------------------

    def entry_count(self) -> int:
        return len(self._entry_names())

    def stats(self) -> Dict[str, Any]:
        entries = self.entry_count()  # listdir outside the counter lock
        with self._lock:
            total = self.hits + self.misses
            return {
                "path": self.path,
                "write": self.write,
                "entries": entries,
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": round(self.hits / total, 4) if total else None,
                "stores": self.stores,
                "store_skips": self.store_skips,
                "costs_only": self.costs_only,
                "load_errors": self.load_errors,
                "store_errors": self.store_errors,
                "write_degrades": self.write_degrades,
                "snapshots": self.snapshots,
                "load_s": round(self.load_s, 6),
                "store_s": round(self.store_s, 6),
                "env": dict(self._fp),
                "store": (self._store.stats()
                          if self._store is not None else None),
            }
