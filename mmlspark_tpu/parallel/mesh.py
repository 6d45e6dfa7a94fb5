"""Device mesh runtime: discovery, construction, topology.

Replaces the reference's cluster-topology layer (core/utils/ClusterUtil.scala:13-90 —
executor/core counting from BlockManager state; lightgbm/LightGBMUtils.scala:105-173 —
driver-socket rendezvous) with the TPU-native equivalents:

  - device discovery         = jax.devices()
  - rendezvous               = jax.distributed.initialize (multi-host; ICI needs none)
  - worker count             = mesh axis sizes
  - barrier gang start       = SPMD launch (inherent on TPU pods)

Standard axis names follow the scaling-book convention: ``data`` (DP over ICI/DCN),
``fsdp`` (param sharding), ``tensor`` (TP), ``seq`` (sequence/context parallel),
``expert`` (EP). Single-chip meshes are 1-sized on every axis, so all code paths are
mesh-agnostic.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger("mmlspark_tpu")

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
TENSOR_AXIS = "tensor"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"
PIPE_AXIS = "pipe"


def devices(backend: Optional[str] = None) -> List:
    import jax
    return jax.devices(backend) if backend else jax.devices()


def local_device_count() -> int:
    import jax
    return jax.local_device_count()


_dist_initialized = False


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> bool:
    """Multi-host bootstrap (replaces driver-socket rendezvous,
    LightGBMUtils.scala:105-173). Called automatically by ``make_mesh`` before
    device discovery; explicit earlier calls are fine and idempotent.

    Arguments default from the environment — ``MMLSPARK_COORDINATOR``,
    ``MMLSPARK_NUM_PROCESSES``, ``MMLSPARK_PROCESS_ID`` — so a pod launch
    (one process per host, same program) needs no code changes: set the env
    on each host and every ``make_mesh()`` sees the global device set.
    No-op when single-process. Returns True iff jax.distributed was
    initialized by this call.
    """
    global _dist_initialized
    if _dist_initialized:
        return False
    coordinator_address = coordinator_address or \
        os.environ.get("MMLSPARK_COORDINATOR")
    if num_processes is None and "MMLSPARK_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["MMLSPARK_NUM_PROCESSES"])
    if process_id is None and "MMLSPARK_PROCESS_ID" in os.environ:
        process_id = int(os.environ["MMLSPARK_PROCESS_ID"])
    if num_processes in (None, 1):
        # single-process no-op does NOT latch: a later explicit call (or one
        # made after the env appears) must still be able to initialize
        return False
    import jax

    if jax.distributed.is_initialized():
        # the user bootstrapped jax.distributed themselves (standard JAX
        # multi-host practice) — respect it, don't double-initialize
        _dist_initialized = True
        return False
    jax.distributed.initialize(coordinator_address, num_processes, process_id)
    _dist_initialized = True  # latch only after a successful init
    log.info("jax.distributed initialized: process %s of %s via %s",
             process_id, num_processes, coordinator_address)
    return True


def process_shard(df, process_id: Optional[int] = None,
                  num_processes: Optional[int] = None):
    """Per-process input sharding: each host feeds its own slice of a
    DataFrame's partitions into the mesh (the SPMD input-pipeline story —
    the reference's equivalent is Spark assigning partitions to executors).
    Round-robin by partition index; identity when single-process."""
    import jax

    if process_id is None or num_processes is None:
        # env-var launches must shard correctly even before make_mesh runs
        initialize_distributed()
    pid = jax.process_index() if process_id is None else process_id
    n = jax.process_count() if num_processes is None else num_processes
    if n <= 1:
        return df
    from ..core.dataframe import DataFrame

    mine = [p for i, p in enumerate(df.partitions) if i % n == pid]
    if not mine:
        return df.limit(0)
    return DataFrame(mine, schema=df.schema)


@dataclasses.dataclass
class MeshSpec:
    """Declarative mesh shape; -1 on one axis absorbs remaining devices."""

    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1
    pipe: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = dataclasses.asdict(self)
        fixed = 1
        wild = None
        for k, v in sizes.items():
            if v == -1:
                if wild is not None:
                    raise ValueError("Only one mesh axis may be -1")
                wild = k
            else:
                fixed *= v
        if wild is not None:
            if n_devices % fixed:
                raise ValueError(f"{n_devices} devices not divisible by fixed axes {fixed}")
            sizes[wild] = n_devices // fixed
        else:
            total = int(np.prod(list(sizes.values())))
            if total != n_devices:
                raise ValueError(f"Mesh {sizes} needs {total} devices, have {n_devices}")
        return sizes


def make_mesh(spec: Optional[MeshSpec] = None, device_list: Optional[Sequence] = None):
    """Build a jax.sharding.Mesh over the available devices.

    Axes with size 1 are kept in the mesh (harmless; lets sharding rules name them
    unconditionally). Uses jax.make_mesh so device order follows physical topology
    (ICI-contiguous) rather than enumeration order.
    """
    import jax

    if device_list is None:
        initialize_distributed()  # env-driven multi-host bootstrap (no-op local)
    spec = spec or MeshSpec()
    devs = list(device_list) if device_list is not None else jax.devices()
    sizes = spec.resolve(len(devs))
    axis_names = tuple(sizes.keys())
    shape = tuple(sizes[a] for a in axis_names)
    # Auto axis types: GSPMD propagation (annotate shardings, XLA inserts
    # collectives) — jax 0.9 defaults make_mesh to Explicit, which we don't
    # want for the framework's implicit-sharding style.
    kwargs = {"axis_types": (jax.sharding.AxisType.Auto,) * len(axis_names)}
    if device_list is not None:
        arr = np.asarray(devs).reshape(shape)
        return jax.sharding.Mesh(arr, axis_names, **kwargs)
    return jax.make_mesh(shape, axis_names, devices=devs, **kwargs)


def shard_map_compat(f, **kwargs):
    """Alias of ``jax.shard_map`` (jax 0.9.0 is the one installation)."""
    import jax

    return jax.shard_map(f, **kwargs)


def data_sharding(mesh, *batch_axes: str):
    """NamedSharding that shards the leading (batch) dim over the data axes."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes = batch_axes or (DATA_AXIS,)
    return NamedSharding(mesh, P(axes))


def replicated_sharding(mesh):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P())


def num_data_shards(mesh) -> int:
    return int(mesh.shape.get(DATA_AXIS, 1) * mesh.shape.get(FSDP_AXIS, 1))


def fetch_global(x):
    """``jax.device_get`` that also works when arrays span PROCESSES (the
    multi-host counterpart of the reference's executor-to-driver collects,
    LightGBMBase.scala:157-159): fully-addressable values fetch directly —
    in single-process runs that is every value, so this is a drop-in;
    fully-replicated global arrays read the local shard; row-sharded
    global arrays allgather across processes. Collective when
    multi-process — every process must call it in lockstep (true for the
    SPMD host loops that use it)."""
    import jax

    def one(a):
        if not isinstance(a, jax.Array) or a.is_fully_addressable:
            return a
        if a.is_fully_replicated:
            return np.asarray(a.addressable_data(0))
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(a, tiled=True))

    return jax.device_get(jax.tree.map(one, x))


class MeshContext:
    """Process-wide default mesh (lazily built single-axis DP mesh).

    Stages that dispatch to devices consult this unless given an explicit mesh —
    the analogue of the reference stages consulting ClusterUtil for worker counts
    (lightgbm/LightGBMBase.scala:120-128).
    """

    _default = None
    _explicit = False

    @classmethod
    def get(cls):
        if cls._default is None:
            cls._default = make_mesh()  # lazy: does NOT count as explicit
        return cls._default

    @classmethod
    def current(cls):
        """The explicitly-set mesh (via set()), or None. A mesh that get()
        built lazily does not count. Auto-mode consumers (DNNModel
        useMesh=None) use this so that 'no mesh configured' stays
        single-device instead of silently adopting a lazily-constructed
        global-device mesh (which would span non-addressable devices in a
        multi-host deployment)."""
        return cls._default if cls._explicit else None

    @classmethod
    def set(cls, mesh) -> None:
        cls._default = mesh
        cls._explicit = True

    @classmethod
    def reset(cls) -> None:
        cls._default = None
        cls._explicit = False
