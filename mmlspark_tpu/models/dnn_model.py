"""DNNModel — distributed DNN inference as a pipeline stage (CNTKModel parity).

The reference's north-star path (SURVEY §3.1, cntk/CNTKModel.scala:30-540):
broadcast a serialized CNTK graph to executors, minibatch rows, evaluate through JNI
per batch, unbatch, coerce outputs to vectors. The TPU-native redesign:

  - broadcast                → params resident on device(s); with a mesh, replicated
                               (or tensor-sharded) via NamedSharding once per transform.
  - per-row JNI eval loop    → one ``jax.jit``-compiled forward over a padded [B, ...]
                               batch; compile cache keyed by (output node, shape, dtype).
  - minibatcher              → parallel/batching.Minibatcher with power-of-two bucket
                               padding so XLA compiles O(log n) shapes (CNTKModel's
                               FixedMiniBatchTransformer default of batch 10 becomes a
                               static-shape batch: cntk/CNTKModel.scala:374,496-500).
  - feedDict/fetchDict       → input column -> model argument; output column <- named
                               node or OUTPUT_i (cntk/CNTKModel.scala:204-223 and
                               CNTK/SerializableFunction.scala:61-63,115-129).
  - output coercion          → per-row float32 vectors (CNTKModel.scala:462-483).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..core.device_stage import DeviceFn
from ..core.params import ComplexParam, HasBatchSize, HasInputCol, HasOutputCol, Param
from ..core.dataframe import DataFrame
from ..core.pipeline import Model
from ..core.schema import ColType, Schema
from ..parallel.batching import Minibatcher, concat_outputs
from ..parallel.ingest import IngestStats, PreprocessSpec, TransferRing
from ..parallel.mesh import (DATA_AXIS, MeshContext, data_sharding,
                             fetch_global, replicated_sharding)
from .module import FunctionModel


class DNNModel(Model, HasInputCol, HasOutputCol, HasBatchSize):
    """Evaluate a FunctionModel over an input column of arrays/images.

    Mirrors CNTKModel's public surface: setModel, setInputCol/setOutputCol,
    setFeedDict/setFetchDict (multi-input / multi-output column<->node maps,
    all outputs fetched in ONE forward — CNTKModel.scala:204-260),
    setOutputNode/setOutputNodeIndex (SerializableFunction node addressing),
    setMiniBatchSize.
    """

    model = ComplexParam("model", "The FunctionModel to evaluate")
    outputNode = Param("outputNode", "Named layer to fetch (None = final output)", None, ptype=str)
    feedDict = Param("feedDict",
                     "Map of model argument names (ARGUMENT_i or graph input "
                     "names; keys) to input column names (values) — the "
                     "multi-input form of inputCol "
                     "(cntk/CNTKModel.scala:204-214)", None, ptype=dict)
    fetchDict = Param("fetchDict",
                      "Map of output column names (keys) to fetch nodes "
                      "(OUTPUT_i or layer paths; values) — the multi-output "
                      "form of outputCol, all fetched in ONE forward pass "
                      "(cntk/CNTKModel.scala:215-223)", None, ptype=dict)
    batchSize = Param("batchSize", "Rows per evaluation minibatch", 64, lambda v: v > 0, int)
    preprocess = ComplexParam(
        "preprocess",
        "PreprocessSpec fused into the compiled forward (cast/scale/offset/"
        "layout-transpose run on device, so input batches ride the host link "
        "in their wire dtype — uint8 pixels = 4x fewer H2D bytes). "
        "Single-input models only.")
    ringDepth = Param("ringDepth",
                      "In-flight batches in the transfer ring: the next "
                      "batches' H2D + compute overlap the previous fetch",
                      2, lambda v: v > 0, int)
    donateInputs = Param("donateInputs",
                         "Donate the input batch buffer into the compiled "
                         "step so XLA reuses the staging allocation. None "
                         "(default) = auto: on for accelerator backends, off "
                         "on CPU where donation is a no-op.", None, ptype=bool)
    useMesh = Param("useMesh",
                    "Shard eval batches over the active mesh data axis; "
                    "None (default) = auto: on whenever a >1-device mesh has "
                    "been explicitly set via MeshContext.set, off otherwise. "
                    "True additionally builds a default mesh if none is set; "
                    "False forces single-device eval.", None, ptype=bool)

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self._jit_cache: Dict[Tuple, Any] = {}
        self._last_ingest_stats: Optional[IngestStats] = None

    @property
    def last_ingest_stats(self) -> Optional[IngestStats]:
        """Ingest decomposition of the most recent transform() (queue/h2d/
        compute/readback per batch, bytes, overlap ratio) — the e2e-vs-
        per-call gap as a measured quantity."""
        return self._last_ingest_stats

    # -- fluent setters mirroring the reference API -----------------------
    def set_model(self, model: FunctionModel) -> "DNNModel":
        self._jit_cache.clear()  # compiled closures capture the model
        return self.set("model", model)

    def get_model(self) -> FunctionModel:
        return self.get_or_throw("model")

    def set_output_node(self, node: str) -> "DNNModel":
        return self.set("outputNode", node)

    def set_output_node_index(self, i: int) -> "DNNModel":
        return self.set("outputNode", f"OUTPUT_{i}")

    def set_mini_batch_size(self, n: int) -> "DNNModel":
        return self.set("batchSize", n)

    def set_preprocess(self, spec: Optional[PreprocessSpec]) -> "DNNModel":
        return self.set("preprocess", spec)

    def set_ring_depth(self, n: int) -> "DNNModel":
        return self.set("ringDepth", n)

    def set_feed_dict(self, *args) -> "DNNModel":
        """set_feed_dict({arg: col, ...}) or set_feed_dict(arg, col)."""
        d = {args[0]: args[1]} if len(args) == 2 else dict(args[0])
        return self.set("feedDict", d)

    def set_fetch_dict(self, *args) -> "DNNModel":
        """set_fetch_dict({col: node, ...}) or set_fetch_dict(col, node)."""
        d = {args[0]: args[1]} if len(args) == 2 else dict(args[0])
        return self.set("fetchDict", d)

    # -- I/O maps ----------------------------------------------------------
    def _io_maps(self, model):
        """Resolve (input_name -> column, out_column -> tap) maps from either
        the dict params or the single-column params."""
        feed = self.get("feedDict")
        if feed:
            in_map = {model.resolve_input(k): v for k, v in feed.items()}
        else:
            in_map = {model.resolve_input("ARGUMENT_0"):
                      self.get_or_throw("inputCol")}
        fetch = self.get("fetchDict")
        if fetch:
            out_map = {c: model.resolve_output(n) for c, n in fetch.items()}
        else:
            out_map = {self.get_or_throw("outputCol"):
                       model.resolve_output(self.get("outputNode"))}
        return in_map, out_map

    # -- compiled forward -------------------------------------------------
    def _compiled(self, taps: Tuple[Optional[str], ...], multi_in: bool,
                  spec: Optional[PreprocessSpec] = None,
                  donate: bool = False):
        """jit-compiled (params, x) -> tuple of activations, one per tap
        (all fetched in ONE forward). ``x`` is an array, or a dict of arrays
        for multi-input models.

        ``spec``: PreprocessSpec fused ahead of the forward — the wire
        carries the raw batch dtype (uint8 pixels) and XLA folds the
        cast/scale/transpose into the first layer's own input cast.
        ``donate``: donate the batch argument so XLA reuses its staging
        buffer across steps (used only when the caller committed the batch
        to device; a no-op on CPU)."""
        import jax

        model = self.get_model()
        # even an identity-scale spec keeps its dtype cast: the wire batch
        # may be uint8 and the module must see spec.dtype (a float cast of
        # an already-float input is free in XLA)
        key = ("fwd", id(model), taps, multi_in, spec, donate)
        if key not in self._jit_cache:

            def fwd(params, x):
                if spec is not None:
                    x = spec.apply_device(x)
                live = FunctionModel(model.module, params, model.input_shape,
                                     model.layer_names, model.name)
                acts = live.apply_taps(x, list(taps))
                return tuple(acts[t] for t in taps)

            self._jit_cache[key] = jax.jit(
                fwd, donate_argnums=(1,)) if donate else jax.jit(fwd)
        return self._jit_cache[key]

    def device_fn(self, schema: Schema):
        """Fusion contract: single-input eval fuses as [optional
        PreprocessSpec] + ONE forward fetching every tap — the same traced
        jaxpr the unfused _compiled() path jits, so fused == unfused
        bitwise. Mesh-sharded eval and dict-feed (multi-input) models keep
        the unfused path. No ``finalize``: the fused executor emits each
        output row as a READ-ONLY view of the batch it was read back in
        (the unfused path's rows are views of one fresh array; same bits,
        but a caller that writes into a row in place copies it first)."""
        model = self.get("model")
        if model is None or self.get("useMesh") is True:
            return None
        from ..parallel.mesh import DATA_AXIS, MeshContext

        mesh = MeshContext.current()
        if mesh is not None and mesh.shape.get(DATA_AXIS, 1) > 1:
            return None
        in_map, out_map = self._io_maps(model)
        if list(in_map) != model.argument_names()[:1]:
            return None  # multi-input feedDict eval stays unfused
        in_col = list(in_map.values())[0]
        out_cols = tuple(out_map)
        taps = tuple(out_map[c] for c in out_cols)
        spec: Optional[PreprocessSpec] = self.get("preprocess")
        # cache_token (not id): the shared CompileCache key must survive a
        # process restart for the fleet's persistent tier to hit
        key = ("DNNModel", model.cache_token(), in_col, out_cols, taps,
               None if spec is None else spec.cache_key())

        def fn(params, env):
            import jax.numpy as jnp

            x = env[in_col]
            if spec is not None:
                x = spec.apply_device(x)
            live = FunctionModel(model.module, params, model.input_shape,
                                 model.layer_names, model.name)
            acts = live.apply_taps(x, list(taps))
            # f32 on device == the unfused np.asarray(y, float32) readback
            return {c: acts[t].astype(jnp.float32)
                    for c, t in zip(out_cols, taps)}

        def accepts(probes):
            p = probes.get(in_col)
            if p is None or p["dtype"] is None:
                return True
            return p["sparse"] or p["dtype"].kind in "fuib"

        # what the model's work depends on in a batch of its input as shipped
        # (a causal scorer's live query blocks), kept in BatchTiming.counts
        counter = getattr(model.module, "batch_counts", None) if spec is None else None

        return DeviceFn(
            key=key, in_cols=(in_col,), out_cols=out_cols, fn=fn,
            params=model.params, accepts=accepts, reject_sparse=False,
            heavy=True,
            # pod-scale planner declaration (parallel/shardplan.py): flat
            # [N, F] feature inputs may shard their feature dim over the
            # mesh's tensor axis (GSPMD inserts the activation collectives)
            shard_dims={in_col: 1},
            count=None if counter is None else lambda arrays: counter(arrays[in_col]))

    def transform_schema(self, schema: Schema) -> Schema:
        if self.get("model") is None:
            # schema-only validation before the model is set: fall back to
            # the column params (node-name resolution needs a live model)
            feed = self.get("feedDict")
            in_cols = list(feed.values()) if feed \
                else [self.get_or_throw("inputCol")]
            fetch = self.get("fetchDict")
            out_cols = list(fetch) if fetch else [self.get_or_throw("outputCol")]
        else:
            model = self.get_model()
            in_map, out_map = self._io_maps(model)
            in_cols, out_cols = list(in_map.values()), list(out_map)
        for col in in_cols:
            schema.require(col)
        out = schema.copy()
        for col in out_cols:
            out.types[col] = ColType.VECTOR
        return out

    def transform(self, df: DataFrame) -> DataFrame:
        import jax

        from ..core.runtime import ensure_compile_cache

        ensure_compile_cache()
        model = self.get_model()
        in_map, out_map = self._io_maps(model)      # input name -> col, col -> tap
        in_cols = list(in_map.values())
        out_cols = list(out_map)
        taps = tuple(out_map[c] for c in out_cols)
        # dict-feed unless the map is exactly {primary input: col} — a single
        # entry naming a SECONDARY input must go through the dict path so
        # GraphModule validates the incomplete feed instead of silently
        # binding the column to the primary input
        multi_in = list(in_map) != model.argument_names()[:1]
        spec: Optional[PreprocessSpec] = self.get("preprocess")
        if spec is not None and multi_in:
            raise ValueError(
                "preprocess spec applies to single-input models only "
                "(feedDict consumers preprocess per column upstream)")
        fwd = self._compiled(taps, multi_in, spec)
        donate = self.get("donateInputs")
        if donate is None:
            donate = jax.default_backend() != "cpu"  # CPU donation is a no-op
        fwd_donated = self._compiled(taps, multi_in, spec, donate=True) \
            if donate else None
        batcher = Minibatcher(self.get("batchSize"), bucket=True,
                              dtype=np.float32, preserve_int=True)
        stats = IngestStats()
        self._last_ingest_stats = stats

        params_dev = jax.device_put(model.params)  # resident once (broadcast parity)

        use = self.get("useMesh")
        mesh = MeshContext.get() if use is True else \
            (MeshContext.current() if use is None else None)
        sharding = None
        if mesh is not None and mesh.shape.get(DATA_AXIS, 1) > 1:
            sharding = data_sharding(mesh)
            params_dev = jax.device_put(params_dev, replicated_sharding(mesh))

        def eval_partition(part):
            n = len(part[in_cols[0]])
            cols = {c: np.empty(n, dtype=object) for c in out_cols}
            if n == 0:
                for c in out_cols:
                    part[c] = cols[c]
                return part
            # null inputs produce null outputs (CNTKModel emits null rows for
            # undecodable inputs rather than failing the partition); a row is
            # valid only if EVERY fed column is non-null
            valid_idx = np.array(
                [i for i in range(n)
                 if all(part[c][i] is not None for c in in_cols)],
                dtype=np.int64)
            if len(valid_idx) == 0:
                for c in out_cols:
                    part[c] = cols[c]
                return part
            sub = {c: part[c][valid_idx] for c in in_cols}
            outs = []

            def to_device(batch):
                """Stack/pad + H2D for one batch — runs on the ring's
                prefetch thread so the NEXT batch's transfer overlaps this
                one's compute (DynamicBufferedBatcher parity,
                stages/Batchers.scala:12-160)."""
                if multi_in:
                    x = {name: batch.arrays[col]
                         for name, col in in_map.items()}
                    if sharding is not None:
                        # mesh-indivisible batches stay UNCOMMITTED host
                        # arrays (committing to one device conflicts with
                        # the mesh-replicated params inside jit)
                        if batch.size % mesh.shape[DATA_AXIS] == 0:
                            x = {k: jax.device_put(v, sharding)
                                 for k, v in x.items()}
                    else:
                        x = {k: jax.device_put(v) for k, v in x.items()}
                else:
                    x = batch.arrays[in_cols[0]]
                    if sharding is not None:
                        if x.shape[0] % mesh.shape[DATA_AXIS] == 0:
                            x = jax.device_put(x, sharding)
                    else:
                        x = jax.device_put(x)
                return x, batch.num_valid

            def step(staged):
                x, num_valid = staged
                # the donated executable only when the batch is device-
                # committed (uncommitted host arrays — the mesh-indivisible
                # case — have no staging buffer to reuse)
                leaves = list(x.values()) if isinstance(x, dict) else [x]
                f = fwd_donated if (fwd_donated is not None and
                                    all(isinstance(v, jax.Array)
                                        for v in leaves)) else fwd
                return f(params_dev, x), num_valid

            def fetch(handle):
                # fetch_global: under a multi-PROCESS mesh the sharded
                # output spans non-addressable devices (allgathered);
                # single-process it is a plain blocking readback
                ys, num_valid = handle
                return tuple(np.asarray(fetch_global(y),
                                        dtype=np.float32)[:num_valid]
                             for y in ys)

            ring = TransferRing(batcher.batches(sub, in_cols),
                                put=to_device, step=step, fetch=fetch,
                                depth=self.get("ringDepth"), stats=stats)
            try:
                for out in ring:
                    outs.append(out)
            finally:
                # a failed forward/readback must not strand the producer
                # thread blocked on the bounded queue (it pins device
                # buffers for the process lifetime)
                ring.close()
            for ci, c in enumerate(out_cols):
                full = concat_outputs([o[ci] for o in outs])
                for j, i in enumerate(valid_idx):
                    cols[c][i] = full[j]
            for c in out_cols:
                part[c] = cols[c]
            return part

        return df.map_partitions(eval_partition)
