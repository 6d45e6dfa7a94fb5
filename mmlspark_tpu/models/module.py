"""Minimal functional NN module system with named-layer addressability.

TPU-native replacement for the reference's CNTK graph engine (the C++ evaluation
engine driven through CNTK/SerializableFunction.scala:23-143). Design goals:

  - **Pure-functional**: a module is a pair of pure functions ``init(rng, shape)`` and
    ``apply(params, x)``; params are pytrees of jax/numpy arrays, so the whole forward
    pass jits and shards with `jax.jit`/`shard_map` — no graph VM, XLA *is* the engine.
  - **Named-layer tap points**: every layer has a path name ("stem/conv", "layer4/2/relu").
    ``apply(..., taps={...})`` returns intermediate activations by name. This gives the
    reference's node-addressing semantics (`SerializableFunction.scala:61-63,115-129`:
    name-based feed/fetch plus positional ``ARGUMENT_i``/``OUTPUT_i``) and powers
    ImageFeaturizer's ``cutOutputLayers`` (image/ImageFeaturizer.scala:133-178).
  - **bf16 compute, f32 params**: matmul/conv inputs cast to bfloat16 for the MXU;
    accumulation and parameters stay float32.

No flax dependency: the module tree is plain Python objects (picklable = serializable
via core/serialize.py), params are plain nested dicts.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..obs.scopes import scope

Params = Dict[str, Any]


import contextvars as _contextvars

_MATMUL_DTYPE: "_contextvars.ContextVar[str]" = _contextvars.ContextVar(
    "mmlspark_tpu_matmul_dtype", default="bfloat16")


def matmul_dtype() -> str:
    """Activation/weight dtype for Conv2D/Dense MXU ops: "bfloat16" (default —
    half the HBM traffic; accumulation is always f32), "float32" (exact —
    used by sharded-equals-single-device equivalence tests and accuracy-parity
    gates, where bf16 rounding noise would mask real sharding bugs), or
    "float64" (numerical experiments; requires jax_enable_x64)."""
    return _MATMUL_DTYPE.get()


class _ContextVarScope:
    """Context manager setting a ContextVar for the scope (thread/task-local,
    so concurrent jit traces can't leak each other's setting)."""

    _var: "_contextvars.ContextVar"

    def __init__(self, value):
        self._value = value

    def __enter__(self):
        self._token = self._var.set(self._value)
        return self

    def __exit__(self, *exc):
        self._var.reset(self._token)
        return False


class matmul_precision(_ContextVarScope):
    """Context manager selecting the matmul dtype, read at TRACE time.

    CAUTION: jit retraces read the dtype current at the retrace — a function
    first traced inside ``matmul_precision("float32")`` that later retraces
    (new input shapes) OUTSIDE the context compiles those shapes in the
    then-current default. Keep every call that may trace inside the context
    (or bake the precision in with a trace-time wrapper the way
    compile_train_step does for activation sharding)."""

    _var = _MATMUL_DTYPE

    def __init__(self, dtype: str):
        if dtype not in ("bfloat16", "float32", "float64"):
            raise ValueError(
                f"matmul_precision: unknown dtype {dtype!r} "
                f"(expected bfloat16/float32/float64)")
        if dtype == "float64":
            import jax
            if not jax.config.jax_enable_x64:
                raise RuntimeError(
                    "matmul_precision('float64') requires jax_enable_x64 "
                    "(otherwise astype(float64) silently yields float32)")
        super().__init__(dtype)


_ACTIVATION_SHARDING = _contextvars.ContextVar(
    "mmlspark_tpu_activation_sharding", default=None)


class activation_sharding(_ContextVarScope):
    """Trace-time context: constrain every inter-layer activation to the given
    sharding (normally batch_sharding(mesh)).

    Why this exists: the XLA SPMD partitioners (both Shardy and legacy GSPMD)
    mis-propagate the BACKWARD of conv when a broadcast-multiply sits between
    two channel-sharded convs at small spatial sizes — gradients come back
    wrong by ~1e-1 in f64 (repro: tests/test_models.py
    test_train_step_dp_fsdp_tp_matches_single_device, which fails without
    this). Anchoring each activation to the batch sharding removes the bad
    propagation choice; with the anchors, sharded == single-device to 1e-7.
    compile_train_step(mesh=...) enables it automatically, inside the traced
    function so retraces re-enter it.
    """

    _var = _ACTIVATION_SHARDING


def _constrain_activation(x):
    s = _ACTIVATION_SHARDING.get()
    if s is None:
        return x
    import jax
    return jax.lax.with_sharding_constraint(x, s)


def _rng_split(rng, n):
    import jax
    return jax.random.split(rng, n)


class Module:
    """Base module. Subclasses implement init/apply; both must be jit-pure."""

    name: str = ""

    def init(self, rng, in_shape: Tuple[int, ...]) -> Tuple[Params, Tuple[int, ...]]:
        """Returns (params, out_shape). Shapes exclude the batch dim."""
        raise NotImplementedError

    def apply(self, params: Params, x, train: bool = False):
        raise NotImplementedError

    # -- graph introspection ------------------------------------------------
    def layer_paths(self, prefix: str = "") -> List[str]:
        """All addressable layer names under this module (depth-first)."""
        return [prefix or self.name or type(self).__name__.lower()]

    def num_params(self, params: Params) -> int:
        import jax
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))


class Sequential(Module):
    """Named chain of modules; the unit of layer addressing.

    ``apply`` optionally records activations for tap names into ``taps_out`` and
    batch statistics (from BatchNorm layers in train mode) into ``stats_out``,
    keyed by layer path — the side channel the train step uses for EMA updates.
    """

    is_container = True

    def __init__(self, layers: Sequence[Tuple[str, Module]], name: str = ""):
        self.layers: List[Tuple[str, Module]] = list(layers)
        self.name = name

    def init(self, rng, in_shape):
        params: Params = {}
        keys = _rng_split(rng, max(len(self.layers), 1))
        shape = in_shape
        for (lname, layer), k in zip(self.layers, keys):
            p, shape = layer.init(k, shape)
            if p:
                params[lname] = p
        return params, shape

    def apply(self, params, x, train: bool = False,
              taps: Optional[Set[str]] = None, taps_out: Optional[Dict[str, Any]] = None,
              stats_out: Optional[Dict[str, Any]] = None, _prefix: str = ""):
        for lname, layer in self.layers:
            path = f"{_prefix}{lname}"
            p = params.get(lname, {})
            with scope(lname):
                if getattr(layer, "is_container", False):
                    x = layer.apply(p, x, train=train, taps=taps, taps_out=taps_out,
                                    stats_out=stats_out, _prefix=path + "/")
                elif isinstance(layer, BatchNorm):
                    x = layer.apply(p, x, train=train, stats_out=stats_out, _path=path)
                else:
                    x = layer.apply(p, x, train=train)
                x = _constrain_activation(x)
            if taps is not None and taps_out is not None and path in taps:
                taps_out[path] = x
        return x

    def layer_paths(self, prefix: str = "") -> List[str]:
        out: List[str] = []
        for lname, layer in self.layers:
            path = f"{prefix}{lname}"
            if getattr(layer, "is_container", False):
                out.extend(layer.layer_paths(path + "/"))
            out.append(path)
        return out


class Fn(Module):
    """Stateless elementwise/shape op from a pure function."""

    def __init__(self, fn: Callable, out_shape_fn: Optional[Callable] = None):
        self.fn = fn
        self.out_shape_fn = out_shape_fn

    def init(self, rng, in_shape):
        if self.out_shape_fn is not None:
            return {}, self.out_shape_fn(in_shape)
        # abstract shape probe: traces fn without running it on any backend,
        # so ops that only work under jit (or would be wrong on host numpy)
        # still probe correctly, and value-dependent shapes fail loudly at
        # init instead of silently committing to the zero-input's shape
        import jax

        spec = jax.ShapeDtypeStruct((1,) + tuple(in_shape), np.float32)
        out = jax.eval_shape(self.fn, spec)
        return {}, tuple(out.shape[1:])

    def apply(self, params, x, train: bool = False):
        return self.fn(x)


def _relu_fn(x):
    import jax.numpy as jnp
    return jnp.maximum(x, 0)


def _identity_shape(s):
    return s


def _flatten_fn(x):
    import jax.numpy as jnp
    return jnp.reshape(x, (x.shape[0], -1))


def _flat_shape(s):
    return (int(np.prod(s)),)


# module-level fns (not lambdas) so Fn modules pickle for persistence
def relu() -> Fn:
    return Fn(_relu_fn, _identity_shape)


def flatten() -> Fn:
    return Fn(_flatten_fn, _flat_shape)


def _conv_out_dim(size: int, k: int, stride: int, pad) -> int:
    """Output spatial dim for one axis; pad is 'SAME' | 'VALID' | (lo, hi)."""
    if pad == "SAME":
        return -(-size // stride)
    if pad == "VALID":
        return (size - k) // stride + 1
    lo, hi = pad
    return (size + lo + hi - k) // stride + 1


def _axis_pads(padding, n_axes: int):
    """Normalize a padding spec to per-axis entries for _conv_out_dim."""
    if isinstance(padding, str):
        return [padding] * n_axes
    return list(padding)


class Conv2D(Module):
    """NHWC conv on the MXU: inputs/kernel in matmul_dtype() (bf16 default;
    the MXU accumulates f32 internally — preferred_element_type can't be used
    here, see the comment in apply()).

    ``padding``: "SAME" | "VALID" | explicit ((top,bottom),(left,right)) — the explicit
    form gives bit-parity with frameworks that pad symmetrically where XLA's SAME would
    split the remainder low/high differently (torch transplants, see torch_import.py).
    """

    def __init__(self, features: int, kernel: Tuple[int, int] = (3, 3),
                 strides: Tuple[int, int] = (1, 1), padding="SAME",
                 use_bias: bool = False):
        self.features = features
        self.kernel = kernel
        self.strides = strides
        self.padding = padding if isinstance(padding, str) else \
            tuple((int(a), int(b)) for a, b in padding)
        self.use_bias = use_bias

    def init(self, rng, in_shape):
        import jax
        h, w, c = in_shape
        kh, kw = self.kernel
        fan_in = kh * kw * c
        wkey, _ = _rng_split(rng, 2)
        kernel = jax.random.normal(wkey, (kh, kw, c, self.features), dtype=np.float32)
        kernel = kernel * np.float32(math.sqrt(2.0 / fan_in))
        params = {"kernel": kernel}
        if self.use_bias:
            params["bias"] = np.zeros((self.features,), dtype=np.float32)
        ph, pw = _axis_pads(self.padding, 2)
        oh = _conv_out_dim(h, kh, self.strides[0], ph)
        ow = _conv_out_dim(w, kw, self.strides[1], pw)
        return params, (oh, ow, self.features)

    def apply(self, params, x, train: bool = False):
        import jax
        import jax.numpy as jnp
        dt = getattr(jnp, matmul_dtype())
        # no preferred_element_type: the conv transpose rule requires the
        # cotangent dtype to match the inputs, so an f32-accumulate bf16 conv
        # is not differentiable; the TPU MXU accumulates f32 internally anyway
        y = jax.lax.conv_general_dilated(
            x.astype(dt),
            jnp.asarray(params["kernel"]).astype(dt),
            window_strides=self.strides,
            padding=self.padding if isinstance(self.padding, str) else list(self.padding),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )  # bf16 activations end-to-end: half the HBM traffic
        if self.use_bias:
            y = y + params["bias"].astype(y.dtype)
        return y


class Dense(Module):
    def __init__(self, features: int, use_bias: bool = True):
        self.features = features
        self.use_bias = use_bias

    def init(self, rng, in_shape):
        import jax
        d = in_shape[-1]  # acts on the last dim; leading dims (e.g. time) pass through
        wkey, _ = _rng_split(rng, 2)
        w = jax.random.normal(wkey, (d, self.features), dtype=np.float32)
        w = w * np.float32(1.0 / math.sqrt(d))
        params = {"kernel": w}
        if self.use_bias:
            params["bias"] = np.zeros((self.features,), dtype=np.float32)
        return params, tuple(in_shape[:-1]) + (self.features,)

    def apply(self, params, x, train: bool = False):
        import jax.numpy as jnp
        dt = getattr(jnp, matmul_dtype())
        y = jnp.dot(x.astype(dt), jnp.asarray(params["kernel"]).astype(dt),
                    preferred_element_type=jnp.float64 if dt == jnp.float64
                    else jnp.float32)
        y = y.astype(dt if dt == jnp.float64 else jnp.float32)
        if self.use_bias:
            y = y + params["bias"]
        return y


class BatchNorm(Module):
    """Inference-style batchnorm (scale/bias/moving stats).

    Train-mode uses batch statistics; the cross-device mean/var reduction is left to
    XLA (inside pjit, reductions over the batch dim are automatically global when the
    batch is sharded — no explicit psum needed under jit-of-sharded-computation).
    """

    def __init__(self, momentum: float = 0.9, eps: float = 1e-5):
        self.momentum = momentum
        self.eps = eps

    def init(self, rng, in_shape):
        c = in_shape[-1]
        params = {
            "scale": np.ones((c,), dtype=np.float32),
            "bias": np.zeros((c,), dtype=np.float32),
            "mean": np.zeros((c,), dtype=np.float32),
            "var": np.ones((c,), dtype=np.float32),
        }
        return params, in_shape

    def apply(self, params, x, train: bool = False,
              stats_out: Optional[Dict[str, Any]] = None, _path: str = ""):
        import jax
        import jax.numpy as jnp
        if train:
            axes = tuple(range(x.ndim - 1))
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=axes)
            var = jnp.var(xf, axis=axes)
            if stats_out is not None:
                # stop_gradient: stats feed EMA updates, not the loss
                stats_out[_path] = (jax.lax.stop_gradient(mean),
                                    jax.lax.stop_gradient(var))
        else:
            mean, var = params["mean"], params["var"]
        inv = params["scale"] * jnp.reciprocal(jnp.sqrt(var + self.eps))
        shift = params["bias"] - mean * inv
        return x * inv.astype(x.dtype) + shift.astype(x.dtype)


class MaxPool(Module):
    """Max pooling; ``padding`` like Conv2D ("SAME"/"VALID"/explicit per-axis pairs).
    Explicit pads fill with -inf (pure window semantics, matches torch)."""

    def __init__(self, window: Tuple[int, int] = (2, 2),
                 strides: Optional[Tuple[int, int]] = None, padding="SAME"):
        self.window = window
        self.strides = strides or window
        self.padding = padding if isinstance(padding, str) else \
            tuple((int(a), int(b)) for a, b in padding)

    def init(self, rng, in_shape):
        h, w, c = in_shape
        ph, pw = _axis_pads(self.padding, 2)
        oh = _conv_out_dim(h, self.window[0], self.strides[0], ph)
        ow = _conv_out_dim(w, self.window[1], self.strides[1], pw)
        return {}, (oh, ow, c)

    def apply(self, params, x, train: bool = False):
        import jax
        import jax.numpy as jnp
        pad = self.padding if isinstance(self.padding, str) else \
            [(0, 0)] + list(self.padding) + [(0, 0)]
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max,
            (1,) + self.window + (1,), (1,) + self.strides + (1,), pad)


class GlobalAvgPool(Module):
    def init(self, rng, in_shape):
        return {}, (in_shape[-1],)

    def apply(self, params, x, train: bool = False):
        import jax.numpy as jnp
        return jnp.mean(x.astype(jnp.float32), axis=(1, 2))


# ---------------------------------------------------------------------------
# Residual blocks (used by resnet.py)
# ---------------------------------------------------------------------------

class Residual(Module):
    """y = act(body(x) + shortcut(x)); shortcut projects when shapes change.
    ``activation``: "relu" (ResNet convention) or None (pre-norm transformer
    blocks, where the residual stream stays linear)."""

    is_container = True

    def __init__(self, body: Sequential, shortcut: Optional[Sequential] = None,
                 activation: Optional[str] = "relu"):
        self.body = body
        self.shortcut = shortcut
        self.activation = activation

    def init(self, rng, in_shape):
        k1, k2 = _rng_split(rng, 2)
        bp, out_shape = self.body.init(k1, in_shape)
        params = {"body": bp}
        if self.shortcut is not None:
            sp, s_shape = self.shortcut.init(k2, in_shape)
            if s_shape != out_shape:
                raise ValueError(f"Residual shapes differ: {s_shape} vs {out_shape}")
            params["shortcut"] = sp
        elif in_shape != out_shape:
            raise ValueError(
                f"Residual needs a projection shortcut: {in_shape} -> {out_shape}")
        return params, out_shape

    def apply(self, params, x, train: bool = False,
              taps: Optional[Set[str]] = None, taps_out: Optional[Dict[str, Any]] = None,
              stats_out: Optional[Dict[str, Any]] = None, _prefix: str = ""):
        import jax.numpy as jnp
        with scope("body"):
            y = self.body.apply(params["body"], x, train=train, taps=taps,
                                taps_out=taps_out, stats_out=stats_out,
                                _prefix=_prefix + "body/")
        s = x
        if self.shortcut is not None:
            with scope("shortcut"):
                s = self.shortcut.apply(params["shortcut"], x, train=train, taps=taps,
                                        taps_out=taps_out, stats_out=stats_out,
                                        _prefix=_prefix + "shortcut/")
        out = y + s
        if getattr(self, "activation", "relu") == "relu":
            out = jnp.maximum(out, 0)
        return _constrain_activation(out)

    def layer_paths(self, prefix: str = "") -> List[str]:
        out = self.body.layer_paths(prefix + "body/")
        if self.shortcut is not None:
            out.extend(self.shortcut.layer_paths(prefix + "shortcut/"))
        return out


# ---------------------------------------------------------------------------
# FunctionModel: the SerializableFunction-equivalent handle
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FunctionModel:
    """A (module, params) pair with named inputs/outputs — the unit DNNModel evaluates.

    Plays the role of the reference's ``SerializableFunction`` wrapper around a native
    CNTK ``Function`` (CNTK/SerializableFunction.scala:85-143): a self-contained,
    persistable model handle with addressable argument/output nodes. Serialization is
    structural (module tree pickles; params pytree saved as npz by core/serialize.py)
    instead of opaque native bytes.

    ``layer_names``: orderd list of tap paths from the classifier head backwards, used
    by ImageFeaturizer's cutOutputLayers (reference downloader/Schema.scala:44-100).
    """

    module: Module
    params: Params
    input_shape: Tuple[int, ...]
    layer_names: List[str] = dataclasses.field(default_factory=list)
    name: str = "model"
    # image-input layout: native modules are NHWC; ONNX imports are NCHW.
    # Consumers (ImageFeaturizer) read this to orient the pixel array.
    data_format: str = "NHWC"

    def cache_token(self) -> str:
        """Stable cross-process identity of the traced computation, for
        compile-cache keys (DeviceFn.key). Params are ARGUMENTS to the
        compiled forward, so the token binds the architecture (the pickled
        module tree — the same structural-serialization contract
        core/serialize.py relies on) plus the param tree's layout
        (treedef, leaf shapes, dtypes) — NOT weight values. Two processes
        loading the same model therefore agree on the token, which is what
        lets the fleet's persistent compile cache (serving/fleet/cache.py)
        hand a fresh replica an executable compiled elsewhere. Falls back
        to the process-local ``id()`` when the module tree won't pickle
        (opaque native handles) — correctness keeps, cross-process reuse
        degrades."""
        tok = getattr(self, "_cache_token", None)
        if tok is None:
            import hashlib
            import pickle

            import jax
            try:
                leaves, treedef = jax.tree.flatten(self.params)
                spec = tuple(
                    (tuple(int(d) for d in np.shape(leaf)),
                     str(getattr(leaf, "dtype", type(leaf).__name__)))
                    for leaf in leaves)
                blob = pickle.dumps(
                    (self.module, tuple(self.input_shape),
                     tuple(self.layer_names), self.name, self.data_format,
                     str(treedef), spec), protocol=4)
                tok = "m:" + hashlib.sha256(blob).hexdigest()[:20]
            except Exception:  # noqa: BLE001 — unpicklable module tree
                tok = f"id:{id(self)}"
            self._cache_token = tok
        return tok

    def argument_names(self) -> List[str]:
        """Graph input names (multi-input GraphModules list all of them)."""
        names = getattr(self.module, "input_names", None)
        return list(names) if names else ["ARGUMENT_0"]

    def resolve_input(self, node: str) -> str:
        """Resolve an input spec (``ARGUMENT_i`` or a raw graph input name)
        to the module's input tensor name. (Reference:
        SerializableFunction.scala:61-63 ARGUMENT_i addressing.)"""
        names = self.argument_names()
        if node.startswith("ARGUMENT_"):
            suffix = node[len("ARGUMENT_"):]
            if not suffix.isdigit() or int(suffix) >= len(names):
                raise KeyError(
                    f"{node!r}: model has {len(names)} argument(s) ({names}); "
                    f"valid indices are 0..{len(names) - 1}")
            return names[int(suffix)]
        if node in names:
            return node
        raise KeyError(f"Unknown input node {node!r}; known: {names} "
                       f"or ARGUMENT_i")

    def output_names(self) -> List[str]:
        return ["OUTPUT_0"] + list(self.layer_names)

    def resolve_output(self, node: Optional[str]) -> Optional[str]:
        """Resolve a fetch-node spec to a tap path (None = final output).

        Accepts a layer path, ``OUTPUT_i`` positional addressing, or None.
        (Reference: SerializableFunction.scala:61-63,115-129.)
        """
        if node is None or node == "OUTPUT_0" or node == self.name:
            return None
        if node.startswith("OUTPUT_"):
            i = int(node.split("_", 1)[1])
            return self.layer_names[i - 1] if i > 0 else None
        paths = set(self.module.layer_paths())
        if node in paths:
            return node
        raise KeyError(f"Unknown output node {node!r}; known: OUTPUT_i, {sorted(paths)[:20]}...")

    def apply(self, x, tap: Optional[str] = None, train: bool = False):
        """Forward pass; if ``tap`` is a layer path, return that activation instead."""
        if tap is None:
            return self.module.apply(self.params, x, train=train)
        taps_out: Dict[str, Any] = {}
        if not getattr(self.module, "is_container", False):
            raise ValueError(
                "taps need a container root (Sequential/GraphModule)")
        self.module.apply(self.params, x, train=train, taps={tap}, taps_out=taps_out)
        if tap not in taps_out:
            raise KeyError(f"Tap {tap!r} not produced; known {self.module.layer_paths()[:20]}")
        return taps_out[tap]

    def apply_taps(self, x, taps, train: bool = False):
        """ONE forward pass fetching several nodes (fetchDict parity —
        cntk/CNTKModel.scala:204-223 evaluates all fetch variables in a
        single native eval). ``taps`` is a list of tap paths where ``None``
        means the final output; returns {tap: activation}."""
        real = {t for t in taps if t is not None}
        taps_out: Dict[str, Any] = {}
        if real:
            if not getattr(self.module, "is_container", False):
                raise ValueError(
                    "taps need a container root (Sequential/GraphModule)")
            out = self.module.apply(self.params, x, train=train, taps=real,
                                    taps_out=taps_out)
        else:
            out = self.module.apply(self.params, x, train=train)
        missing = real - set(taps_out)
        if missing:
            raise KeyError(f"Taps {sorted(missing)} not produced; known "
                           f"{self.module.layer_paths()[:20]}")
        result = dict(taps_out)
        if None in list(taps):
            result[None] = out
        return result
