"""Stage persistence: params + complex values to a directory tree.

Re-design of the reference's persistence stack:
  - org/apache/spark/ml/Serializer.scala:1-203  — type-directed complex-param writers
  - org/apache/spark/ml/ComplexParamsSerializer.scala:1-181 — ComplexParamsWritable/Readable
  - core/serialize/ConstructorWriter.scala:23-60 — models serialized by constructor args

Layout (per stage):
    <path>/metadata.json            {"class": ..., "params": {...}, "timestamp": ...}
    <path>/complex/<param>/         one subdir per complex param, type-tagged payload
    <path>/stages/<i>_<name>/       nested stages (Pipeline / PipelineModel)

Complex payload types handled: numpy arrays (npz), jax arrays (npz via host copy),
pytrees of arrays (flattened npz + treedef json), DataFrames (npz of object columns via
pickle fallback), nested stages (recursive), plain picklable objects (pkl; last resort).
"""

from __future__ import annotations

import json
import os
import pickle
import shutil
import time
from typing import Any, Dict, List, Optional

import numpy as np

from .dataframe import DataFrame
from .params import Params


def _is_jax_array(v: Any) -> bool:
    try:
        import jax
        return isinstance(v, jax.Array)
    except Exception:
        return False


def _write_json(path: str, obj: Any) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1, default=_json_default)


def _json_default(o: Any):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"Not JSON serializable: {type(o)}")


def _npz_safe(arr: np.ndarray) -> np.ndarray:
    """npz keeps only numpy's own dtypes: an extension type (bfloat16, the
    float8s) goes in as the unsigned integers of its bytes, and the manifest
    carries its name so that it comes back as it went."""
    if arr.dtype.kind == "V":
        return arr.view(f"u{arr.dtype.itemsize}")
    return arr


def _npz_restore(arr: np.ndarray, dtype: Optional[str]) -> np.ndarray:
    if dtype is None or str(arr.dtype) == dtype:
        return arr
    import ml_dtypes  # noqa: F401 - registers the extension dtypes' names

    return arr.view(np.dtype(dtype))


def _save_value(value: Any, path: str) -> Dict[str, Any]:
    """Save one complex value under ``path``; return its type-tag manifest."""
    os.makedirs(path, exist_ok=True)
    from .pipeline import PipelineStage
    if isinstance(value, PipelineStage):
        save_stage(value, os.path.join(path, "stage"), overwrite=True)
        return {"kind": "stage"}
    if isinstance(value, DataFrame):
        with open(os.path.join(path, "df.pkl"), "wb") as f:
            pickle.dump(value.partitions, f)
        return {"kind": "dataframe"}
    if isinstance(value, np.ndarray) and value.dtype != object:
        np.savez(os.path.join(path, "array.npz"), arr=_npz_safe(value))
        return {"kind": "ndarray", "dtype": str(value.dtype)}
    if _is_jax_array(value):
        np.savez(os.path.join(path, "array.npz"), arr=_npz_safe(np.asarray(value)))
        return {"kind": "jax_array", "dtype": str(value.dtype)}
    if isinstance(value, bytes):
        with open(os.path.join(path, "blob.bin"), "wb") as f:
            f.write(value)
        return {"kind": "bytes"}
    if isinstance(value, str):
        with open(os.path.join(path, "text.txt"), "w") as f:
            f.write(value)
        return {"kind": "str"}
    # pytree of arrays?
    try:
        import jax
        leaves, treedef = jax.tree.flatten(value)
        if leaves and all(isinstance(l, (np.ndarray,)) or _is_jax_array(l)
                          or isinstance(l, (int, float)) for l in leaves):
            np.savez(os.path.join(path, "tree.npz"),
                     **{f"leaf_{i}": _npz_safe(np.asarray(l))
                        for i, l in enumerate(leaves)})
            with open(os.path.join(path, "treedef.pkl"), "wb") as f:
                pickle.dump(treedef, f)
            return {"kind": "pytree", "num_leaves": len(leaves),
                    "dtypes": [str(np.asarray(l).dtype) for l in leaves]}
    except Exception:
        pass
    with open(os.path.join(path, "value.pkl"), "wb") as f:
        pickle.dump(value, f)
    return {"kind": "pickle"}


def _load_value(manifest: Dict[str, Any], path: str) -> Any:
    kind = manifest["kind"]
    if kind == "stage":
        return load_stage(os.path.join(path, "stage"))
    if kind == "dataframe":
        with open(os.path.join(path, "df.pkl"), "rb") as f:
            return DataFrame(pickle.load(f))
    if kind in ("ndarray", "jax_array"):
        with np.load(os.path.join(path, "array.npz")) as z:
            return _npz_restore(z["arr"], manifest.get("dtype"))
    if kind == "bytes":
        with open(os.path.join(path, "blob.bin"), "rb") as f:
            return f.read()
    if kind == "str":
        with open(os.path.join(path, "text.txt")) as f:
            return f.read()
    if kind == "pytree":
        with np.load(os.path.join(path, "tree.npz")) as z:
            dtypes = manifest.get("dtypes") or [None] * manifest["num_leaves"]
            leaves = [_npz_restore(z[f"leaf_{i}"], dtypes[i])
                      for i in range(manifest["num_leaves"])]
        with open(os.path.join(path, "treedef.pkl"), "rb") as f:
            treedef = pickle.load(f)
        import jax
        return jax.tree.unflatten(treedef, leaves)
    if kind == "pickle":
        with open(os.path.join(path, "value.pkl"), "rb") as f:
            return pickle.load(f)
    raise ValueError(f"Unknown complex value kind {kind!r}")


def save_stage(stage: "Params", path: str, overwrite: bool = True) -> None:
    if os.path.exists(path):
        if not overwrite:
            raise FileExistsError(path)
        shutil.rmtree(path)
    os.makedirs(path)

    meta: Dict[str, Any] = {
        "class": f"{type(stage).__module__}.{type(stage).__name__}",
        "timestamp": time.time(),
        "params": stage.simple_params(),
        "complex": {},
    }
    complex_params = stage.complex_params()
    if complex_params:
        cdir = os.path.join(path, "complex")
        for name, value in complex_params.items():
            meta["complex"][name] = _save_value(value, os.path.join(cdir, name))

    # nested stage lists (Pipeline/PipelineModel constructor args — ConstructorWritable parity)
    stages = getattr(stage, "_stages", None)
    if stages is not None:
        meta["num_stages"] = len(stages)
        for i, s in enumerate(stages):
            save_stage(s, os.path.join(path, "stages", f"{i:03d}_{type(s).__name__}"))

    _write_json(os.path.join(path, "metadata.json"), meta)


def load_stage(path: str) -> Any:
    with open(os.path.join(path, "metadata.json")) as f:
        meta = json.load(f)
    from .pipeline import get_stage_class
    cls = get_stage_class(meta["class"])

    kwargs: Dict[str, Any] = {}
    if "num_stages" in meta:
        sdir = os.path.join(path, "stages")
        names = sorted(os.listdir(sdir)) if os.path.isdir(sdir) else []
        kwargs["stages"] = [load_stage(os.path.join(sdir, n)) for n in names]

    stage = cls(**kwargs) if kwargs else cls()
    for k, v in meta["params"].items():
        stage.set(k, v)
    for name, manifest in meta.get("complex", {}).items():
        stage.set(name, _load_value(manifest, os.path.join(path, "complex", name)))
    return stage
