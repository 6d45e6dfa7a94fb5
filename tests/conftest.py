"""Test config: force an 8-device virtual CPU mesh BEFORE jax initializes.

Mirrors the reference's distributed-without-a-cluster strategy (SURVEY §4): local[*]
with each partition acting as a machine. Here: 8 virtual CPU devices so every mesh/
collective code path is the real one.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax

# The environment may name another platform; tests always run on the CPU.
jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def mesh8():
    from mmlspark_tpu.parallel.mesh import make_mesh, MeshSpec
    return make_mesh(MeshSpec(data=8))


def assert_df_equality(df1, df2, eps: float = 1e-4):
    """DataFrameEquality parity (reference TestBase.scala:244-316)."""
    assert df1.columns == df2.columns, f"{df1.columns} != {df2.columns}"
    c1, c2 = df1.collect(), df2.collect()
    for name in df1.columns:
        a, b = c1[name], c2[name]
        assert len(a) == len(b), f"column {name}: {len(a)} vs {len(b)} rows"
        if a.dtype == object or b.dtype == object:
            for i, (x, y) in enumerate(zip(a, b)):
                if isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
                    np.testing.assert_allclose(
                        np.asarray(x, dtype=np.float64), np.asarray(y, dtype=np.float64),
                        atol=eps, err_msg=f"column {name} row {i}")
                else:
                    assert x == y, f"column {name} row {i}: {x!r} != {y!r}"
        elif a.dtype.kind in "fc":
            np.testing.assert_allclose(a, b, atol=eps, err_msg=f"column {name}")
        else:
            np.testing.assert_array_equal(a, b, err_msg=f"column {name}")


def _pallas_names(fn, *args):
    """The names of the Pallas kernels ``fn(*args)`` traces, in order: the
    names a device trace shows."""
    from jax._src import core

    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
                continue
            for value in eqn.params.values():
                for j in value if isinstance(value, (tuple, list)) else (value,):
                    if isinstance(j, core.ClosedJaxpr):
                        walk(j.jaxpr)
                    elif isinstance(j, core.Jaxpr):
                        walk(j)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


@pytest.fixture
def interpreted(monkeypatch):
    """Every kernel of the models in the Pallas interpreter where its
    dispatch takes it (``models/paired.py``); the fixture's value lists the
    kernels a function traces (``names(fn, *args)``)."""
    from mmlspark_tpu.models import paired

    monkeypatch.setattr(paired, "INTERPRET", True)
    return _pallas_names
