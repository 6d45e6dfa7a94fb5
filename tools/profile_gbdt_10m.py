"""Stage-level wall breakdown of the 10M-row dense GBDT training run.

Times each pipeline stage separately (data gen excluded): BinMapper.fit,
transform, feature-major transpose, H2D, and the fit's own phases from the
spans it records under its root ``fit`` (obs/trace.py: ``gbdt:bin_fit``,
``gbdt:bins``, ``gbdt:scan`` / ``gbdt:scan_chunk`` / ``gbdt:fetch``,
``gbdt:trees``). Drives the verdict item 'profile the 10M dense run, then
attack the top cost'.
"""

import os
import time

import numpy as np


def print_fit_spans(spans):
    """One line a span of the last fit, children indented under parents."""
    roots = [s for s in spans if s["name"] == "fit" and not s["parent_id"]]
    if not roots:
        return
    mine = [s for s in spans if s["trace_id"] == roots[-1]["trace_id"]]
    by_id = {s["span_id"]: s for s in mine}
    for s in sorted(mine, key=lambda s: s["t0"]):
        depth, at = 0, s
        while at["parent_id"] in by_id:
            at, depth = by_id[at["parent_id"]], depth + 1
        print(f"  {'  ' * depth}{s['name']} {s['dur_s']:.3f}s {s['attrs']}",
              flush=True)


def main():
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.gbdt.binning import BinMapper
    from mmlspark_tpu.gbdt.booster import TrainParams, train

    n = int(os.environ.get("ROWS", "10000000"))
    d = 28
    iters = 50
    rng = np.random.default_rng(1)
    t0 = time.perf_counter()
    X = rng.normal(size=(n, d)).astype(np.float64)
    w = rng.normal(size=d)
    y = ((X @ w + 0.5 * X[:, 0] * X[:, 1] + rng.normal(0, 2.0, n)) > 0
         ).astype(np.float64)
    print(f"datagen {time.perf_counter()-t0:.1f}s", flush=True)

    # stage timings outside train()
    t0 = time.perf_counter()
    mapper = BinMapper.fit(X, 255, (), seed=0)
    t_fit = time.perf_counter() - t0
    t0 = time.perf_counter()
    bins = mapper.transform(X)
    t_tr = time.perf_counter() - t0
    t0 = time.perf_counter()
    bins_fm = np.ascontiguousarray(bins.T).astype(np.uint8)
    t_tp = time.perf_counter() - t0
    t0 = time.perf_counter()
    dev = jax.device_put(jnp.asarray(bins_fm))
    np.asarray(jax.device_get(dev[:, :8]))  # force completion (fetch = sync)
    t_h2d = time.perf_counter() - t0
    print(f"binfit {t_fit:.1f}s transform {t_tr:.1f}s transpose {t_tp:.1f}s "
          f"h2d({bins_fm.nbytes/1e6:.0f}MB) {t_h2d:.1f}s", flush=True)
    del dev, bins, bins_fm

    params = TrainParams(objective="binary", num_iterations=iters,
                         num_leaves=31, learning_rate=0.1,
                         min_data_in_leaf=20, max_bin=255, seed=0)
    from mmlspark_tpu.obs.trace import default_tracer

    for run in range(int(os.environ.get("RUNS", "2"))):
        t0 = time.perf_counter()
        train(params, X, y)
        print(f"run{run} total {time.perf_counter()-t0:.1f}s", flush=True)
        print_fit_spans(default_tracer().spans())


if __name__ == "__main__":
    main()
