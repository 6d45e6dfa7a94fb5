"""Standalone GBDT predict-throughput measurement (GEMM forest kernel).

Re-measures the predict section of tools/bench_gbdt_train.py after the
device-forest rewrite (per-node gathers -> comparison-sign x path-matrix
GEMM; predict.py module docstring) without re-paying the full training
bench. Trains the same models the train bench does, measures batch
predict via the chained-dependency discipline + single-row via the host
path.
"""

import json
import time

import numpy as np

from bench_gbdt_train import bench_predict, make_data  # noqa: E402


def main():
    import jax

    from mmlspark_tpu.gbdt.booster import TrainParams, train

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    n, d, iters = 200_000, 28, 50
    X, y = make_data(n, d, rng)
    params = TrainParams(objective="binary", num_iterations=iters,
                         num_leaves=31, learning_rate=0.1,
                         min_data_in_leaf=20, max_bin=255, seed=0)
    booster = train(params, X, y)
    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "predict_200k_model": bench_predict(booster, X)}

    if dev.platform != "cpu":
        # larger row block through the same 50-tree forest (predict cost
        # scales with rows x trees; the model's training size is irrelevant)
        Xl, _ = make_data(1_000_000, d, np.random.default_rng(1))
        out["predict_1m_rows"] = bench_predict(booster, Xl)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
