"""The FLOP count kept with the benchmark, against the published figure and
against XLA's own count of the plain reference compiled for a described v5e."""

import os

import pytest

from benchmarks.harness import spec

CONFIG = spec.load_json(os.path.join(spec.BENCH, "configs", "resnet50-featurize.json"))
WORK = spec.bench_module("work", "resnet50")
REF = spec.bench_module("references", CONFIG["reference"])


def test_macs_are_the_published_ones():
    # 3.8 GMAC with the stride on the first 1x1 (the paper's v1), 4.1 with it
    # on the 3x3 (v1.5) and every tap counted; taps on the padding left out
    assert 3.8e9 <= WORK.macs_per_image(CONFIG) <= 4.1e9


def test_parameter_count_matches_the_table():
    import numpy as np

    total = sum(int(np.prod(shape)) for path, shape, _ in REF.weight_specs(CONFIG)
                if not path.endswith(("/mean", "/var")))
    assert total == CONFIG["parameters"] == 25557032


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_flops_agree_with_xla_for_a_described_v5e(one_chip):
    import jax
    import jax.numpy as jnp

    batch, size = 8, CONFIG["image_size"]
    w = {path: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
         for path, shape, _ in REF.weight_specs(CONFIG)}
    x = jax.ShapeDtypeStruct((batch, size, size, 3), jnp.uint8, sharding=one_chip)
    compiled = jax.jit(lambda w_, x_: REF.features(CONFIG, w_, x_)).lower(w, x).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    xla = cost["flops"] / batch
    mine = WORK.flops_per_image(CONFIG)
    # XLA also counts batch norm, pooling and the element-wise work
    assert abs(mine - xla) / xla < 0.03, (mine, xla)
