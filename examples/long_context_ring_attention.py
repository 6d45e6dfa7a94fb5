"""Long-context inference with ring attention (sequence parallelism).

A long document overruns one device's O(T^2) attention memory; shard it
over the mesh's ``seq`` axis: each device holds T/n tokens, KV blocks
rotate around the ring (one ICI hop per step), and the streaming softmax
keeps per-device memory at O(T_local^2) — 64x smaller score blocks on an
8-device mesh. The same MultiHeadAttention module runs dense on one chip
and ring-parallel under shard_map; this journey proves the outputs agree
(sized to stay light on the CI's virtual CPU mesh; on real chips the same
code runs tens of thousands of tokens).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.models import dense_attention, ring_attention
from mmlspark_tpu.models.module import matmul_precision
from mmlspark_tpu.parallel import MeshSpec, make_mesh

SEQ = 2048
HEADS, HEAD_DIM = 4, 32


def main():
    n = jax.device_count()
    mesh = make_mesh(MeshSpec(data=1, seq=n))
    local = SEQ // n
    print(f"{SEQ}-token document over {n} devices: {local} tokens/device, "
          f"score blocks {local}x{local} instead of {SEQ}x{SEQ}")

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(
        rng.normal(size=(1, SEQ, HEADS, HEAD_DIM)).astype(np.float32) * 0.3)
        for _ in range(3))

    spec = P(None, "seq", None, None)
    with matmul_precision("float32"):
        ring = jax.jit(jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "seq", n, causal=True),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec))
        got = np.asarray(ring(q, k, v))
        want = np.asarray(dense_attention(q, k, v, causal=True))

    err = float(np.abs(got - want).max())
    print(f"ring vs dense max err = {err:.2e}")
    assert err < 1e-4, err
    assert got.shape == (1, SEQ, HEADS, HEAD_DIM)
    print(f"EXAMPLE OK seq={SEQ} devices={n} err={err:.2e}")


if __name__ == "__main__":
    main()
