"""One Mamba sublayer and one differential-attention sublayer of the hybrid
cell alone, at the cell's widths and row length, each with its kernel and
with its plain XLA form: time (median of three, after a warm-up) and
agreement. `python3 benchmarks/selfcheck/layers_on_chip_phi4flash.py
[positions]` (32,768 without). Prints one JSON line a measurement. Needs a
chip; not part of a run, and never a source of a cell's number."""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def timed(fn, *args):
    import jax

    out = jax.block_until_ready(fn(*args))
    seconds = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        seconds.append(time.perf_counter() - t0)
    return out, statistics.median(seconds)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import device
    from mmlspark_tpu.models import ssm, transformer

    device.fix_compile_cache()
    device.require_chips(1)
    T = int(argv[0]) if argv else 32768
    d = 2560
    u = jax.random.normal(jax.random.key(1), (1, T, d), jnp.float32)

    def both(name, layer, switch_of, switch):
        params = layer.init(jax.random.key(2), (T, d))[0]
        run = jax.jit(lambda p, x: layer.apply(p, x))
        got, with_kernel = timed(run, params, u)
        applies = getattr(switch_of, switch)
        setattr(switch_of, switch, lambda *a: False)
        try:
            want, plain = timed(jax.jit(lambda p, x: layer.apply(p, x)), params, u)
        finally:
            setattr(switch_of, switch, applies)
        print(json.dumps({
            "layer": name, "positions": T, "kernel_s": with_kernel, "xla_s": plain,
            "widest_difference": float(jnp.abs(got - want).max()),
            "largest_output": float(jnp.abs(want).max())}), flush=True)

    both("mamba", ssm.Mamba(2 * d, 16, 4, 160, param_dtype="bfloat16"),
         ssm, "_scan_kernel_applies")
    for window in (512, 0):
        both(f"diff_attention_window_{window}",
             transformer.DiffAttention(40, 20, 64, 17, window, param_dtype="bfloat16"),
             transformer, "_diff_pallas_applies")

    # the kernels alone, on operands of their own
    piece = min(T, ssm.PIECE)
    f32 = jnp.float32
    delta = jax.nn.softplus(jax.random.normal(jax.random.key(3), (1, piece, 2 * d), f32) - 3)
    x = jax.random.normal(jax.random.key(4), (1, piece, 2 * d), f32)
    bc = jax.random.normal(jax.random.key(5), (2, 1, piece, 16), f32)
    A = -jnp.broadcast_to(jnp.arange(1, 17, dtype=f32), (2 * d, 16))
    ops = (delta, x, bc[0], bc[1], A, jnp.ones((2 * d,), f32), jnp.zeros((1, 2 * d, 16), f32))
    (y, h), s_kernel = timed(jax.jit(ssm.ssm_scan_pallas), *ops)
    (yw, hw), s_plain = timed(jax.jit(ssm.ssm_scan_xla), *ops)
    print(json.dumps({"kernel": "ssm_scan", "positions": piece, "kernel_s": s_kernel,
                      "xla_s": s_plain, "widest_difference": float(jnp.abs(y - yw).max()),
                      "largest_output": float(jnp.abs(yw).max())}), flush=True)
    q = jax.random.normal(jax.random.key(6), (1, T, d), jnp.bfloat16)
    k, v = (jax.random.normal(jax.random.key(n), (1, T, d // 2), jnp.bfloat16) for n in (7, 8))
    for window in (512, 0):
        _, seconds = timed(jax.jit(lambda q, k, v: transformer.diff_pallas(
            q, k, v, jnp.float32(0.3), jnp.ones((128,), f32), window, 20, 10, 1e-5)), q, k, v)
        print(json.dumps({"kernel": "attn_window_diff" if window else "attn_full_diff",
                          "positions": T, "kernel_s": seconds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
