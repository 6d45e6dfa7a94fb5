"""Sequence-family benchmark: transformer encoder + BiLSTM throughput.

Steady-state tokens/sec on the available chip (device-resident inputs, AOT-
compiled executables, scalar witnesses force completion). Also A/Bs the
attention kernel (Pallas flash vs the XLA lowering) at long sequence lengths
with the repeat loop ON DEVICE, so the kernel is timed, not the per-call
host dispatch. Prints one JSON line.
"""

import json
import os
import time

import numpy as np


def _bench(fn, args, per_call_tokens, iters=10, warmup=3):
    for _ in range(warmup):
        float(fn(*args))
    t0 = time.perf_counter()
    outs = [fn(*args) for _ in range(iters)]
    for o in outs:
        assert np.isfinite(float(o))
    dt = time.perf_counter() - t0
    return per_call_tokens * iters / dt


def main():
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models import bilstm_tagger, transformer_encoder

    dev = jax.devices()[0]
    on_accel = dev.platform != "cpu"
    B, T = (256, 512) if on_accel else (4, 64)
    rng = np.random.default_rng(0)

    # transformer encoder, GPT-small-ish block dims
    tf = transformer_encoder(seq_len=T, dim=512, depth=4, num_heads=8,
                             vocab_size=32000)
    toks = jax.device_put(rng.integers(0, 32000, size=(B, T)))

    @jax.jit
    def tf_fwd(params, x):
        return jnp.sum(tf.module.apply(params, x).astype(jnp.float32))

    tf_c = tf_fwd.lower(tf.params, toks).compile()
    tf_tps = _bench(lambda p, x: tf_c(p, x), (jax.device_put(tf.params), toks),
                    B * T)

    # BiLSTM tagger (scan-bound: sequential over T by construction)
    bi = bilstm_tagger(seq_len=T, vocab_size=32000, embed_dim=128,
                       hidden=256, num_tags=16)

    @jax.jit
    def bi_fwd(params, x):
        return jnp.sum(bi.module.apply(params, x).astype(jnp.float32))

    bi_c = bi_fwd.lower(bi.params, toks).compile()
    bi_tps = _bench(lambda p, x: bi_c(p, x), (jax.device_put(bi.params), toks),
                    B * T)

    # flash-vs-XLA attention A/B (TPU only; flash dispatches on bf16 inputs)
    flash_ab = {}
    if on_accel:
        from mmlspark_tpu.models.attention import dense_attention

        def attn_ms(flash: bool, T: int, B=4, H=8, D=64, inner=10):
            os.environ.pop("MMLSPARK_TPU_NO_FLASH", None)
            if not flash:
                os.environ["MMLSPARK_TPU_NO_FLASH"] = "1"
            q, k, v = (jnp.asarray(
                rng.normal(size=(B, T, H, D)).astype(np.float32))
                .astype(jnp.bfloat16) for _ in range(3))

            @jax.jit
            def f(q, k, v):
                def body(i, acc):
                    # dtype-preserving dependency on acc: keeps q bf16 (the
                    # flash gate requires it) while defeating loop hoisting
                    o = dense_attention(q + acc.astype(q.dtype) * 0, k, v,
                                        causal=True)
                    return acc + o.astype(jnp.float32).sum()

                return jax.lax.fori_loop(0, inner, body, jnp.float32(0))

            float(f(q, k, v))  # compile + warm
            t0 = time.perf_counter()
            float(f(q, k, v))
            return (time.perf_counter() - t0) / inner * 1e3

        for t_ab in (2048, 8192):
            fl, xla = attn_ms(True, t_ab), attn_ms(False, t_ab)
            flash_ab[f"T{t_ab}"] = {
                "flash_ms": round(fl, 2), "xla_ms": round(xla, 2),
                "speedup": round(xla / fl, 2)}
        os.environ.pop("MMLSPARK_TPU_NO_FLASH", None)

    print(json.dumps({
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "transformer_tokens_per_sec": round(tf_tps, 1),
        "transformer_config": {"batch": B, "seq": T, "dim": 512, "depth": 4,
                               "heads": 8},
        "bilstm_tokens_per_sec": round(bi_tps, 1),
        "bilstm_config": {"batch": B, "seq": T, "embed": 128, "hidden": 256},
        "attention_flash_vs_xla": flash_ab or None,
    }))


if __name__ == "__main__":
    main()
