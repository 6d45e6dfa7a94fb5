"""The second language model the benchmark scores: latent attention (MLA)
with YaRN positions, a hyper-connected residual path of four streams
(`models/residual.py`) and an expert layer that holds every expert, at a tiny
size on the CPU against the plain reference the benchmark keeps
(`benchmarks/references/xing4.0-29b-a4b-ep1.py`: it imports nothing of the
program), through the normal path; the new Pallas kernels in the interpreter
against their plain forms, gradients too. Their compiles at the published
widths are in `test_causal_lm.py`, the one file that describes the topology."""

import dataclasses
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.harness import spec  # noqa: E402
from mmlspark_tpu.core.dataframe import DataFrame  # noqa: E402
from mmlspark_tpu.core.pipeline import PipelineModel  # noqa: E402
from mmlspark_tpu.models import moe, residual, transformer  # noqa: E402
from mmlspark_tpu.models.dnn_model import DNNModel  # noqa: E402
from mmlspark_tpu.models.module import matmul_precision  # noqa: E402

T = 32
SEED = 4294970129
NAME = "xing4.0-29b-a4b-ep1"


def tiny_config(**changes):
    """Hidden 64, 4 latent heads (16 content + 8 rotary lanes, values of 16)
    through ranks 24 and 16, YaRN by 4 over 8 positions, 4 streams with 20
    Sinkhorn steps, layers dense, sparse, sparse with 16 experts top-4 all
    held, vocabulary 64. The same keys the configuration's file has."""
    cfg = dict(
        hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, vocab_size=64,
        intermediate_size=96, moe_intermediate_size=32, n_routed_experts=16,
        num_experts_per_tok=4, n_shared_experts=1, num_hidden_layers=3,
        first_k_dense_replace=1, moe_layer_freq=1, rms_norm_eps=1e-6,
        rope_theta=10000, rope_scaling=dict(
            beta_fast=32, beta_slow=1, factor=4, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=8, type="yarn"),
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
        mhc_h_res_clamp_max=30, scoring_func="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2, pad_id=0, max_positions=T)
    cfg.update(changes)
    return cfg


@pytest.fixture(scope="module")
def ref():
    return spec.bench_module("references", NAME)


@pytest.fixture(scope="module")
def builder():
    return spec.bench_module("builders", NAME)


def rows(n=6, seed=0):
    ids = np.random.default_rng(seed).integers(1, 64, (n, T), dtype=np.int32)
    lengths = np.full(n, T)
    lengths[2:4] = (20, 5)[:max(0, n - 2)]  # rows shorter than the cap
    ids[np.arange(T)[None, :] >= lengths[:, None]] = 0
    return ids, lengths


def model_with(builder, ref, cfg):
    weights = ref.make_weights(cfg, SEED)
    model = builder.model_of(cfg, T)
    return dataclasses.replace(model, params=builder._nest(weights)), weights


def through_the_pipeline(model, ids, fetch=None):
    col = np.empty(len(ids), dtype=object)
    for i in range(len(ids)):
        col[i] = ids[i]
    stage = DNNModel(inputCol="tokens", batchSize=4,
                     fetchDict=fetch or {"logprob": "OUTPUT_0"}).set_model(model)
    fused = PipelineModel([stage]).fuse()
    with matmul_precision("float32"):
        out = fused.transform(DataFrame.from_dict({"tokens": col}, num_partitions=2))
    assert fused.fusion_stats()["fallbacks_total"] == 0
    return out, fused


def layer_weights(weights, i):
    return {p[len(f"layer{i}/"):]: a.astype(jnp.float32) for p, a in weights.items()
            if p.startswith(f"layer{i}/")}


# -- the parts against the reference ----------------------------------------

def test_yarn_frequencies_are_the_configurations_equations(ref):
    cfg = spec.load_cell("xing4.score").config
    inv, by = transformer.rope_frequencies(64, 10000.0, cfg["rope_scaling"])
    assert np.allclose(inv, ref.yarn_frequencies(cfg), rtol=1e-12) and by == 1.0
    f = 10000.0 ** (-np.arange(32) / 32.0)
    assert np.allclose(inv[:11], f[:11]) and np.allclose(inv[23:], f[23:] / 64)
    assert f[16] / 64 < inv[16] < f[16]                       # on the ramp
    assert transformer.yarn_softmax_scale(cfg["rope_scaling"]) == pytest.approx(1.41589 ** 2,
                                                                                rel=1e-5)
    plain, one = transformer.rope_frequencies(64, 10000.0, None)
    assert np.allclose(plain, f) and one == 1.0


@pytest.mark.parametrize("scaling", ["yarn", "plain"])
def test_latent_attention_agrees_with_the_references(scaling, ref, builder):
    cfg = tiny_config()
    _, weights = model_with(builder, ref, cfg)
    w = layer_weights(weights, 0)
    x = jax.random.normal(jax.random.key(1), (2, T, 64), jnp.float32)
    layer = transformer.LatentAttention(
        4, 24, 16, 16, 8, 16, 10000.0,
        cfg["rope_scaling"] if scaling == "yarn" else None, 1e-6)
    params = {k[len("attn/"):]: v for k, v in w.items() if k.startswith("attn/")}
    with matmul_precision("float32"):
        got = layer.apply(params, x)
    fault = None if scaling == "yarn" else "no_yarn"
    want = jnp.stack([ref.attention(cfg, w, r, None, fault) for r in x])
    if scaling == "plain":       # without YaRN the softmax scale has no m^2 either
        want_m = jnp.stack([ref.attention(cfg, w, r, None, "no_mscale") for r in x])
        assert float(jnp.abs(want - want_m).max()) > 1e-3
        with matmul_precision("float32"):
            yarn = transformer.LatentAttention(
                4, 24, 16, 16, 8, 16, 10000.0, cfg["rope_scaling"], 1e-6).apply(params, x)
        assert float(jnp.abs(yarn - got).max()) > 1e-3       # YaRN moves the result
        return
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(jnp.abs(want).max())


def test_a_hyper_connected_sublayer_agrees_with_the_references(ref, builder):
    cfg = tiny_config()
    _, weights = model_with(builder, ref, cfg)
    w = layer_weights(weights, 0)
    n, d = 4, 64
    x = jax.random.normal(jax.random.key(2), (2, T, n * d), jnp.float32)
    hc = residual.HyperConnection(n, 20, 1e-6, (-30, 30), 1e-6)
    params = {k[len("mlp_hc/"):]: v for k, v in w.items() if k.startswith("mlp_hc/")}

    def f(v):
        return jnp.tanh(v) * 3.0

    with matmul_precision("float32"):
        x_in, coeffs = hc.pre(params, x)
        got = hc.post(x, f(x_in), coeffs)
    h_res = coeffs[:, n:].reshape(-1, n, n)
    # doubly stochastic after 20 steps: the columns, divided last, to rounding;
    # the rows to 1e-4 on the average token and 1e-3 on the worst of these 64
    assert float(jnp.abs(h_res.sum(axis=1) - 1).max()) < 1e-5
    assert float(jnp.abs(h_res.sum(axis=2) - 1).mean()) < 1e-4
    assert float(jnp.abs(h_res.sum(axis=2) - 1).max()) < 1e-3
    assert float(jnp.abs(h_res - jnp.eye(n)).max()) > 0.05          # and no identity
    for r in range(2):
        xr = x[r].reshape(T, n, d)
        h_pre, h_post, res = ref.hyper_maps(cfg, w, "mlp_hc", xr, None, None)
        assert float(jnp.abs(x_in[r] - ref.mix_in(h_pre, xr)).max()) < 1e-4
        want = ref.mix_out(h_post, res, xr, f(ref.mix_in(h_pre, xr))).reshape(T, n * d)
        assert float(jnp.abs(got[r] - want).max()) < 1e-4 * float(jnp.abs(want).max())
    one = residual.sinkhorn(2.0 * jax.random.normal(jax.random.key(9), (n, n, 3)), 1, 1e-6)
    assert float(jnp.abs(one.sum(axis=1) - 1).max()) > 1e-2         # one step is not enough


def test_the_expert_layer_with_every_expert_held_is_the_uncut_layer(ref, builder):
    cfg = tiny_config()
    _, weights = model_with(builder, ref, cfg)
    w = layer_weights(weights, 1)
    x = jax.random.normal(jax.random.key(3), (3, T, 64), jnp.float32)
    flat = x.reshape(-1, 64)
    idx, g, _ = ref.route(cfg, w, flat, None, None)
    whole = ref.experts(cfg, w, flat, idx, g, None, None).reshape(x.shape)
    layer = moe.ExpertLayer(16, 16, 4, 32, scale=2.0)
    with matmul_precision("float32"):
        shared = transformer.SwiGLU(32).apply(
            {"w_gate_up": w["shared/w_gate_up"], "w_down": w["shared/w_down"]}, x)
        got, load = layer.apply_with_load(
            {k[len("moe/"):]: v for k, v in w.items() if k.startswith("moe/")}, x,
            add_to=shared)
    assert load.shape == (3, 16) and float(load.sum()) == 3 * T * 4   # every visit is here
    assert float(jnp.abs(got - whole).max()) < 1e-4 * float(jnp.abs(whole).max())


# -- the whole model through the normal path ---------------------------------

def test_the_normal_path_agrees_with_the_plain_reference(ref, builder):
    cfg = tiny_config()
    model, weights = model_with(builder, ref, cfg)
    ids, lengths = rows()
    out, fused = through_the_pipeline(
        model, ids, {"logprob": "OUTPUT_0", "expert_load": "expert_load"})
    got = np.stack(list(out.column("logprob")))
    assert got.shape == (6, T) and got.dtype == np.float32
    scored = ref.score(cfg, SEED, ids, weights=weights)
    real = np.arange(T)[None, :] < lengths[:, None]
    stable = real & (scored["margin"] > 1e-4)
    assert stable.sum() > 0.9 * real.sum()
    # float32 on both sides: what is left is the order of the sums
    assert np.abs(got - scored["logprob"])[stable].max() < 5e-4
    load = np.stack(list(out.column("expert_load")))
    assert load.shape == (6, 2, 16) and (load.sum(axis=2) == T * 4).all()
    assert fused.fusion_stats()["segments"][0]["fetched"] == ["logprob", "expert_load"]


@pytest.mark.parametrize("fault", ["sinkhorn_1", "static_maps", "h_post_no_2",
                                   "streams_first", "streams_mean", "no_yarn",
                                   "no_mscale", "no_rope_key", "no_topk_norm",
                                   "no_shared"])
def test_each_planted_fault_moves_the_references_answer(fault, ref):
    cfg = tiny_config()
    ids, _ = rows(2)
    weights = ref.make_weights(cfg, SEED)
    good = ref.score(cfg, SEED, ids, weights=weights)["logprob"]
    bad = ref.score(cfg, SEED, ids, weights=weights, fault=fault)["logprob"]
    if fault == "streams_mean":
        # the mean is the sum over 4, and the final RMSNorm divides that out:
        # no comparison of outputs can see it, so it is not among FAULTS
        assert fault not in ref.FAULTS and np.abs(good - bad).max() < 1e-5
        return
    assert fault in ref.FAULTS and np.abs(good - bad).max() > 1e-2, fault


def test_gradients_of_the_mean_log_probability_agree_with_the_references(ref, builder):
    cfg = tiny_config()
    model, weights = model_with(builder, ref, cfg)
    ids = rows(2, seed=1)[0]
    f32 = {p: a.astype(jnp.float32) for p, a in weights.items()}

    def mine(w):
        with matmul_precision("float32"):
            return jnp.mean(model.module.apply(builder._nest(w), jnp.asarray(ids)))

    def plain(w):
        total = 0.0
        for row in ids:
            x = jnp.repeat(w["embed/table"][row][:, None, :], 4, axis=1)
            for i, sparse in enumerate(ref.layer_plan(cfg)):
                lw = {p[len(f"layer{i}/"):]: a for p, a in w.items()
                      if p.startswith(f"layer{i}/")}
                x = ref.layer(cfg, lw, x, sparse, None, None)[0]
            total = total + jnp.sum(ref.log_probs(cfg, w, x, jnp.asarray(row), None))
        return total / ids.size

    got, want = jax.grad(mine)(f32), jax.grad(plain)(f32)
    for path in want:
        scale = float(jnp.abs(want[path]).max())
        if path.endswith("router_bias"):              # it chooses, never weighs
            assert scale == 0.0 and float(jnp.abs(got[path]).max()) == 0.0
            continue
        assert scale > 0.0, path
        assert float(jnp.abs(got[path] - want[path]).max()) < 2e-3 * scale, path


def test_log_probabilities_in_blocks_of_positions_are_the_whole_rows(ref, builder,
                                                                      monkeypatch):
    cfg = tiny_config(num_hidden_layers=1)
    model, _ = model_with(builder, ref, cfg)
    ids = jnp.asarray(rows()[0])

    def run():
        with matmul_precision("float32"):
            return (model.module.apply(model.params, ids), jax.jit(
                lambda p, i: model.module.apply(p, i)).lower(model.params, ids).as_text())

    whole, lowered = run()
    assert "32x64xf32" in lowered                    # a whole row's logits at once
    monkeypatch.setattr(transformer, "LOGITS_BLOCK", 8 * 64)    # 8 positions at a time
    parts, lowered = run()
    assert "8x64xf32" in lowered
    assert np.allclose(np.asarray(whole), np.asarray(parts), atol=1e-6)


@pytest.mark.parametrize("shape,most,positionwise,calls_of", [
    ((4, 8, 3), 16, False, (2, 8, 3)),       # rows in groups, as before
    ((2, 32, 3), 8, False, (1, 32, 3)),      # a row longer than most_tokens goes alone
    ((2, 32, 3), 8, True, (1, 8, 3)),        # or, position-wise, in pieces of 8
    ((1, 24, 3), 16, True, (1, 12, 3)),      # equal pieces: 2 of 12, not 16 and 8
    ((2, 8, 3), 64, True, (2, 8, 3)),        # everything at once
])
def test_by_rows_groups_rows_and_cuts_a_long_row_only_position_wise(
        shape, most, positionwise, calls_of):
    x = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
    seen = []

    def fn(v):
        seen.append(v.shape)
        return v * 2.0 + 1.0

    got = transformer._by_rows(fn, x, most, positionwise)
    assert seen == [calls_of]
    assert np.array_equal(np.asarray(got), np.asarray(x) * 2.0 + 1.0)


# -- the kernels, in the interpreter ----------------------------------------

def _mla_operands(seed, B=2, H=4, t=512, nope=128, v=128):
    """The kernel's layout (above `transformer.mla_xla`) with 64 rotary
    lanes: at 128 content lanes q ``[nope | rope | 0]``, kv ``[k | v]``, kr
    ``[k_r | 0]``; at 192 q ``[nope | rope]``, kv ``[k | 0 (64) | v]``, kr
    ``[0 (64) | k_r]``."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, t, H * 256)) * 0.2, jnp.bfloat16)
    keys = 256 if nope % 128 else nope
    kv = jnp.asarray(rng.normal(size=(B, t, H, keys + v)), jnp.bfloat16)
    kv = kv.at[..., nope:keys].set(0).reshape(B, t, -1)
    kr = jnp.asarray(rng.normal(size=(B, t, 128)), jnp.bfloat16)
    if nope % 128:
        return q, kv, kr.at[..., :nope % 128].set(0)
    q = q.reshape(B, t, H, 256).at[..., nope + 64:].set(0).reshape(B, t, -1)
    return q, kv, kr.at[..., 64:].set(0)


@pytest.mark.parametrize("t,nope,v", [(512, 128, 128), (1024, 128, 128), (512, 192, 256)],
                         ids=["512", "1024", "512-192-256"])
def test_the_latent_kernel_agrees_with_the_plain_form(t, nope, v):
    q, kv, kr = _mla_operands(t, t=t, nope=nope, v=v)
    keys = kv.shape[2] // 4 - v                  # a head's key lanes in kv
    got = transformer.mla_pallas(q, kv, kr, 4, keys, interpret=True)
    want = transformer.mla_xla(*(a.astype(jnp.float32) for a in (q, kv, kr)), 4, keys)
    assert got.shape == (2, t, 4 * v) and got.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 0.02
    # the plain form is the masked softmax of both products: the content
    # lanes against the content keys, the 64 rotary lanes against k_r
    B, H = 2, 4
    q4 = q.astype(jnp.float32).reshape(B, t, H, 256)
    kv4 = kv.astype(jnp.float32).reshape(B, t, H, -1)
    k_r = kr.astype(jnp.float32)[..., nope % 128:nope % 128 + 64]
    s = jnp.einsum("bqhd,bkhd->bhqk", q4[..., :nope], kv4[..., :nope]) \
        + jnp.einsum("bqhd,bkd->bhqk", q4[..., nope:nope + 64], k_r)
    p = jax.nn.softmax(jnp.where(np.tril(np.ones((t, t), bool)), s, -jnp.inf), axis=-1)
    direct = jnp.einsum("bhqk,bkhd->bqhd", p, kv4[..., keys:]).reshape(B, t, -1)
    assert float(jnp.abs(direct - want).max()) < 1e-4


def test_the_stream_kernels_agree_with_their_plain_forms():
    rng = np.random.default_rng(4)
    n, d, N = 4, 256, 384
    x = jnp.asarray(rng.normal(size=(N, n * d)), jnp.float32)
    phi = jnp.asarray(rng.normal(size=(n * d, 24)) * (n * d) ** -0.5, jnp.bfloat16)
    pre = jnp.asarray([1.0, 0.3, -0.2, 0.5, 0.1], jnp.float32)
    want = residual.mhc_pre_plain(x, phi, pre, n, 1e-6)
    got = residual.mhc_pre_pallas(x, phi, pre, n, 1e-6, interpret=True)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) < 1e-5 * max(1.0, float(jnp.abs(b).max()))
    y = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    h = jnp.asarray(rng.uniform(size=(N, n + n * n)), jnp.float32)
    assert float(jnp.abs(residual.mhc_post_pallas(x, y, h, n, interpret=True)
                         - residual.mhc_post_plain(x, y, h, n)).max()) < 1e-5


def test_the_kernels_gradients_are_their_plain_forms(interpreted):
    q, kv, kr = (a.astype(jnp.float32) for a in _mla_operands(7, B=1, H=2))
    tilt = jnp.cos(jnp.arange(2 * 128, dtype=jnp.float32))

    def loss(attend):
        return lambda q, kv, kr: jnp.sum(attend(q, kv, kr, 2, 128).astype(jnp.float32) * tilt)

    whole = jnp.full((1,), q.shape[1], jnp.int32)         # every row to its end
    got = jax.grad(loss(lambda q, kv, kr, *c: transformer._mla_pair(q, kv, kr, whole, *c)),
                   (0, 1, 2))(q, kv, kr)
    want = jax.grad(loss(transformer.mla_xla), (0, 1, 2))(q, kv, kr)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) <= 1e-5 * float(jnp.abs(b).max())

    rng = np.random.default_rng(5)
    n, d, N = 4, 128, 128
    x = jnp.asarray(rng.normal(size=(N, n * d)), jnp.float32)
    phi = jnp.asarray(rng.normal(size=(n * d, 24)) * (n * d) ** -0.5, jnp.float32)
    pre = jnp.asarray([1.0, 0.3, -0.2, 0.5, 0.1], jnp.float32)
    y = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    h = jnp.asarray(rng.uniform(size=(N, n + n * n)), jnp.float32)
    k_pre, k_post = residual._pre_pair, residual._post_pair

    def both(pre_fn, post_fn):
        def f(x, phi, pre, y, h):
            m, ssq, x_in = pre_fn(x, phi, pre, n, 1e-6)
            return jnp.sum(post_fn(x, y + x_in, h, n)) + jnp.sum(m * m) + jnp.sum(jnp.sqrt(ssq))
        return f

    with matmul_precision("float32"):
        got = jax.grad(both(k_pre, k_post), (0, 1, 2, 3, 4))(x, phi, pre, y, h)
        want = jax.grad(both(residual.mhc_pre_plain, residual.mhc_post_plain),
                        (0, 1, 2, 3, 4))(x, phi, pre, y, h)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) <= 1e-5 * float(jnp.abs(b).max())


def test_the_modules_take_their_kernels_where_they_apply(monkeypatch, interpreted):
    """On a TPU the two modules pad and lay out their operands for the
    kernels; here the kernels run in the interpreter in their place, and the
    modules' results are those of their plain paths."""
    x = jax.random.normal(jax.random.key(4), (1, 128, 256), jnp.float32)
    layer = transformer.LatentAttention(4, 48, 32, 128, 64, 128, 10000.0, dict(
        type="yarn", factor=4, original_max_position_embeddings=32, beta_fast=32,
        beta_slow=1, mscale=1, mscale_all_dim=1), 1e-6)
    params, _ = layer.init(jax.random.key(5), (128, 256))
    hc = residual.HyperConnection(4, 20, 1e-6, (-30, 30), 1e-6)
    hc_params, _ = hc.init(jax.random.key(6), (128, 128))
    streams = jax.random.normal(jax.random.key(7), (1, 128, 4 * 128), jnp.float32)

    def both():
        with matmul_precision("float32"):
            x_in, coeffs = hc.pre(hc_params, streams)
            return layer.apply(params, x), hc.post(streams, jnp.sin(x_in), coeffs)

    def run(through=lambda fn: fn()):          # `interpreted`: the kernels it traces
        return through(both)

    plain = run()
    monkeypatch.setattr(transformer, "_mla_pallas_applies", lambda *a: True)
    monkeypatch.setattr(residual, "_kernels_apply", lambda *a: True)
    calls = run(interpreted)
    kernels = run()
    assert calls == ["mhc_pre", "attn_mla", "mhc_post"]
    for a, b in zip(kernels, plain):
        assert float(jnp.abs(a - b).max()) < 1e-4 * float(jnp.abs(b).max())
