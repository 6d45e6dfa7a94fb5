"""The fourth language model the benchmark scores: latent attention made
sparse by a learned indexer (DeepSeek Sparse Attention: a query's softmax
runs over the keys an indexer of its own scores highest), a sigmoid gate on
each head's output, gated RMSNorms and group-limited routing, at a tiny size
on the CPU against the plain reference the benchmark keeps
(`benchmarks/references/a.x-k2-688b-a33b-ep16.py`: it imports nothing of the
program), through the normal path; the two new Pallas kernels in the
interpreter against their plain forms. Their compiles at the published widths
are in `test_causal_lm.py`, the one file that describes the topology. The
tiny rows are longer than the selection (64 positions, the top 16), so the
selection drops keys."""

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
from mmlspark_tpu.models import moe, transformer  # noqa: E402
from mmlspark_tpu.models.dnn_model import DNNModel  # noqa: E402
from mmlspark_tpu.models.module import matmul_precision  # noqa: E402

T = 64
TOPK = 16
SEED = 4294970129
NAME = "a.x-k2-688b-a33b-ep16"


def tiny_config(**changes):
    """Hidden 64, 4 latent heads (16 content + 8 rotary lanes, values and
    gates of 16) through ranks 24 and 16, an indexer of 4 heads of 16 that
    keeps 16 keys, gated norms of rank 4, YaRN by 2 over 16 positions, layers
    dense, sparse, sparse with 16 experts in 4 groups (2 kept), top 4, all
    held, vocabulary 64. The keys the configuration's file has."""
    cfg = dict(spec.load_cell("axk2.score").config)
    cfg.update(
        hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, vocab_size=64,
        index_n_heads=4, index_head_dim=16, index_topk=TOPK, gated_norm_rank=4,
        intermediate_size=96, moe_intermediate_size=32, n_routed_experts=16,
        n_routed_experts_published=16, n_group=4, topk_group=2,
        num_experts_per_tok=4, num_hidden_layers=3, max_positions=T,
        rope_parameters=dict(rope_type="yarn", rope_theta=10000, factor=2, beta_fast=32,
                             beta_slow=1, mscale=1, mscale_all_dim=1,
                             original_max_position_embeddings=16))
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
    lengths[2:4] = (40, 9)[:max(0, n - 2)]  # rows shorter than the cap
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
    stage = DNNModel(inputCol="tokens", batchSize=2,
                     fetchDict=fetch or {"logprob": "OUTPUT_0"}).set_model(model)
    fused = PipelineModel([stage]).fuse()
    with matmul_precision("float32"):
        out = fused.transform(DataFrame.from_dict({"tokens": col}, num_partitions=2))
    assert fused.fusion_stats()["fallbacks_total"] == 0
    return out, fused


def layer_weights(weights, i):
    return {p[len(f"layer{i}/"):]: a.astype(jnp.float32) for p, a in weights.items()
            if p.startswith(f"layer{i}/")}


def attention_module(cfg, **changes):
    rp = cfg["rope_parameters"]
    kw = dict(index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
              index_topk=cfg["index_topk"], out_gate=True)
    kw.update(changes)
    return transformer.LatentAttention(
        cfg["num_attention_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        float(rp["rope_theta"]), {k: v for k, v in rp.items() if k != "rope_theta"},
        cfg["rms_norm_eps"], **kw)


def attn_params(w):
    return {k[len("attn/"):]: v for k, v in w.items() if k.startswith("attn/")}


def inputs(seed=1, t=T):
    return jax.random.normal(jax.random.key(seed), (2, t, 64), jnp.float32)


def relative(a, b):
    return float(jnp.abs(a - b).max()) / max(float(jnp.abs(b).max()), 1e-30)


# -- the parts against the reference ----------------------------------------

def test_yarn_frequencies_are_the_configurations_equations(ref):
    cfg = spec.load_cell("axk2.score").config
    rp = cfg["rope_parameters"]
    inv, by = transformer.rope_frequencies(64, float(rp["rope_theta"]), rp)
    ref_inv, ref_by = ref.yarn(cfg)
    assert np.allclose(inv, ref_inv, rtol=1e-12) and by == ref_by == 1.0
    f = 1e6 ** (-np.arange(32) / 32.0)
    assert np.allclose(inv[:16], f[:16]) and np.allclose(inv[24:], f[24:] / 2)
    assert transformer.yarn_softmax_scale(rp) == pytest.approx((0.1 * np.log(2) + 1) ** 2)


def test_the_indexers_scores_and_selection_agree_with_the_reference(ref, builder):
    cfg = tiny_config()
    _, weights = model_with(builder, ref, cfg)
    w = layer_weights(weights, 0)
    x = inputs()
    layer = attention_module(cfg)
    with matmul_precision("float32"):
        c_q = transformer.rms_norm(x @ w["attn/wq_a"], w["attn/q_norm"], 1e-6)
        qi, ki, wi = layer.index_inputs(attn_params(w), x, c_q)
        got = transformer.index_scores_xla(qi, ki, wi, 4)
        bits = transformer.mla_index_xla(qi, ki, wi, 4, TOPK)
    causal = np.tril(np.ones((T, T), bool))
    cos, sin = ref.rotary_tables(cfg, T)
    for b in range(2):
        rq = ref.index_queries(cfg, w, c_q[b], cos, sin, None)
        rk, rw = ref.index_keys(cfg, w, x[b], cos, sin, None, None)
        want = jnp.einsum("qjk,qj->qk", jnp.maximum(jnp.einsum(
            "qjd,kd->qjk", rq, rk, precision="highest"), 0.0), rw, precision="highest")
        assert relative(jnp.where(causal, got[b], 0), jnp.where(causal, want, 0)) < 1e-5
        # the selected sets, as sets: the reference's top_k of its own scores
        _, idx = jax.lax.top_k(jnp.where(causal, want, -jnp.inf), TOPK)
        mine = np.asarray(transformer.unpack_selection(bits[b], T))
        for t in range(T):
            assert set(np.nonzero(mine[t])[0]) == set(int(s) for s in idx[t] if s <= t), t
        assert (mine.sum(axis=1) == np.minimum(np.arange(T) + 1, TOPK)).all()


def test_the_selection_breaks_ties_to_the_lower_position():
    B, t = 1, 40
    qi = jnp.ones((B, t, 2 * 8), jnp.float32)
    ki = jnp.ones((B, t, 8), jnp.float32).at[0, 30].set(2.0)
    wi = jnp.ones((B, t, 2), jnp.float32)
    sel = np.asarray(transformer.unpack_selection(
        transformer.mla_index_xla(qi, ki, wi, 2, 5), t))[0]
    assert list(np.nonzero(sel[39])[0]) == [0, 1, 2, 3, 30]
    assert list(np.nonzero(sel[20])[0]) == [0, 1, 2, 3, 4]
    assert list(np.nonzero(sel[2])[0]) == [0, 1, 2]


def test_the_bits_pack_and_unpack_in_the_kernels_layout():
    sel = jax.random.bernoulli(jax.random.key(0), 0.3, (3, 5000))
    bits = transformer.pack_selection(sel)
    assert bits.shape == (3, 256) and bits.dtype == jnp.int32
    assert (transformer.unpack_selection(bits, 5000) == sel).all()
    one = transformer.pack_selection(jnp.zeros((4600,), bool).at[4096 + 3 * 128 + 5].set(True))
    assert int(jnp.count_nonzero(one)) == 1 and int(one[128 + 5]) == 1 << 3


@pytest.mark.parametrize("fault", [None, "no_out_gate"])
def test_the_sparse_gated_layer_agrees_with_the_reference(fault, ref, builder):
    cfg = tiny_config()
    _, weights = model_with(builder, ref, cfg)
    w = layer_weights(weights, 1)
    x = inputs(2)
    layer = attention_module(cfg, out_gate=fault is None)
    p = attn_params(w)
    if fault:      # the same columns of W_qb less the gates'
        p["wq_b"] = p["wq_b"].reshape(24, 4, -1)[..., :24].reshape(24, -1)
    with matmul_precision("float32"):
        got = layer.apply(p, x)
    want = jnp.stack([ref.attention(cfg, w, x[b], None, fault) for b in range(2)])
    assert relative(got, want) < 2e-5
    if fault is None:   # the gate, the selection and the dense layer all move it
        for other in ("no_out_gate", "dense_attention", "index_no_relu"):
            moved = jnp.stack([ref.attention(cfg, w, x[b], None, other) for b in range(2)])
            assert relative(moved, want) > 1e-2, other


def test_a_row_no_longer_than_the_selection_is_the_dense_layer(ref, builder):
    cfg = tiny_config(index_topk=T)
    _, weights = model_with(builder, ref, cfg)
    p = attn_params(layer_weights(weights, 0))
    x = inputs(3)
    dense = {k: v for k, v in p.items() if not k.startswith("index_")}
    with matmul_precision("float32"):
        sparse_out = attention_module(cfg, index_topk=T).apply(p, x)
        dense_out = attention_module(cfg, index_topk=0).apply(dense, x)
    assert (np.asarray(sparse_out) == np.asarray(dense_out)).all()
    # and the plain forms: a selection of every causal key is the causal core
    q = jax.random.normal(jax.random.key(4), (1, T, 4 * 24))
    kv = jax.random.normal(jax.random.key(5), (1, T, 4 * 32))
    kr = jax.random.normal(jax.random.key(6), (1, T, 8))
    every = transformer.pack_selection(jnp.tril(jnp.ones((T, T), bool))[None])
    assert (np.asarray(transformer.mla_sparse_xla(q, kv, kr, every, 4, 16))
            == np.asarray(transformer.mla_xla(q, kv, kr, 4, 16))).all()


@pytest.mark.parametrize("fault", [None, "no_gated_norm"])
def test_the_gated_norm_agrees_with_the_reference(fault, ref, builder):
    cfg = tiny_config()
    _, weights = model_with(builder, ref, cfg)
    w = layer_weights(weights, 0)
    x = inputs(5)[0] * 3.0
    norm = transformer.GatedRMSNorm(1e-6, 4) if fault is None else transformer.RMSNorm(1e-6)
    p = {k[len("attn_norm/"):]: v for k, v in w.items() if k.startswith("attn_norm/")}
    with matmul_precision("float32"):
        got = norm.apply(p, x)
    want = ref.norm(cfg, w, "attn_norm", x, None, fault)
    assert relative(got, want) < 1e-5
    if fault is None:
        assert relative(ref.norm(cfg, w, "attn_norm", x, None, "no_gated_norm"), want) > 0.5


def test_group_limited_routing_agrees_with_the_reference(ref):
    cfg = tiny_config()
    d, n_exp = 64, 16
    key = jax.random.key(7)
    w = {"moe/router": jax.random.normal(key, (d, n_exp)) * d ** -0.5,
         "moe/router_bias": jax.random.normal(jax.random.fold_in(key, 1), (n_exp,)) * 0.01}
    x = jax.random.normal(jax.random.fold_in(key, 2), (200, d))
    layer = moe.ExpertLayer(n_exp, n_exp, 4, 32, scale=2.5, n_group=4, topk_group=2)
    p = {"router": w["moe/router"], "router_bias": w["moe/router_bias"]}
    idx, g = layer.route(p, x)
    r_idx, r_g, margin = ref.route(cfg, w, x, None, None)
    assert (np.sort(np.asarray(idx), 1) == np.sort(np.asarray(r_idx), 1)).all()
    assert np.allclose(np.sort(np.asarray(g), 1), np.sort(np.asarray(r_g), 1), rtol=1e-6)
    # every chosen expert lies in one of the token's two kept groups
    assert all(len(set(int(e) // 4 for e in row)) <= 2 for row in np.asarray(idx))
    # and somewhere the best expert of all lies in a dropped group
    s = jax.nn.sigmoid(x @ w["moe/router"]) + w["moe/router_bias"]
    best = np.asarray(jnp.argmax(s, axis=1))
    dropped = [i for i in range(200) if best[i] not in np.asarray(idx[i])]
    assert dropped, "no token whose best expert is in a dropped group"
    plain, _ = moe.ExpertLayer(n_exp, n_exp, 4, 32, scale=2.5).route(p, x)
    assert any(set(np.asarray(plain[i])) != set(np.asarray(idx[i])) for i in dropped)
    assert (np.asarray(margin) > 0).all()


def test_one_group_routes_exactly_as_before():
    """`n_group` 1 is the router of the layers before groups: top-k of score +
    bias, the weights normalised over the chosen and scaled, bit for bit."""
    key = jax.random.key(8)
    p = {"router": jax.random.normal(key, (64, 16)) * 0.125,
         "router_bias": jax.random.normal(jax.random.fold_in(key, 1), (16,)) * 0.01}
    x = jax.random.normal(jax.random.fold_in(key, 2), (300, 64))
    idx, g = moe.ExpertLayer(16, 8, 4, 32, scale=2.5, first_expert=8).route(p, x)
    s = jax.nn.sigmoid(jnp.dot(x, p["router"], precision=jax.lax.Precision.HIGHEST))
    _, want = jax.lax.top_k(s + p["router_bias"], 4)
    wg = jnp.take_along_axis(s, want, axis=-1)
    assert (np.asarray(idx) == np.asarray(want)).all()
    assert (np.asarray(g) == np.asarray(wg / jnp.sum(wg, -1, keepdims=True)
                                        * np.float32(2.5))).all()


def test_the_dense_ungated_layer_is_todays_latent_attention():
    """`index_topk` 0 and `out_gate` False: today's parameters and today's
    arithmetic, which this test writes out as it stood (the plain form)."""
    layer = transformer.LatentAttention(4, 24, 16, 16, 8, 16, 10000.0, None, 1e-6)
    params, _ = layer.init(jax.random.key(9), (T, 64))
    assert set(params) == {"wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo"}
    assert params["wq_b"].shape == (24, 4 * 24)
    x = inputs(9)
    got = layer.apply(params, x)
    dt = transformer._mm_dtype()

    def dot(a, name, out=jnp.float32):
        return jnp.dot(a.astype(dt), jnp.asarray(params[name]).astype(dt),
                       preferred_element_type=out)

    B = 2
    c_q = transformer.rms_norm(dot(x, "wq_a"), params["q_norm"], 1e-6) * np.float32(24 ** -0.5)
    q = dot(c_q, "wq_b").reshape(B, T, 4, 24)
    q_rope = transformer.rotary(q[..., 16:], 10000.0, None).astype(dt)
    q = jnp.concatenate([q[..., :16].astype(dt), q_rope], axis=-1).reshape(B, T, -1)
    ckv = dot(x, "wkv_a")
    kr = transformer.rotary(ckv[..., None, 16:], 10000.0, None).astype(dt).reshape(B, T, -1)
    kv = dot(transformer.rms_norm(ckv[..., :16], params["kv_norm"], 1e-6), "wkv_b", dt)
    want = dot(transformer.mla_xla(q, kv, kr, 4, 16), "wo")
    assert (np.asarray(got) == np.asarray(want)).all()


def test_the_shares_of_group_limited_routing_add_up_to_the_uncut_layer(ref):
    """Guide, section 4: 32 experts in 8 groups (4 kept), top 4; the parts
    the 16 shares of 2 experts give, with the shared expert counted once,
    are the whole layer the uncut reference gives, and their loads are every
    visit."""
    cfg = tiny_config(n_routed_experts=32, n_routed_experts_published=32, n_group=8,
                      topk_group=4)
    key = jax.random.key(3)
    d, eff, n_exp, top_k = 64, 32, 32, 4
    w = {n: jax.random.normal(jax.random.fold_in(key, i), s, jnp.float32) * sc
         for i, (n, s, sc) in enumerate([
             ("moe/router", (d, n_exp), d ** -0.5), ("moe/router_bias", (n_exp,), 0.01),
             ("moe/w1", (n_exp, d, 2 * eff), d ** -0.5),
             ("moe/w2", (n_exp, eff, d), eff ** -0.5),
             ("shared/w_gate_up", (d, 2 * eff), d ** -0.5),
             ("shared/w_down", (eff, d), eff ** -0.5)])}
    x = jax.random.normal(jax.random.fold_in(key, 99), (3, T, d), jnp.float32)
    flat = x.reshape(-1, d)
    whole = ref.experts(cfg, w, flat, *ref.route(cfg, w, flat, None, None)[:2],
                        None).reshape(x.shape)
    with matmul_precision("float32"):
        total = transformer.SwiGLU(eff).apply(
            {"w_gate_up": w["shared/w_gate_up"], "w_down": w["shared/w_down"]}, x)
        loads = 0.0
        for first in range(0, n_exp, 2):
            layer = moe.ExpertLayer(n_exp, 2, top_k, eff, scale=2.5, first_expert=first,
                                    n_group=8, topk_group=4)
            share, load = layer.apply_with_load(
                {"router": w["moe/router"], "router_bias": w["moe/router_bias"],
                 "w1": w["moe/w1"][first:first + 2], "w2": w["moe/w2"][first:first + 2]}, x)
            total, loads = total + share, loads + float(load.sum())
    assert loads == 3 * T * top_k
    assert relative(total, whole) < 1e-4


def test_the_normal_path_agrees_with_the_plain_reference(ref, builder):
    cfg = tiny_config()
    model, _ = model_with(builder, ref, cfg)
    ids, lengths = rows()
    out, _ = through_the_pipeline(model, ids, {"logprob": "OUTPUT_0",
                                               "expert_load": "expert_load"})
    got = np.stack(list(out.column("logprob")))
    want = ref.score(cfg, SEED, ids)
    gaps = ref.row_gaps(got, want["logprob"], lengths)
    assert np.nanmax(gaps) < 1e-4
    load = np.stack(list(out.column("expert_load")))
    assert load.shape == (6, 2, 16) and (load.sum(axis=(1, 2)) == 2 * T * 4).all()
    # a fault of each kind moves the reference's answer past that
    for fault in ref.FAULTS:
        if fault == "topk_1024":
            continue                  # beyond a tiny row
        moved = ref.score(cfg, SEED, ids[:2], fault=fault)["logprob"]
        assert np.nanmax(ref.row_gaps(moved, want["logprob"][:2], lengths[:2])) > 1e-3, fault


# -- the kernels, in the interpreter ----------------------------------------

@pytest.mark.parametrize("case", ["scores", "ties", "short"])
def test_the_index_kernel_agrees_with_the_plain_form(case):
    B, t, H, D = 1, 512, 2, 128
    k = jax.random.split(jax.random.key(11), 3)
    qi = jax.random.normal(k[0], (B, t, H * D)).astype(jnp.bfloat16)
    ki = jax.random.normal(k[1], (B, t, D)).astype(jnp.bfloat16)
    wi = jax.random.normal(k[2], (B, t, H)) * 0.1
    if case == "ties":          # every score 0: the lower positions win
        wi = jnp.zeros_like(wi)
    topk = 600 if case == "short" else 64     # "short": no query has more keys
    got = transformer.mla_index_pallas(qi, ki, wi, H, topk, interpret=True)
    want = transformer.mla_index_xla(qi, ki, wi, H, topk)
    assert got.shape == (B, t, 128) and (np.asarray(got) == np.asarray(want)).all()


def test_the_sparse_core_kernel_agrees_with_the_plain_form():
    B, t, H = 1, 1024, 4
    k = jax.random.split(jax.random.key(12), 6)
    q = jax.random.normal(k[0], (B, t, H * 256)).astype(jnp.bfloat16)
    kv = jax.random.normal(k[1], (B, t, H * 256)).astype(jnp.bfloat16)
    kr = jax.random.normal(k[2], (B, t, 128)).astype(jnp.bfloat16)
    bits = transformer.mla_index_xla(
        jax.random.normal(k[3], (B, t, 128)), jax.random.normal(k[4], (B, t, 128)),
        jax.random.normal(k[5], (B, t, 1)), 1, 100)
    got = transformer.mla_sparse_pallas(q, kv, kr, bits, H, 128, interpret=True)
    want = transformer.mla_sparse_xla(q, kv, kr, bits, H, 128)
    assert relative(got.astype(jnp.float32), want.astype(jnp.float32)) < 1e-2


def test_the_sparse_core_kernels_gradient_is_the_plain_forms(interpreted):
    B, t, H = 1, 256, 2
    k = jax.random.split(jax.random.key(13), 4)
    q, kv = (jax.random.normal(k[i], (B, t, H * 256)) for i in range(2))
    kr = jax.random.normal(k[2], (B, t, 128))
    bits = transformer.pack_selection(jax.random.bernoulli(k[3], 0.3, (B, t, t))
                                      & jnp.tril(jnp.ones((t, t), bool))
                                      | jnp.eye(t, dtype=bool))

    def loss(fn):
        return lambda q, kv, kr: jnp.sum(fn(q, kv, kr, bits, H, 128) ** 2)

    got = jax.grad(loss(transformer._mla_sparse_pair), (0, 1, 2))(q, kv, kr)
    want = jax.grad(loss(transformer.mla_sparse_xla), (0, 1, 2))(q, kv, kr)
    for a, b in zip(got, want):       # the forward passes differ in rounding alone
        assert relative(a, b) < 1e-4


def test_the_layer_takes_its_kernels_where_they_apply(monkeypatch, interpreted):
    """On a TPU at bfloat16 the layer's indexer and core are the kernels;
    here they run in the interpreter in their place, and the layer's output
    is the plain forms'."""
    layer = transformer.LatentAttention(2, 64, 64, 128, 64, 128, 10000.0, None, 1e-6,
                                        index_heads=2, index_dim=128, index_topk=100,
                                        out_gate=True)
    params, _ = layer.init(jax.random.key(14), (512, 64))
    x = jax.random.normal(jax.random.key(15), (1, 512, 64))
    with matmul_precision("bfloat16"):
        want = layer.apply(params, x)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        calls = interpreted(layer.apply, params, x)
        got = layer.apply(params, x)
    assert calls == ["mla_index", "attn_mla_sparse"]
    assert relative(got, want) < 2e-2
