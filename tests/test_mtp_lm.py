"""The fifth language model the benchmark scores: latent attention whose
content heads are no whole tile (the published 192 lanes; 48 here), an
expert layer that holds every expert, and the multi-token-prediction (MTP)
module run on the scoring path, at a tiny size on the CPU against the plain
reference the benchmark keeps (`benchmarks/references/glm-4.7-flash-mtp-ep1.py`:
it imports nothing of the program), through the normal path; the latent
kernel at 192/256 lanes in the interpreter. Its compile at the published
widths is in `test_causal_lm.py`, the one file that describes the topology."""

import dataclasses
import os
import re
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

T = 32
SEED = 4294970129
NAME = "glm-4.7-flash-mtp-ep1"
FETCH = {"logprob": "OUTPUT_0", "mtp_logprob": "mtp_logprob", "expert_load": "expert_load"}


def tiny_config(**changes):
    """The configuration's file at hidden 64: 4 latent heads of 48 content
    and 16 rotary lanes, values of 64, ranks 24 and 16; layers dense, sparse,
    sparse with 8 experts top-4 all held; vocabulary 64; the MTP module."""
    cfg = spec.load_json(os.path.join(spec.ROOT, "benchmarks", "configs", NAME + ".json"))
    cfg.update(hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
               qk_nope_head_dim=48, qk_rope_head_dim=16, v_head_dim=64, vocab_size=64,
               intermediate_size=96, moe_intermediate_size=32, n_routed_experts=8,
               num_hidden_layers=3, max_positions=T)
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


def through_the_pipeline(model, ids, fetch):
    col = np.empty(len(ids), dtype=object)
    for i in range(len(ids)):
        col[i] = ids[i]
    stage = DNNModel(inputCol="tokens", batchSize=4, fetchDict=fetch).set_model(model)
    fused = PipelineModel([stage]).fuse()
    with matmul_precision("float32"):
        out = fused.transform(DataFrame.from_dict({"tokens": col}, num_partitions=2))
    assert fused.fusion_stats()["fallbacks_total"] == 0
    return out, fused


def layer_weights(weights, prefix):
    return {p[len(prefix):]: a.astype(jnp.float32) for p, a in weights.items()
            if p.startswith(prefix)}


# -- the whole model through the normal path ---------------------------------

def test_both_outputs_of_the_normal_path_agree_with_the_plain_reference(ref, builder):
    cfg = tiny_config()
    model, weights = model_with(builder, ref, cfg)
    ids, lengths = rows()
    out, fused = through_the_pipeline(model, ids, FETCH)
    scored = ref.score(cfg, SEED, ids, weights=weights)
    real = np.arange(T)[None, :] < lengths[:, None]
    for key, margin in (("logprob", "margin"), ("mtp_logprob", "mtp_margin")):
        got = np.stack(list(out.column(key)))
        assert got.shape == (6, T) and got.dtype == np.float32
        stable = real & (scored[margin] > 1e-4)
        assert stable.sum() > 0.9 * real.sum(), key
        # float32 on both sides: what is left is the order of the sums
        assert np.abs(got - scored[key])[stable].max() < 5e-4, key
    # the MTP predicts another token than the head: the two outputs differ
    assert np.abs(np.stack(list(out.column("logprob")))
                  - np.stack(list(out.column("mtp_logprob"))))[real].mean() > 0.1
    load = np.stack(list(out.column("expert_load")))
    # the two sparse layers, then the MTP's: every visit of every position
    assert load.shape == (6, 3, 8) and (load.sum(axis=2) == T * 4).all()
    assert fused.fusion_stats()["segments"][0]["fetched"] == list(FETCH)


def test_the_mtp_reads_the_last_layers_output_and_the_following_ids(ref, builder):
    """A change of one id moves the main output from the position before it
    on, the MTP's from two positions before it on: the module embeds the id
    after its position and predicts the one after that."""
    cfg = tiny_config()
    model, _ = model_with(builder, ref, cfg)
    ids = rows(2)[0]
    with matmul_precision("float32"):
        before = model.apply_taps(jnp.asarray(ids), [None, "mtp_logprob"])
        moved = ids.copy()
        moved[:, 20] = (moved[:, 20] % 63) + 1
        after = model.apply_taps(jnp.asarray(moved), [None, "mtp_logprob"])
    main = np.abs(np.asarray(before[None]) - np.asarray(after[None])).max(axis=0)
    mtp = np.abs(np.asarray(before["mtp_logprob"])
                 - np.asarray(after["mtp_logprob"])).max(axis=0)
    assert main[:19].max() == 0.0 and main[19] > 0.0
    assert mtp[:18].max() == 0.0 and mtp[18] > 0.0


def test_each_expert_layer_held_in_shares_adds_up_to_the_uncut_reference(ref, builder):
    """Guide, section 4: each sparse layer, the MTP's among them, held in two
    shares of 4 experts (what the deployment holds whole on one chip); the
    shares' parts with the shared expert counted once are the whole layer the
    uncut reference gives, and their loads are every visit."""
    cfg = tiny_config()
    _, weights = model_with(builder, ref, cfg)
    x = jax.random.normal(jax.random.key(3), (3, T, 64), jnp.float32)
    flat = x.reshape(-1, 64)
    for prefix in ("layer1/", "layer2/", "mtp/layer/"):
        w = layer_weights(weights, prefix)
        idx, g, _ = ref.route(cfg, w, flat, None, None)
        whole = ref.experts(cfg, w, flat, idx, g, None, None).reshape(x.shape)
        with matmul_precision("float32"):
            total = transformer.SwiGLU(32).apply(
                {"w_gate_up": w["shared/w_gate_up"], "w_down": w["shared/w_down"]}, x)
            visits = 0.0
            for first in (0, 4):
                layer = moe.ExpertLayer(8, 4, 4, 32, scale=1.8, first_expert=first)
                share, load = layer.apply_with_load(
                    {"router": w["moe/router"], "router_bias": w["moe/router_bias"],
                     "w1": w["moe/w1"][first:first + 4], "w2": w["moe/w2"][first:first + 4]},
                    x)
                total, visits = total + share, visits + float(load.sum())
        assert visits == 3 * T * 4, prefix
        assert float(jnp.abs(total - whole).max()) < 1e-4 * float(jnp.abs(whole).max()), prefix


@pytest.mark.parametrize("fault", ["mtp_embeds_current", "mtp_final_normed", "mtp_no_hnorm",
                                   "mtp_targets_next", "routed_scale_1", "no_shared",
                                   "content_128"])
def test_each_planted_fault_moves_the_references_answer(fault, ref):
    cfg = tiny_config()
    ids, _ = rows(2)
    weights = ref.make_weights(cfg, SEED)
    good = ref.score(cfg, SEED, ids, weights=weights)
    bad = ref.score(cfg, SEED, ids, weights=weights, fault=fault)
    assert fault in ref.FAULTS
    assert np.abs(good["mtp_logprob"] - bad["mtp_logprob"]).max() > 1e-2, fault
    main = np.abs(good["logprob"] - bad["logprob"]).max()
    if fault.startswith("mtp_"):         # the MTP's own faults leave the main output
        assert main == 0.0, fault
    else:
        assert main > 1e-2, fault


# -- what a fetch without the module lowers to --------------------------------

def _stripped(text):
    return re.sub(r"#loc\d*[^\n]*\n", "", re.sub(r"\s*loc\([^)]*\)", "", text))


@pytest.mark.parametrize("taps", [[None], [None, "expert_load"]])
def test_a_fetch_without_mtp_logprob_lowers_as_the_model_without_the_module(
        taps, ref, builder):
    cfg = tiny_config()
    with_mtp, _ = model_with(builder, ref, cfg)
    without = builder.model_of(dict(cfg, num_nextn_predict_layers=0), T)
    params = {k: v for k, v in with_mtp.params.items() if k != "mtp"}
    ids = jax.ShapeDtypeStruct((2, T), jnp.int32)

    def lowered(model):
        def fn(p, i):
            acts = dataclasses.replace(model, params=p).apply_taps(i, taps)
            return tuple(acts[t] for t in taps)
        return _stripped(jax.jit(fn).lower(params, ids).as_text())

    assert lowered(with_mtp) == lowered(without)
    with pytest.raises(KeyError, match="mtp_logprob"):
        dataclasses.replace(without, params=params).apply_taps(
            jnp.zeros((1, T), jnp.int32), [None, "mtp_logprob"])
    assert "mtp_logprob" in with_mtp.module.layer_paths()
    assert "mtp_logprob" not in without.module.layer_paths()


def test_the_mtp_module_has_its_scopes_and_its_logits_are_not_the_head(ref, builder):
    from mmlspark_tpu.obs import scopes

    cfg = tiny_config()
    model, _ = model_with(builder, ref, cfg)
    ids = jnp.zeros((1, T), jnp.int32)
    taps = [None, "mtp_logprob", "expert_load"]
    text = jax.jit(lambda p, i: tuple(dataclasses.replace(model, params=p).apply_taps(
        i, taps)[t] for t in taps)).lower(model.params, ids).compile().as_text()
    paths = set(scopes.parse(text)[1].values())
    for part in ("mtp/fuse", "mtp/layer3/attn/core", "mtp/layer3/moe/route",
                 "mtp/layer3/shared", "mtp/logits", "head", "layer2/moe/combine"):
        assert any(("/" + p + "/").find("/" + part + "/") >= 0 for p in paths), part
    assert not any(re.search(r"(^|/)mtp/.*\bhead\b", p) for p in paths)


def test_one_module_is_built_and_it_reads_one_stream():
    with pytest.raises(ValueError, match="one is built"):
        transformer.latent_causal_lm(T, 64, 64, 4, 24, 16, 48, 16, 64, [False, True], 96, 32,
                                     8, 8, 4, nextn=2)
    with pytest.raises(ValueError, match="one stream"):
        transformer.latent_causal_lm(T, 64, 64, 4, 24, 16, 48, 16, 64, [False, True], 96, 32,
                                     8, 8, 4, streams=4, nextn=1)


# -- the latent kernel with content heads of no whole tile -------------------

def test_the_module_takes_its_kernel_at_192_content_lanes(monkeypatch, interpreted):
    """On a TPU the module lays its operands out for the kernel (W_kvb's
    columns with zeros after each head's 192 content keys, the rotary key in
    the last tile's free lanes); here the kernel runs in the interpreter in
    its place, and the module's result is that of its plain path."""
    x = jax.random.normal(jax.random.key(4), (1, 128, 256), jnp.float32)
    layer = transformer.LatentAttention(2, 48, 32, 192, 64, 256, 1e6, None, 1e-5)
    params, _ = layer.init(jax.random.key(5), (128, 256))

    def run(through=lambda fn: fn()):          # `interpreted`: the kernels it traces
        def attend():
            with matmul_precision("float32"):
                return layer.apply(params, x)
        return through(attend)

    plain = run()
    monkeypatch.setattr(transformer, "_mla_pallas_applies", lambda *a: True)
    assert run(interpreted) == ["attn_mla"]
    kernel = run()
    assert float(jnp.abs(kernel - plain).max()) < 1e-5 * float(jnp.abs(plain).max())
