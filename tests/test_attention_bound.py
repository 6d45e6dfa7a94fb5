"""The attention kernels stop at a row's last real token: the bound
(`transformer.live_lengths`) reaches every flash kernel as a run-time scalar
(`FlashSchedule`), query blocks wholly past it compute nothing and store
zeros, and the plain forms zero the same positions. In the Pallas
interpreter: real positions bitwise those of the same kernel with every row
whole, positions past the bound zero in the kernel and its plain form,
gradients through each pair those of the plain form. Then the models: one
program whatever the lengths, with the parent's kernels, and the host's
count of live query blocks (`BatchTiming.counts`) the device's."""

import os
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mmlspark_tpu.core.dataframe import DataFrame  # noqa: E402
from mmlspark_tpu.core.pipeline import PipelineModel  # noqa: E402
from mmlspark_tpu.models import transformer  # noqa: E402
from mmlspark_tpu.models.dnn_model import DNNModel  # noqa: E402
from mmlspark_tpu.models.module import matmul_precision  # noqa: E402

T = 1024
# a row's last real token mid-block, exactly on a block edge (512: a full
# layer's block, two of a window's), inside the first block, at the cap; a
# row of pads alone
ROWS = {"mid_block": 300, "block_edge": 512, "first_block": 100, "cap": T, "pads_only": 0}
LIVE = np.asarray(list(ROWS.values()), np.int32)
REAL = np.arange(T)[None, :] < LIVE[:, None]          # [B, T]
EPS = 1e-5


def _normal(seed, shape, dtype, scale=1.0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape) * scale, dtype)


def _gqa(window):
    def operands(dtype):
        return tuple(_normal(s, (len(ROWS), T, n * 128), dtype)
                     for s, n in ((1, 4), (2, 2), (3, 2)))

    return (operands,
            lambda ops, live: transformer.gqa_pallas(*ops, window, 4, 2, interpret=True,
                                                     live=live),
            lambda ops, live: transformer._gqa_flat_xla(*ops, window, 4, 2, live=live),
            lambda ops, live: transformer._gqa_pair(*ops, live, window, 4, 2))


def _diff(window, kv_seed=5):
    """`kv_seed` apart from the queries': a cross layer's keys and values are
    another layer's, which the kernel reads in place."""
    lam, gain = jnp.float32(0.37), _normal(4, (128,), jnp.float32, 0.1) + 1.0

    def operands(dtype):
        return (_normal(6, (len(ROWS), T, 4 * 128), dtype),
                _normal(kv_seed, (len(ROWS), T, 2 * 128), dtype),
                _normal(kv_seed + 1, (len(ROWS), T, 2 * 128), dtype))

    return (operands,
            lambda ops, live: transformer.diff_pallas(*ops, lam, gain, window, 4, 2, EPS,
                                                      interpret=True, live=live),
            lambda ops, live: transformer.diff_xla(*ops, lam, gain, window, 4, 2, EPS,
                                                   live=live),
            lambda ops, live: transformer._diff_pair(*ops, lam, gain, live, window, 4, 2, EPS))


def _mla_operands(dtype, nope, v, H=4):
    """The kernel's layout (above `transformer.mla_xla`), 64 rotary lanes."""
    keys = 256 if nope % 128 else nope
    q = _normal(7, (len(ROWS), T, H, 256), dtype, 0.2)
    kv = _normal(8, (len(ROWS), T, H, keys + v), dtype).at[..., nope:keys].set(0)
    kr = _normal(9, (len(ROWS), T, 128), dtype)
    if nope % 128:
        return q.reshape(len(ROWS), T, -1), kv.reshape(len(ROWS), T, -1), \
            kr.at[..., :nope % 128].set(0)
    return q.at[..., nope + 64:].set(0).reshape(len(ROWS), T, -1), \
        kv.reshape(len(ROWS), T, -1), kr.at[..., 64:].set(0)


def _mla(nope, v):
    keys = 256 if nope % 128 else nope
    return (lambda dtype: _mla_operands(dtype, nope, v),
            lambda ops, live: transformer.mla_pallas(*ops, 4, keys, interpret=True, live=live),
            lambda ops, live: transformer.mla_xla(*ops, 4, keys, live=live),
            lambda ops, live: transformer._mla_pair(*ops, live, 4, keys))


def _mla_sparse():
    k = jax.random.split(jax.random.key(10), 3)
    bits = transformer.mla_index_xla(
        jax.random.normal(k[0], (len(ROWS), T, 128)), jax.random.normal(k[1], (len(ROWS), T, 128)),
        jax.random.normal(k[2], (len(ROWS), T, 1)), 1, 100)
    return (lambda dtype: _mla_operands(dtype, 128, 128),
            lambda ops, live: transformer.mla_sparse_pallas(*ops, bits, 4, 128, interpret=True,
                                                            live=live),
            lambda ops, live: transformer.mla_sparse_xla(*ops, bits, 4, 128, live=live),
            lambda ops, live: transformer._mla_sparse_pair(*ops, bits, live, 4, 128))


KERNELS = {
    "attn_window": lambda: _gqa(128),
    "attn_full": lambda: _gqa(0),
    "attn_window_diff": lambda: _diff(512),
    "attn_full_diff": lambda: _diff(0),
    "attn_full_diff_cross": lambda: _diff(0, kv_seed=11),
    "attn_mla": lambda: _mla(128, 128),
    "attn_mla_192": lambda: _mla(192, 256),
    "attn_mla_sparse": _mla_sparse,
}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_real_positions_are_the_whole_rows_and_past_the_bound_is_zero(kernel):
    operands, run, plain, _ = KERNELS[kernel]()
    ops = operands(jnp.bfloat16)
    live = jnp.asarray(LIVE)
    got = np.asarray(run(ops, live).astype(jnp.float32))
    whole = np.asarray(run(ops, jnp.full_like(live, T)).astype(jnp.float32))
    want = np.asarray(plain(tuple(a.astype(jnp.float32) for a in ops), live))
    assert np.isfinite(got).all() and got.shape == want.shape
    # a real query's output is the unbounded kernel's to the bit
    assert np.array_equal(got[REAL], whole[REAL])
    # past the bound: zero in the kernel and in the plain form
    assert not got[~REAL].any() and not want[~REAL].any()
    assert np.abs(got[REAL] - want[REAL]).max() < 0.03


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_gradients_through_the_pair_are_the_plain_forms(kernel, interpreted):
    operands, _, plain, pair = KERNELS[kernel]()
    ops = operands(jnp.float32)
    live = jnp.asarray(LIVE)
    width = plain(ops, live).shape[-1]
    tilt = jnp.cos(jnp.arange(T * width, dtype=jnp.float32)).reshape(T, width)

    def loss(attend):
        return lambda *a: jnp.sum(attend(a, live) * tilt)

    got = jax.grad(loss(pair), range(3))(*ops)
    want = jax.grad(loss(plain), range(3))(*ops)
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        # the pair's backward is the plain form's VJP, on the same cotangent
        assert np.array_equal(a[REAL], b[REAL])
        # nothing at or past a row's bound reaches a real output
        assert not a[~REAL].any() and not b[~REAL].any()


def test_the_bound_is_the_last_real_token_of_each_row():
    ids = np.array([[5, 0, 7, 0, 0], [0, 0, 0, 0, 0], [1, 2, 3, 4, 5], [0, 0, 0, 0, 9]],
                   np.int32)
    assert transformer.live_lengths(ids, 0).tolist() == [3, 0, 5, 5]
    assert transformer.live_lengths(jnp.asarray(ids), 7).tolist() == [5, 5, 5, 5]
    counts = transformer.CausalLM(16, 8, [], pad_id=0).batch_counts(
        np.pad(ids, ((0, 0), (0, 1019))))           # [4, 1024]: two blocks of 512 a row
    assert counts == {"attn_query_blocks": 8, "attn_live_blocks": 3}


# -- the models: one program whatever the lengths ----------------------------

M = 256           # a model's row: two blocks of 128


def _models():
    common = dict(dense_hidden=64, expert_hidden=32, num_experts=4, experts_held=4,
                  top_k=2, param_dtype="bfloat16", seed=3)
    latent = dict(vocab_size=64, hidden=128, heads=2, q_rank=32, kv_rank=32, nope=128,
                  rope=64, v_dim=128, sparse=[False, True], **common)
    return {
        "causal_lm": lambda: transformer.causal_lm(
            M, 64, 128, 2, 1, 128, [128, 0], [False, True], **common),
        "latent_causal_lm": lambda: transformer.latent_causal_lm(M, **latent),
        "latent_causal_lm_sparse": lambda: transformer.latent_causal_lm(
            M, index_heads=2, index_dim=128, index_topk=64, **latent),
        "latent_causal_lm_mtp": lambda: transformer.latent_causal_lm(M, nextn=1, **latent),
        "hybrid_causal_lm": lambda: transformer.hybrid_causal_lm(
            M, 64, 256, 4, 2, 8, 128, 128, expand=4, d_state=4, param_dtype="bfloat16",
            seed=2),
    }


# the kernels each model traces, as the parent traced them
KERNEL_CALLS = {
    "causal_lm": ["attn_window", "attn_full"],
    "latent_causal_lm": ["attn_mla", "attn_mla"],
    "latent_causal_lm_sparse": ["mla_index", "attn_mla_sparse"] * 2,
    "latent_causal_lm_mtp": ["attn_mla"] * 3,
    "hybrid_causal_lm": ["attn_window_diff", "attn_window_diff", "attn_full_diff",
                         "attn_full_diff"],
}


def _kernels_on(monkeypatch):
    for name in ("_pallas_applies", "_diff_pallas_applies", "_mla_pallas_applies",
                 "_index_pallas_applies"):
        monkeypatch.setattr(transformer, name, lambda *a: True)


@pytest.mark.parametrize("name", sorted(KERNEL_CALLS))
def test_one_program_whatever_the_lengths(name, monkeypatch, interpreted):
    model = _models()[name]()
    taps = {transformer.CausalLM.MTP} if name.endswith("mtp") else None
    rng = np.random.default_rng(4)
    a = rng.integers(1, 64, (2, M), dtype=np.int32)
    b = a.copy()
    a[0, 200:], a[1, 31:] = 0, 0                # trailing pads that differ
    b[1, 129:] = 0

    def forward(p, x):
        out = {}
        return model.module.apply(p, x, taps=taps, taps_out=out), out

    def text(ids):
        lowered = jax.jit(forward).lower(model.params, ids)
        return re.sub(r"\s*loc\(.*?\)", "", lowered.as_text())

    _kernels_on(monkeypatch)
    assert interpreted(forward, model.params, a) == KERNEL_CALLS[name]
    assert text(a) == text(b)


def test_the_host_counts_the_live_query_blocks_the_device_runs(monkeypatch, interpreted):
    """The pipeline's `BatchTiming.counts` (host, from the shipped ids) and
    the bounds the kernels were handed (device) name the same blocks of 512."""
    model = transformer.causal_lm(T, 64, 128, 2, 1, 128, [0], [False], dense_hidden=64,
                                  expert_hidden=32, num_experts=4, experts_held=4,
                                  top_k=2, seed=5)
    lengths = [300, 512, 100, 1024, 0, 700]
    ids = np.random.default_rng(6).integers(1, 64, (len(lengths), T), dtype=np.int32)
    ids[np.arange(T)[None, :] >= np.asarray(lengths)[:, None]] = 0

    col = np.empty(len(ids), dtype=object)
    for i in range(len(ids)):
        col[i] = ids[i]
    fused = PipelineModel([DNNModel(inputCol="tokens", batchSize=4,
                                    fetchDict={"logprob": "OUTPUT_0"}).set_model(model)]).fuse()
    fused.transform(DataFrame.from_dict({"tokens": col}, num_partitions=2))
    records = fused.last_ingest_stats.records
    host = {k: sum(r.counts[k] for r in records)
            for k in ("attn_query_blocks", "attn_live_blocks")}

    handed = []                              # the bounds the kernel pair was handed
    bound = transformer._bound

    def spy(live, x):
        out = bound(live, x)
        if not isinstance(out, jax.core.Tracer):        # the module's, run eagerly
            handed.append(np.asarray(out))
        return out

    monkeypatch.setattr(transformer, "_bound", spy)
    _kernels_on(monkeypatch)
    with matmul_precision("float32"):
        model.module.apply(model.params, jnp.asarray(ids))
    (device,) = handed                       # one full layer, every row at once
    assert device.tolist() == lengths
    blocks = -(-np.asarray(device) // 512)
    # the batches of 4 rows pad to their bucket with rows of pads: no live block
    assert host["attn_live_blocks"] == int(blocks.sum()) == 7
    assert host["attn_query_blocks"] == 2 * sum(r.padded_rows for r in records) >= 12


def test_a_kernel_is_traced_once_for_each_configuration(monkeypatch):
    """Set-up: a model's layers call their kernel alike, and each call is
    the kernel traced once and inlined (`paired.pair`), not traced again: a
    row of 384 (three blocks of 128, a length no other test traces), four
    layers, two configurations (window 128, full)."""
    from jax._src.pallas import pallas_call

    traced = []
    trace = pallas_call._trace_kernel_to_jaxpr
    monkeypatch.setattr(pallas_call, "_trace_kernel_to_jaxpr",
                        lambda *a, **k: traced.append(1) or trace(*a, **k))
    _kernels_on(monkeypatch)
    model = transformer.causal_lm(384, 64, 128, 2, 1, 128, [128, 0, 128, 0], [False] * 4,
                                  dense_hidden=64, expert_hidden=32, num_experts=4,
                                  experts_held=4, top_k=2, seed=7)
    ids = np.ones((1, 384), np.int32)
    jaxpr = jax.make_jaxpr(lambda p, x: model.module.apply(p, x))(model.params, ids)
    assert str(jaxpr).count("pallas_call") == 4        # every layer keeps its kernel
    assert len(traced) == 2
