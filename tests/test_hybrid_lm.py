"""The third language model the benchmark scores: a decoder of Mamba layers,
differential attention (window, full, cross over one shared key/value) and
Gated Memory Units (`models/ssm.py`, `models/transformer.py`), at a tiny size
on the CPU against a float32 reference of the same equations written HERE (no
import from `benchmarks/`): the model through the normal path, each of the
five layer kinds alone, the chunked scan against the step-by-step recurrence,
a row cut into pieces, the two kernels in the interpreter against their plain
forms, gradients through both VJPs, and every planted fault failing. The
kernels' compiles at the published widths are in `test_causal_lm.py`, the one
file that describes the topology."""

import dataclasses
import math
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mmlspark_tpu.core.dataframe import DataFrame  # noqa: E402
from mmlspark_tpu.core.pipeline import PipelineModel  # noqa: E402
from mmlspark_tpu.models import ssm, transformer  # noqa: E402
from mmlspark_tpu.models.dnn_model import DNNModel  # noqa: E402
from mmlspark_tpu.models.module import matmul_precision  # noqa: E402

T, D, L, WINDOW, VOCAB = 48, 64, 8, 8, 96
HEADS, KV, HD, INNER, STATES, TAPS, RANK = 4, 2, 16, 128, 4, 4, 4
EPS = 1e-5
PLAN = transformer.hybrid_plan(L)      # mamba window mamba window memory full gmu cross


def tiny_model(seed=3):
    """8 layers so that all five kinds, the memory and the shared keys
    occur; every gain, bias and vector moved off its initial value so that
    each term of the equations moves the result."""
    model = transformer.hybrid_causal_lm(T, VOCAB, D, HEADS, KV, L, 96, WINDOW,
                                         d_state=STATES, seed=seed)
    leaves, tree = jax.tree.flatten(model.params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    moved = [jnp.asarray(a) + 0.1 * jax.random.normal(k, np.shape(a), jnp.float32)
             if np.ndim(a) == 1 else jnp.asarray(a) for a, k in zip(leaves, keys)]
    return dataclasses.replace(model, params=jax.tree.unflatten(tree, moved))


def rows(n=6, seed=0):
    ids = np.random.default_rng(seed).integers(1, VOCAB, (n, T), dtype=np.int32)
    lengths = np.full(n, T)
    lengths[2], lengths[3] = 20, 5          # rows shorter than the cap
    ids[np.arange(T)[None, :] >= lengths[:, None]] = 0
    return ids, lengths


# -- the reference: one row [T, D] at a time, float32, the equations as written

FAULTS = ("window_less", "window_more", "memory_after_gate", "cross_own_keys",
          "lambda_init_0", "no_d", "taps_reversed", "state_reset", "memory_of_layer_2")


def ref_norm(w, x):
    mu = x.mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(((x - mu) ** 2).mean(-1, keepdims=True) + EPS) \
        * w["scale"] + w["bias"]


def ref_mamba(w, u, fault=None):
    """-> (the mixer's output, the memory it would hand on)."""
    xz = u @ w["w_in"]
    xs, z = xz[:, :INNER], xz[:, INNER:]
    taps = w["conv_w"][:, ::-1] if fault == "taps_reversed" else w["conv_w"]
    run = jnp.concatenate([jnp.zeros((TAPS - 1, INNER)), xs])
    xc = jax.nn.silu(w["conv_b"] + sum(taps[:, j] * run[j:j + len(u)]
                                       for j in range(TAPS)))
    dbc = xc @ w["w_x"]
    delta = jax.nn.softplus(dbc[:, :RANK] @ w["w_dt"] + w["b_dt"])
    A = -jnp.exp(w["a_log"])

    def step(h, a):
        t, d, x, b, c = a
        if fault == "state_reset":
            h = jnp.where(t % 16 == 0, 0.0, h)
        h = jnp.exp(d[:, None] * A) * h + (d * x)[:, None] * b[None, :]
        return h, h @ c
    _, y = jax.lax.scan(step, jnp.zeros((INNER, STATES)),
                        (jnp.arange(len(u)), delta, xc, dbc[:, RANK:RANK + STATES],
                         dbc[:, RANK + STATES:]))
    if fault != "no_d":
        y = y + w["d"] * xc
    gated = y * jax.nn.silu(z)
    return gated @ w["w_out"], gated if fault == "memory_after_gate" else y


def ref_gmu(w, u, m):
    return (m * jax.nn.silu(u @ w["w1"])) @ w["w2"]


def ref_diff(w, u, i, window, kv=None, fault=None, live=None):
    """-> (the mixer's output, its (k, v)); `kv`: another layer's. A position
    at or past `live` (the row's last real token's, plus one) attends to
    nothing: its heads' output is zero."""
    t = len(u)
    q = (u @ w["wq"] + w["bq"]).reshape(t, HEADS // 2, 2, HD)
    if kv is None:
        k, v = jnp.split(u @ w["wkv"] + w["bkv"], 2, axis=-1)
        kv = (k, v)
    k = kv[0].reshape(t, KV // 2, 2, HD)
    v = kv[1].reshape(t, KV // 2, 2 * HD)
    back = np.arange(t)[:, None] - np.arange(t)[None, :]
    window = {"window_less": window - 1, "window_more": window + 1}.get(fault, window) \
        if window else 0
    seen = (back >= 0) & ((back < window) if window else True)
    init = 0.8 - 0.6 * math.exp(-0.3 * (0 if fault == "lambda_init_0" else i))
    lam = jnp.exp(w["lq1"] @ w["lk1"]) - jnp.exp(w["lq2"] @ w["lk2"]) + init
    out = []
    for j in range(HEADS // 2):
        g = j // ((HEADS // 2) // (KV // 2))
        p1, p2 = (jax.nn.softmax(jnp.where(seen, q[:, j, h] @ k[:, g, h].T / math.sqrt(HD),
                                           -jnp.inf), axis=-1) for h in range(2))
        o = p1 @ v[:, g] - lam * (p2 @ v[:, g])
        out.append(o / jnp.sqrt((o * o).mean(-1, keepdims=True) + EPS)
                   * w["subln"] * (1 - init))
    heads = jnp.concatenate(out, axis=-1)
    if live is not None:
        heads = jnp.where(np.arange(t)[:, None] < live, heads, 0.0)
    return heads @ w["wo"] + w["bo"], kv


def ref_swiglu(w, x):
    gate, up = jnp.split(x @ w["w_gate_up"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w["w_down"]


def ref_layer(w, x, i, carry, fault=None):
    kind = PLAN[i]
    name = {"mamba": "ssm", "mamba_memory": "ssm", "gmu": "gmu"}.get(kind, "attn")
    u = ref_norm(w[name + "_norm"], x)
    if name == "ssm":
        y, memory = ref_mamba(w["ssm"], u, fault)
        if kind == "mamba_memory" or (fault == "memory_of_layer_2" and i == 2):
            carry.setdefault("memory", memory)
    elif kind == "gmu":
        y = ref_gmu(w["gmu"], u, carry["memory"])
    elif kind == "cross":
        kv = carry["kv"]
        if fault == "cross_own_keys":       # layer 17's weights on its own input
            kv = jnp.split(u @ carry["wkv"]["wkv"] + carry["wkv"]["bkv"], 2, axis=-1)
        y, _ = ref_diff(w["attn"], u, i, 0, kv, fault, carry.get("live"))
    else:
        y, kv = ref_diff(w["attn"], u, i, WINDOW if kind == "window" else 0, None, fault,
                         carry.get("live"))
        if kind == "full":
            carry["kv"], carry["wkv"] = kv, w["attn"]
    h = x + y
    return h + ref_swiglu(w["mlp"], ref_norm(w["mlp_norm"], h))


def ref_model(params, ids, fault=None):
    out = []
    for row in ids:
        # the row's positions up to its last real (non-pad) id: past them the
        # attention layers attend to nothing
        live = int(np.flatnonzero(row != 0).max(initial=-1)) + 1
        x, carry = params["embed"]["table"][row], {"live": live}
        for i in range(L):
            x = ref_layer(params[f"layer{i}"], x, i, carry, fault)
        logp = jax.nn.log_softmax(ref_norm(params["final_norm"], x)
                                  @ params["embed"]["table"].T)
        out.append(logp[np.arange(T), np.append(row[1:], 0)])
    return np.stack(out)


def through_the_pipeline(model, ids):
    col = np.empty(len(ids), dtype=object)
    for i in range(len(ids)):
        col[i] = ids[i]
    stage = DNNModel(inputCol="tokens", batchSize=4,
                     fetchDict={"logprob": "OUTPUT_0"}).set_model(model)
    fused = PipelineModel([stage]).fuse()
    with matmul_precision("float32"):
        out = fused.transform(DataFrame.from_dict({"tokens": col}, num_partitions=2))
    assert fused.fusion_stats()["fallbacks_total"] == 0
    return np.stack(list(out.column("logprob")))


@pytest.fixture(scope="module")
def model():
    return tiny_model()


@pytest.fixture(scope="module")
def scored(model):
    ids, lengths = rows()
    return ids, lengths, through_the_pipeline(model, ids)


# -- the model and its layers against the reference --------------------------

def test_the_plan_of_32_layers_is_the_published_one():
    plan = transformer.hybrid_plan(32)
    assert [plan.count(k) for k in ("mamba", "mamba_memory", "window", "full", "gmu",
                                    "cross")] == [8, 1, 8, 1, 7, 7]
    assert plan[16] == "mamba_memory" and plan[17] == "full"
    assert all(k in ("mamba", "window") for k in plan[:16])
    assert plan[18::2] == ["gmu"] * 7 and plan[19::2] == ["cross"] * 7
    assert PLAN == ["mamba", "window", "mamba", "window", "mamba_memory", "full", "gmu",
                    "cross"]


def test_the_normal_path_agrees_with_the_float32_reference(model, scored):
    ids, lengths, got = scored
    assert got.shape == (6, T) and got.dtype == np.float32
    want = ref_model(model.params, ids)
    # float32 on both sides: what is left is the order of the sums
    assert np.abs(got - want).max() < 2e-4
    assert "head" not in model.params           # the head is the table, transposed


def test_a_pad_after_a_rows_real_tokens_moves_none_of_its_real_outputs(model, scored):
    ids, lengths, got = scored
    other = ids.copy()
    other[2, lengths[2]:] = 7
    again = through_the_pipeline(model, other)
    assert np.array_equal(again[2, :lengths[2] - 1], got[2, :lengths[2] - 1])
    assert not np.array_equal(again[2, lengths[2]:], got[2, lengths[2]:])


@pytest.mark.parametrize("i", range(L), ids=[f"{i}-{k}" for i, k in enumerate(PLAN)])
def test_each_layer_alone_agrees_with_the_reference(i, model):
    """Layers before it run in the reference, to fill what it reads."""
    x0 = jax.random.normal(jax.random.key(i), (2, T, D), jnp.float32)
    want, carries = [], []
    for row in x0:
        carry, x = {}, row
        for j in range(i + 1):
            before = x
            x = ref_layer(model.params[f"layer{j}"], x, j, carry)
        want.append(x)
        carries.append((before, carry))
    before = jnp.stack([b for b, _ in carries])
    carry = {}
    if "memory" in carries[0][1] and PLAN[i] == "gmu":
        carry["memory"] = jnp.stack([c["memory"] for _, c in carries])
    if PLAN[i] == "cross":
        carry["kv"] = tuple(jnp.stack([c["kv"][n] for _, c in carries]) for n in range(2))
    with matmul_precision("float32"):
        got, load = model.module.layers[i].apply_with_load(
            model.params[f"layer{i}"], before, carry)
    assert load is None
    assert float(jnp.abs(got - jnp.stack(want)).max()) < 1e-4
    if PLAN[i] == "mamba_memory":
        assert float(jnp.abs(carry["memory"][0] - carries[0][1]["memory"]).max()) < 1e-4
    if PLAN[i] == "full":
        assert float(jnp.abs(carry["kv"][0][1] - carries[1][1]["kv"][0]).max()) < 1e-4
    if PLAN[i] in ("mamba", "window"):
        assert not carry                        # nothing crosses from such a layer


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_fails(fault, model, scored):
    ids, lengths, got = scored
    real = np.arange(T)[None, :] < lengths[:, None]
    assert np.abs(got - ref_model(model.params, ids, fault))[real].max() > 2e-3, fault


def test_a_cross_layer_reads_the_full_layers_keys_and_a_gmu_layer_4s_memory(model):
    """Moved values or memory move the reader (values in another order: twice
    the values would be undone by the norm after the subtraction); the reader
    has none of its own to fall back on."""
    x = jax.random.normal(jax.random.key(9), (1, T, D), jnp.float32)
    cross, gmu = model.module.layers[7], model.module.layers[6]
    assert "wkv" not in model.params["layer7"]["attn"]
    kv = tuple(jax.random.normal(jax.random.key(n), (1, T, KV * HD)) for n in (1, 2))
    m = jax.random.normal(jax.random.key(3), (1, T, INNER))
    with matmul_precision("float32"):
        a = cross.apply_with_load(model.params["layer7"], x, {"kv": kv})[0]
        b = cross.apply_with_load(model.params["layer7"], x, {"kv": (kv[0], kv[1][:, ::-1])})[0]
        c = gmu.apply_with_load(model.params["layer6"], x, {"memory": m})[0]
        d = gmu.apply_with_load(model.params["layer6"], x, {"memory": 2 * m})[0]
        with pytest.raises(KeyError):
            cross.apply_with_load(model.params["layer7"], x, {})
        with pytest.raises(KeyError):
            gmu.apply_with_load(model.params["layer6"], x, {})
    assert float(jnp.abs(a - b).max()) > 1e-3 and float(jnp.abs(c - d).max()) > 1e-3


# -- the scan -----------------------------------------------------------------

def _scan_operands(seed, B=2, t=40, C=32, N=4):
    rng = np.random.default_rng(seed)
    f = jnp.float32
    return (jnp.asarray(np.abs(rng.normal(size=(B, t, C))) * 0.5, f),
            jnp.asarray(rng.normal(size=(B, t, C)), f),
            jnp.asarray(rng.normal(size=(B, t, N)), f),
            jnp.asarray(rng.normal(size=(B, t, N)), f),
            -jnp.exp(jnp.asarray(rng.normal(size=(C, N)), f)),
            jnp.asarray(rng.normal(size=(C,)), f),
            jnp.asarray(rng.normal(size=(B, C, N)), f))


def _recurrence(delta, x, Bm, Cm, A, D, h0):
    def step(h, a):
        d, xx, b, c = a
        h = jnp.exp(d[:, :, None] * A) * h + (d * xx)[:, :, None] * b[:, None, :]
        return h, jnp.einsum("bcn,bn->bc", h, c) + D * xx
    h, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(a, 1, 0) for a in (delta, x, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1), h


@pytest.mark.parametrize("t", [16, 37, 48, 5])
def test_the_chunked_scan_is_the_step_by_step_recurrence(t):
    ops = _scan_operands(t, t=t)
    (y, h), (yw, hw) = ssm.ssm_scan_xla(*ops), _recurrence(*ops)
    assert y.shape == yw.shape and h.shape == hw.shape
    assert float(jnp.abs(y - yw).max()) < 1e-4 * float(jnp.abs(yw).max())
    assert float(jnp.abs(h - hw).max()) < 1e-5 * max(1.0, float(jnp.abs(hw).max()))


def test_a_large_step_times_a_fast_state_does_not_overflow_the_chunk():
    """delta A of -40 a step: exp(-S) of the naive closed form would be inf."""
    delta, x, Bm, Cm, A, D, h0 = _scan_operands(1, B=1, t=32)
    y, h = ssm.ssm_scan_xla(delta + 2.5, x, Bm, Cm, A - 16.0, D, h0)
    yw, hw = _recurrence(delta + 2.5, x, Bm, Cm, A - 16.0, D, h0)
    assert bool(jnp.isfinite(y).all()) and float(jnp.abs(y - yw).max()) < 1e-4


@pytest.mark.parametrize("piece", [16, 24])
def test_a_row_cut_into_pieces_is_the_row(piece, model, monkeypatch):
    """The state and the convolution's 3 positions are handed from piece to
    piece; the memory comes back whole."""
    u = jax.random.normal(jax.random.key(2), (2, T, D), jnp.float32)
    layer, params = model.module.layers[4].parts[1][1], model.params["layer4"]["ssm"]
    with matmul_precision("float32"):
        whole, carry = {}, {}
        want = layer.apply_carry(params, u, whole)
        monkeypatch.setattr(ssm, "PIECE", piece)
        got = layer.apply_carry(params, u, carry)
        gmu = model.module.layers[6].parts[1][1]
        g_whole = gmu.apply_carry(model.params["layer6"]["gmu"], u, whole)
        monkeypatch.setattr(ssm, "PIECE", 8192)
        g_want = gmu.apply_carry(model.params["layer6"]["gmu"], u, whole)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert carry["memory"].shape == (2, T, INNER)
    assert float(jnp.abs(carry["memory"] - whole["memory"]).max()) < 1e-5
    assert float(jnp.abs(g_whole - g_want).max()) < 1e-5
    row = jnp.stack([ref_mamba(params, r)[0] for r in u])
    assert float(jnp.abs(got - row).max()) < 1e-4


@pytest.mark.parametrize("t", [40, 512])
def test_the_scan_kernel_agrees_with_the_plain_form(t):
    ops = _scan_operands(t, B=1 if t > 100 else 2, t=t, C=1024, N=16)
    got = ssm.ssm_scan_pallas(*ops, interpret=True)
    want = ssm.ssm_scan_xla(*ops)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == jnp.float32
        assert float(jnp.abs(a - b).max()) < 1e-5 * max(1.0, float(jnp.abs(b).max()))


def test_the_scan_kernels_gradient_is_the_plain_forms(interpreted):
    ops = _scan_operands(6, B=1, t=16, C=1024, N=4)
    tilt = jnp.cos(jnp.arange(1024, dtype=jnp.float32))

    def loss(scan):
        def f(*a):
            y, h = scan(*a)
            return jnp.sum(y * tilt) + jnp.sum(h * h)
        return f

    got = jax.grad(loss(ssm._scan_pair), range(7))(*ops)
    want = jax.grad(loss(ssm.ssm_scan_xla), range(7))(*ops)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) <= 1e-5 * max(float(jnp.abs(b).max()), 1e-30)


# -- the differential core ----------------------------------------------------

def _diff_operands(seed, t, B=1, pairs=4, kv_pairs=2, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(B, t, pairs * 128)), dtype),
            jnp.asarray(rng.normal(size=(B, t, kv_pairs * 128)), dtype),
            jnp.asarray(rng.normal(size=(B, t, kv_pairs * 128)), dtype),
            jnp.float32(0.37), jnp.asarray(rng.normal(size=(128,)) * 0.1 + 1, jnp.float32))


@pytest.mark.parametrize("window,t", [(0, 512), (0, 1024), (512, 1024), (130, 512)])
def test_the_differential_kernel_agrees_with_the_plain_form(window, t):
    q, k, v, lam, gain = _diff_operands(t + window, t)
    got = transformer.diff_pallas(q, k, v, lam, gain, window, 4, 2, EPS, interpret=True)
    want = transformer.diff_xla(*(a.astype(jnp.float32) for a in (q, k, v)), lam, gain,
                                window, 4, 2, EPS)
    assert got.shape == q.shape and got.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 0.03


@pytest.mark.parametrize("window", [0, 8])
def test_the_plain_differential_form_is_the_two_masked_softmaxes(window, model):
    u = jax.random.normal(jax.random.key(5), (T, D), jnp.float32)
    w = model.params["layer5" if not window else "layer1"]["attn"]
    i = 5 if not window else 1
    want, _ = ref_diff(w, u, i, window)
    with matmul_precision("float32"):
        got = model.module.layers[i].parts[1][1].apply(w, u[None])[0]
    assert float(jnp.abs(got - want).max()) < 1e-4


def test_the_differential_kernels_gradient_is_the_plain_forms(interpreted):
    q, k, v, lam, gain = _diff_operands(3, 128, pairs=2, kv_pairs=1, dtype=jnp.float32)
    tilt = jnp.cos(jnp.arange(256, dtype=jnp.float32))

    def loss(attend):
        return lambda *a: jnp.sum(attend(*a, 0, 2, 1, EPS).astype(jnp.float32) * tilt)

    whole = jnp.full((q.shape[0],), q.shape[1], jnp.int32)   # every row to its end
    got = jax.grad(loss(lambda *a: transformer._diff_pair(*a[:5], whole, *a[5:])),
                   range(5))(q, k, v, lam, gain)
    want = jax.grad(loss(transformer.diff_xla), range(5))(q, k, v, lam, gain)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) <= 1e-5 * float(jnp.abs(b).max())


def test_the_modules_take_their_kernels_where_they_apply(monkeypatch, interpreted):
    """On a TPU the mixers hand their cores to the kernels; here the kernels
    run in the interpreter in their place (heads of 64, 1,024 channels), and
    the model's result is that of its plain path."""
    model = transformer.hybrid_causal_lm(128, VOCAB, 256, 4, 2, 8, 128, 16, expand=4,
                                         d_state=4, seed=2)
    ids = np.random.default_rng(1).integers(1, VOCAB, (1, 128), dtype=np.int32)

    def run(through=lambda fn, *a: fn(*a)):     # `interpreted`: the kernels it traces
        with matmul_precision("float32"):
            return through(jax.jit(lambda p, x: model.module.apply(p, x)), model.params, ids)

    plain = run()
    monkeypatch.setattr(ssm, "_scan_kernel_applies", lambda *a: True)
    monkeypatch.setattr(transformer, "_diff_pallas_applies", lambda *a: True)
    calls = run(interpreted)
    kernels = run()
    assert calls == ["ssm_scan", "attn_window_diff", "ssm_scan", "attn_window_diff",
                     "ssm_scan", "attn_full_diff", "attn_full_diff"]
    assert float(jnp.abs(kernels - plain).max()) < 1e-3
    # off a TPU neither applies, whatever the shapes
    monkeypatch.undo()
    assert not ssm._scan_kernel_applies(jnp.zeros((1, 128, 1024)))
    assert not transformer._diff_pallas_applies(jnp.zeros((1, 128, 256), jnp.bfloat16), 0, 2)
