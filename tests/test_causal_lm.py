"""The causal language model the benchmark scores (`models/transformer.py`,
`models/moe.ExpertLayer`) at a tiny size on the CPU, against the plain
reference the benchmark keeps (`benchmarks/references/k-exaone-236b-a23b-ep8.py`:
it imports nothing of the program), through the normal path
(`PipelineModel([DNNModel]).fuse().transform`); the Pallas kernels in the
interpreter; and the kernels compiled at the published widths for a described
v5e (no chip: on-chip-measurement guide, section 2; the one file of `tests/`
that describes the topology, in a fixture)."""

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

T = 32
SEED = 4294970129


def tiny_config(**changes):
    """The satellite's preset: hidden 64, 4 query / 2 key-value heads of 16,
    layers L, L, L, G, L with window 4 at T 32, 16 experts top-4 with 4 held,
    vocabulary 64. The same keys the configuration's file has."""
    cfg = dict(
        hidden_size=64, head_dim=16, num_attention_heads=4, num_key_value_heads=2,
        vocab_size=64, num_experts=4, num_experts_published=16, first_expert_held=0,
        num_experts_per_tok=4, num_shared_experts=1, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5,
        sliding_windows=[4, 4, 4, 0, 4],
        mlp_layer_types=["dense"] + ["sparse"] * 4, rms_norm_eps=1e-5,
        rope_parameters={"rope_theta": 1e6}, scoring_func="sigmoid",
        norm_topk_prob=True, routed_scaling_factor=2.5, pad_id=0,
        max_positions=T, assumed={"qk_norm": True, "rope_layers": "sliding"})
    cfg.update(changes)
    return cfg


@pytest.fixture(scope="module")
def ref():
    return spec.bench_module("references", "k-exaone-236b-a23b-ep8")


@pytest.fixture(scope="module")
def builder():
    return spec.bench_module("builders", "k-exaone-236b-a23b-ep8")


def rows(n=6, seed=0):
    ids = np.random.default_rng(seed).integers(1, 64, (n, T), dtype=np.int32)
    lengths = np.full(n, T)
    lengths[2], lengths[3] = 20, 5          # rows shorter than the cap
    ids[np.arange(T)[None, :] >= lengths[:, None]] = 0
    return ids, lengths


def model_with(builder, ref, cfg, weights=None):
    weights = ref.make_weights(cfg, SEED) if weights is None else weights
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


LAYERS = {   # one layer of each kind, and the whole preset
    "sliding-dense": dict(num_hidden_layers=1),
    "full-dense": dict(num_hidden_layers=1, sliding_windows=[0]),
    "sliding-sparse": dict(num_hidden_layers=1, mlp_layer_types=["sparse"]),
    "full-sparse": dict(num_hidden_layers=1, sliding_windows=[0],
                        mlp_layer_types=["sparse"]),
    "all-five": {},
}


@pytest.mark.parametrize("kind", sorted(LAYERS))
def test_the_normal_path_agrees_with_the_plain_reference(kind, ref, builder):
    cfg = tiny_config(**LAYERS[kind])
    model, weights = model_with(builder, ref, cfg)
    ids, lengths = rows()
    out, _ = through_the_pipeline(model, ids)
    got = np.stack(list(out.column("logprob")))
    assert got.shape == (6, T) and got.dtype == np.float32
    want = ref.score(cfg, SEED, ids, weights=weights)["logprob"]
    real = np.arange(T)[None, :] < lengths[:, None]
    # float32 on both sides: what is left is the order of the sums
    assert np.abs(got - want)[real].max() < 2e-4, kind
    assert np.abs(got - want).max() < 2e-4          # the pads' outputs too


def test_a_pad_after_a_rows_real_tokens_moves_none_of_its_real_outputs(ref, builder):
    cfg = tiny_config()
    model, _ = model_with(builder, ref, cfg)
    ids, lengths = rows()
    other = ids.copy()
    other[2, 20:] = 7                                 # other ids where row 2 is padded
    a = np.stack(list(through_the_pipeline(model, ids)[0].column("logprob")))
    b = np.stack(list(through_the_pipeline(model, other)[0].column("logprob")))
    # position 19's target is the id after it, which changed: up to 18 agree
    assert np.array_equal(a[2, :19], b[2, :19])
    assert not np.array_equal(a[2, 19:], b[2, 19:])
    assert np.array_equal(np.delete(a, 2, axis=0), np.delete(b, 2, axis=0))


def test_expert_load_is_a_second_output_node_and_counts_the_visits(ref, builder):
    cfg = tiny_config()
    model, _ = model_with(builder, ref, cfg)
    ids, _ = rows()
    out, fused = through_the_pipeline(
        model, ids, {"logprob": "OUTPUT_0", "expert_load": "expert_load"})
    load = np.stack(list(out.column("expert_load")))
    assert load.shape == (6, 4, 4) and load.dtype == np.float32
    assert (load == np.round(load)).all() and (load >= 0).all()
    assert (load.sum(axis=2) <= T * 4).all() and load.sum() > 0
    seg = fused.fusion_stats()["segments"][0]
    assert seg["fetched"] == ["logprob", "expert_load"]


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(ref):
    """Guide, section 4: the parts the four shares give (experts 0-3, 4-7,
    8-11, 12-15), with the shared expert counted once, are the whole layer
    the uncut reference gives; and their loads are every visit."""
    cfg = tiny_config(num_experts=16)                 # the reference holds all 16
    key = jax.random.key(3)
    d, eff, n_exp, top_k = 64, 32, 16, 4
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
                        None, None).reshape(x.shape)
    with matmul_precision("float32"):
        total = transformer.SwiGLU(eff).apply(
            {"w_gate_up": w["shared/w_gate_up"], "w_down": w["shared/w_down"]}, x)
        loads = 0.0
        for first in (0, 4, 8, 12):
            layer = moe.ExpertLayer(n_exp, 4, top_k, eff, scale=2.5, first_expert=first)
            share, load = layer.apply_with_load(
                {"router": w["moe/router"], "router_bias": w["moe/router_bias"],
                 "w1": w["moe/w1"][first:first + 4], "w2": w["moe/w2"][first:first + 4]}, x)
            assert load.shape == (3, 4)
            total, loads = total + share, loads + float(load.sum())
    assert loads == 3 * T * top_k
    assert float(jnp.abs(total - whole).max()) < 1e-4 * float(jnp.abs(whole).max())


def test_expert_shardings_places_the_expert_layers_own_leaves():
    from jax.sharding import Mesh, PartitionSpec as P

    layer = moe.ExpertLayer(16, 8, 4, 32)
    params, _ = layer.init(jax.random.key(0), (T, 64))
    mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))
    placed = moe.expert_shardings(mesh, params)
    assert placed["w1"].spec == placed["w2"].spec == P("expert")
    assert placed["router"].spec == placed["router_bias"].spec == P()
    on_mesh = jax.device_put(params, placed)        # 8 experts over 4 devices
    assert on_mesh["w1"].addressable_shards[0].data.shape == (2, 64, 64)


def test_gradients_of_the_mean_log_probability_agree_with_the_references(ref, builder):
    cfg = tiny_config()
    model, weights = model_with(builder, ref, cfg)
    ids = rows(4, seed=1)[0]
    f32 = {p: a.astype(jnp.float32) for p, a in weights.items()}

    def mine(w):
        with matmul_precision("float32"):
            return jnp.mean(model.module.apply(builder._nest(w), jnp.asarray(ids)))

    def plain(w):
        x = w["embed/table"][ids]
        for i, (window, sparse) in enumerate(ref.layer_plan(cfg)):
            lw = {p[len(f"layer{i}/"):]: a for p, a in w.items()
                  if p.startswith(f"layer{i}/")}
            x = ref.layer(cfg, lw, x, window, sparse, None, None)[0]
        return jnp.mean(jax.vmap(lambda r, i: ref.log_probs(cfg, w, r, i, None))(
            x, jnp.asarray(ids)))

    got, want = jax.grad(mine)(f32), jax.grad(plain)(f32)
    for path in want:
        scale = float(jnp.abs(want[path]).max())
        if path.endswith("router_bias"):              # it chooses, never weighs
            assert scale == 0.0 and float(jnp.abs(got[path]).max()) == 0.0
            continue
        assert scale > 0.0, path
        assert float(jnp.abs(got[path] - want[path]).max()) < 2e-3 * scale, path


def test_save_and_load_keep_bfloat16_parameters_bfloat16(ref, builder, tmp_path):
    from mmlspark_tpu.core import serialize

    cfg = tiny_config()
    model, _ = model_with(builder, ref, cfg)
    ids, _ = rows()
    stage = DNNModel(inputCol="tokens", outputCol="logprob", batchSize=4).set_model(model)
    PipelineModel([stage]).save(str(tmp_path / "m"))
    loaded = PipelineModel.load(str(tmp_path / "m"))
    leaves = jax.tree.leaves(loaded.stages[0].get_model().params)
    assert leaves and {str(leaf.dtype) for leaf in leaves} == {"bfloat16"}
    a = np.stack(list(through_the_pipeline(model, ids)[0].column("logprob")))
    b = np.stack(list(through_the_pipeline(loaded.stages[0].get_model(), ids)[0]
                      .column("logprob")))
    assert np.array_equal(a, b)
    # a tree of parameters saved on its own (npz knows no bfloat16) as well
    tree = {"a": np.asarray(leaves[0]), "b": {"c": leaves[1]}}
    manifest = serialize._save_value(tree, str(tmp_path / "tree"))
    back = serialize._load_value(manifest, str(tmp_path / "tree"))
    assert manifest["kind"] == "pytree" and str(back["a"].dtype) == "bfloat16"
    assert np.array_equal(np.asarray(back["b"]["c"]), np.asarray(leaves[1]))


def test_put_params_says_how_many_bytes_of_which_dtype(ref, builder):
    from mmlspark_tpu.obs import trace

    cfg = tiny_config(num_hidden_layers=1)
    model, _ = model_with(builder, ref, cfg)
    through_the_pipeline(model, rows()[0])
    spans = [s for s in trace.default_tracer().spans() if s["name"] == "put_params"]
    nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(model.params))
    assert spans and spans[-1]["attrs"]["bytes"] == nbytes
    assert spans[-1]["attrs"]["dtypes"] == f"bfloat16={nbytes}"


# -- the flash schedule -------------------------------------------------------

# (T, window) -> (query block, key block, steps a query block): the language
# cells' attention layers (attn_window / attn_full in kexaone.score, the
# differential pair in phi4flash.score, attn_mla in xing4.score,
# attn_mla_sparse in axk2.score), then tiny ones
FLASH_SCHEDULES = [
    (4096, 128, (256, 128, 3)), (4096, 0, (512, 512, 8)), (32768, 512, (256, 128, 6)),
    (32768, 0, (512, 512, 64)), (16384, 0, (512, 512, 32)),
    (128, 0, (128, 128, 1)), (256, 64, (256, 128, 2)), (384, 130, (128, 128, 3)),
    (512, 0, (512, 512, 1)), (512, 130, (256, 128, 4)), (640, 0, (128, 128, 5)),
    (1024, 512, (256, 128, 6)), (1024, 100, (256, 128, 3)),
]


@pytest.mark.parametrize("T,window,blocks", FLASH_SCHEDULES)
def test_the_flash_schedule_computes_and_fetches_the_band(T, window, blocks):
    """The key blocks a query block computes are exactly those holding a key
    one of its queries sees (causal, inside the window), one a step, in
    order, the diagonal's last; a step that computes nothing fetches the
    block the step before it fetched."""
    s = transformer.FlashSchedule(T, window)
    assert (s.bq, s.bk, s.steps, s.blocks) == (*blocks, T // blocks[0])
    qi, j = np.arange(s.blocks)[:, None], np.arange(s.steps)[None, :]
    kb = np.broadcast_to(np.asarray(s.key_block(qi, j)), (s.blocks, s.steps))
    last = np.asarray(s.diagonal(qi))
    fetched = np.asarray(s.fetched(qi, j))
    # a block pair holds a seen (query, key) where q - k, over [q0 - k1, q1 - k0],
    # meets [0, window - 1]
    k0 = np.arange(T // s.bk)[None, :] * s.bk
    q0 = qi * s.bq
    band = (q0 + s.bq - 1 - k0 >= 0) & (q0 - (k0 + s.bk - 1) <= (window or T) - 1)
    for i in range(s.blocks):
        computes = kb[i] <= last[i]
        assert list(kb[i][computes]) == list(np.flatnonzero(band[i]))
        assert kb[i][computes][-1] == last[i]
        assert (fetched[i][computes] == kb[i][computes]).all()
        idle = np.flatnonzero(~computes)
        assert (fetched[i][idle] == fetched[i][idle - 1]).all() and 0 not in idle


# -- the kernels, in the interpreter ----------------------------------------

@pytest.mark.parametrize("window", [0, 128, 200])
def test_the_attention_kernel_agrees_with_the_plain_form(window):
    rng = np.random.default_rng(window)
    B, H, KV, D, t = 2, 4, 2, 128, 512
    q, k, v = (jnp.asarray(rng.normal(size=(B, t, n * D)), jnp.bfloat16)
               for n in (H, KV, KV))
    got = transformer.gqa_pallas(q, k, v, window, H, KV, interpret=True)
    want = transformer._gqa_flat_xla(*(a.astype(jnp.float32) for a in (q, k, v)),
                                     window, H, KV)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 0.02


def test_the_plain_window_form_is_the_masked_softmax():
    rng = np.random.default_rng(5)
    B, H, KV, D, t, window = 2, 4, 2, 8, 24, 5        # T no multiple of the window
    q, k, v = (jnp.asarray(rng.normal(size=(B, t, n, D)), jnp.float32)
               for n in (H, KV, KV))
    kk, vv = (jnp.repeat(a, H // KV, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(D)
    pos = np.arange(t)
    for w in (window, 0):
        seen = (pos[None, :] <= pos[:, None]) & ((pos[None, :] > pos[:, None] - w) | (w == 0))
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        want = jnp.einsum("bhqk,bkhd->bqhd", p, vv)
        assert float(jnp.abs(transformer.gqa_xla(q, k, v, w) - want).max()) < 1e-5


def test_the_grouped_product_kernel_agrees_with_the_ragged_product():
    rng = np.random.default_rng(2)
    lhs = jnp.asarray(rng.normal(size=(2048, 1024)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(4, 1024, 2048)) / 32, jnp.bfloat16)
    sizes = jnp.asarray([512, 0, 1024, 0], jnp.int32)   # 512 rows past the groups
    got = moe.gmm_pallas(lhs, rhs, sizes, jnp.float32, interpret=True)
    want = moe._gmm_ragged(lhs, rhs, sizes, jnp.float32)
    assert float(jnp.abs(got - want)[:1536].max()) < 1e-4
    assert float(jnp.abs(got[1536:]).max()) == 0.0


def _routing(idx, first, held, tile, rows_):
    """What `ExpertLayer.apply_with_load` derives from the router's choices
    `idx [N, K]`, by hand in numpy: whether each choice is held here, its
    place among the rows sorted by expert (every expert's rows padded to
    `tile`), each padded row's visit (`N * K` for padding) and expert
    (`held` past the last), and the experts' visits; whole trips of `rows_`."""
    N, K = idx.shape
    local = idx - first
    mine = (local >= 0) & (local < held)
    local = np.where(mine, local, held)
    order = np.argsort(local.reshape(-1), kind="stable")
    counts = np.bincount(local.reshape(-1), minlength=held + 1)[:held]
    padded = -(-counts // tile) * tile
    p_start, start = np.cumsum(padded) - padded, np.cumsum(counts) - counts
    visit = np.full(-(-padded.sum() // rows_) * rows_, N * K, np.int32)
    group = np.full(len(visit), held, np.int32)
    place = np.zeros(N * K, np.int32)
    for e in range(held):
        mine_e = order[start[e]:start[e] + counts[e]]
        visit[p_start[e]:p_start[e] + counts[e]] = mine_e
        group[p_start[e]:p_start[e] + padded[e]] = e
        place[mine_e] = p_start[e] + np.arange(counts[e])
    return mine, place.reshape(N, K), visit, group, p_start, counts


def _hand_built_choices(one_visit: bool):
    """200 tokens choosing 3 of 8 experts, of which 2-5 are held here: token 0
    has no visit here, token 1 three, expert 4 (the third held) no rows; or
    every token at most one visit here."""
    rng = np.random.default_rng(11)
    N, K, first = 200, 3, 2
    if one_visit:
        idx = np.stack([rng.permutation([0, 1, 6, 7])[:K] for _ in range(N)])
        here = rng.integers(0, 2, N).astype(bool)
        idx[here, rng.integers(0, K, N)[here]] = rng.choice([2, 3, 5], here.sum())
        return idx.astype(np.int32), first
    idx = np.stack([rng.permutation([0, 1, 2, 3, 5, 6, 7])[:K] for _ in range(N)])
    idx[0], idx[1] = [0, 1, 6], [5, 2, 3]
    return idx.astype(np.int32), first


@pytest.mark.parametrize("case", ["first-trip", "second-trip", "one-visit-a-token"])
def test_the_combine_kernel_agrees_with_the_scan(case, monkeypatch):
    """`moe_combine` in the interpreter against `combine_xla` on a routing
    built by hand: groups of 79-94 rows padded to 16, so the runs of a tile
    of 64 tokens straddle the chunks of 128 rows; 200 tokens are three tiles
    and a part of a fourth; `y` arrives non-zero; two column blocks."""
    monkeypatch.setattr(moe, "COMBINE_TILE", (64, 128))
    idx, first = _hand_built_choices(case == "one-visit-a-token")
    (N, K), held, D, rows_ = idx.shape, 4, 256, 256
    mine, place, visit, group, p_start, counts = _routing(idx, first, held, 16, rows_)
    if case != "one-visit-a-token":
        assert not mine[0].any() and mine[1].all() and counts[2] == 0
        assert len(visit) == 2 * rows_ and len(set(counts % 16)) > 1
        assert p_start[3] < rows_ < p_start[3] + counts[3]   # a group cut by the trip
    rng = np.random.default_rng(12)
    gate = jnp.asarray(rng.uniform(0.1, 1.0, (N, K)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    out = jnp.asarray(rng.normal(size=(len(visit), D)), jnp.bfloat16)   # padding too
    at = rows_ if case == "second-trip" else 0
    want = moe.combine_xla(y, out[at:at + rows_], gate, jnp.asarray(place),
                           jnp.asarray(mine), at)
    got = moe.combine_pallas(y, out[at:at + rows_], gate, jnp.asarray(visit[at:at + rows_]),
                             jnp.asarray(group[at:at + rows_]), held, interpret=True)
    assert float(jnp.abs(want - y).max()) > 0.1         # the trip added something
    if case == "one-visit-a-token":
        assert bool(jnp.all(got == want))
    assert float(jnp.abs(got - want).max()) <= 1e-6 * float(jnp.abs(want).max())


RULE = [   # experts held, the router's experts, backend, operands: the kernel?
    (16, 128, "tpu", jnp.bfloat16, True),
    (64, 64, "tpu", jnp.bfloat16, False),       # every pass of the scan is needed
    (16, 128, "cpu", jnp.bfloat16, False),
    (16, 128, "gpu", jnp.bfloat16, False),
    (16, 128, "tpu", jnp.float32, False),       # where `moe_gmm` does not run either
]


@pytest.mark.parametrize("held,experts,backend,dtype,kernel", RULE)
def test_the_combines_form_follows_from_the_layers_own_shape(held, experts, backend,
                                                             dtype, kernel, monkeypatch):
    import inspect

    assert moe._combine_kernel_applies(held, experts, backend, dtype) is kernel
    assert list(inspect.signature(moe._combine_kernel_applies).parameters) \
        == ["experts_held", "num_experts", "backend", "dtype"]
    asked = []
    monkeypatch.setattr(moe, "_combine_kernel_applies",
                        lambda *a: asked.append(a) or False)
    layer = moe.ExpertLayer(experts, held, 2, 8)
    params, _ = layer.init(jax.random.key(0), (T, 16))
    with matmul_precision("float32"):
        layer.apply(params, jnp.ones((1, T, 16), jnp.float32))
    assert asked == [(held, experts, "cpu", jnp.float32)]
    assert not {"combine", "kernel"} & {
        p for p in inspect.signature(moe.ExpertLayer.__init__).parameters}


@pytest.mark.parametrize("add_to", [False, True])
def test_the_gradient_through_the_combine_kernel_is_the_scans(add_to, monkeypatch,
                                                              interpreted):
    """A small `ExpertLayer` whose combine is the kernel (in the interpreter,
    groups padded to its chunk of 128 rows): values and gradients, with
    respect to the input, every parameter and `add_to`, are the plain form's."""
    layer = moe.ExpertLayer(8, 4, 3, 32, scale=2.5, first_expert=2)
    params, _ = layer.init(jax.random.key(1), (T, 64))
    x = jax.random.normal(jax.random.key(2), (3, T, 64), jnp.float32)
    base = jax.random.normal(jax.random.key(3), (3, T, 64), jnp.float32)
    tilt = jnp.cos(jnp.arange(64, dtype=jnp.float32))

    def loss(params, x, base):
        with matmul_precision("float32"):
            y, _ = layer.apply_with_load(params, x, add_to=base if add_to else None)
        return jnp.sum(y * tilt), y

    (_, want_y), want = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(params, x, base)
    monkeypatch.setattr(moe, "_gmm_tile_rows", lambda x: 128)
    monkeypatch.setattr(moe, "grouped_matmul",
                        lambda lhs, rhs, sizes, dt, tile: moe._gmm_ragged(lhs, rhs, sizes, dt))
    monkeypatch.setattr(moe, "_combine_kernel_applies", lambda *a: True)
    grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)
    calls = interpreted(grads, params, x, base)
    (_, got_y), got = grads(params, x, base)
    assert calls == ["moe_combine"]
    assert float(jnp.abs(got_y - want_y).max()) <= 1e-6 * float(jnp.abs(want_y).max())
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) <= 1e-5 * max(float(jnp.abs(b).max()), 1e-30)


@pytest.mark.parametrize("case", ["first-trip", "second-trip"])
@pytest.mark.parametrize("width", [1024, 1536])
def test_the_gathering_product_is_the_grouped_products_to_the_last_bit(case, width):
    """`moe_gmm_gather` in the interpreter against `gmm_pallas` on the rows
    XLA gathers, on a routing built by hand: 200 tokens, 3 choices, experts
    2-5 of 8 held, expert 4 with no visits; each group of 79-94 visits padded
    to a tile of 512, so a tile's pads follow its rows; trips of 1,024 rows,
    the second one live tile and one past the last group's rows; 2 or 3
    contraction steps (1,536 is no multiple of 1,024: its copies carry pad
    lanes) and 2 column tiles."""
    idx, first = _hand_built_choices(False)
    (N, K), held, tile, rows_ = idx.shape, 4, moe.GMM_TILE[0], 1024
    mine, place, visit, group, p_start, counts = _routing(idx, first, held, tile, rows_)
    assert counts[2] == 0 and len(visit) == 2 * rows_ and (counts[counts > 0] % tile).all()
    rng = np.random.default_rng(width)
    x = jnp.asarray(rng.normal(size=(N, width)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(held, width, 2048)) / 32, jnp.bfloat16)
    at = rows_ if case == "second-trip" else 0
    padded = -(-counts // tile) * tile
    p_end = np.cumsum(padded)
    sizes = jnp.asarray(np.clip(p_end - at, 0, rows_) - np.clip(p_end - padded - at, 0, rows_),
                        jnp.int32)
    token = jnp.asarray(np.where(visit < N * K, visit // K, -1)[at:at + rows_])
    xg = jnp.where((token >= 0)[:, None], x[jnp.maximum(token, 0)], 0).astype(jnp.bfloat16)
    want = moe.gmm_pallas(xg, w, sizes, jnp.bfloat16, interpret=True)
    got = moe.gmm_gather_pallas(moe.gather_layout(x), token, w, sizes, jnp.bfloat16,
                                interpret=True)
    assert float(jnp.abs(want[:512].astype(jnp.float32)).max()) > 0.1
    assert bool(jnp.all(got == want))


@pytest.mark.parametrize("tokens,width", [(512, 1024), (300, 1536), (200, 384)])
def test_the_layout_kernel_is_the_plain_layout(tokens, width):
    """`moe_gather_layout` in the interpreter: steps of 256 tokens (300 is a
    step and part of one), widths whose tiles end in pad lanes (1,536 and
    384 are no multiple of 1,024), a bfloat16 input widened exactly."""
    x = jnp.asarray(np.random.default_rng(tokens).normal(size=(tokens, width)), jnp.bfloat16)
    got = moe.gather_layout_pallas(x, interpret=True)
    assert got.shape == (tokens, moe._gather_lanes(width), 128) and got.dtype == jnp.float32
    assert bool(jnp.all(got == moe.gather_layout(x)))
    assert bool(jnp.all(got.reshape(tokens, -1)[:, :width] == x.astype(jnp.float32)))


def _with_the_kernels_in_the_interpreter(monkeypatch):
    """An `ExpertLayer` whose products are the kernels in the interpreter:
    rows padded to a tile of 128; the first product the gathering one while
    the list returned holds True."""
    from mmlspark_tpu.models import paired

    gathers = [False]
    monkeypatch.setattr(paired, "INTERPRET", True)
    monkeypatch.setattr(moe, "GMM_TILE", (128, 256, 256))
    monkeypatch.setattr(moe, "_gmm_tile_rows", lambda x: 128)
    monkeypatch.setattr(moe, "_gmm_gathers", lambda *a: gathers[0])
    return gathers


def test_the_expert_layer_through_the_gathering_product_is_the_plain_forms(monkeypatch):
    layer = moe.ExpertLayer(8, 4, 3, 128, scale=2.5, first_expert=2)
    params, _ = layer.init(jax.random.key(4), (T, 512))
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    x = jax.random.normal(jax.random.key(5), (3, T, 512), jnp.float32)
    outs, gathers = [], _with_the_kernels_in_the_interpreter(monkeypatch)
    for gathers[0] in (False, True):
        outs.append(layer.apply_with_load(params, x))
    (want, want_load), (got, got_load) = outs
    assert float(jnp.abs(want).max()) > 0.1
    assert bool(jnp.all(got == want)) and bool(jnp.all(got_load == want_load))


def test_the_gradient_through_the_gathering_product_is_the_plain_forms(monkeypatch):
    """Values and gradients, with respect to the input and every parameter,
    through the gathering kernel's VJP against the plain gather's and
    product's."""
    layer = moe.ExpertLayer(8, 4, 3, 128, scale=2.5, first_expert=2)
    params, _ = layer.init(jax.random.key(6), (T, 256))
    x = jax.random.normal(jax.random.key(7), (2, T, 256), jnp.float32)
    tilt = jnp.cos(jnp.arange(256, dtype=jnp.float32))

    def loss(params, x):
        y, _ = layer.apply_with_load(params, x)
        return jnp.sum(y * tilt), y

    runs, gathers = [], _with_the_kernels_in_the_interpreter(monkeypatch)
    for gathers[0] in (False, True):
        runs.append(jax.value_and_grad(loss, (0, 1), has_aux=True)(params, x))
    ((_, want_y), want), ((_, got_y), got) = runs
    assert bool(jnp.all(got_y == want_y))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) <= 1e-5 * max(float(jnp.abs(b).max()), 1e-30)


GATHER_RULE = [   # backend, operands, contraction width, columns: the gathering form?
    ("tpu", jnp.bfloat16, 6144, 4096, True),          # kexaone.score
    ("tpu", jnp.bfloat16, 3584, 2048, True),          # xing4.score: no multiple of 1,024
    ("tpu", jnp.bfloat16, 7168, 4096, True),          # axk2.score
    ("cpu", jnp.bfloat16, 6144, 4096, False),
    ("gpu", jnp.bfloat16, 6144, 4096, False),
    ("tpu", jnp.float32, 6144, 4096, False),          # where `moe_gmm` does not run either
    ("tpu", jnp.bfloat16, 600, 4096, False),          # no whole tiles of 128 lanes
    ("tpu", jnp.bfloat16, 30720, 4096, False),        # two row tiles do not fit VMEM
]


@pytest.mark.parametrize("backend,dtype,width,columns,gathers", GATHER_RULE)
def test_the_first_products_form_follows_from_backend_dtype_and_width(
        backend, dtype, width, columns, gathers, monkeypatch):
    import inspect

    assert moe._gmm_gathers(backend, dtype, width, columns) is gathers
    asked = []
    monkeypatch.setattr(moe, "_gmm_gathers", lambda *a: asked.append(a) or False)
    layer = moe.ExpertLayer(8, 4, 2, 8)
    params, _ = layer.init(jax.random.key(0), (T, 16))
    with matmul_precision("float32"):
        layer.apply(params, jnp.ones((1, T, 16), jnp.float32))
    assert asked == [("cpu", jnp.float32, 16, 16)]
    assert not {"gather", "gathers"} & set(inspect.signature(moe.ExpertLayer.__init__).parameters)


# -- the kernels at the published widths, compiled for a described v5e ------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("window", [128, 0])
def test_the_attention_kernel_compiles_at_the_published_widths(window, one_chip):
    B, t, H, KV, D = 2, 4096, 64, 8, 128
    q = jax.ShapeDtypeStruct((B, t, H * D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, t, KV * D), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(lambda q, k, v: transformer.gqa_pallas(q, k, v, window, H, KV),
                          q, kv, kv)
    assert "tpu_custom_call" in text
    assert ("attn_window" if window else "attn_full") in text


def test_the_grouped_product_kernel_compiles_at_the_published_widths(one_chip):
    held, d, eff, rows_ = 16, 6144, 2048, 40960
    x = jax.ShapeDtypeStruct((rows_, d), jnp.bfloat16, sharding=one_chip)
    w1 = jax.ShapeDtypeStruct((held, d, 2 * eff), jnp.bfloat16, sharding=one_chip)
    w2 = jax.ShapeDtypeStruct((held, eff, d), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip)

    def both(x, w1, w2, sizes):
        hid = moe.gmm_pallas(x, w1, sizes, jnp.bfloat16)
        return moe.gmm_pallas(hid[:, :eff], w2, sizes, jnp.bfloat16)

    text = _compiled_text(both, x, w1, w2, sizes)
    assert text.count("moe_gmm") >= 2 and "tpu_custom_call" in text


def test_the_latent_attention_kernel_compiles_at_the_published_widths(one_chip):
    """Xing4.0's core: 32 heads, a 192-wide score (128 content lanes and 64
    rotary ones padded to a tile) against one shared rotary key, values of
    128, a row of 16,384."""
    B, t, H = 1, 16384, 32
    q = jax.ShapeDtypeStruct((B, t, H * 256), jnp.bfloat16, sharding=one_chip)
    kr = jax.ShapeDtypeStruct((B, t, 128), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(lambda q, kv, kr: transformer.mla_pallas(q, kv, kr, H, 128),
                          q, q, kr)
    assert "tpu_custom_call" in text and "attn_mla" in text


def test_the_stream_mix_kernels_compile_at_the_published_widths(one_chip):
    from mmlspark_tpu.models import residual

    n, d, tokens = 4, 3584, 16384
    x = jax.ShapeDtypeStruct((tokens, n * d), jnp.float32, sharding=one_chip)
    y = jax.ShapeDtypeStruct((tokens, d), jnp.float32, sharding=one_chip)
    phi = jax.ShapeDtypeStruct((n * d, 24), jnp.bfloat16, sharding=one_chip)
    pre = jax.ShapeDtypeStruct((1 + n,), jnp.float32, sharding=one_chip)
    h = jax.ShapeDtypeStruct((tokens, n + n * n), jnp.float32, sharding=one_chip)

    def both(x, y, phi, pre, h):
        m, ssq, x_in = residual.mhc_pre_pallas(x, phi, pre, n, 1e-6)
        return residual.mhc_post_pallas(x, y + x_in, h, n), m, ssq

    text = _compiled_text(both, x, y, phi, pre, h)
    assert "mhc_pre" in text and "mhc_post" in text and "tpu_custom_call" in text


def test_the_grouped_product_kernel_compiles_for_a_width_of_3584(one_chip):
    """Every expert held: 64 groups, 98,304 padded visit rows of a batch of
    16,384 positions; the down product's 3,584 columns in tiles of 896."""
    held, d, eff, rows_ = 64, 3584, 1024, 98304
    assert moe._whole_tile(d, moe.GMM_TILE[2]) == 896
    assert [moe._whole_tile(w, moe.GMM_TILE[2]) for w in (4096, 6144)] == [1024, 1024]
    x = jax.ShapeDtypeStruct((rows_, d), jnp.bfloat16, sharding=one_chip)
    w1 = jax.ShapeDtypeStruct((held, d, 2 * eff), jnp.bfloat16, sharding=one_chip)
    w2 = jax.ShapeDtypeStruct((held, eff, d), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip)

    def both(x, w1, w2, sizes):
        hid = moe.gmm_pallas(x, w1, sizes, jnp.bfloat16)
        return moe.gmm_pallas(hid[:, :eff], w2, sizes, jnp.bfloat16)

    text = _compiled_text(both, x, w1, w2, sizes)
    assert text.count("moe_gmm") >= 2 and "tpu_custom_call" in text


GATHER_SHAPES = {    # tokens, width, rows a trip, experts held, columns of `w1`
    "kexaone.score": (32768, 6144, 73728, 16, 4096),
    "xing4.score": (16384, 3584, 98304, 64, 2048),
    "axk2.score": (32768, 7168, 40960, 16, 4096),
}


@pytest.mark.parametrize("shape", sorted(GATHER_SHAPES))
def test_the_gathering_product_compiles_at_the_published_widths(shape, one_chip):
    N, d, rows_, held, cols = GATHER_SHAPES[shape]
    assert moe._gmm_gathers("tpu", jnp.bfloat16, d, cols)
    x = jax.ShapeDtypeStruct((N, d), jnp.bfloat16, sharding=one_chip)
    token = jax.ShapeDtypeStruct((rows_,), jnp.int32, sharding=one_chip)
    w1 = jax.ShapeDtypeStruct((held, d, cols), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip)
    text = _compiled_text(lambda x, token, w1, sizes: moe.gmm_gather_pallas(
        moe.gather_layout_pallas(x), token, w1, sizes, jnp.bfloat16), x, token, w1, sizes)
    assert "moe_gmm_gather" in text and "moe_gather_layout" in text
    assert "tpu_custom_call" in text


COMBINE_SHAPES = {   # tokens, width, rows a trip, experts held, top k
    "16-of-128-held": (32768, 6144, 73728, 16, 8),
}


@pytest.mark.parametrize("shape", sorted(COMBINE_SHAPES))
def test_the_combine_kernel_compiles_at_the_published_widths(shape, one_chip):
    N, d, rows_, held, top_k = COMBINE_SHAPES[shape]
    y = jax.ShapeDtypeStruct((N, d), jnp.float32, sharding=one_chip)
    out = jax.ShapeDtypeStruct((rows_, d), jnp.bfloat16, sharding=one_chip)
    gate = jax.ShapeDtypeStruct((N, top_k), jnp.float32, sharding=one_chip)
    visit = jax.ShapeDtypeStruct((rows_,), jnp.int32, sharding=one_chip)
    text = _compiled_text(lambda *a: moe.combine_pallas(*a, held), y, out, gate, visit, visit)
    assert "moe_combine" in text and "tpu_custom_call" in text


def test_the_selective_scan_kernel_compiles_at_the_published_widths(one_chip):
    """A piece of 8,192 positions of the hybrid model's 5,120 channels, 16
    states: five tiles of 1,024 channels, `B_t` and `C_t` blocked into SMEM."""
    from mmlspark_tpu.models import ssm

    B, t, C, N = 1, 8192, 5120, 16

    def of(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    text = _compiled_text(ssm.ssm_scan_pallas, of(B, t, C), of(B, t, C), of(B, t, N),
                          of(B, t, N), of(C, N), of(C), of(B, C, N))
    assert "tpu_custom_call" in text and "ssm_scan" in text


@pytest.mark.parametrize("form", ["forward", "reverse", "both"])
def test_the_lstm_kernel_compiles_at_the_taggers_widths(form, one_chip):
    """The tagger's batch: 8,192 rows of 128 positions, 50 wide, into 300
    hidden units a direction (16 row blocks of 512, 16 time blocks of 8);
    ``both`` as a ``BiLSTM`` runs them: the first direction's result, time-
    major and lane-padded, goes into the second's call, which writes
    ``[B, T, 600]``."""
    from mmlspark_tpu.models import attention

    B, t, D, H = 8192, 128, 50, 300

    def of(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def run(x, wx, wh, b):
        if form != "both":
            return attention.lstm_scan_pallas(x, wx, wh, b, form == "reverse")
        left = attention.lstm_scan_pallas(x, wx, wh, b, padded=True)
        return attention.lstm_scan_pallas(x, wx, wh, b, True, beside=left)

    text = _compiled_text(run, of(B, t, D), of(D, 4 * H), of(H, 4 * H), of(4 * H))
    assert "tpu_custom_call" in text and "lstm_scan" in text
    assert f"f32[8192,128,{600 if form == 'both' else 300}]" in text


def test_the_lstm_kernel_of_a_wider_layer_fits_the_chip(one_chip):
    """512 hidden units over 128-wide rows: ``_lstm_blocks`` takes 256 rows a
    step to stay within its VMEM budget, and the compiler agrees."""
    from mmlspark_tpu.models import attention

    B, t, D, H = 1024, 16, 128, 512
    assert attention._lstm_blocks(B, t, D, H) == (256, 8)

    def of(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def run(x, wx, wh, b):
        left = attention.lstm_scan_pallas(x, wx, wh, b, padded=True)
        return attention.lstm_scan_pallas(x, wx, wh, b, True, beside=left)

    text = _compiled_text(run, of(B, t, D), of(D, 4 * H), of(H, 4 * H), of(4 * H))
    assert "lstm_scan" in text and "f32[1024,16,1024]" in text


@pytest.mark.parametrize("window", [512, 0])
def test_the_differential_kernel_compiles_at_the_published_widths(window, one_chip):
    """20 query pairs over 10 key/value pairs of 128 lanes, a row of 32,768."""
    B, t = 1, 32768
    q = jax.ShapeDtypeStruct((B, t, 20 * 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, t, 10 * 128), jnp.bfloat16, sharding=one_chip)
    lam = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    gain = jax.ShapeDtypeStruct((128,), jnp.float32, sharding=one_chip)
    text = _compiled_text(lambda *a: transformer.diff_pallas(*a, window, 20, 10, 1e-5),
                          q, kv, kv, lam, gain)
    assert "tpu_custom_call" in text
    assert ("attn_window_diff" if window else "attn_full_diff") in text


def test_the_index_kernel_compiles_at_the_published_widths(one_chip):
    """A.X-K2's indexer: 64 heads of 128 against one key a position, the top
    2,048 of a row of 32,768, the scores of a block of 128 queries in VMEM."""
    B, t = 1, 32768
    qi = jax.ShapeDtypeStruct((B, t, 64 * 128), jnp.bfloat16, sharding=one_chip)
    ki = jax.ShapeDtypeStruct((B, t, 128), jnp.bfloat16, sharding=one_chip)
    wi = jax.ShapeDtypeStruct((B, t, 64), jnp.float32, sharding=one_chip)
    text = _compiled_text(lambda *a: transformer.mla_index_pallas(*a, 64, 2048), qi, ki, wi)
    assert "tpu_custom_call" in text and "mla_index" in text
    assert "s32[1,32768,1024]" in text                 # a bit a key


def test_the_sparse_latent_kernel_compiles_at_the_published_widths(one_chip):
    """A.X-K2's core: 64 heads, a 192-wide score padded to 256 against the
    shared rotary key, values of 128, masked by a selection's bits, a row of
    32,768."""
    B, t, H = 1, 32768, 64
    q = jax.ShapeDtypeStruct((B, t, H * 256), jnp.bfloat16, sharding=one_chip)
    kr = jax.ShapeDtypeStruct((B, t, 128), jnp.bfloat16, sharding=one_chip)
    bits = jax.ShapeDtypeStruct((B, t, transformer.selection_words(t)), jnp.int32,
                                sharding=one_chip)
    text = _compiled_text(lambda q, kv, kr, b: transformer.mla_sparse_pallas(
        q, kv, kr, b, H, 128), q, q, kr, bits)
    assert "tpu_custom_call" in text and "attn_mla_sparse" in text
