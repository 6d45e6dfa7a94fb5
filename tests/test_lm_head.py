"""The language model's head as one kernel (`transformer.head_logprob`): each
position's `logits[target] - logsumexp(logits)` from tiles of the head
product and an online logsumexp. In the Pallas interpreter against its plain
form (the head's blocks of positions): the untied head and the tied table, a
vocabulary whose last tile is partial, targets in the first and the last
tile and the pad id, a position count no multiple of the position tile, one
row and several; the gradient through the pair; the predicate; and the
models taking the kernel for both passes over the head."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mmlspark_tpu.models import transformer  # noqa: E402

HIDDEN = 128
VOCAB = 4 * 1024 + 300
TM = transformer.HEAD_ROWS


def _operands(rows, positions, tied, seed=0):
    tn = transformer._head_tiles(HIDDEN, VOCAB, tied)[1]
    assert VOCAB % tn                 # a partial last tile
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, positions, HIDDEN)), jnp.bfloat16)
    shape = (VOCAB, HIDDEN) if tied else (HIDDEN, VOCAB)
    head = jnp.asarray(rng.normal(size=shape) * HIDDEN ** -0.5, jnp.bfloat16)
    target = rng.integers(0, VOCAB, (rows, positions)).astype(np.int32)
    # the pad id, the first tile's ends, the last tile's first and last
    # column, and the last whole tile's last
    last = VOCAB // tn * tn
    ends = [0, 1, tn - 1, last, VOCAB - 1, last - 1, 0, tn]
    target[0, :len(ends)] = ends
    target[-1, -len(ends):] = ends
    return x, head, jnp.asarray(target)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("rows,positions", [(1, TM + 100), (3, TM // 2 + 37)],
                         ids=["one_row", "three_rows"])
def test_the_head_kernel_is_its_plain_form(tied, rows, positions):
    assert (rows * positions) % TM                     # a partial last tile of positions
    x, head, target = _operands(rows, positions, tied)
    got = transformer.head_logprob_pallas(x, head, target, tied, interpret=True)
    want = transformer.head_logprob_xla(x, head, target, tied)
    assert got.shape == want.shape == (rows, positions) and got.dtype == jnp.float32
    # the same products and float32 normaliser, summed in another order
    assert float(jnp.abs(got - want).max()) <= 2e-6 * float(jnp.abs(want).max())


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_the_gradient_through_the_pair_is_the_plain_forms(tied, interpreted):
    x, head, target = _operands(2, TM // 2 + 37, tied, seed=2)
    weight = jnp.asarray(np.random.default_rng(3).normal(size=target.shape), jnp.float32)

    def loss(fn):
        return lambda x, head: jnp.sum(weight * fn(x, head, target, tied))

    pair = jax.grad(loss(transformer._head_pair), argnums=(0, 1))
    plain = jax.grad(loss(transformer.head_logprob_xla), argnums=(0, 1))
    assert interpreted(pair, x, head) == ["head_logprob"]
    for a, b in zip(pair(x, head), plain(x, head)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                           rtol=1e-6, atol=0)


# the five scorer cells' heads: hidden, vocabulary
CELL_HEADS = {"kexaone.score": (6144, 19200), "xing4.score": (3584, 131072),
              "phi4flash.score": (2560, 200064), "axk2.score": (7168, 20480),
              "glm47flash.score": (2048, 154880)}


def test_the_head_takes_the_kernel_on_a_tpu_with_bfloat16_operands_alone():
    applies = transformer._head_kernel_applies
    for hidden, vocab in CELL_HEADS.values():
        assert applies("tpu", jnp.bfloat16, hidden, vocab)
        assert not applies("cpu", jnp.bfloat16, hidden, vocab)
        assert not applies("tpu", jnp.float32, hidden, vocab)
    assert not applies("tpu", jnp.bfloat16, 300, 130000)        # no whole lane tile
    assert not applies("tpu", jnp.bfloat16, 64 * 1024, 32000)    # no tile fits VMEM


def _ids(positions, rows=2):
    ids = np.random.default_rng(4).integers(1, 64, (rows, positions), dtype=np.int32)
    ids[0, positions // 2:] = 0                   # a row cut short: its pads' targets
    return jnp.asarray(ids)


@pytest.mark.parametrize("name", ["causal_lm", "latent_causal_lm_mtp", "hybrid_causal_lm"])
def test_the_models_take_the_head_kernel_for_every_pass(name, monkeypatch, interpreted):
    """The untied head (one pass, and two with the multi-token-prediction
    module) and the tied table of the hybrid model: the kernel in the
    interpreter where the plain form ran, every position (pads too) the
    plain form's up to the order of the float32 sums."""
    common = dict(dense_hidden=64, expert_hidden=32, num_experts=4, experts_held=4,
                  top_k=2, param_dtype="bfloat16", seed=3)
    t = 256
    model = {
        "causal_lm": lambda: transformer.causal_lm(
            t, 64, 128, 2, 1, 128, [128, 0], [False, True], **common),
        "latent_causal_lm_mtp": lambda: transformer.latent_causal_lm(
            t, nextn=1, vocab_size=64, hidden=128, heads=2, q_rank=32, kv_rank=32,
            nope=128, rope=64, v_dim=128, sparse=[False, True], **common),
        "hybrid_causal_lm": lambda: transformer.hybrid_causal_lm(
            t, 64, 256, 4, 2, 8, 128, 128, expand=4, d_state=4, param_dtype="bfloat16",
            seed=2),
    }[name]()
    mtp = name.endswith("mtp")
    ids = _ids(t)

    def forward(p, x):
        out = {}
        taps = {transformer.CausalLM.MTP} if mtp else None
        return model.module.apply(p, x, taps=taps, taps_out=out), out

    plain = forward(model.params, ids)
    monkeypatch.setattr(transformer, "_head_kernel_applies", lambda *a: True)
    assert interpreted(forward, model.params, ids) == ["head_logprob"] * (1 + mtp)
    kernel = forward(model.params, ids)
    pairs = [(kernel[0], plain[0])] + [(kernel[1][k], plain[1][k]) for k in plain[1]]
    for a, b in pairs:
        assert a.shape == b.shape == ids.shape
        assert float(jnp.abs(a - b).max()) <= 2e-6 * float(jnp.abs(b).max())


def test_the_head_tiles_at_the_scorer_cells():
    """512 positions a tile; the widest vocabulary tile within VMEM and the
    logits' budget, one that divides the vocabulary where one is at least
    half as wide (not 200,064 = 1,563 x 128: a partial last tile of rows of
    the tied table)."""
    tiles = {cell: transformer._head_tiles(h, v, cell == "phi4flash.score")
             for cell, (h, v) in CELL_HEADS.items()}
    assert tiles == {"kexaone.score": (512, 768), "xing4.score": (512, 2048),
                     "phi4flash.score": (512, 1024), "axk2.score": (512, 1024),
                     "glm47flash.score": (512, 1408)}
