"""Sequence models: ring attention == dense attention on a real 8-device
seq mesh, transformer encoder, BiLSTM tagger."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.models import (
    BiLSTM,
    LSTM,
    MultiHeadAttention,
    bilstm_tagger,
    dense_attention,
    ring_attention,
    transformer_encoder,
)
from mmlspark_tpu.models import attention
from mmlspark_tpu.models.module import matmul_precision
from mmlspark_tpu.parallel import MeshSpec, make_mesh


@pytest.fixture(scope="module")
def seq_mesh():
    return make_mesh(MeshSpec(data=1, seq=8))


def _qkv(B=2, T=32, H=2, D=8, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
                 for _ in range(3))


class TestRingAttention:
    def _run_ring(self, mesh, q, k, v, causal):
        spec = P(None, "seq", None, None)

        def fn(q, k, v):
            return ring_attention(q, k, v, "seq", 8, causal=causal)

        f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3,
                                  out_specs=spec))
        return np.asarray(f(q, k, v))

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, seq_mesh, causal):
        q, k, v = _qkv()
        with matmul_precision("float32"):
            want = np.asarray(dense_attention(q, k, v, causal=causal))
            got = self._run_ring(seq_mesh, q, k, v, causal)
        np.testing.assert_allclose(got, want, atol=2e-5)

    def test_long_sequence_memory_shape(self, seq_mesh):
        """Each chip only ever holds [T_local, T_local] score blocks."""
        q, k, v = _qkv(B=1, T=64, H=1, D=4, seed=1)
        got = self._run_ring(seq_mesh, q, k, v, False)
        with matmul_precision("float32"):
            want = np.asarray(dense_attention(q, k, v))
        np.testing.assert_allclose(got, want, atol=2e-5)

    def test_grads_flow_through_ring(self, seq_mesh):
        q, k, v = _qkv(B=1, T=16, H=1, D=4, seed=2)
        spec = P(None, "seq", None, None)

        def loss(q, k, v):
            o = ring_attention(q, k, v, "seq", 8, causal=False)
            return jnp.sum(o * o)

        inner = jax.shard_map(
            lambda q, k, v: jax.grad(loss, argnums=(0, 1, 2))(q, k, v),
            mesh=seq_mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 3)
        gq, gk, gv = jax.jit(inner)(q, k, v)
        for g in (gq, gk, gv):
            arr = np.asarray(g)
            assert np.isfinite(arr).all()
            assert np.abs(arr).max() > 0


class TestDenseAttentionOffsets:
    def test_blockwise_causal_offsets_no_nan(self):
        """A query block strictly BEFORE every key in the block (the sharded
        causal edge) yields zeros, not NaN."""
        with matmul_precision("float32"):
            q, k, v = _qkv(B=1, T=4, H=1, D=4, seed=5)
            out = dense_attention(q, k, v, causal=True,
                                  q_offset=0, k_offset=100)
            arr = np.asarray(out)
            assert np.isfinite(arr).all()
            np.testing.assert_allclose(arr, 0.0, atol=0)

    def test_blockwise_offsets_recompose_full_causal(self):
        """Manual two-block streaming with offsets == full causal attention."""
        import math

        with matmul_precision("float32"):
            q, k, v = _qkv(B=1, T=8, H=1, D=4, seed=6)
            want = np.asarray(dense_attention(q, k, v, causal=True))
            # second query block (rows 4..7) attends to both key blocks
            qb = q[:, 4:]
            full = np.asarray(dense_attention(
                qb, k, v, causal=True, q_offset=4, k_offset=0))
            np.testing.assert_allclose(full, want[:, 4:], atol=1e-5)


class TestFlashDispatch:
    """Gate logic for the Pallas flash-attention route (the kernel itself
    only runs on TPU; equivalence there is proven by the TPU-gated test
    below and by chip_smoke.py's kernels phase)."""

    def test_gates_keep_cpu_and_f32_on_xla_path(self):
        from mmlspark_tpu.models.attention import _flash_dispatch

        q, k, v = _qkv(B=1, T=128, H=2, D=64)
        # f32 inputs: stay exact
        assert _flash_dispatch(q, k, v, False, 0, 0) is None
        qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
        # bf16 but CPU backend: no pallas kernel
        if jax.default_backend() != "tpu":
            assert _flash_dispatch(qb, kb, vb, False, 0, 0) is None

    def test_gates_reject_unsupported_shapes(self, monkeypatch):
        from mmlspark_tpu.models import attention as A

        # pretend TPU + drop the length threshold so only shape gates decide
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setenv("MMLSPARK_TPU_FLASH_MIN_T", "64")
        q, k, v = (a.astype(jnp.bfloat16) for a in _qkv(B=1, T=96, H=2, D=64))
        assert A._flash_dispatch(q, k, v, False, 0, 0) is None  # T%128
        q, k, v = (a.astype(jnp.bfloat16) for a in _qkv(B=1, T=128, H=2, D=48))
        assert A._flash_dispatch(q, k, v, False, 0, 0) is None  # head dim
        q, k, v = (a.astype(jnp.bfloat16) for a in _qkv(B=1, T=128, H=2, D=64))
        assert A._flash_dispatch(q, k, v, False, 4, 0) is None  # shard offset
        monkeypatch.setenv("MMLSPARK_TPU_NO_FLASH", "1")
        assert A._flash_dispatch(q, k, v, False, 0, 0) is None  # kill switch

    @pytest.mark.skipif(jax.default_backend() != "tpu",
                        reason="flash kernel is TPU-only")
    def test_flash_matches_xla_on_tpu(self, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_FLASH_MIN_T", "128")
        q, k, v = (a.astype(jnp.bfloat16)
                   for a in _qkv(B=2, T=256, H=4, D=64, seed=3))
        for causal in (False, True):
            got = np.asarray(dense_attention(q, k, v, causal=causal),
                             dtype=np.float32)
            monkeypatch.setenv("MMLSPARK_TPU_NO_FLASH", "1")
            want = np.asarray(dense_attention(q, k, v, causal=causal),
                              dtype=np.float32)
            monkeypatch.delenv("MMLSPARK_TPU_NO_FLASH")
            assert np.abs(got - want).max() < 0.05  # bf16-scale agreement


class TestMultiHeadAttention:
    def test_module_dense_path(self):
        mha = MultiHeadAttention(num_heads=2)
        params, out_shape = mha.init(jax.random.key(0), (8, 16))
        assert out_shape == (8, 16)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 8, 16)),
                        dtype=jnp.float32)
        y = mha.apply(params, x)
        assert y.shape == (3, 8, 16)
        assert np.isfinite(np.asarray(y)).all()

    def test_causal_is_causal(self):
        """Changing a future token must not change earlier outputs."""
        with matmul_precision("float32"):
            mha = MultiHeadAttention(num_heads=1, causal=True)
            params, _ = mha.init(jax.random.key(0), (6, 8))
            rng = np.random.default_rng(1)
            x = rng.normal(size=(1, 6, 8)).astype(np.float32)
            y1 = np.asarray(mha.apply(params, jnp.asarray(x)))
            x2 = x.copy()
            x2[0, -1] += 10.0  # perturb the LAST token only
            y2 = np.asarray(mha.apply(params, jnp.asarray(x2)))
        np.testing.assert_allclose(y1[0, :-1], y2[0, :-1], atol=1e-5)
        assert np.abs(y1[0, -1] - y2[0, -1]).max() > 1e-3


class TestTransformer:
    def test_encoder_forward_and_taps(self):
        m = transformer_encoder(seq_len=12, dim=16, depth=2, num_heads=2,
                                vocab_size=50, num_classes=None)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, 50, size=(2, 12))
        out = np.asarray(m.apply(jnp.asarray(toks)))
        assert out.shape == (2, 12, 16)
        tapped = np.asarray(m.apply(jnp.asarray(toks), tap="block0"))
        assert tapped.shape == (2, 12, 16)
        assert m.layer_names[0] == "ln_f"

    def test_ring_encoder_matches_dense_encoder(self, seq_mesh):
        """The SAME weights run dense single-chip and ring-parallel under
        shard_map; outputs agree — the module is mesh-agnostic."""
        with matmul_precision("float32"):
            dense_m = transformer_encoder(seq_len=16, dim=8, depth=1,
                                          num_heads=1)
            ring_m = transformer_encoder(seq_len=16, dim=8, depth=1,
                                         num_heads=1, ring_axis="seq",
                                         ring_axis_size=8)
            ring_m = type(ring_m)(ring_m.module, dense_m.params,
                                  ring_m.input_shape, ring_m.layer_names,
                                  ring_m.name)
            rng = np.random.default_rng(3)
            x = jnp.asarray(rng.normal(size=(2, 16, 8)).astype(np.float32))
            want = np.asarray(dense_m.apply(x))

            spec = P(None, "seq", None)

            def fn(params, x):
                return ring_m.module.apply(params, x)

            f = jax.jit(jax.shard_map(
                fn, mesh=seq_mesh, in_specs=(P(), spec), out_specs=spec))
            got = np.asarray(f(ring_m.params, x))
        np.testing.assert_allclose(got, want, atol=5e-5)


def _plain_lstm(params, x, reverse):
    """The recurrence as one ``lax.scan`` step a position (what ``LSTM.apply``
    was before it blocked its scan): the yardstick for gradients and for the
    structure guard."""
    wx, wh, b = (jnp.asarray(params[k]) for k in ("wx", "wh", "b"))
    xp = jnp.swapaxes(jnp.einsum("btd,dk->btk", x, wx) + b, 0, 1)

    def cell(carry, xt):
        hprev, cprev = carry
        gates = xt + hprev @ wh
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * cprev + jax.nn.sigmoid(i) * jnp.tanh(g)
        hh = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (hh, c), hh

    zeros = jnp.zeros((x.shape[0], wh.shape[0]), jnp.float32)
    _, ys = jax.lax.scan(cell, (zeros, zeros), xp, reverse=reverse)
    return jnp.swapaxes(ys, 0, 1)


def _scan_output_writes(hlo_text, out_elems):
    """Element counts of the update of every ``dynamic-update-slice`` of the
    optimised HLO whose result holds ``out_elems`` elements (the writes into
    the scan's whole output)."""
    def elems(dims):
        return int(np.prod([int(d) for d in dims.split(",") if d] or [1]))

    shapes, writes = {}, []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = \w+\[([0-9,]*)\]", line)
        if not m:
            continue
        shapes[m.group(1)] = elems(m.group(2))
        if " dynamic-update-slice(" in line and shapes[m.group(1)] == out_elems:
            update = re.search(r"dynamic-update-slice\(%?[\w.-]+, %?([\w.-]+)",
                               line).group(1)
            writes.append(shapes[update])
    return writes


class TestLSTM:
    @pytest.mark.parametrize("reverse", [False, True])
    @pytest.mark.parametrize("T", [1, 4, 15, 16, 17, 33, 128])
    def test_scan_matches_manual_loop(self, T, reverse):
        with matmul_precision("float32"):
            lstm = LSTM(hidden=5, reverse=reverse)
            params, out_shape = lstm.init(jax.random.key(0), (T, 3))
            assert out_shape == (T, 5)
            rng = np.random.default_rng(T)
            x = rng.normal(size=(2, T, 3)).astype(np.float32)
            ys = np.asarray(lstm.apply(params, jnp.asarray(x)))
            assert ys.shape == (2, T, 5)
            # manual numpy re-implementation
            wx, wh, b = (np.asarray(params[k]) for k in ("wx", "wh", "b"))

            def sig(a):
                return 1 / (1 + np.exp(-a))

            h = np.zeros((2, 5))
            c = np.zeros((2, 5))
            for t in (range(T - 1, -1, -1) if reverse else range(T)):
                gates = x[:, t] @ wx + b + h @ wh
                i, f, g, o = np.split(gates, 4, axis=-1)
                c = sig(f) * c + sig(i) * np.tanh(g)
                h = sig(o) * np.tanh(c)
                np.testing.assert_allclose(ys[:, t], h, atol=1e-5)

    @pytest.mark.parametrize("reverse", [False, True])
    def test_gradients_match_plain_scan(self, reverse):
        """The training path differentiates through the blocked scan: its
        gradients are the plain scan's (T = 19: whole blocks and a rest)."""
        with matmul_precision("float32"):
            lstm = LSTM(hidden=5, reverse=reverse)
            params, _ = lstm.init(jax.random.key(1), (19, 3))
            params = {k: jnp.asarray(v) for k, v in params.items()}
            rng = np.random.default_rng(2)
            x = jnp.asarray(rng.normal(size=(3, 19, 3)).astype(np.float32))
            w = jnp.asarray(rng.normal(size=(3, 19, 5)).astype(np.float32))

            def loss(fn):
                return lambda p, v: jnp.sum(w * fn(p, v))

            got = jax.grad(loss(lstm.apply), argnums=(0, 1))(params, x)
            want = jax.grad(loss(lambda p, v: _plain_lstm(p, v, reverse)),
                            argnums=(0, 1))(params, x)
            for g, e in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                           rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("reverse", [False, True])
    def test_scan_output_is_written_by_blocks(self, reverse):
        """The structure, on the CPU: at T = 128 no write into the scan's
        whole output carries one time step (a one-step write is a row of
        every tile of the output on the TPU, PERF.md PR 33); the plain scan
        kept above shows the detector sees one when it is there."""
        B, T, H = 4, 128, 6
        lstm = LSTM(hidden=H, reverse=reverse)
        params, _ = lstm.init(jax.random.key(0), (T, 3))
        x = jnp.zeros((B, T, 3), jnp.float32)

        def writes(fn):
            text = jax.jit(fn).lower(params, x).compile().as_text()
            return _scan_output_writes(text, T * B * H)

        assert writes(lambda p, v: _plain_lstm(p, v, reverse)) == [B * H]
        blocked = writes(lstm.apply)
        assert blocked and min(blocked) >= 8 * B * H, blocked

    def test_bilstm_backward_sees_future(self):
        bi = BiLSTM(hidden=4)
        params, out_shape = bi.init(jax.random.key(0), (6, 3))
        assert out_shape == (6, 8)
        rng = np.random.default_rng(1)
        x = rng.normal(size=(1, 6, 3)).astype(np.float32)
        y1 = np.asarray(bi.apply(params, jnp.asarray(x)))
        x2 = x.copy()
        x2[0, -1] += 5.0
        y2 = np.asarray(bi.apply(params, jnp.asarray(x2)))
        # forward half at t=0 unchanged; backward half at t=0 changed
        np.testing.assert_allclose(y1[0, 0, :4], y2[0, 0, :4], atol=1e-6)
        assert np.abs(y1[0, 0, 4:] - y2[0, 0, 4:]).max() > 1e-4

    def test_tagger_builder(self):
        m = bilstm_tagger(seq_len=10, vocab_size=30, embed_dim=8, hidden=6,
                          num_tags=4)
        toks = np.random.default_rng(0).integers(0, 30, size=(3, 10))
        out = np.asarray(m.apply(jnp.asarray(toks)))
        assert out.shape == (3, 10, 4)
        emb = np.asarray(m.apply(jnp.asarray(toks), tap="embed"))
        assert emb.shape == (3, 10, 8)


def _lstm_operands(B, T, D, H, seed=0):
    rng = np.random.default_rng(seed + B + T)
    return (jnp.asarray(rng.normal(size=(B, T, D)), jnp.float32),
            {"wx": jnp.asarray(rng.normal(size=(D, 4 * H)) / np.sqrt(D), jnp.float32),
             "wh": jnp.asarray(rng.normal(size=(H, 4 * H)) / np.sqrt(H), jnp.float32),
             "b": jnp.asarray(rng.normal(size=(4 * H,)) * 0.3, jnp.float32)})


class TestLSTMKernel:
    """``lstm_scan_pallas`` in the Pallas interpreter on the CPU against the
    recurrence a step a position (``_plain_lstm``), and what chooses it."""

    # H, D: 5 + 3 and 300 + 50 fit one operand ([h | x | 0]); 130 + 200 and
    # 128 + 3 do not ([h | 0 | x | 0]). T: 8 and 16 are whole blocks of 8
    # steps, 19, 11 and 9 end in a part of one. B: 8 and 16 are one row
    # block, 24 three of 8.
    SHAPES = [(8, 8, 3, 5), (24, 19, 3, 5), (8, 16, 50, 300), (16, 11, 50, 300),
              (16, 11, 200, 130), (8, 9, 3, 128)]

    @pytest.mark.parametrize("reverse", [False, True])
    @pytest.mark.parametrize("B,T,D,H", SHAPES)
    def test_kernel_is_the_plain_recurrence(self, B, T, D, H, reverse):
        """float32 operands: the two differ by the order of a sum alone."""
        x, p = _lstm_operands(B, T, D, H)
        want = _plain_lstm(p, x, reverse)
        got = attention.lstm_scan_pallas(x, p["wx"], p["wh"], p["b"], reverse,
                                         interpret=True, operands="float32")
        assert got.shape == (B, T, H) and got.dtype == jnp.float32
        assert float(jnp.abs(got - want).max()) < 2e-6

    @pytest.mark.parametrize("reverse", [False, True])
    @pytest.mark.parametrize("B,T,D,H", [(24, 19, 3, 5), (8, 16, 50, 300)])
    def test_kernel_as_the_chip_runs_it(self, B, T, D, H, reverse):
        """bfloat16 operands, float32 accumulation and state, as on the TPU
        at its default precision: within 0.02 of the float32 recurrence."""
        x, p = _lstm_operands(B, T, D, H)
        got = attention.lstm_scan_pallas(x, p["wx"], p["wh"], p["b"], reverse,
                                         interpret=True)
        assert float(jnp.abs(got - _plain_lstm(p, x, reverse)).max()) < 2e-2

    @pytest.mark.parametrize("reverse", [False, True])
    def test_gradient_through_the_kernel_is_the_plain_forms(self, reverse):
        x, p = _lstm_operands(8, 19, 3, 5)
        w = jnp.cos(jnp.arange(8 * 19 * 5, dtype=jnp.float32)).reshape(8, 19, 5)

        def loss(fn):
            return lambda p, v: jnp.sum(w * fn(p, v))

        kernel = attention._lstm_kernel_vjp(reverse, False, True)
        got = jax.grad(loss(lambda p, v: kernel(v, p["wx"], p["wh"], p["b"], None)),
                       argnums=(0, 1))(p, x)
        want = jax.grad(loss(lambda p, v: _plain_lstm(p, v, reverse)),
                        argnums=(0, 1))(p, x)
        for g, e in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                       rtol=1e-4, atol=1e-5)

    @staticmethod
    def _kernels_on_the_cpu(monkeypatch, calls):
        """``BiLSTM`` as on a TPU, its kernels in the interpreter at float32."""
        monkeypatch.setattr(attention, "_lstm_kernel_applies", lambda x, hidden: True)
        monkeypatch.setattr(attention, "_lstm_kernel_vjp", lambda reverse, padded=False: (
            lambda x, wx, wh, b, beside: calls.append((reverse, padded, beside is not None))
            or attention.lstm_scan_pallas(x, wx, wh, b, reverse, beside, padded,
                                          interpret=True, operands="float32")))

    # H = 5, 100, 300 end inside a lane tile (the second direction is rotated
    # to there: 100 + 100 and 300 + 300 spill into a tile of their own, 5 + 5
    # does not), 128 at its end, 130 two lanes into the next
    @pytest.mark.parametrize("B,T,D,H", [(8, 16, 3, 5), (24, 19, 7, 100), (8, 9, 3, 128),
                                         (16, 11, 200, 130), (8, 16, 50, 300)])
    def test_bilstm_is_the_two_directions_side_by_side(self, B, T, D, H, monkeypatch):
        """The first direction's kernel leaves its result time-major and
        lane-padded, the second's writes both halves of every position."""
        x, pf = _lstm_operands(B, T, D, H)
        _, pb = _lstm_operands(B, T, D, H, seed=7)
        bi, params = BiLSTM(hidden=H), {"fwd": pf, "bwd": pb}
        plain = bi.apply(params, x)
        calls = []
        self._kernels_on_the_cpu(monkeypatch, calls)
        got = bi.apply(params, x)
        assert calls == [(False, True, False), (True, False, True)]
        want = jnp.concatenate([_plain_lstm(pf, x, False), _plain_lstm(pb, x, True)], -1)
        assert got.shape == (B, T, 2 * H) and got.dtype == jnp.float32
        assert float(jnp.abs(got - want).max()) < 2e-6
        assert float(jnp.abs(got - plain).max()) < 2e-6

    def test_gradient_through_both_kernels_of_a_bilstm(self, monkeypatch):
        x, pf = _lstm_operands(8, 11, 3, 5)
        _, pb = _lstm_operands(8, 11, 3, 5, seed=7)
        bi, params = BiLSTM(hidden=5), {"fwd": pf, "bwd": pb}
        w = jnp.cos(jnp.arange(8 * 11 * 10, dtype=jnp.float32)).reshape(8, 11, 10)

        def grads():
            return jax.grad(lambda p, v: jnp.sum(w * bi.apply(p, v)), argnums=(0, 1))(params, x)

        want = grads()
        monkeypatch.setattr(attention, "_lstm_kernel_applies", lambda x, hidden: True)
        monkeypatch.setattr(attention, "_lstm_kernel_vjp", functools.partial(
            attention._lstm_kernel_vjp.__wrapped__, interpret=True))
        for g, e in zip(jax.tree.leaves(grads()), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=1e-4, atol=1e-5)

    # the tagger's batch; B = 24 and 768 take the most rows that divide them;
    # no block where B is no multiple of 8 or T under a block of steps; wider
    # layers take fewer rows a step, and none once the weights alone fill VMEM
    @pytest.mark.parametrize("B,T,D,H,want", [
        (8192, 128, 50, 300, (512, 8)), (24, 19, 3, 5, (8, 8)), (768, 8, 50, 300, (256, 8)),
        (7, 128, 50, 300, None), (8, 4, 50, 300, None), (8192, 128, 256, 1024, (128, 8)),
        (8192, 128, 512, 2048, None)])
    def test_blocks_of_a_kernel_step(self, B, T, D, H, want):
        assert attention._lstm_blocks(B, T, D, H) == want

    def test_off_the_tpu_the_plain_form_runs(self, monkeypatch):
        """Whatever the shapes: the CPU suite keeps running ``lstm_scan_xla``."""
        x, p = _lstm_operands(8, 16, 3, 5)
        assert not attention._lstm_kernel_applies(jnp.zeros((8192, 128, 50)), 300)
        assert not attention._lstm_kernel_applies(x, 5)

        def never(*a, **k):
            raise AssertionError("the kernel ran off the TPU")

        monkeypatch.setattr(attention, "lstm_scan_pallas", never)
        got = LSTM(hidden=5).apply(p, x)
        assert float(jnp.abs(got - _plain_lstm(p, x, False)).max()) < 1e-5


class TestSequenceModelsThroughDNNModel:
    """The DNNModel stage machinery (minibatching, output nodes, save/load)
    is model-family-agnostic: sequence models plug in like CNNs."""

    def test_dnn_model_serves_bilstm_tagger(self, tmp_path):
        from mmlspark_tpu import DataFrame
        from mmlspark_tpu.models import DNNModel

        m = bilstm_tagger(seq_len=12, vocab_size=25, embed_dim=8, hidden=6,
                          num_tags=3)
        rng = np.random.default_rng(1)
        rows = [rng.integers(0, 25, size=12) for _ in range(10)]
        df = DataFrame.from_dict({"tokens": rows}, num_partitions=2)

        stage = (DNNModel(inputCol="tokens", outputCol="tags", batchSize=4)
                 .set_model(m))
        out = stage.transform(df)
        tags = out.column("tags")
        assert len(tags) == 10
        assert all(np.asarray(t).shape == (12, 3) for t in tags)
        # output-node addressing works for sequence taps too
        emb = (DNNModel(inputCol="tokens", outputCol="emb", batchSize=4)
               .set_model(m).set_output_node("embed")).transform(df)
        assert np.asarray(emb.column("emb")[0]).shape == (12, 8)
        # save/load round trip preserves outputs
        stage.save(str(tmp_path / "tagger"))
        from mmlspark_tpu.core.serialize import load_stage

        loaded = load_stage(str(tmp_path / "tagger"))
        out2 = loaded.transform(df)
        np.testing.assert_allclose(np.stack(list(out2.column("tags"))),
                                   np.stack(list(tags)), atol=1e-6)

    def test_dnn_model_serves_transformer(self):
        from mmlspark_tpu import DataFrame
        from mmlspark_tpu.models import DNNModel

        m = transformer_encoder(seq_len=8, dim=16, depth=1, num_heads=2,
                                vocab_size=20, num_classes=5)
        rng = np.random.default_rng(2)
        rows = [rng.integers(0, 20, size=8) for _ in range(6)]
        df = DataFrame.from_dict({"tokens": rows})
        out = (DNNModel(inputCol="tokens", outputCol="logits", batchSize=3)
               .set_model(m)).transform(df)
        logits = out.column("logits")
        assert all(np.asarray(v).shape == (8, 5) for v in logits)
        assert all(np.isfinite(np.asarray(v)).all() for v in logits)


class TestSequenceTraining:
    """The shared training loop handles per-token targets: compile_train_step
    trains the BiLSTM tagger over the mesh (sequence-model parity with the
    CNN path — no hand-rolled loop needed)."""

    def test_train_step_per_token_labels(self, seq_mesh):
        from mmlspark_tpu.models import training as T
        from mmlspark_tpu.models.module import Sequential
        from mmlspark_tpu.models.attention import BiLSTM, Embed
        from mmlspark_tpu.models.module import Dense
        from mmlspark_tpu.parallel import MeshSpec, make_mesh

        mesh = make_mesh(MeshSpec(data=-1))
        module = Sequential([
            ("embed", Embed(20, 8)),
            ("bilstm", BiLSTM(8)),
            ("tags", Dense(2)),
        ], name="tagger")
        opt = T.make_optimizer(learning_rate=0.2, momentum=0.9)
        with mesh:
            state = T.init_train_state(module, (10,), opt, mesh=mesh)
            step = T.compile_train_step(module, opt, mesh=mesh)
            sharding = T.batch_sharding(mesh)
            rng = np.random.default_rng(0)
            first = last = None
            for _ in range(60):
                toks = rng.integers(0, 20, size=(16, 10))
                tags = (toks >= 10).astype(np.int32)  # learnable per-token rule
                batch = {"x": jax.device_put(toks, sharding),
                         "y": jax.device_put(tags, sharding)}
                state, metrics = step(state, batch)
                last = {k: float(v) for k, v in metrics.items()}
                if first is None:
                    first = dict(last)
        assert last["loss"] < first["loss"] * 0.2, (first, last)
        assert last["accuracy"] > 0.95, last

    def test_loss_helper_shapes(self):
        from mmlspark_tpu.models.training import accuracy, cross_entropy_loss

        rng = np.random.default_rng(1)
        # [B, K] classification still works
        lo = jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32))
        y = jnp.asarray([0, 1, 2, 1])
        assert np.isfinite(float(cross_entropy_loss(lo, y)))
        # [B, T, K] per-token with mask
        lo3 = jnp.asarray(rng.normal(size=(2, 5, 3)).astype(np.float32))
        y3 = jnp.asarray(rng.integers(0, 3, size=(2, 5)))
        m = jnp.asarray([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]])
        l_masked = float(cross_entropy_loss(lo3, y3, m))
        assert np.isfinite(l_masked)
        a = float(accuracy(lo3, y3, m))
        assert 0.0 <= a <= 1.0
        # fully confident logits -> ~0 loss, accuracy 1
        perfect = jax.nn.one_hot(y3, 3) * 50.0
        assert float(cross_entropy_loss(perfect, y3)) < 1e-3
        assert float(accuracy(perfect, y3)) == 1.0
