#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One Python process (the parent IS the worker: a chip belongs to one process
at a time, so nothing here starts a child that needs JAX) drives every main
path through the entry points a user would call, at the full width of the
ResNet-50 the repo lists, on seeded synthetic data with no network:

  env              native C++ library built from the committed source; where
                   the persistent compile cache goes
  kernels          every Pallas kernel through Mosaic (interpret=False) at
                   production tile sizes and at its guard's limit, against
                   its numpy/XLA reference, "tpu_custom_call" in the text
  batch_transform  PipelineModel([ImageTransformer, ImageFeaturizer(ResNet-50
                   @224)]).fuse().transform over 512 images of 256x256 uint8
  server           the same chain behind serve_pipeline(fused=True,
                   async_exec=True): JSON and binary-frame requests, two
                   concurrent bursts, stats, clean stop
  trainer          init_train_state + compile_train_step + run_train_loop,
                   ResNet-50 @224, batch 64, three steps
  gbdt             LightGBMClassifier.fit on 2M x 28 (GOSS, device scan path)
                   + fused forest predict; a bagged fit; one sparse CSR fit;
                   one VW scan pass
  multichip        (>= 4 devices) four replicas on four devices, data-sharded
                   and pipelined fused chain, sharded GBDT histogram, the
                   __graft_entry__ battery on the real devices

A phase that raises is recorded with its traceback and later phases still
run (chip calls are budgeted), but any failed phase makes the exit code
non-zero. Exits non-zero WITHOUT a result line when JAX finds no TPU, or
when run outside a checkout of the repo. The last line of stdout is one JSON
object with exactly these keys and no other, the device as JAX reports it:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
The line before it (``[chip_smoke] summary {...}``) and
``chiprun_out/chip_smoke.json`` carry the per-phase status, the set-up
seconds and the compile-cache directory and entry counts. Times are printed
as set-up information only — never under a metric name.

    python chip_smoke.py                  # everything the device count allows
    python chip_smoke.py --phases kernels,gbdt
    python chip_smoke.py --tiny           # builder's CPU debug run: tiny
                                          # sizes, interpret-mode kernels;
                                          # never prints ok=true, exits 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple

EXIT_PHASE_FAILED = 1
EXIT_TINY_DEBUG = 3
EXIT_NO_ACCELERATOR = 4
EXIT_NOT_A_CHECKOUT = 5
EXIT_DEADLINE = 6

ALL_PHASES = ("env", "kernels", "batch_transform", "server", "trainer",
              "gbdt", "multichip")

#: bf16-scale agreement bound, stated: max|a - b| <= REL_TOL * max|b|. The
#: ResNet-50 forward multiplies in bf16 (8 mantissa bits, ~4e-3 per product)
#: through 53 layers; features are compared against an f32 reference and
#: across differently-tiled batch buckets.
REL_TOL = 0.05


def _die(code: int, reason: str) -> None:
    """One-line reason on stderr, no result line, non-zero exit."""
    print(f"chip_smoke: {reason}", file=sys.stderr)
    sys.exit(code)


class Sizes:
    """Problem sizes: the real ones, or --tiny for the CPU debug run."""

    def __init__(self, tiny: bool):
        self.tiny = tiny
        self.image_rows = 24 if tiny else 512
        self.src_px = 40 if tiny else 256
        self.px = 32 if tiny else 224
        self.width = 8 if tiny else 64
        self.classes = 16 if tiny else 1000
        self.feat_dim = self.width * 32          # ResNet-50 pooled features
        self.ref_rows = 8 if tiny else 32
        self.train_batch = 8 if tiny else 64
        self.gbdt_rows = 6000 if tiny else 2_000_000
        # rows x iterations must exceed MMLSPARK_TPU_NATIVE_TRAIN_MAX (2e7)
        # or the fit goes to the host C++ engine
        self.gbdt_iters = 3 if tiny else 12
        self.gbdt_ref_rows = 2000 if tiny else 200_000
        self.sparse_rows = 400 if tiny else 100_000
        self.sparse_nnz_per_row = 8 if tiny else 24   # >= 2M nnz in total
        self.sparse_width = 64 if tiny else 1 << 14
        self.vw_rows = 500 if tiny else 50_000
        self.hist_rows = 4096 if tiny else 1_000_000
        self.select_rows = 6000 if tiny else 2_000_000
        self.flash_t = (256,) if tiny else (2048, 8192)
        # B, T, D, H: the tagger's batch; T = 19 ends in a part of a block of
        # steps; 130 + 200 does not fit one operand tile beside h ([h | 0 | x | 0])
        self.lstm_shapes = (((8, 19, 3, 5),) if tiny else
                            ((8192, 128, 50, 300), (1024, 19, 50, 300), (256, 16, 200, 130)))


def run_phases(phases: List[Tuple[str, Callable[[], Any]]]
               ) -> Dict[str, Dict[str, Any]]:
    """Run each phase; a raise is recorded and the next phase still runs."""
    results: Dict[str, Dict[str, Any]] = {}
    for name, fn in phases:
        print(f"[chip_smoke] phase {name} ...", flush=True)
        t0 = time.perf_counter()
        try:
            info = fn()
            if isinstance(info, str) and info.startswith("skipped"):
                results[name] = {"status": info}
            else:
                results[name] = {"status": "pass", "info": info}
        except Exception:  # noqa: BLE001 — the phase boundary: record, go on
            tb = traceback.format_exc()
            print(tb, file=sys.stderr, flush=True)
            results[name] = {"status": "fail", "error": tb[-2000:]}
        results[name]["setup_and_run_seconds"] = round(
            time.perf_counter() - t0, 1)
        print(f"[chip_smoke] phase {name}: {results[name]['status']} "
              f"({results[name]['setup_and_run_seconds']} s, set-up "
              f"included)", flush=True)
    return results


def exit_code(results: Dict[str, Dict[str, Any]]) -> int:
    bad = [n for n, r in results.items() if r["status"] == "fail"]
    return EXIT_PHASE_FAILED if bad else 0


def result_line(ok: bool, device: Dict[str, Any]) -> str:
    """The last line of stdout: these keys and no other (the driver's check
    refuses anything else); everything more goes on the summary line."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


class Checks:
    """Sub-checks of one phase: all run, the phase fails if any did."""

    def __init__(self):
        self.done: Dict[str, Any] = {}
        self.failed: Dict[str, str] = {}

    def run(self, name: str, fn: Callable[[], Any]) -> None:
        t0 = time.perf_counter()
        try:
            self.done[name] = fn()
            print(f"[chip_smoke]   {name}: ok "
                  f"({time.perf_counter() - t0:.1f} s with compile)",
                  flush=True)
        except Exception as e:  # noqa: BLE001 — recorded, raised by finish()
            msg = "".join(traceback.format_exception_only(type(e), e))
            print(f"[chip_smoke]   {name}: FAIL {msg[:1500]}",
                  file=sys.stderr, flush=True)
            self.failed[name] = msg[-600:]

    def finish(self) -> Dict[str, Any]:
        if self.failed:
            raise AssertionError(
                f"{len(self.failed)} of {len(self.failed) + len(self.done)} "
                f"checks failed: {json.dumps(self.failed)[:3000]}")
        return self.done


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _rel_err(got, ref) -> float:
    import numpy as np

    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


# ---------------------------------------------------------------------------
# shared fixtures (built once, used by several phases)
# ---------------------------------------------------------------------------


class Fixtures:
    def __init__(self, sz: Sizes, seed: int):
        self.sz = sz
        self.seed = seed
        self._cache: Dict[str, Any] = {}
        #: [N, feat_dim] features of the fused batch transform: set by the
        #: batch_transform phase, compared against by server and multichip
        self.batch_features = None

    def _once(self, key: str, build: Callable[[], Any]) -> Any:
        if key not in self._cache:
            self._cache[key] = build()
        return self._cache[key]

    @property
    def model(self):
        from mmlspark_tpu.models.resnet import resnet

        sz = self.sz
        return self._once("model", lambda: resnet(
            50, num_classes=sz.classes, image_size=sz.px, width=sz.width,
            seed=self.seed))

    @property
    def images(self):
        import numpy as np

        sz = self.sz
        rng = np.random.default_rng(self.seed)
        return self._once("images", lambda: rng.integers(
            0, 256, (sz.image_rows, sz.src_px, sz.src_px, 3), dtype=np.uint8))

    @property
    def image_stages(self):
        """[ImageTransformer, ImageFeaturizer] — shared by the batch chain
        and the served chain, so both hit the same compiled segment."""
        from mmlspark_tpu.image.featurizer import ImageFeaturizer
        from mmlspark_tpu.image.stages import ImageTransformer

        sz = self.sz
        return self._once("image_stages", lambda: [
            ImageTransformer().resize(sz.px, sz.px).flip(1),
            ImageFeaturizer(scaleFactor=1 / 255.).set_model(self.model)])

    def image_df(self, rows: Optional[int] = None, parts: int = 2):
        import numpy as np

        from mmlspark_tpu.core.dataframe import DataFrame
        from mmlspark_tpu.core.schema import ImageSchema

        imgs = self.images[:rows] if rows else self.images
        col = np.empty(len(imgs), dtype=object)
        for i, img in enumerate(imgs):
            col[i] = ImageSchema.make(img, f"img{i}")
        return DataFrame.from_dict({"image": col}, num_partitions=parts)


def _features_of(df, col: str = "features"):
    import numpy as np

    return np.stack([np.asarray(v, dtype=np.float32)
                     for v in df.column(col)])


# ---------------------------------------------------------------------------
# phase: env
# ---------------------------------------------------------------------------


def phase_env(fx: Fixtures) -> Dict[str, Any]:
    from mmlspark_tpu import native_loader
    from mmlspark_tpu.core.runtime import compile_cache_dir, \
        ensure_compile_cache

    _check(native_loader.available(),
           "native C++ library unavailable (g++ build failed?)")
    enabled = ensure_compile_cache()
    if not fx.sz.tiny:
        _check(enabled == compile_cache_dir(),
               f"compile cache not enabled: {enabled!r}")
    return {"native_so": os.path.basename(native_loader._SO_PATH),
            "compile_cache_dir": enabled}


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------


def _has_custom_call(jitted, *args, **kwargs) -> bool:
    return "tpu_custom_call" in jitted.lower(*args, **kwargs).compile(
        ).as_text()


def phase_kernels(fx: Fixtures) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.core import kernels
    from mmlspark_tpu.gbdt import pallas_hist, pallas_select, pallas_sparse
    from mmlspark_tpu.models import attention

    sz, ck = fx.sz, Checks()
    interp = sz.tiny            # Mosaic on the chip, interpreter on the CPU
    rng = np.random.default_rng(fx.seed)

    # -- pallas_hist: F=28, B=256; default chunk 512 + registered variants
    f, b, n = 28, 256, sz.hist_rows
    bins_i32 = rng.integers(0, b, size=(f, n)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    m = rng.uniform(size=n) < 0.7
    mf = m.astype(np.float64)
    ref = np.zeros((f, b, 3))
    for j in range(f):
        ref[j, :, 0] = np.bincount(bins_i32[j], weights=g * mf, minlength=b)
        ref[j, :, 1] = np.bincount(bins_i32[j], weights=h * mf, minlength=b)
        ref[j, :, 2] = np.bincount(bins_i32[j], weights=mf, minlength=b)
    gd, hd, md = jnp.asarray(g), jnp.asarray(h), jnp.asarray(m)

    def hist_case(bins, variant, hilo):
        def go():
            bd = jnp.asarray(bins)
            with kernels.activate(variant):
                chunk = int(kernels.active_param("hist", "chunk",
                                                 pallas_hist.CHUNK))
                got = np.asarray(pallas_hist.compute_histogram_mxu(
                    bd, gd, hd, md, b, interpret=interp, hilo=hilo))
            err = float(np.max(np.abs(got[..., :2] - ref[..., :2])))
            # hi/lo bf16 contraction: documented ~0.4 absolute on |sum|~70
            # cells at 1M rows; exact mode is f32 summation-order error
            _check(err <= (1.0 if hilo else 2e-2), f"max abs err {err}")
            _check(np.array_equal(got[..., 2], ref[..., 2]),
                   "count channel not exact")
            if not interp:
                _check(_has_custom_call(
                    pallas_hist._compute_histogram_mxu, bd, gd, hd, md, b,
                    False, hilo, chunk), "no tpu_custom_call in the program")
            return {"chunk": chunk, "max_abs_err": round(err, 5)}
        return go

    for dt in (np.uint8, np.int32):
        for hilo in (True, False):
            ck.run(f"hist.{np.dtype(dt).name}.c512.hilo{int(hilo)}",
                   hist_case(bins_i32.astype(dt), None, hilo))
    for vid in ("hist.c256", "hist.c1024"):
        ck.run(f"{vid}.hilo1", hist_case(bins_i32, vid, True))

    def ambient_highest(case):
        # a caller's jax.default_matmul_precision("highest") must not reach
        # the kernels' bf16 one-hot contractions (Mosaic: "Bad lhs type")
        def go():
            with jax.default_matmul_precision("highest"):
                return case()
        return go

    ck.run("hist.c256.hilo1.ambient_highest",
           ambient_highest(hist_case(bins_i32, "hist.c256", True)))

    # -- pallas_select: N=2M; default chunk 1024 + registered variants
    ns = sz.select_rows
    sbins = rng.integers(0, 255, size=(f, ns)).astype(np.int32)
    sg = jnp.asarray(rng.normal(size=ns).astype(np.float32))
    sh = jnp.asarray(rng.uniform(size=ns).astype(np.float32))
    smask = jnp.asarray(rng.uniform(size=ns) < 0.3)
    cnt = int(smask.sum())
    cap = -(-cnt // 4096) * 4096
    idx = jnp.nonzero(smask, size=cap, fill_value=0)[0]
    want_g = np.asarray(jnp.take(sg, idx))[:cnt]
    want_h = np.asarray(jnp.take(sh, idx))[:cnt]
    want_b = sbins[:, np.asarray(idx)[:cnt]]

    def select_case(bins, variant):
        def go():
            bd = jnp.asarray(bins)
            with kernels.activate(variant):
                chunk = int(kernels.active_param("select", "chunk",
                                                 pallas_select.CHUNK))
                bc, gc, hc = pallas_select.select_rows(
                    bd, sg, sh, smask, cap, interpret=interp)
            _check(np.array_equal(np.asarray(bc)[:, :cnt], want_b),
                   "bins not bit-exact")
            _check(np.array_equal(np.asarray(gc)[:cnt], want_g)
                   and np.array_equal(np.asarray(hc)[:cnt], want_h),
                   "grad/hess not bit-exact")
            _check(bool(np.all(np.asarray(gc)[cnt:] == 0)), "dirty tail")
            if not interp:
                _check(_has_custom_call(
                    pallas_select._select_rows, bd, sg, sh, smask, cap,
                    False, chunk), "no tpu_custom_call in the program")
            return {"chunk": chunk, "selected": cnt}
        return go

    ck.run("select.int32.c1024", select_case(sbins, None))
    ck.run("select.uint8.c1024", select_case(sbins.astype(np.uint8), None))
    for vid in ("select.c512", "select.c2048"):
        ck.run(vid, select_case(sbins, vid))
    ck.run("select.c2048.ambient_highest",
           ambient_highest(select_case(sbins, "select.c2048")))

    # -- csr_gather_pallas at the largest shapes its guard admits
    def gather_case(n_rows, n_used, per_row, width=None):
        def go():
            nonlocal width
            _check(n_rows * max(128, -(-n_used // 128) * 128)
                   <= pallas_sparse._GATHER_MAX_CELLS, "outside the guard")
            # the XLA reference keys entries by row * width + index in i32:
            # its caller (fusion._stage_csr) admits rows * width < 2^31
            width = width or min(1 << 18, (1 << 30) // n_rows)
            stride = width // per_row
            cols = (np.sort(rng.integers(0, stride, (n_rows, per_row)), 1)
                    + np.arange(per_row) * stride).astype(np.int32)
            indptr = (np.arange(n_rows + 1) * per_row).astype(np.int32)
            indices = cols.reshape(-1)
            values = rng.normal(size=indices.shape[0]).astype(np.float32)
            used = np.unique(rng.choice(indices, size=n_used,
                                        replace=False)).astype(np.int32)
            args = tuple(jnp.asarray(a) for a in (indptr, indices, values))
            xla = jax.jit(lambda i, j, v: pallas_sparse.csr_gather_xla(
                i, j, v, width, used))
            pal = jax.jit(lambda i, j, v: pallas_sparse.csr_gather_pallas(
                i, j, v, width, used, interpret=interp))
            want = np.asarray(xla(*args))
            _check(np.array_equal(np.asarray(pal(*args)), want),
                   "gather not bitwise-equal to the XLA formulation")
            _check(int((want != 0).sum()) > 0, "degenerate case: all zero")
            if not interp:
                _check(_has_custom_call(pal, *args),
                       "no tpu_custom_call in the program")
            return {"n": n_rows, "used": int(len(used)),
                    "nnz": int(indices.shape[0])}
        return go

    if sz.tiny:
        ck.run("gather.tiny", gather_case(48, 20, 8, width=1 << 10))
    else:
        ck.run("gather.n16384.u128", gather_case(16384, 128, 32))
        ck.run("gather.n4096.u512", gather_case(4096, 512, 32))
        ck.run("gather.n2048.u1024", gather_case(2048, 1024, 32))

    # -- sparse_histogram_mxu at its guard's limit and mid-range
    def shist_case(tb, nnz):
        def go():
            _check(tb <= pallas_sparse._SPARSE_HIST_MAX_TB,
                   "outside the guard")
            fb = rng.integers(0, tb, size=nnz, dtype=np.int32)
            stats = rng.normal(size=(3, nnz)).astype(np.float32)
            stats[2] = 1.0
            want = np.stack([np.bincount(
                fb, weights=stats[c].astype(np.float64), minlength=tb)
                for c in range(3)])
            fn = jax.jit(lambda x, s: pallas_sparse.sparse_histogram_mxu(
                x, s, tb, interpret=interp))
            args = (jnp.asarray(fb), jnp.asarray(stats))
            got = np.asarray(fn(*args))
            tol = kernels.get("hist.csr").tolerance
            np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
            _check(np.array_equal(got[2], want[2]), "count not exact")
            if not interp:
                _check(_has_custom_call(fn, *args),
                       "no tpu_custom_call in the program")
            return {"total_bins": tb, "nnz": nnz}
        return go

    if sz.tiny:
        ck.run("sparse_hist.tiny", shist_case(96, 400))
    else:
        ck.run("sparse_hist.tb4096", shist_case(4096, 200_000))
        ck.run("sparse_hist.tb131072",
               shist_case(pallas_sparse._SPARSE_HIST_MAX_TB, 16_384))

    # -- flash attention: bf16, D=64, the library kernel's default blocks
    def flash_case(t, causal):
        def go():
            q, k, v = (jnp.asarray(rng.normal(size=(1, t, 4, 64)).astype(
                np.float32)).astype(jnp.bfloat16) for _ in range(3))
            fn = jax.jit(lambda q, k, v: attention.dense_attention(
                q, k, v, causal=causal))
            got = np.asarray(fn(q, k, v).astype(jnp.float32))
            # f32 inputs never dispatch to the flash kernel: XLA reference
            want = np.asarray(jax.jit(lambda q, k, v: attention.
                              dense_attention(q, k, v, causal=causal))(
                *(a.astype(jnp.float32) for a in (q, k, v))))
            _check(bool(np.isfinite(got).all()), "non-finite output")
            err = float(np.max(np.abs(got - want)))
            # bf16 output rounding is relative: causal rows that see few
            # keys reach |o| ~ 4, where one bf16 ulp is already 0.016
            bound = 2e-2 * max(1.0, float(np.max(np.abs(want))))
            _check(err <= bound, f"max abs err {err} > {bound} vs f32 ref")
            if not interp:
                _check(_has_custom_call(fn, q, k, v),
                       "no tpu_custom_call: the XLA path ran instead")
            return {"max_abs_err": round(err, 5)}
        return go

    for t in sz.flash_t:
        for causal in (False, True):
            ck.run(f"flash.t{t}.causal{int(causal)}", flash_case(t, causal))

    # -- lstm_scan: the tagger's recurrence, the kernel against the plain form
    def lstm_case(B, T, D, H, which):
        """``which``: 0 forward, 1 reverse, 2 both as a ``BiLSTM`` runs them
        (the first direction's result handed to the second's call)."""
        def go():
            def weights():
                return (jnp.asarray(rng.normal(size=(D, 4 * H)) / np.sqrt(D), jnp.float32),
                        jnp.asarray(rng.normal(size=(H, 4 * H)) / np.sqrt(H), jnp.float32),
                        jnp.asarray(rng.normal(size=(4 * H,)) * 0.1, jnp.float32))

            x = jnp.asarray(rng.normal(size=(B, T, D)), jnp.float32)
            if which < 2:
                args = (x, *weights())
                plain = jax.jit(lambda *a: attention.lstm_scan_xla(*a, bool(which)))
                kern = jax.jit(lambda *a: attention.lstm_scan_pallas(
                    *a, bool(which), interpret=interp))
            else:
                args = (x, *weights(), *weights())
                plain = jax.jit(lambda x, *w: jnp.concatenate(
                    [attention.lstm_scan_xla(x, *w[:3]),
                     attention.lstm_scan_xla(x, *w[3:], True)], axis=-1))
                kern = jax.jit(lambda x, *w: attention.lstm_scan_pallas(
                    x, *w[3:], True, interpret=interp,
                    beside=attention.lstm_scan_pallas(x, *w[:3], padded=True,
                                                      interpret=interp)))

            def ms(fn):     # the median of three calls after the one that compiles
                out, took = fn(*args).block_until_ready(), []
                for _ in range(3):
                    t0 = time.perf_counter()
                    fn(*args).block_until_ready()
                    took.append((time.perf_counter() - t0) * 1e3)
                return out, round(sorted(took)[1], 2)

            (want, plain_ms), (got, kernel_ms) = ms(plain), ms(kern)
            _check(got.shape == want.shape, f"{got.shape} != {want.shape}")
            gap = float(jnp.max(jnp.abs(got - want)))
            # both round their operands to bfloat16 on the chip and add in
            # another order; the interpreter's plain form is float32
            _check(gap <= 2e-2, f"largest gap of h {gap} > 0.02")
            if interp:      # a CPU run yields no rate
                return {"largest_gap_h": round(gap, 5)}
            _check(_has_custom_call(kern, *args), "no tpu_custom_call in the program")
            return {"largest_gap_h": round(gap, 5), "kernel_ms": kernel_ms,
                    "plain_ms": plain_ms}
        return go

    for shape in sz.lstm_shapes:
        for which, name in enumerate(("forward", "reverse", "both")):
            ck.run("lstm_scan.b%d.t%d.d%d.h%d." % shape + name, lstm_case(*shape, which))
    return ck.finish()


# ---------------------------------------------------------------------------
# phase: batch transform
# ---------------------------------------------------------------------------


def phase_batch_transform(fx: Fixtures) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.core.pipeline import PipelineModel
    from mmlspark_tpu.models.module import FunctionModel, matmul_precision

    sz = fx.sz
    chain = PipelineModel(fx.image_stages)
    fused = chain.fuse()
    df = fx.image_df()
    t0 = time.perf_counter()
    feats = _features_of(fused.transform(df))
    first_s = time.perf_counter() - t0
    stats = fused.fusion_stats()
    _check(feats.shape == (sz.image_rows, sz.feat_dim),
           f"features shape {feats.shape}")
    _check(bool(np.isfinite(feats).all()), "non-finite features")
    _check(stats["fallbacks"] == [] and stats["fallbacks_total"] == 0,
           f"fallbacks: {stats['fallbacks']}")
    _check(stats["n_fused_segments"] == 1, f"plan: {stats['segments']}")
    # main() already refused any platform but tpu: device 0 IS the chip
    _check(set(stats["devices"]) == {str(jax.local_devices()[0])},
           f"outputs landed on {stats['devices']}")
    fx.batch_features = feats

    # agreement with the unfused per-stage path on a subset (bitwise on the
    # CPU; across two differently-shaped TPU programs, bf16-scale)
    sub = fx.image_df(rows=sz.ref_rows, parts=1)
    unfused = _features_of(chain.transform(sub))
    err_unfused = _rel_err(feats[:sz.ref_rows], unfused)
    _check(err_unfused <= REL_TOL, f"fused vs unfused: {err_unfused}")

    # agreement with a plain float32 jax.numpy forward (no framework)
    from mmlspark_tpu.ops import image as ops

    model = fx.model
    x = np.stack([ops.resize(img, sz.px, sz.px)[:, ::-1]
                  for img in fx.images[:sz.ref_rows]])

    def forward(params, xb):
        live = FunctionModel(model.module, params, model.input_shape,
                             model.layer_names, model.name)
        return live.apply(xb.astype(jnp.float32) * np.float32(1 / 255.),
                          tap="avgpool")

    # true float32: on a TPU the default for f32 operands is one bf16 pass
    with matmul_precision("float32"), \
            jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(forward)(model.params, jnp.asarray(x)))
    err_ref = _rel_err(feats[:sz.ref_rows], ref)
    _check(err_ref <= REL_TOL, f"fused bf16 vs plain f32 forward: {err_ref}")
    return {"rows": sz.image_rows, "feature_dim": sz.feat_dim,
            "devices": stats["devices"],
            "rel_err_vs_unfused": round(err_unfused, 5),
            "rel_err_vs_f32_reference": round(err_ref, 5),
            "tolerance_rel": REL_TOL,
            "first_transform_seconds_with_compile": round(first_s, 1),
            "segment_compile_s": {
                shape: rec.get("compile_s") for shapes in
                stats["segment_costs"].values()
                for shape, rec in shapes.items()}}


# ---------------------------------------------------------------------------
# phase: server
# ---------------------------------------------------------------------------


def _served_chain(fx: Fixtures):
    """request value -> uint8 image struct (host) -> the fused segment."""
    import numpy as np

    from mmlspark_tpu.core.pipeline import PipelineModel
    from mmlspark_tpu.core.schema import ImageSchema
    from mmlspark_tpu.stages import UDFTransformer

    def to_image(col):
        out = np.empty(len(col), dtype=object)
        for i, v in enumerate(col):
            out[i] = ImageSchema.make(np.asarray(v, dtype=np.uint8), f"r{i}")
        return out

    decode = UDFTransformer(inputCol="data", outputCol="image",
                            vectorizedUdf=to_image)
    return PipelineModel([decode] + fx.image_stages)


def _post(address: str, body: bytes, headers: Dict[str, str],
          timeout: float = 600.0) -> Tuple[int, bytes]:
    import urllib.request

    req = urllib.request.Request(address, data=body, method="POST",
                                 headers=headers)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read()


def _get_json(url: str, timeout: float = 60.0) -> Dict[str, Any]:
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


def _drive_server(fx: Fixtures, server, n_seq: int, bursts: Tuple[int, ...]
                  ) -> Dict[str, Any]:
    """Post sequential then concurrent-burst requests (JSON and binary frame
    alternating); every reply must be 200 and equal the expected features."""
    import numpy as np

    from mmlspark_tpu.io.binary import FRAME_CONTENT_TYPE, encode_frame

    images = fx.images
    want = fx.batch_features

    def one(i: int) -> Tuple[int, int, Any]:
        img = images[i % len(images)]
        if i % 2:
            body, hdrs = encode_frame({"img": img}), \
                {"Content-Type": FRAME_CONTENT_TYPE}
        else:
            body, hdrs = json.dumps({"data": img.tolist()}).encode(), \
                {"Content-Type": "application/json"}
        status, reply = _post(server.address, body, hdrs)
        return i, status, reply

    results: List[Tuple[int, int, Any]] = [one(i) for i in range(n_seq)]
    nxt = n_seq
    for size in bursts:
        got: List[Any] = [None] * size
        threads = [threading.Thread(
            target=lambda k=k: got.__setitem__(k, one(nxt + k)))
            for k in range(size)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        _check(all(r is not None for r in got), "a burst request hung")
        results.extend(got)
        nxt += size
    worst = 0.0
    for i, status, reply in results:
        _check(status == 200, f"request {i}: HTTP {status} {reply[:200]!r}")
        vec = np.asarray(json.loads(reply), dtype=np.float32)
        _check(vec.shape == (fx.sz.feat_dim,), f"reply shape {vec.shape}")
        if want is not None:
            worst = max(worst, _rel_err(vec, want[i % len(images)]))
    _check(worst <= REL_TOL, f"served vs batch features: rel err {worst}")
    return {"requests": len(results), "rel_err_vs_batch": round(worst, 5)}


def phase_server(fx: Fixtures) -> Dict[str, Any]:
    from mmlspark_tpu.serving import serve_pipeline

    if fx.batch_features is None:
        raise RuntimeError("needs the batch_transform phase's features")
    before = set(threading.enumerate())
    server = serve_pipeline(_served_chain(fx), input_col="data",
                            reply_col="features", parse="json",
                            host="127.0.0.1", port=0, fused=True,
                            async_exec=True)
    with server:
        base = f"http://{server.host}:{server.port}"
        info = _drive_server(fx, server, n_seq=8, bursts=(12, 12))
        stats = _get_json(base + "/_mmlspark/stats")
    fusion, ex = stats["fusion"], stats["async"]
    _check(fusion["fallbacks_total"] == 0 and fusion["fallbacks"] == [],
           f"fallbacks: {fusion['fallbacks']}")
    _check(len(ex["replicas"]) == 1 and ex["replicas"][0]["batches"] >= 1,
           f"replicas: {ex['replicas']}")
    buckets = sorted({shape for shapes in fusion["segment_costs"].values()
                      for shape in shapes})
    _check(len(buckets) >= 2, f"one batch bucket only: {buckets}")
    time.sleep(0.5)
    leaked = [t.name for t in set(threading.enumerate()) - before
              if t.is_alive() and not t.daemon]
    _check(not leaked, f"threads alive after stop: {leaked}")
    info.update(batches=ex["replicas"][0]["batches"], buckets=buckets,
                wire=stats["wire"]["requests"])
    return info


# ---------------------------------------------------------------------------
# phase: trainer
# ---------------------------------------------------------------------------


def phase_trainer(fx: Fixtures) -> Dict[str, Any]:
    import jax
    import numpy as np

    from mmlspark_tpu.models import training as T
    from mmlspark_tpu.models.resnet import build_resnet

    sz = fx.sz
    module = build_resnet(50, num_classes=sz.classes, image_size=sz.px,
                          width=sz.width)
    optimizer = T.make_optimizer(learning_rate=0.05, momentum=0.9)
    state = T.init_train_state(module, (sz.px, sz.px, 3), optimizer,
                               seed=fx.seed)
    step = T.compile_train_step(module, optimizer)
    # the step donates its state: keep host copies of two leaves to compare
    leaves = jax.tree_util.tree_leaves_with_path(state.params)
    watch = [leaves[0], leaves[-1]]
    before = [np.array(v) for _, v in watch]
    rng = np.random.default_rng(fx.seed + 1)
    batches = [{"x": rng.normal(size=(sz.train_batch, sz.px, sz.px, 3)
                                ).astype(np.float32),
                "y": rng.integers(0, sz.classes, sz.train_batch
                                  ).astype(np.int32)} for _ in range(3)]
    res = T.run_train_loop(state, step, batches)
    _check(res.steps_run == 3, f"steps_run {res.steps_run}")
    loss = res.last_metrics["loss"]
    _check(bool(np.isfinite(loss)), f"loss {loss}")
    after = dict(jax.tree_util.tree_leaves_with_path(res.state.params))
    for (path, _), old in zip(watch, before):
        new = np.asarray(after[path])
        _check(bool(np.isfinite(new).all()), f"non-finite params at {path}")
        _check(not np.array_equal(new, old), f"params unchanged at {path}")
    platform = jax.devices()[0].platform
    devs = {d.platform for leaf in jax.tree_util.tree_leaves(res.state.params)
            for d in leaf.devices()}
    _check(devs == {platform}, f"state on {devs}, expected {platform}")
    return {"steps": res.steps_run, "batch": sz.train_batch,
            "final_loss": round(float(loss), 4), "state_platform": platform}


# ---------------------------------------------------------------------------
# phase: gbdt (dense device fit + fused predict, sparse fit, VW scan pass)
# ---------------------------------------------------------------------------


class _Spy:
    """Count calls of a module attribute while a block runs."""

    def __init__(self, module, name: str):
        self.module, self.name, self.calls = module, name, 0

    def __enter__(self):
        self.orig = getattr(self.module, self.name)

        def wrapped(*a, **k):
            self.calls += 1
            return self.orig(*a, **k)

        setattr(self.module, self.name, wrapped)
        return self

    def __exit__(self, *exc):
        setattr(self.module, self.name, self.orig)


def _higgs_like(n: int, d: int, rng):
    import numpy as np

    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d).astype(np.float32)
    y = ((x @ w + 0.5 * x[:, 0] * x[:, 1]
          + rng.normal(0, 2.0, n).astype(np.float32)) > 0)
    return x, y.astype(np.float64)


def phase_gbdt(fx: Fixtures) -> Dict[str, Any]:
    import numpy as np

    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.core.pipeline import PipelineModel
    from mmlspark_tpu.gbdt import booster as B
    from mmlspark_tpu.gbdt import pallas_hist, pallas_select
    from mmlspark_tpu.gbdt.stages import LightGBMClassifier

    sz, ck = fx.sz, Checks()
    rng = np.random.default_rng(fx.seed + 2)
    x, y = _higgs_like(sz.gbdt_rows, 28, rng)

    def dense():
        df = DataFrame.from_dict({"features": x, "label": y})
        clf = LightGBMClassifier(numIterations=sz.gbdt_iters,
                                 boostingType="goss", numLeaves=31,
                                 minDataInLeaf=20, seed=fx.seed)
        with _Spy(B, "_train_scan") as scan, \
                _Spy(B, "_train_native") as native, \
                _Spy(pallas_hist, "compute_histogram_mxu") as hist, \
                _Spy(pallas_select, "select_rows") as select:
            model = clf.fit(df)
        # the TPU-only branches tier-1 cannot reach: whole-run device scan
        # (not the host C++ engine), Pallas histogram + row-select traced in
        _check(scan.calls == 1 and native.calls == 0,
               f"scan={scan.calls} native={native.calls}: not the device path")
        _check(hist.calls > 0, "Pallas histogram never traced")
        if not sz.tiny:
            _check(select.calls > 0, "Pallas row-select never traced")
        m = sz.gbdt_ref_rows
        sub = DataFrame.from_dict({"features": x[:m], "label": y[:m]},
                                  num_partitions=2)
        fused = PipelineModel([model]).fuse()
        pred = np.asarray(fused.transform(sub).column("prediction"),
                          dtype=np.float64)
        fstats = fused.fusion_stats()
        _check(fstats["n_fused_segments"] == 1
               and fstats["fallbacks_total"] == 0,
               f"forest not fused: {fstats['segments']} "
               f"{fstats['fallbacks']}")
        acc = float((pred == y[:m]).mean())
        # the native host engine on the subsample (below the size budget
        # the same estimator routes there by itself)
        with _Spy(B, "_train_native") as native_ref:
            ref_model = LightGBMClassifier(
                numIterations=sz.gbdt_iters, boostingType="goss",
                numLeaves=31, minDataInLeaf=20, seed=fx.seed).fit(sub)
        if not sz.tiny:
            _check(native_ref.calls == 1, "reference fit was not native")
        ref_pred = np.asarray(ref_model.transform(sub).column("prediction"),
                              dtype=np.float64)
        ref_acc = float((ref_pred == y[:m]).mean())
        _check(abs(acc - ref_acc) <= 0.03 and acc > 0.6,
               f"accuracy {acc} vs native host engine {ref_acc} "
               f"(margin 0.03)")
        return {"rows": sz.gbdt_rows, "iterations": sz.gbdt_iters,
                "accuracy_on_subsample": round(acc, 4),
                "native_engine_accuracy": round(ref_acc, 4),
                "margin": 0.03, "hist_traces": hist.calls,
                "select_traces": select.calls,
                "predict_devices": fstats["devices"]}

    def bagging():
        # bagging instead of GOSS: host-precomputed row masks, and on a TPU
        # at >= 100k rows with <= 0.625 selected, in-scan row compaction
        # (booster._train_scan bag_cap) — the branch GOSS excludes
        df = DataFrame.from_dict({"features": x, "label": y})
        clf = LightGBMClassifier(numIterations=sz.gbdt_iters - 1,
                                 baggingFraction=0.5, baggingFreq=1,
                                 numLeaves=31, minDataInLeaf=20,
                                 seed=fx.seed)
        with _Spy(B, "_train_scan") as scan, \
                _Spy(B, "_train_native") as native:
            model = clf.fit(df)
        _check(scan.calls == 1 and native.calls == 0,
               f"scan={scan.calls} native={native.calls}: not the device path")
        m = sz.gbdt_ref_rows
        sub = DataFrame.from_dict({"features": x[:m], "label": y[:m]})
        pred = np.asarray(model.transform(sub).column("prediction"),
                          dtype=np.float64)
        acc = float((pred == y[:m]).mean())
        _check(acc > 0.7 if not sz.tiny else acc > 0.6,
               f"bagged fit accuracy {acc}")
        return {"rows": sz.gbdt_rows, "iterations": sz.gbdt_iters - 1,
                "bagging_fraction": 0.5,
                "accuracy_on_subsample": round(acc, 4)}

    def sparse():
        from mmlspark_tpu.gbdt import sparse as S

        n, k, width = sz.sparse_rows, sz.sparse_nnz_per_row, sz.sparse_width
        stride = width // k
        cols = (np.sort(rng.integers(0, stride, (n, k)), 1)
                + np.arange(k) * stride).astype(np.int64)
        vals = rng.normal(size=(n, k))
        y = (vals[:, 0] * (cols[:, 0] % 2 * 2 - 1) + vals[:, 1] > 0
             ).astype(np.float64)
        indptr = np.arange(n + 1, dtype=np.int64) * k
        ds = S.SparseDataset.from_csr(indptr, cols.reshape(-1),
                                      vals.reshape(-1), width)
        params = B.TrainParams(objective="binary", boosting_type="goss",
                               num_iterations=5, num_leaves=15, max_depth=6,
                               min_data_in_leaf=20, seed=fx.seed)
        with _Spy(S, "_train_scan_sparse") as scan:
            booster = S.train_sparse(params, ds, y)
        _check(scan.calls == 1, "sparse fit did not take the device scan")
        raw = S.predict_csr(booster.trees, indptr, cols.reshape(-1),
                            vals.reshape(-1), 1)[:, 0] + booster.base_score[0]
        _check(bool(np.isfinite(raw).all()), "non-finite sparse scores")
        acc = float(((raw > 0) == (y > 0)).mean())
        base = max(y.mean(), 1 - y.mean())
        _check(acc > base + 0.02, f"sparse accuracy {acc} vs majority {base}")
        return {"rows": n, "nnz": int(n * k), "width": width,
                "accuracy": round(acc, 4), "majority": round(float(base), 4)}

    def vw():
        from mmlspark_tpu.vw import learner as L
        from mmlspark_tpu.vw.stages import VowpalWabbitClassifier

        n, k, bits = sz.vw_rows, 16, 16
        idx = rng.integers(0, 1 << bits, size=(n, k)).astype(np.int32)
        val = (rng.normal(size=(n, k)) / np.sqrt(k)).astype(np.float32)
        w_true = rng.normal(size=1 << bits).astype(np.float32)
        y = ((w_true[idx] * val).sum(axis=1) > 0).astype(np.float64)
        rows = np.empty(n, dtype=object)
        for i in range(n):
            rows[i] = {"indices": idx[i], "values": val[i]}
        df = DataFrame.from_dict({"features": rows, "label": y})
        # FTRL has no native sequential learner: the device scan engine
        with _Spy(L, "make_scan_pass") as scan:
            model = VowpalWabbitClassifier(
                numBits=bits, numPasses=2,
                passThroughArgs="--ftrl --ftrl_alpha 0.1").fit(df)
        _check(scan.calls >= 1, "VW fit did not build the scan pass")
        pred = np.asarray(model.transform(df).column("prediction"))
        acc = float((pred == y).mean())
        _check(acc > 0.6, f"VW scan-engine train accuracy {acc}")
        return {"rows": n, "accuracy": round(acc, 4)}

    ck.run("dense_fit_and_fused_predict", dense)
    ck.run("dense_bagging_fit", bagging)
    ck.run("sparse_fit", sparse)
    ck.run("vw_scan_pass", vw)
    return ck.finish()


# ---------------------------------------------------------------------------
# phase: multichip
# ---------------------------------------------------------------------------


def phase_multichip(fx: Fixtures) -> Any:
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_dev = jax.device_count()
    if n_dev < 4:
        return f"skipped: {n_dev} device"
    from mmlspark_tpu.core.pipeline import PipelineModel
    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
    from mmlspark_tpu.serving import serve_pipeline

    sz, ck = fx.sz, Checks()
    devs = jax.local_devices()[:4]   # where ReplicaSet places 4 replicas
    rng = np.random.default_rng(fx.seed + 3)

    def serial_features():
        if fx.batch_features is None:
            fused = PipelineModel(fx.image_stages).fuse()
            fx.batch_features = _features_of(fused.transform(fx.image_df()))
        return fx.batch_features

    def replicas():
        serial_features()
        server = serve_pipeline(_served_chain(fx), input_col="data",
                                reply_col="features", parse="json",
                                host="127.0.0.1", port=0, fused=True,
                                async_exec=True, replicas=4, inflight=4,
                                max_batch_size=4)
        with server:
            base = f"http://{server.host}:{server.port}"
            info = _drive_server(fx, server, n_seq=2, bursts=(16, 16, 16))
            stats = _get_json(base + "/_mmlspark/stats")
        reps = stats["async"]["replicas"]
        _check(len(reps) == 4 and all(r["batches"] >= 1 for r in reps),
               f"replicas: {reps}")
        _check({r["device"] for r in reps} == {str(d) for d in devs},
               f"replica devices: {[r['device'] for r in reps]}")
        # where the fused executables' outputs actually landed: each
        # replica's batches on that replica's device, none elsewhere
        landed = stats["fusion"]["devices"]
        _check(landed == {r["device"]: r["batches"] for r in reps},
               f"outputs landed on {landed}, replicas ran {reps}")
        _check(stats["fusion"]["fallbacks_total"] == 0, "fallbacks")
        info["outputs_by_device"] = landed
        return info

    def sharded_chain():
        want = serial_features()
        fused = PipelineModel(fx.image_stages).fuse()
        df = fx.image_df()
        fused.transform(fx.image_df(rows=sz.ref_rows, parts=1))
        label = next(iter(fused.fusion_stats()["per_segment"]))
        fused.set_mesh(make_mesh(MeshSpec(data=4), device_list=devs))
        fused.set_tuning(sharding={label: "data"})
        got = _features_of(fused.transform(df))
        stats = fused.fusion_stats()
        _check(stats["fallbacks_total"] == 0, f"{stats['fallbacks']}")
        seg = stats["sharding"]["segments"][label]
        _check(seg["shards"] == 4, f"sharding: {seg}")
        err = _rel_err(got, want)
        _check(err <= REL_TOL, f"sharded vs unsharded: {err}")
        return {"shards": 4, "rel_err_vs_unsharded": round(err, 6)}

    def pipelined_chain():
        from mmlspark_tpu.models.dnn_model import DNNModel
        from mmlspark_tpu.models.module import (Dense, FunctionModel,
                                                Sequential, relu)

        head = Sequential([("d1", Dense(256)), ("a", relu()),
                           ("d2", Dense(16))], name="smokehead")
        hp, _ = head.init(jax.random.PRNGKey(fx.seed), (sz.feat_dim,))
        dnn = DNNModel(inputCol="features", outputCol="emb")
        dnn.set_model(FunctionModel(head, hp, (sz.feat_dim,),
                                    name="smokehead"))
        fused = PipelineModel(fx.image_stages + [dnn]).fuse()
        df = fx.image_df(rows=min(sz.image_rows, 256))
        want = _features_of(fused.transform(df), "emb")
        fused.set_mesh(make_mesh(MeshSpec(data=2, pipe=2), device_list=devs))
        fused.set_tuning(pipe_depth=2)
        got = _features_of(fused.transform(df), "emb")
        stats = fused.fusion_stats()
        pipe = stats.get("pipeline") or {}
        _check(pipe.get("depth") == 2, f"pipeline stats: {pipe}")
        _check(pipe.get("serial_fallback_partitions") == 0
               and stats["fallbacks_total"] == 0, f"fallbacks: {pipe}")
        _check(np.array_equal(got, want),
               f"pipelined != serial: {np.abs(got - want).max()}")
        return {"depth": 2, "bitwise_equal_to_serial": True,
                "handoff_bytes": pipe.get("handoff_bytes")}

    def sharded_hist():
        from jax.sharding import NamedSharding, PartitionSpec as P

        from mmlspark_tpu.gbdt import pallas_hist

        f, b, n = 28, 256, (4096 if sz.tiny else 1 << 20)
        bins = rng.integers(0, b, size=(f, n)).astype(np.int32)
        g = rng.normal(size=n).astype(np.float32)
        h = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
        m = rng.uniform(size=n) < 0.7
        mesh = make_mesh(MeshSpec(data=4), device_list=devs)
        rows = NamedSharding(mesh, P("data"))
        sharded = pallas_hist.compute_histogram_sharded(
            jax.device_put(bins, NamedSharding(mesh, P(None, "data"))),
            jax.device_put(g, rows), jax.device_put(h, rows),
            jax.device_put(m, rows), b, interpret=sz.tiny)
        single = pallas_hist.compute_histogram_mxu(
            jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
            jnp.asarray(m), b, interpret=sz.tiny)
        _check(len(sharded.sharding.device_set) == 4, "result not on 4")
        err = float(np.max(np.abs(np.asarray(sharded) - np.asarray(single))))
        # same kernel, four partial sums + psum: f32 reassociation only
        _check(err <= 0.5, f"sharded vs single-device: max abs err {err}")
        _check(np.array_equal(np.asarray(sharded)[..., 2],
                              np.asarray(single)[..., 2]), "counts differ")
        return {"rows": n, "max_abs_err": round(err, 5)}

    def battery():
        import __graft_entry__ as graft

        _check(n_dev == 4, f"battery meshes span all {n_dev} devices")
        graft.run_battery(4)
        return {"gates": 8}

    ck.run("replicas4", replicas)
    ck.run("sharded_chain", sharded_chain)
    ck.run("pipelined_chain", pipelined_chain)
    ck.run("sharded_gbdt_hist", sharded_hist)
    ck.run("graft_battery", battery)
    return ck.finish()


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def _cache_entries(path: Optional[str]) -> Optional[int]:
    if not path or not os.path.isdir(path):
        return None
    return sum(1 for n in os.listdir(path) if n.endswith("-cache"))


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default=",".join(ALL_PHASES),
                    help="comma-separated subset of: " + ",".join(ALL_PHASES))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline", type=float, default=1150.0,
                    help="hard wall limit in seconds: past it the process "
                         "reports the phase it was in and exits non-zero "
                         "(a hung chip call must not outlive its machine)")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU debug run at tiny sizes; never reports a pass")
    args = ap.parse_args(argv)
    wanted = [p.strip() for p in args.phases.split(",") if p.strip()]
    unknown = sorted(set(wanted) - set(ALL_PHASES))
    if unknown:
        _die(2, f"unknown phase(s) {unknown}; known: {list(ALL_PHASES)}")

    here = os.path.dirname(os.path.abspath(__file__))
    if not os.path.isdir(os.path.join(here, "mmlspark_tpu")):
        _die(EXIT_NOT_A_CHECKOUT,
             f"no mmlspark_tpu package beside {__file__}: run from a "
             f"checkout of the repo")
    sys.path.insert(0, here)
    if args.tiny:
        # the forcing switches tier-1 uses to reach device paths on a CPU
        os.environ.setdefault("MMLSPARK_TPU_PALLAS_INTERPRET", "1")
        os.environ.setdefault("MMLSPARK_TPU_SCAN_TRAIN", "1")
        os.environ.setdefault("MMLSPARK_TPU_FUSED_TREE", "1")
        os.environ.setdefault("MMLSPARK_TPU_NATIVE_VW", "0")

    t_start = time.perf_counter()
    current = {"phase": "start-up"}

    def on_deadline() -> None:
        print(f"chip_smoke: deadline of {args.deadline:.0f} s exceeded in "
              f"phase {current['phase']}", file=sys.stderr, flush=True)
        os._exit(EXIT_DEADLINE)

    timer = threading.Timer(args.deadline, on_deadline)
    timer.daemon = True
    timer.start()
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[chip_smoke] jax {jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind} count={device['count']}",
          flush=True)
    if dev.platform != "tpu" and not args.tiny:
        _die(EXIT_NO_ACCELERATOR,
             f"JAX found no accelerator (platform={dev.platform!r}): this "
             f"smoke only passes on a TPU")

    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    from mmlspark_tpu.core.runtime import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    entries_before = _cache_entries(cache_dir)
    print(f"[chip_smoke] compile cache: {cache_dir} "
          f"({entries_before} entries)", flush=True)

    fx = Fixtures(Sizes(args.tiny), args.seed)
    table = {"env": phase_env, "kernels": phase_kernels,
             "batch_transform": phase_batch_transform,
             "server": phase_server, "trainer": phase_trainer,
             "gbdt": phase_gbdt, "multichip": phase_multichip}
    def tracked(name: str) -> Callable[[], Any]:
        def go():
            current["phase"] = name
            return table[name](fx)
        return go

    results = run_phases([(p, tracked(p)) for p in ALL_PHASES if p in wanted])
    timer.cancel()
    code = exit_code(results)
    entries_after = _cache_entries(cache_dir)
    summary = {
        "ok": code == 0 and not args.tiny,
        "device": device,
        "jax": jax.__version__,
        "seed": args.seed,
        "phases": {n: r["status"] for n, r in results.items()},
        "setup_and_run_seconds": {
            n: r["setup_and_run_seconds"] for n, r in results.items()},
        "total_seconds": round(time.perf_counter() - t_start, 1),
        "compile_cache": {
            "dir": cache_dir, "entries_before": entries_before,
            "entries_after": entries_after,
            "entries_written": (entries_after - entries_before
                                if entries_after is not None
                                and entries_before is not None else None),
            "hits": cache_events["hits"], "misses": cache_events["misses"]},
        "detail": {n: r.get("info") or r.get("error")
                   for n, r in results.items()},
    }
    if args.tiny:
        summary["tiny_debug_run"] = True
        code = code or EXIT_TINY_DEBUG
    os.makedirs(os.path.join(here, "chiprun_out"), exist_ok=True)
    with open(os.path.join(here, "chiprun_out", "chip_smoke.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    print("[chip_smoke] summary " + json.dumps(summary, default=str),
          flush=True)
    sys.stderr.flush()
    print(result_line(summary["ok"], device), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
