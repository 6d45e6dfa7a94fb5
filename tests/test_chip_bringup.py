"""Bring-up contracts that can be checked without a chip (PR 21).

What the chip run itself proves lives in ``chip_smoke.py``; these are the
CPU-checkable halves of the same repairs:

  - the persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
    says (and then nothing is set in code) or to ``<checkout>/.jax_cache``;
  - a compile error inside a fused segment's ``lower().compile()``
    surfaces instead of being retried as a lazy jit;
  - a fused pipeline shared by four replicas executes on four devices
    (the AOT executable is keyed by device, staged batches follow it);
  - ``chip_smoke.py`` refuses to report anything without a TPU, outside a
    checkout, and turns any failed phase into a non-zero exit.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from mmlspark_tpu.core import runtime
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.device_stage import CompileCache
from mmlspark_tpu.core.fusion import FusedPipelineModel
from mmlspark_tpu.core.schema import ImageSchema
from mmlspark_tpu.image.featurizer import ImageFeaturizer
from mmlspark_tpu.image.stages import ImageTransformer
from mmlspark_tpu.models.module import (Conv2D, Dense, FunctionModel,
                                        GlobalAvgPool, Sequential, relu)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _fused_image_chain():
    size = 16
    mod = Sequential([("conv", Conv2D(4, (3, 3))), ("act", relu()),
                      ("pool", GlobalAvgPool()), ("head", Dense(4))],
                     name="bringupcnn")
    params, _ = mod.init(jax.random.PRNGKey(0), (size, size, 3))
    backbone = FunctionModel(mod, params, (size, size, 3),
                             layer_names=["head", "pool"], name="bringupcnn")
    rng = np.random.default_rng(4)
    rows = np.empty(8, dtype=object)
    for i in range(8):
        rows[i] = ImageSchema.make(
            rng.integers(0, 256, (20, 20, 3), dtype=np.uint8), f"img{i}")
    df = DataFrame.from_dict({"image": rows})
    fused = FusedPipelineModel(
        [ImageTransformer().resize(size, size),
         ImageFeaturizer(scaleFactor=1 / 255., batchSize=8)
         .set_model(backbone)], cache=CompileCache())
    return fused, df


# -- compile cache placement -------------------------------------------------


class TestCompileCachePlacement:
    @pytest.fixture
    def fresh(self, monkeypatch):
        """ensure_compile_cache resolves once per process: reset it, and
        record (never apply) what it would set on jax.config."""
        monkeypatch.setattr(runtime, "_cache_resolved", False)
        monkeypatch.setattr(runtime, "_cache_dir", None)
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.append((k, v)))
        monkeypatch.setattr(runtime.os, "makedirs", lambda *a, **k: None)
        return updates

    def test_env_dir_is_left_to_jax(self, fresh, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert runtime.ensure_compile_cache() == "/x"
        assert runtime.compile_cache_dir() == "/x"
        assert fresh == []          # nothing set in code

    def test_default_is_checkout_dir_from_any_cwd(self, fresh, monkeypatch,
                                                  tmp_path):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.chdir(tmp_path)
        want = os.path.join(REPO, ".jax_cache")
        assert runtime.compile_cache_dir() == want
        assert runtime.ensure_compile_cache() == want
        assert fresh == [("jax_compilation_cache_dir", want)]
        # idempotent: the second call sets nothing again
        assert runtime.ensure_compile_cache() == want
        assert len(fresh) == 1

    def test_cpu_backend_keeps_the_cache_off(self, fresh, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert jax.default_backend() == "cpu"
        assert runtime.ensure_compile_cache() is None
        assert fresh == []

    def test_no_private_directory_variable(self, fresh, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("MMLSPARK_TPU_COMPILE_CACHE_DIR", "/private")
        assert runtime.compile_cache_dir() == os.path.join(REPO,
                                                           ".jax_cache")


# -- no fallback that hides the compiler --------------------------------------


def test_segment_compile_error_propagates(monkeypatch):
    """A Mosaic refusal / VMEM OOM raised by ``lower().compile()`` must reach
    the caller — not be swallowed and replaced by a lazy jit."""
    fused, df = _fused_image_chain()

    def refuse(self, *a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: scoped vmem limit exceeded")

    monkeypatch.setattr(jax.stages.Lowered, "compile", refuse)
    with pytest.raises(RuntimeError, match="scoped vmem"):
        fused.transform(df)


# -- replicas execute where they were placed ----------------------------------


def test_fused_pipeline_under_replicaset_runs_on_four_devices():
    """One fused model shared by four replicas (what serve_pipeline builds):
    each replica's segment outputs land on that replica's device."""
    from mmlspark_tpu.serving.executor import ReplicaSet

    assert jax.device_count() >= 4
    fused, df = _fused_image_chain()
    rs = ReplicaSet(fused.transform, n=4)
    outs = [rs.run(r, df) for r in rs.replicas]
    want = {str(r.device): 1 for r in rs.replicas}
    assert len(want) == 4
    assert fused.fusion_stats()["devices"] == want
    ref = np.stack([np.asarray(v) for v in outs[0].column("features")])
    for out in outs[1:]:
        got = np.stack([np.asarray(v) for v in out.column("features")])
        np.testing.assert_array_equal(got, ref)
    assert fused.fusion_stats()["fallbacks_total"] == 0
    # a per-device executable each, none shared across devices
    assert fused.compile_cache.stats()["misses"] == 4


# -- chip_smoke.py contract ----------------------------------------------------


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _no_result_line(stdout: str) -> bool:
    return not any(ln.lstrip().startswith("{")
                   for ln in stdout.splitlines())


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SMOKE], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    reason = [ln for ln in r.stderr.splitlines()
              if ln.startswith("chip_smoke:")]
    assert len(reason) == 1 and "no accelerator" in reason[0]
    assert _no_result_line(r.stdout)


def test_chip_smoke_alone_is_not_a_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_bytes(open(SMOKE, "rb").read())
    r = subprocess.run([sys.executable, str(lone)], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stderr.startswith("chip_smoke:") and _no_result_line(r.stdout)


def test_the_last_stdout_line_has_the_contract_keys_and_no_other():
    """The driver refuses a last line with any key besides ok/device and
    platform/kind/count; the per-phase summary rides on the line before."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SMOKE, "--tiny", "--phases", "env"],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 3                # a --tiny run never passes
    lines = r.stdout.splitlines()
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is False
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert isinstance(last["device"]["platform"], str)
    assert isinstance(last["device"]["kind"], str)
    assert type(last["device"]["count"]) is int
    assert lines[-2].startswith("[chip_smoke] summary {")
    summary = json.loads(lines[-2].split("summary ", 1)[1])
    assert summary["phases"] == {"env": "pass"}
    assert "compile_cache" in summary and summary["device"] == last["device"]
    smoke = _load_smoke()
    ok = json.loads(smoke.result_line(
        True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}))
    assert ok == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_a_failed_phase_makes_the_exit_code_nonzero(capsys):
    smoke = _load_smoke()

    def boom():
        raise RuntimeError("injected")

    ran = []
    results = smoke.run_phases([("first", boom),
                                ("second", lambda: ran.append(1) or {})])
    capsys.readouterr()
    assert ran == [1]                       # later phases still run
    assert results["first"]["status"] == "fail"
    assert "injected" in results["first"]["error"]
    assert results["second"]["status"] == "pass"
    assert smoke.exit_code(results) != 0
    ok = smoke.run_phases([("only", lambda: "skipped: 1 device")])
    capsys.readouterr()
    assert smoke.exit_code(ok) == 0
