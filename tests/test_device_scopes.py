"""Device-side scopes (`obs/scopes.py`): the parser on hand-written HLO, the
names the fused programs of four model families carry, that a scope changes
metadata only, and that nothing is parsed or kept alive unless asked."""

import contextlib
import gc
import pathlib
import re
import weakref

import numpy as np
import pytest

import jax

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.device_stage import CompileCache, live_caches
from mmlspark_tpu.core.fusion import FusedPipelineModel
from mmlspark_tpu.core.pipeline import PipelineModel
from mmlspark_tpu.core.schema import ImageSchema
from mmlspark_tpu.image.featurizer import ImageFeaturizer
from mmlspark_tpu.image.stages import ImageTransformer
from mmlspark_tpu.models import attention, transformer
from mmlspark_tpu.models.resnet import resnet
from mmlspark_tpu.models.dnn_model import DNNModel
from mmlspark_tpu.obs import scopes

T = 32

# -- the parser on hand-written lines -----------------------------------------

# a fused program's optimized HLO in small: TPU layouts, a loop in the entry
# computation, a branch with a Pallas call, two fusions (one the compiler's own)
HLO = (pathlib.Path(__file__).parent / "resources" / "device_scopes_fused.hlo.txt"
       ).read_text()


@pytest.fixture(scope="module")
def parsed():
    return scopes.parse(HLO)


@pytest.mark.parametrize("op_name, path", [
    ("jit(fused)/~DNNModel/~layer3/~moe/while/body/closed_call/~combine/add",
     "DNNModel/layer3/moe/combine"),
    ("jit(fused)/~ImageFeaturizer/~layer2/~1/~body/~conv1/conv_general_dilated",
     "ImageFeaturizer/layer2/1/body/conv1"),
    # a model's own `body` is kept, a loop's `body` is JAX's and dropped
    ("jit(fused)/~DNNModel/~bilstm/~fwd/while/body/checkpoint/custom_vjp_call/dot_general",
     "DNNModel/bilstm/fwd"),
    ("jit(fused)/vmap(jit(inner))/cond/branch_0_fun/transpose(jvp(f))/mul", ""),
    # the training path: a transform wraps the scope it was applied under
    ("jit(step)/transpose(jvp(~layer0))/~attn/~proj_in/transpose", "layer0/attn/proj_in"),
    # instructions XLA merged: the first name
    ("jit(f)/jvp(~head)/vmap()/mul;jit(f)/transpose(jvp(~head))/vmap()/mul", "head"),
    ("params_tuple[0][\\'embed\\'][\\'table\\']", ""),
    ("", ""),
])
def test_a_path_is_the_marked_components_alone(op_name, path):
    assert scopes.path_of(op_name) == path


@pytest.mark.parametrize("instruction, path", [
    ("dot_general.1", "DNNModel/layer0/attn/proj_in"),        # the entry computation
    ("while.9", "DNNModel/layer1/moe"),                       # a loop, its own metadata
    ("tanh_add_fusion", "DNNModel/layer1/moe/combine"),       # a fusion in a loop's body
    ("lt.9", "DNNModel/layer1/moe"),                          # a loop's condition
    ("moe_gmm.3", "DNNModel/layer1/moe/experts"),             # a Pallas call in a branch
    ("reduce.1", "DNNModel/head"),
    # the compiler's own fusion: under what all it fused shares
    ("convert_bitcast_fusion", "DNNModel/layer0/attn"),
    ("copy.8", ""), ("copy.3", ""), ("x.1", ""), ("tuple.5", ""),
])
def test_the_map_names_every_event_bearing_instruction(parsed, instruction, path):
    module, found = parsed
    assert module == "jit_fused"
    assert found[instruction] == path


def test_what_runs_inside_one_event_is_no_entry_of_the_map(parsed):
    _, found = parsed
    # a fusion's inner instructions, a reducer's, a name inside a kernel's config
    assert not {"tanh.2", "add.4", "slice.1", "convert.9", "reduce_max.5",
                "not_a_computation"} & found.keys()
    assert {"p.2", "get-tuple-element.10"} <= found.keys()    # both branches, the body


def test_operations_and_callees_survive_tpu_layouts_and_tuple_types():
    rows = {name: (kind, called)
            for _c, _e, name, kind, _o, called in scopes.instructions(HLO)}
    assert rows["while.9"] == ("while", ["region_1.3", "region_0.2"])
    assert rows["cond.1.clone"] == ("conditional", ["region_3.5", "region_4.6"])
    assert rows["tanh_add_fusion"] == ("fusion", ["fused_computation"])
    assert rows["moe_gmm.3"] == ("custom-call", [])
    assert rows["get-tuple-element.10"][0] == "get-tuple-element"


# -- the fused programs of four model families ---------------------------------

def token_df(n=8, vocab=64):
    ids = np.random.default_rng(0).integers(1, vocab, (n, T), dtype=np.int32)
    col = np.empty(n, dtype=object)
    for i in range(n):
        col[i] = ids[i]
    return DataFrame.from_dict({"tokens": col}, num_partitions=2)


def image_df(n=8):
    rng = np.random.default_rng(3)
    rows = np.empty(n, dtype=object)
    for i in range(n):
        rows[i] = ImageSchema.make(rng.integers(0, 256, (20, 24, 3), dtype=np.uint8),
                                   f"img{i}")
    return DataFrame.from_dict({"image": rows}, num_partitions=2)


YARN = dict(beta_fast=32, beta_slow=1, factor=4, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=8, type="yarn")


def causal():
    return transformer.causal_lm(T, 64, 64, 4, 2, 16, [4, 0], [False, True],
                                 96, 32, 8, 4, 2)


def latent():
    return transformer.latent_causal_lm(T, 64, 64, 4, 24, 16, 16, 8, 16,
                                        [False, True], 96, 32, 8, 8, 2,
                                        streams=4, rope_scaling=YARN)


def chain_of(family):
    """(stages, frame, the stage that holds the model, the names below it)."""
    if family == "resnet":
        model = resnet(18, num_classes=10, image_size=16, width=8)
        stages = [ImageTransformer().resize(16, 16),
                  ImageFeaturizer(scaleFactor=1 / 255., batchSize=4).set_model(model)]
        return stages, image_df(), "ImageFeaturizer", top_names(model)
    model = {"bilstm_tagger": lambda: attention.bilstm_tagger(T, 64, 8, 12, 5),
             "causal_lm": causal, "latent_causal_lm": latent}[family]()
    stage = DNNModel(inputCol="tokens", outputCol="out", batchSize=4).set_model(model)
    names = top_names(model)
    if isinstance(model.module, transformer.CausalLM):
        names = {n for n in names if n.startswith("layer")} | {"embed", "head"}
    return [stage], token_df(), "DNNModel", names


def top_names(model):
    return {p.split("/")[0] for p in model.module.layer_paths()}


def fused_text(stages, df):
    cache = CompileCache()
    out = FusedPipelineModel(stages, cache=cache).transform(df)
    (_, fn), = cache.resident()
    return out, fn.as_text()


FAMILIES = ["resnet", "bilstm_tagger", "causal_lm", "latent_causal_lm"]


@pytest.fixture(scope="module", params=FAMILIES)
def program(request):
    stages, df, stage, names = chain_of(request.param)
    _, text = fused_text(stages, df)
    return request.param, text, stage, names


def test_every_traced_instruction_is_under_its_stage_and_a_layer_name(program):
    family, text, stage, names = program
    module, found = scopes.parse(text)
    assert module == "jit_fused"
    traced = [(name, op_name) for _c, _e, name, _k, op_name, _called
              in scopes.instructions(text)
              if name in found and op_name.startswith("jit(fused)/")]
    assert len(traced) > 10
    for name, op_name in traced:
        parts = found[name].split("/")
        if family == "resnet" and parts[0] == "ImageTransformer":
            continue                 # the resize's stage: nothing below it
        assert parts[0] == stage, (name, op_name)
        assert len(parts) > 1 and parts[1] in names, (name, op_name, sorted(names))
    # the featurizer cuts the network at its feature layer; the pool is one
    # reduction the CPU's compiler rewrites without metadata
    assert {found[n].split("/")[1] for n, _ in traced
            if found[n].count("/")} >= names - {"expert_load", "avgpool", "fc"}


def test_the_sublayers_have_their_phases(program):
    family, text, _stage, _names = program
    paths = set(scopes.parse(text)[1].values())
    has = lambda tail: any(re.search(tail + "$", p) for p in paths)   # noqa: E731
    if family == "bilstm_tagger":
        assert has("DNNModel/bilstm/fwd") and has("DNNModel/bilstm/bwd")
    if family == "resnet":
        assert has(r"layer2/1/body/\w+") and has(r"layer2/0/shortcut/\w+")
    if family in ("causal_lm", "latent_causal_lm"):
        for phase in ("route", "sort", "gather", "experts", "combine"):
            assert has(f"layer1/moe/{phase}"), phase
        for phase in ("proj_in", "core", "proj_out", "attn_norm"):
            assert has(f"layer0/attn/{phase}"), phase
        assert has("layer0/mlp/mlp_norm") and has("layer1/shared") and has("/head")
    if family == "latent_causal_lm":
        for part in ("attn_hc", "mlp_hc"):
            for phase in ("pre", "coeff", "post"):
                assert has(f"layer0/{part}/{phase}"), (part, phase)


SCOPED = ["mmlspark_tpu.core.fusion", "mmlspark_tpu.models.module",
          "mmlspark_tpu.models.attention", "mmlspark_tpu.models.transformer",
          "mmlspark_tpu.models.moe", "mmlspark_tpu.models.residual"]


@pytest.mark.parametrize("family", ["bilstm_tagger", "causal_lm"])
def test_a_scope_changes_metadata_and_no_output_bit(family, monkeypatch):
    import importlib

    stages, df, _stage, _names = chain_of(family)
    with_scopes, text = fused_text(stages, df)
    for module in SCOPED:
        monkeypatch.setattr(importlib.import_module(module), "scope",
                            lambda name: contextlib.nullcontext())
    without, bare = fused_text(stages, df)
    assert scopes.MARK in text and scopes.MARK not in bare
    a, b = with_scopes.collect()["out"], without.collect()["out"]
    assert len(a) == len(b) == 8
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- nothing parsed unless asked, nothing kept alive -----------------------------

def test_programs_parse_when_asked_once_and_keep_no_evicted_executable(monkeypatch):
    parsed_texts = []
    real = scopes.parse
    monkeypatch.setattr(scopes, "parse",
                        lambda text: parsed_texts.append(len(text)) or real(text))
    stages, df, _stage, _names = chain_of("bilstm_tagger")
    cache = CompileCache()
    fused = FusedPipelineModel(stages, cache=cache)
    fused.transform(df)
    fused.transform(df)
    assert parsed_texts == []          # a build and a dispatch parse nothing
    assert cache in live_caches()

    (_, fn), = cache.resident()
    mine = [p for p in scopes.programs() if p.scopes is scopes._PARSED[fn][1]]
    assert [(p.label, p.module) for p in mine] == [("DNNModel", "jit_fused")]
    assert "DNNModel/bilstm/fwd" in set(mine[0].scopes.values())
    asked = len(parsed_texts)
    assert asked >= 1
    scopes.programs()
    assert len(parsed_texts) == asked  # a program's map is parsed once and kept

    alive = weakref.ref(fn)
    del fn, mine
    cache.set_capacity(1)
    cache.get(("another",), lambda: (lambda *a: None))     # evicts the program
    gc.collect()
    assert alive() is None
    assert all(p.label != "DNNModel" or p.scopes is not None
               for p in scopes.programs())                 # still callable


def test_program_scopes_reads_any_compiled_program():
    def f(x):
        with scopes.scope("outer"):
            with scopes.scope("inner"):
                y = jax.numpy.tanh(x)
            return jax.lax.fori_loop(0, 3, lambda i, c: c * 2.0 + y, y)

    found = scopes.program_scopes(jax.jit(f).lower(np.ones((4,), np.float32)).compile())
    assert {"outer", "outer/inner"} <= set(found.values())
