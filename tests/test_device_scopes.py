"""Device time by the program's own scopes (``monitor.device_scopes()``): the
map from an executable's HLO instructions to ``jax.named_scope``s, where it is
made and kept, and the vocabulary every family's executables speak."""

import contextlib
import os
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.analysis.hlo_scopes import (instruction_scopes, scope_map,
                                               user_scopes)
from deepspeed_tpu.inference import Request, ServingEngine
from deepspeed_tpu.models import build
from deepspeed_tpu.monitor import device_scopes, scope_maps
from deepspeed_tpu.runtime import compile_cache as cc

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# ------------------------------------------------------------- the HLO text
def fixture_map(vocabulary=scope_maps.VOCABULARY):
    with open(os.path.join(HERE, "data", "device_scopes_step.hlo.txt")) as f:
        return scope_map(f.read(), vocabulary)


@pytest.mark.parametrize("instruction,scope", [
    ("fusion.1", "ssm.proj"),                    # a fusion under one scope
    ("fusion.2", ("lm_head", "sentinel")),       # a fusion over two
    ("multiply.3", "ssm.step"),                  # a while body's instruction
    ("custom-call.4", "ssm.step"),               # a Mosaic custom call
    ("mamba2_state_update.5", "ssm.step"),       # one called with name=
    ("add.7", ""),                               # wrapper components alone
    ("while.1", ""),
    ("copy.2", ""),                              # no op_name at all
])
def test_the_map_of_a_compiled_step(instruction, scope):
    module, got = fixture_map()
    assert module == "jit_step"
    assert got[instruction] == scope


def test_the_map_holds_what_a_trace_can_show_and_no_more():
    """Fused computations' and reducers' instructions are their fusion's;
    parameters, constants, tuples and bitcasts take no device time."""
    _, got = fixture_map()
    assert set(got) == {"fusion.1", "fusion.2", "multiply.3", "custom-call.4",
                        "mamba2_state_update.5", "add.7", "while.1", "copy.2",
                        "compare.1"}
    # without the program's vocabulary a kernel's own name stands
    assert fixture_map(None)[1]["mamba2_state_update.5"] \
        == "mamba2_state_update"


@pytest.mark.parametrize("op_name,scopes", [
    ("jit(step)/jit(main)/blocks/while/body/attn.window/dot_general",
     ("blocks", "attn.window", "dot_general")),
    ("jit(_train_step)/transpose(jvp(blocks))/while/body/closed_call/"
     "checkpoint/rematted_computation/attention/mul",
     ("blocks", "attention", "mul")),
    ("jit(f)/jvp(sentinel)/is_finite", ("sentinel", "is_finite")),
    ("jit(f)/cond/branch_1_fun/jit(_where)/select_n", ("select_n",)),
    ("jit(f)/jit(cumsum)/outer.<locals>.f/reduce_window_sum",
     ("reduce_window_sum",)),
    ("jit(f)/jvp()/pallas_call", ()),
])
def test_user_scopes_pass_over_what_jax_adds(op_name, scopes):
    assert user_scopes(op_name) == scopes
    line = f'  %x = f32[] add(%a, %b), metadata={{op_name="{op_name}"}}'
    assert instruction_scopes(line) == scopes[:-1]


def test_a_container_gives_way_in_a_fusion_and_stands_alone():
    """``blocks`` wraps the layer loop: its slice of the stacked weights
    fused into the ``mlp`` matmul is ``mlp`` work; the loop's own
    instructions stay ``blocks``."""
    line = ('  %{name} = f32[8]{{0}} {op}(%p), metadata={{op_name='
            '"jit(f)/blocks/while/body/{scope}{prim}"}}')
    text = "\n".join([
        "HloModule jit_f", "",
        "%fused_computation.1 (p: f32[8]) -> f32[8] {",
        line.format(name="s.1", op="dynamic-slice", scope="", prim="dynamic_slice"),
        line.format(name="d.1", op="negate", scope="mlp/", prim="neg"),
        "}", "",
        "ENTRY %main (p: f32[8]) -> f32[8] {",
        "  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, "
        "calls=%fused_computation.1",
        line.format(name="slice.2", op="dynamic-slice", scope="",
                    prim="dynamic_slice"),
        "}"])
    assert scope_map(text)[1] == {"fusion.1": ("blocks", "mlp"),
                                  "slice.2": "blocks"}
    assert scope_map(text, containers=scope_maps.CONTAINERS)[1] == {
        "fusion.1": "mlp", "slice.2": "blocks"}


def test_a_jitted_function_maps_to_the_innermost_scope():
    def f(x, w):
        with jax.named_scope("outer"):
            with jax.named_scope("inner"):
                y = jnp.tanh(x @ w)
            z = y @ w
        return z

    compiled = jax.jit(f).lower(jnp.ones((8, 16)), jnp.ones((16, 16))
                                ).compile()
    text = compiled.as_text()
    module, got = scope_map(text)
    assert module == "jit_f"
    assert "inner" in got.values() and "outer" in got.values()
    shown = 0
    for line in text.splitlines():
        m = re.match(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        scopes = instruction_scopes(line)
        if m and m.group(1) in got and " fusion(" not in line and scopes:
            assert got[m.group(1)] == scopes[-1], line
            shown += 1
    assert shown >= 2


# ------------------------------------------------- where the map is kept
@pytest.fixture
def fresh_maps():
    scope_maps.reset()
    yield
    scope_maps.reset()


def _scoped(x, w):
    with jax.named_scope("lm_head"):
        y = x @ w
    with jax.named_scope("sentinel"):
        bad = ~jnp.all(jnp.isfinite(y), axis=-1)
    return y, bad


def test_a_build_stores_the_map_and_a_load_renders_no_text(
        tmp_path, fresh_maps, monkeypatch):
    x, w = jnp.ones((8, 16)), jnp.ones((16, 16))
    cold = cc.wrap_step("t.scoped", _scoped, cache=cc.CompileCache(
        str(tmp_path)))
    cold(x, w)
    built = device_scopes()
    scopes = {s for v in built["jit__scoped"].values()
              for s in ((v,) if isinstance(v, str) else v)}
    assert {"lm_head", "sentinel"} <= scopes
    (key,) = cold.keys()
    assert os.path.isfile(os.path.join(str(tmp_path), key, cc.SCOPES_FILE))

    scope_maps.reset()

    def no_text(self, *a, **k):
        raise AssertionError("as_text() on a warm start")
    monkeypatch.setattr(jax.stages.Compiled, "as_text", no_text)
    cache = cc.CompileCache(str(tmp_path))
    warm = cc.wrap_step("t.scoped", _scoped, cache=cache)
    warm(x, w)
    assert cache.stats["hits"] == 1 and cache.stats["misses"] == 0
    assert device_scopes() == built


def test_an_older_entry_is_described_when_first_asked(tmp_path, fresh_maps):
    x, w = jnp.ones((8, 16)), jnp.ones((16, 16))
    cold = cc.wrap_step("t.scoped", _scoped, cache=cc.CompileCache(
        str(tmp_path)))
    cold(x, w)
    built = device_scopes()
    (key,) = cold.keys()
    os.remove(os.path.join(str(tmp_path), key, cc.SCOPES_FILE))
    # the manifest lists the file: an older store's entry never had it
    manifest = os.path.join(str(tmp_path), key, "manifest.json")
    import json
    with open(manifest) as f:
        m = json.load(f)
    m["files"].pop(cc.SCOPES_FILE)
    with open(manifest, "w") as f:
        json.dump(m, f)
    scope_maps.reset()
    warm = cc.wrap_step("t.scoped", _scoped, cache=cc.CompileCache(
        str(tmp_path)))
    warm(x, w)
    assert scope_maps._MAPS == {} and len(scope_maps._PENDING) == 1
    assert device_scopes() == built


def test_same_named_modules_that_disagree_read_ambiguous(fresh_maps):
    scope_maps.note("jit_step", {"fusion.3": "ssm.step", "copy.1": ""})
    scope_maps.note("jit_step", {"fusion.3": "moe.route", "copy.1": "",
                                 "fusion.9": ["lm_head", "sentinel"]})
    assert device_scopes() == {"jit_step": {
        "fusion.3": scope_maps.AMBIGUOUS, "copy.1": "",
        "fusion.9": ("lm_head", "sentinel")}}


# --------------------------------------------------------- the vocabulary
def test_the_documented_table_is_the_vocabulary():
    """``docs/monitoring.md#device-scopes`` lists every scope of
    ``scope_maps.VOCABULARY`` with the families that open it, and none
    else."""
    with open(os.path.join(ROOT, "docs", "monitoring.md")) as f:
        text = f.read()
    section = text.split("## Device scopes", 1)[1].split("\n## ", 1)[0]
    rows = {}
    for line in section.splitlines():
        m = re.match(r"^\| `([\w.]+)` \| ([^|]*) \|", line)
        if m:
            rows[m.group(1)] = tuple(
                w.strip(" `") for w in m.group(2).split(","))
    assert rows == {s: tuple(who) for s, (who, _) in
                    scope_maps.VOCABULARY.items()}


def test_every_named_scope_of_the_models_is_in_the_vocabulary():
    opened = set()
    for folder, names in (("models", None), ("moe", ("dropless.py",)),
                          ("runtime", ("health.py", "engine.py")),
                          ("inference", ("serving.py",))):
        path = os.path.join(ROOT, "deepspeed_tpu", folder)
        for name in names or sorted(os.listdir(path)):
            if name.endswith(".py"):
                with open(os.path.join(path, name)) as f:
                    opened |= set(re.findall(
                        r'jax\.named_scope\("([\w.]+)"\)', f.read()))
    assert opened == set(scope_maps.VOCABULARY)


SERVED = {   # family -> (preset, overrides, block size[, prompt tokens])
    "gpt2": ("gpt2-tiny", {"n_layer": 2, "max_seq": 64}, 8),
    "jamba": ("jamba-tiny", {"max_position_embeddings": 64}, 8),
    "ouro": ("ouro-tiny", {"max_position_embeddings": 64}, 8),
    "deepseek_v2": ("deepseek-v2-tiny", {}, 8),
    "afmoe": ("afmoe-tiny", {"max_position_embeddings": 64}, 4),
    "nemotron_h": ("nemotron-h-tiny", {"max_position_embeddings": 64}, 8),
    "phi4flash": ("phi4flash-tiny", {"max_position_embeddings": 64}, 8),
    "longcat_flash": ("longcat-flash-tiny", {}, 8),
    "qwen3_next": ("qwen3-next-tiny", {"max_position_embeddings": 64,
                                       "num_hidden_layers": 4}, 8),
    # a prompt past one window of 16: the whole-window prefill folds
    "evabyte": ("evabyte-tiny", {"max_position_embeddings": 64}, 4, 21),
}


def _scopes_in(maps, *module_parts):
    return {s for module, have in maps.items()
            if any(p in module for p in module_parts)
            for v in have.values()
            for s in ((v,) if isinstance(v, str) else v)}


def _train_engine():
    model = build("gpt2-tiny", dtype=jnp.bfloat16, n_layer=2, max_seq=32,
                  embd_pdrop=0.0, attn_pdrop=0.0, resid_pdrop=0.0)
    engine, _, _, _ = ds.initialize(config={
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1, "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 1}}, model=model, rng_seed=0,
        mesh=ds.parallel.mesh.make_mesh({"data": 1},
                                        devices=jax.devices()[:1]))
    return engine, model


@pytest.mark.parametrize("family", list(SERVED) + [scope_maps.TRAIN])
def test_every_scope_the_table_lists_is_in_the_executables(family, devices):
    """A refactor that drops a scope fails here, not in a metric: every
    scope the vocabulary lists for a family is in the map of its decode
    step or its prefill (the train step for ``train``), and a step's jaxpr
    printed without name stacks and source info is the same text with and
    without the scopes: a scope changes ``op_name`` metadata alone."""
    scope_maps.reset()
    # (a container has instructions of its own only where its loop is one:
    # at the tiny size XLA unrolls Phi4Flash's one cross pair)
    want = {s for s, (who, _) in scope_maps.VOCABULARY.items()
            if family in who and s not in scope_maps.TPU_ONLY
            and s != "cross.last"}
    if family == scope_maps.TRAIN:
        engine, model = _train_engine()
        batch = np.random.default_rng(0).integers(
            0, model.config.vocab_size, size=(2, 33)).astype(np.int32)
        engine.train_batch(iter([batch]))
        got = _scopes_in(device_scopes(), "train_step")
        engine.close()
        assert want <= got, sorted(want - got)
        return
    preset, overrides, block, *prompt = SERVED[family]
    eng = ds.init_inference(build(preset, dtype=jnp.float32, **overrides),
                            dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={"batch_slots": 2,
                                            "block_size": block})
    srv.run([Request(tokens=np.arange(1, 1 + (prompt or [11])[0],
                                      dtype=np.int32), max_new_tokens=3)])
    maps = device_scopes()
    assert any(m.startswith("jit_prefill_") for m in maps), sorted(maps)
    got = _scopes_in(maps, "jit_step", "jit_prefill_")
    assert want <= got, sorted(want - got)

    def text(name_stack):
        jax.clear_caches()
        return jax.make_jaxpr(srv._decode)(*srv._decode_args()).pretty_print(
            name_stack=name_stack, source_info=False)
    with_scopes, named = text(False), text(True)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope",
                   lambda name: contextlib.nullcontext())
        assert text(False) == with_scopes
        assert text(True) != named        # the patch did take the scopes
    srv.close()
