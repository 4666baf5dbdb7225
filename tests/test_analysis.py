"""Static analysis (`deepspeed_tpu/analysis/`): jaxpr auditor + lint.

Fixture strategy: every auditor check and every lint rule gets a SEEDED
violation (must fire) and a clean twin (must stay quiet).  The
acceptance tests then run the jaxpr auditor on the real
``DeepSpeedEngine._jit_train_step`` for ZeRO stages 1/2/3 and assert
zero host callbacks and honored donation, and run the CLI over the repo
asserting a clean exit — the tier-1 gate.
"""

import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.analysis import (
    CommsBudget, audit_engine, audit_fn, lint_file, select_rules)
from simple_model import SimpleModel

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules_fired(src, rules=None, path="fixture.py"):
    findings = lint_file(path, rules=select_rules(rules), src=src)
    return findings, sorted({f.rule for f in findings})


# ===========================================================================
# lint rules: seeded violation fires / clean twin quiet / suppression works
# ===========================================================================

def test_bare_except_rule():
    bad = "try:\n    x = 1\nexcept:\n    x = 2\n"
    _, fired = _rules_fired(bad)
    assert fired == ["DSTPU001"]
    clean = "try:\n    x = 1\nexcept ValueError:\n    x = 2\n"
    assert _rules_fired(clean)[1] == []


def test_swallowed_oserror_rule():
    bad = "try:\n    f()\nexcept (OSError, ValueError):\n    pass\n"
    _, fired = _rules_fired(bad)
    assert fired == ["DSTPU002"]
    # handled (logged) OSError is fine
    clean = "try:\n    f()\nexcept OSError as e:\n    log(e)\n"
    assert _rules_fired(clean)[1] == []
    # swallowing something non-IO is (this rule's) fine
    other = "try:\n    f()\nexcept KeyError:\n    pass\n"
    assert _rules_fired(other)[1] == []


def test_host_impure_in_jit_rule():
    bad = (
        "import time, jax\n"
        "import numpy as np\n"
        "def step(x):\n"
        "    t = time.time()\n"
        "    n = np.random.rand()\n"
        "    return x + t + n\n"
        "jstep = jax.jit(step)\n")
    findings, fired = _rules_fired(bad)
    assert fired == ["DSTPU101"]
    assert len(findings) == 2           # time.time AND np.random.rand
    # identical body NOT passed to jit: host code is allowed to be impure
    clean = bad.replace("jstep = jax.jit(step)\n", "")
    assert _rules_fired(clean)[1] == []
    # jax.random inside jit is the sanctioned RNG
    ok = ("import jax\n"
          "def step(x, key):\n"
          "    return x + jax.random.normal(key, x.shape)\n"
          "jstep = jax.jit(step)\n")
    assert _rules_fired(ok)[1] == []


def test_global_mutation_in_jit_rule():
    bad = ("import jax\n"
           "N = 0\n"
           "@jax.jit\n"
           "def step(x):\n"
           "    global N\n"
           "    N += 1\n"
           "    return x\n")
    _, fired = _rules_fired(bad)
    assert fired == ["DSTPU101"]


def test_raw_collective_rule_and_wrapper_exemption():
    bad = ("import jax\nfrom jax import lax\n"
           "def f(x):\n    return lax.psum(x, 'data')\n")
    _, fired = _rules_fired(bad)
    assert fired == ["DSTPU102"]
    # the wrapper module itself is exempt
    findings = lint_file("deepspeed_tpu/parallel/collectives.py",
                         rules=select_rules(["DSTPU102"]), src=bad)
    assert findings == []
    # calling the wrapper is the sanctioned spelling
    ok = ("from deepspeed_tpu.parallel import collectives as C\n"
          "def f(x):\n    return C.all_reduce_sum(x, 'data')\n")
    assert _rules_fired(ok)[1] == []


def test_traced_materialization_rule():
    bad = ("import jax\nimport numpy as np\n"
           "def step(x):\n"
           "    s = float(x.sum())\n"
           "    a = np.asarray(x)\n"
           "    return s + a.sum()\n"
           "jstep = jax.jit(step)\n")
    findings, fired = _rules_fired(bad)
    assert fired == ["DSTPU103"]
    assert len(findings) == 2
    ok = ("import jax\nimport jax.numpy as jnp\n"
          "def step(x):\n    return jnp.asarray(x).astype(jnp.float32)\n"
          "jstep = jax.jit(step)\n")
    assert _rules_fired(ok)[1] == []


def test_jit_detection_spellings():
    """Decorator, partial-decorator, shard_map and method-attr spellings
    all mark the function as traced."""
    for src in [
        "import jax\n@jax.jit\ndef f(x):\n    import time\n"
        "    return x + time.time()\n",
        "import jax\nfrom functools import partial\n"
        "@partial(jax.jit, donate_argnums=(0,))\ndef f(x):\n"
        "    import time\n    return x + time.time()\n",
        "import jax\ndef f(x):\n    import time\n    return x + time.time()\n"
        "g = jax.shard_map(f, mesh=None, in_specs=None, out_specs=None)\n",
        "import jax\nclass A:\n"
        "    def _step(self, x):\n        import time\n"
        "        return x + time.time()\n"
        "    def build(self):\n"
        "        self._jit = jax.jit(self._step)\n",
    ]:
        _, fired = _rules_fired(src, rules=["DSTPU101"])
        assert fired == ["DSTPU101"], src


def test_suppression_line_and_file_level():
    bad_line = "try:\n    f()\nexcept OSError:  # dstpu: disable=DSTPU002\n    pass\n"
    assert _rules_fired(bad_line)[1] == []
    bad_above = ("try:\n    f()\n"
                 "# dstpu: disable=DSTPU002\n"
                 "except OSError:\n    pass\n")
    assert _rules_fired(bad_above)[1] == []
    bad_file = ("# dstpu: disable-file=DSTPU002\n"
                "try:\n    f()\nexcept OSError:\n    pass\n"
                "try:\n    g()\nexcept OSError:\n    pass\n")
    assert _rules_fired(bad_file)[1] == []
    # suppressing one rule does not hide another — and a suppression
    # of a rule that never fires there is itself stale (DSTPU003)
    mixed = ("try:\n    f()\nexcept OSError:  # dstpu: disable=DSTPU001\n"
             "    pass\n")
    assert _rules_fired(mixed)[1] == ["DSTPU002", "DSTPU003"]


def test_rule_filter_and_unknown_rule():
    bad = "try:\n    f()\nexcept:\n    pass\n"
    _, fired = _rules_fired(bad, rules=["DSTPU002"])
    assert fired == []                  # bare-except rule not selected
    with pytest.raises(AssertionError, match="unknown rule"):
        select_rules(["DSTPU999"])


# ===========================================================================
# jaxpr auditor: each check fires on a seeded violation, quiet on clean code
# ===========================================================================

def test_audit_host_callback_fires():
    def bad(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    report = audit_fn(bad, jnp.ones((8,)))
    assert len(report.host_callbacks) == 1
    assert report.host_callbacks[0].severity == "error"
    # jax 0.9.0 traces jax.debug.print to the `debug_print` primitive
    assert "debug_print" in report.host_callbacks[0].message
    assert not report.ok()


def test_audit_pure_callback_fires():
    def bad(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    report = audit_fn(bad, jnp.ones((8,)))
    assert len(report.host_callbacks) == 1


def test_audit_clean_step_quiet():
    def clean(x, y):
        return (x.astype(jnp.bfloat16) @ y.astype(jnp.bfloat16)).sum()

    report = audit_fn(clean, jnp.ones((8, 8)), jnp.ones((8, 8)),
                      compute_dtype=jnp.bfloat16)
    assert report.host_callbacks == []
    assert report.promotions == []
    assert report.ok()


def test_audit_promotion_fires_on_f32_matmul_in_bf16_path():
    def promo(a, b):
        return a @ b                    # f32 operands

    report = audit_fn(promo, jnp.ones((8, 8)), jnp.ones((8, 8)),
                      compute_dtype=jnp.bfloat16)
    assert len(report.promotions) == 1
    f = report.promotions[0]
    assert f.severity == "warning" and "float32" in f.message
    # same matmul under an fp32 budget: not a promotion
    report = audit_fn(promo, jnp.ones((8, 8)), jnp.ones((8, 8)),
                      compute_dtype=jnp.float32)
    assert report.promotions == []


def test_audit_promotion_seen_through_scan():
    def stepper(x):
        def body(c, _):
            return c @ x, ()
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    report = audit_fn(stepper, jnp.ones((8, 8)), compute_dtype=jnp.bfloat16)
    assert len(report.promotions) >= 1
    assert "scan" in report.promotions[0].eqn_path


def test_audit_donation_honored():
    report = audit_fn(lambda x: x + 1, jnp.ones((16, 16)),
                      donate_argnums=(0,))
    d = report.donation
    assert d["checked"] and d["declared"] == 1 and d["honored"] == 1
    assert d["unhonored_args"] == [] and d["source"] == "executable"
    assert report.ok()


def test_audit_donation_not_honored_fires():
    # shape-changing output: the donated input can alias nothing
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # jax's own donation warning
        report = audit_fn(lambda x: x.sum(), jnp.ones((16, 16)),
                          donate_argnums=(0,))
    assert report.donation["unhonored_args"] == [0]
    assert [f.rule for f in report.findings] == ["DSTPU204"]
    assert not report.ok()


def test_audit_collective_census_and_budget(mesh8):
    from jax.sharding import NamedSharding, PartitionSpec as P

    def allred(x):
        return jax.lax.psum(x, "data")  # dstpu: disable=DSTPU102

    sm = jax.shard_map(allred, mesh=mesh8, in_specs=P("data"),
                       out_specs=P())
    x = jax.device_put(jnp.ones((8, 16)),
                       NamedSharding(mesh8, P("data")))
    # census sees the op at both levels with axis + payload bytes
    report = audit_fn(sm, x)
    jx = [c for c in report.census if c.level == "jaxpr"]
    assert len(jx) == 1 and jx[0].kind == "all_reduce"
    assert jx[0].axes == ("data",) and jx[0].bytes == 16 * 4
    assert any(c.level == "hlo" and c.kind == "all_reduce"
               for c in report.census)
    # within budget: quiet;  over budget: DSTPU203 fires
    ok = audit_fn(sm, x, comms_budget=CommsBudget(
        {"all_reduce": {"max_count": 1, "max_bytes": 1024}}))
    assert ok.ok()
    over = audit_fn(sm, x, comms_budget=CommsBudget(
        {"all_reduce": {"max_count": 0}}))
    assert [f.rule for f in over.findings] == ["DSTPU203"]
    over_bytes = audit_fn(sm, x, comms_budget=CommsBudget(
        {"all_reduce": {"max_bytes": 1}}))
    assert [f.rule for f in over_bytes.findings] == ["DSTPU203"]


def test_audit_recompile_hazard_weak_scalar():
    report = audit_fn(lambda x, s: x * s, jnp.ones((4,)), 3.0)
    assert len(report.recompile_hazards) == 1
    assert "weak-typed scalar" in report.recompile_hazards[0].message
    # strongly-typed scalar: quiet
    report = audit_fn(lambda x, s: x * s, jnp.ones((4,)),
                      jnp.float32(3.0))
    assert report.recompile_hazards == []


def test_audit_recompile_hazard_large_baked_constant():
    big = jnp.ones((512, 1024))         # 2 MB closure capture

    def f(x):
        return x @ big

    report = audit_fn(f, jnp.ones((8, 512)))
    consts = [f_ for f_ in report.recompile_hazards
              if "constant baked" in f_.message]
    assert len(consts) == 1 and consts[0].severity == "info"


# ===========================================================================
# acceptance: the real engine step, z1/z2/z3
# ===========================================================================

def _engine(mesh, stage):
    cfg = {"train_micro_batch_size_per_gpu": 2,
           "gradient_accumulation_steps": 2,
           "steps_per_print": 10 ** 9,
           "bf16": {"enabled": True},
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": stage}}
    rng = np.random.default_rng(0)
    data = [(rng.normal(size=(8,)).astype(np.float32),
             rng.normal(size=(8,)).astype(np.float32)) for _ in range(32)]
    engine, _, _, _ = ds.initialize(config=cfg, model=SimpleModel(),
                                    training_data=data, mesh=mesh)
    return engine


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_engine_train_step_audit(mesh_2x4, stage):
    """The compiled `_jit_train_step` must contain ZERO host callbacks and
    its `donate_argnums=(0,)` must be honored by the executable for every
    donated state leaf the lowering kept (z2/z3 shard master/grads over
    fsdp — exactly where unhonored donation doubles peak HBM and killed
    the r5 bench ladder with RESOURCE_EXHAUSTED)."""
    engine = _engine(mesh_2x4, stage)
    report = audit_engine(engine, comms_budget=CommsBudget(
        {"all_reduce": {"max_count": 32},
         "all_gather": {"max_count": 32},
         "reduce_scatter": {"max_count": 32}}))
    assert report.host_callbacks == [], [str(f) for f in report.findings]
    d = report.donation
    assert d["checked"] and d["source"] == "executable"
    assert d["lowered_donors"] > 0
    assert d["unhonored_args"] == [], d
    assert d["honored"] == d["lowered_donors"]
    assert not [f for f in report.findings if f.rule == "DSTPU204"]
    # the step really was audited (grad scan, optimizer, constraints)
    assert report.n_eqns > 50
    # ZeRO sharding means the partitioner MUST insert collectives — the
    # census proves the auditor sees them, and a comms budget written
    # from the ZeRO paper's volume math passes
    assert [c for c in report.census if c.level == "hlo"], \
        f"expected partitioner-inserted collectives at z{stage} on 2x4"
    assert not [f for f in report.findings if f.rule == "DSTPU203"]


# z2 (the acceptance configuration) stays in tier-1; z1/z3 ride the slow
# tier per the conftest budget policy (each is one more engine build +
# compile, and the sentinel graph is stage-independent)
@pytest.mark.parametrize("stage", [
    pytest.param(1, marks=pytest.mark.slow), 2,
    pytest.param(3, marks=pytest.mark.slow)])
def test_engine_train_step_audit_with_guardian(mesh_2x4, stage):
    """Health-guardian acceptance companion: with the divergence sentinels
    fully armed (non-finite flags over loss/grads/params, EMA z-score AND
    the in-graph spike skip — a strictly larger sentinel graph than the
    default), the compiled step must still contain ZERO host callbacks
    (DSTPU201) and honor every donated state leaf (DSTPU204): the guardian
    is pure jnp, never a host round-trip."""
    cfg = {"train_micro_batch_size_per_gpu": 2,
           "gradient_accumulation_steps": 2,
           "steps_per_print": 10 ** 9,
           "bf16": {"enabled": True},
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": stage},
           "health_check": {"spike_window": 16, "spike_zmax": 3.0,
                            "skip_on_spike": True}}
    rng = np.random.default_rng(0)
    data = [(rng.normal(size=(8,)).astype(np.float32),
             rng.normal(size=(8,)).astype(np.float32)) for _ in range(32)]
    engine, _, _, _ = ds.initialize(config=cfg, model=SimpleModel(),
                                    training_data=data, mesh=mesh_2x4)
    assert engine._health_enabled
    report = audit_engine(engine)
    assert report.host_callbacks == [], [str(f) for f in report.findings]
    d = report.donation
    assert d["checked"] and d["source"] == "executable"
    assert d["lowered_donors"] > 0
    assert d["unhonored_args"] == [], d
    assert d["honored"] == d["lowered_donors"]
    assert not [f for f in report.findings if f.rule == "DSTPU204"]


def test_engine_audit_seeded_callback_is_caught(mesh8):
    """End-to-end negative control: a model whose loss sneaks a
    debug_callback into the step is flagged by audit_engine."""
    class NoisyModel(SimpleModel):
        def loss(self, params, batch, rng):
            jax.debug.print("loss tick")
            return super().loss(params, batch, rng)

    cfg = {"train_micro_batch_size_per_gpu": 2,
           "gradient_accumulation_steps": 1,
           "steps_per_print": 10 ** 9,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": 0}}
    rng = np.random.default_rng(0)
    data = [(rng.normal(size=(8,)).astype(np.float32),
             rng.normal(size=(8,)).astype(np.float32)) for _ in range(16)]
    engine, _, _, _ = ds.initialize(config=cfg, model=NoisyModel(),
                                    training_data=data, mesh=mesh8)
    report = audit_engine(engine, compile=False)
    assert len(report.host_callbacks) >= 1
    assert not report.ok()


# ===========================================================================
# CLI: the tier-1 gate
# ===========================================================================

def test_cli_json_clean_on_repo():
    """`python -m deepspeed_tpu.analysis --strict --json` must exit 0 on
    the repo with machine-readable output — CI gates on this (strict:
    warnings, including stale DSTPU003 suppressions, also fail)."""
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.analysis", "--strict",
         "--json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is True
    assert payload["counts"]["error"] == 0
    assert payload["counts"]["warning"] == 0
    assert payload["rules"] == sorted(r.id for r in select_rules())


def test_cli_flags_and_exit_codes(tmp_path, capsys):
    """In-process `main()` (the subprocess surface is covered by the
    clean-repo test above; re-spawning the interpreter per flag would
    re-pay the package import in the tier-1 budget)."""
    from deepspeed_tpu.analysis.__main__ import main
    bad = tmp_path / "bad.py"
    bad.write_text("try:\n    f()\nexcept:\n    pass\n")
    assert main([str(bad), "--json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["counts"]["error"] == 1
    assert payload["findings"][0]["rule"] == "DSTPU001"
    assert payload["findings"][0]["line"] == 3
    # --rules filter excludes the violation → clean exit
    assert main([str(bad), "--rules", "DSTPU002"]) == 0
    # --list-rules names every registered rule
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in select_rules():
        assert rule.id in out


# ===========================================================================
# review regressions
# ===========================================================================

def test_suppression_in_string_or_docstring_does_not_suppress():
    """Only real COMMENT tokens suppress — a module QUOTING the syntax
    (docs, this engine's own docstring) must not disable rules."""
    src = ('"""Docs example:\n'
           '    # dstpu: disable-file=DSTPU001\n'
           '"""\n'
           "s = '# dstpu: disable-file=DSTPU001'\n"
           "try:\n    f()\nexcept:\n    pass\n")
    _, fired = _rules_fired(src)
    assert fired == ["DSTPU001"]


def test_hlo_census_counts_variadic_tuple_collectives():
    """XLA's combiner merges per-tensor reductions into ONE tuple-result
    op; the census must count it (it is the dominant traffic)."""
    from deepspeed_tpu.analysis.jaxpr_audit import census_from_hlo_text
    hlo = (
        "  %ar = (f32[8,16]{1,0}, f32[4]{0}) all-reduce(f32[8,16]{1,0} "
        "%a, f32[4]{0} %b), channel_id=1\n"
        "  %ag = bf16[2,64]{1,0} all-gather(bf16[1,64]{1,0} %c), "
        "dimensions={0}\n"
        "  %add = f32[4]{0} add(f32[4]{0} %x, f32[4]{0} %y)\n")
    entries = census_from_hlo_text(hlo)
    kinds = sorted((e.kind, e.bytes) for e in entries)
    assert kinds == [("all_gather", 2 * 64 * 2),
                     ("all_reduce", (8 * 16 + 4) * 4)]


def test_verify_checkpoint_malformed_manifest_record(tmp_path):
    """A manifest that json-parses but lacks record fields must mark THAT
    tag invalid — not abort the caller's newest-valid fallback scan."""
    import json as _json
    from deepspeed_tpu.checkpoint import atomic
    ckpt = tmp_path / "tag"
    ckpt.mkdir()
    (ckpt / "model.bin").write_bytes(b"x" * 8)
    (ckpt / atomic.MANIFEST_FILE).write_text(_json.dumps(
        {"files": {"model.bin": {"bytes": 8}}}))   # no 'size'/'sha256'
    ok, problems = atomic.verify_checkpoint(str(ckpt))
    assert not ok and problems and "model.bin" in problems[0]
    # 'files' not a map at all
    (ckpt / atomic.MANIFEST_FILE).write_text(_json.dumps({"files": [1]}))
    ok, problems = atomic.verify_checkpoint(str(ckpt))
    assert not ok and "not a map" in problems[0]


# ===========================================================================
# quantized-collectives census (DSTPU203 extension; docs/comms-compression.md)
# ===========================================================================

def test_census_classifies_quantized_and_grouped(mesh_2x4):
    """The HLO census must carry payload dtypes (int8 => quantized) and
    replica-group counts (>1 => a sub-axis / two-level phase), and
    wire_report must price logical vs wire bytes accordingly."""
    from jax.sharding import NamedSharding
    from deepspeed_tpu.analysis.comms import wire_report

    def body(x):
        q = jnp.clip(jnp.round(x * 10), -127, 127).astype(jnp.int8)
        qf = jax.lax.all_gather(q, "fsdp", axis=0,
                                tiled=True)  # dstpu: disable=DSTPU102
        return qf.astype(jnp.float32) / 10.0

    sm = jax.shard_map(body, mesh=mesh_2x4, in_specs=P("fsdp"),
                       out_specs=P(), check_vma=False)
    x = jax.device_put(jnp.ones((64, 16)),
                       NamedSharding(mesh_2x4, P("fsdp")))
    report = audit_fn(sm, x)
    hlo = [c for c in report.census if c.level == "hlo"]
    quant = [c for c in hlo if c.quantized]
    assert quant, [c.to_dict() for c in hlo]
    # fsdp sub-axis collective on a 2x4 mesh: data-many replica groups
    assert all(c.groups == 2 for c in quant), [c.groups for c in quant]
    assert quant[0].bytes == 64 * 16                    # 1 byte/element
    wr = wire_report(hlo)
    assert wr["quantized_wire_bytes"] >= 64 * 16
    assert wr["logical_bytes"] >= wr["wire_bytes"] + 3 * 64 * 16
    assert wr["grouped_collectives"] >= 1
    # jaxpr level classifies by dtype too
    jx = [c for c in report.census if c.level == "jaxpr"]
    assert any(c.quantized for c in jx)


def test_engine_compressed_step_audit(mesh_2x4):
    """CI gate (satellite): the quantized z3 step introduces no host
    callbacks (DSTPU201), honors donation for every kept leaf —
    including the new error-feedback state — and its wire-byte census
    fits the engine's declared CommsBudget (DSTPU203); an artificially
    tiny budget must fire."""
    from deepspeed_tpu.analysis.comms import CommsBudget as CB
    cfg = {"train_micro_batch_size_per_gpu": 16,
           "gradient_accumulation_steps": 1,
           "steps_per_print": 10 ** 9,
           "bf16": {"enabled": True},
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": 3,
                                 "stage3_param_persistence_threshold": 0},
           "comms_compression": {"enabled": True, "min_tensor_bytes": 256,
                                 "block_size": 256}}
    rng = np.random.default_rng(0)
    data = [(rng.normal(size=(64,)).astype(np.float32),
             rng.normal(size=(64,)).astype(np.float32)) for _ in range(256)]
    engine, _, _, _ = ds.initialize(
        config=cfg, model=SimpleModel(dim=64, hidden=256),
        training_data=data, mesh=mesh_2x4)
    assert engine._router.weights_active and engine._router.grads_active
    budget = engine.comms_budget()
    assert budget is not None
    report = audit_engine(engine, comms_budget=budget)
    assert report.host_callbacks == [], [str(f) for f in report.findings]
    d = report.donation
    assert d["checked"] and d["unhonored_args"] == [], d
    assert not [f for f in report.findings if f.rule == "DSTPU203"], \
        [str(f) for f in report.findings]
    hlo = [c for c in report.census if c.level == "hlo"]
    assert any(c.quantized for c in hlo), \
        "compressed step must move int8 collectives"
    tiny = audit_engine(engine, comms_budget=CB(
        per_kind={}, total_max_bytes=16))
    assert [f for f in tiny.findings if f.rule == "DSTPU203"]
    engine.close()


@pytest.mark.slow
def test_cli_audit_step_compressed_variant():
    """`--audit-step 3q` builds the quantized z3 engine and exits 0 with
    zero findings (host-callback-free, budget-clean) on this mesh."""
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.analysis", "--audit-step",
         "3q", os.path.join(REPO_ROOT, "deepspeed_tpu", "analysis",
                            "findings.py"), "--json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "DSTPU_COMPILE_CACHE": "0"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is True


def test_cli_audit_step_elastic_resume(devices):
    """`--audit-step elastic` saves an elastic ZeRO-2 engine on the full
    device set, auto-resumes it on half, and audits the RESHARDED first
    step: zero host callbacks, donation honored on the new mesh
    (docs/elasticity.md)."""
    from deepspeed_tpu.analysis.__main__ import _audit_elastic_resume
    findings = _audit_elastic_resume()
    assert findings == [], [str(f) for f in findings]


# ===========================================================================
# DSTPU3xx: typestate lint over the serving lifecycles (the static layer
# of the lifecycle verifier; runtime layers covered in test_lifecycle.py)
# ===========================================================================

def test_lifecycle_transition_rule_illegal_edge():
    """DSTPU301: a _set_state call whose (guarded-from, to) pair is not
    in the replica-health table — DEAD is terminal."""
    bad = ("class R:\n"
           "    def revive(self, st, now):\n"
           "        if st.state == DEAD:\n"
           "            self._set_state(st, HEALTHY, now, 'oops')\n")
    findings = lint_file("inference/router.py",
                         rules=select_rules(["DSTPU301"]), src=bad)
    assert [f.rule for f in findings] == ["DSTPU301"]
    assert "DEAD -> HEALTHY" in findings[0].message
    # the same edge out of SUSPECT is legal — table-driven, not a ban
    ok = bad.replace("DEAD:", "SUSPECT:")
    assert lint_file("inference/router.py",
                     rules=select_rules(["DSTPU301"]), src=ok) == []


def test_lifecycle_transition_rule_out_of_api_store():
    bad = ("class R:\n"
           "    def kill(self, st):\n"
           "        st.state = DEAD\n")
    findings = lint_file("inference/router.py",
                         rules=select_rules(["DSTPU301"]), src=bad)
    assert [f.rule for f in findings] == ["DSTPU301"]
    assert "_set_state" in findings[0].message
    # the owning API itself may store; __init__ may seed the initial
    ok = ("class R:\n"
          "    def __init__(self):\n"
          "        self.state = HEALTHY\n"
          "    def _set_state(self, st, to, now):\n"
          "        st.state = to\n")
    assert lint_file("inference/router.py",
                     rules=select_rules(["DSTPU301"]), src=ok) == []
    # ...but __init__ seeding a non-initial state is a violation
    seeded = ok.replace("self.state = HEALTHY", "self.state = DEAD")
    findings = lint_file("inference/router.py",
                         rules=select_rules(["DSTPU301"]), src=seeded)
    assert [f.rule for f in findings] == ["DSTPU301"]
    assert "must start" in findings[0].message


def test_out_of_api_mutation_rule():
    """DSTPU302: allocator internals poked from outside the owner."""
    bad = ("def steal(engine):\n"
           "    engine.allocator._free.append(0)\n"
           "    engine.allocator._in_use.discard(3)\n")
    findings = lint_file("inference/serving.py",
                         rules=select_rules(["DSTPU302"]), src=bad)
    assert [f.rule for f in findings] == ["DSTPU302", "DSTPU302"]
    # the owning class mutates freely
    ok = ("class BlockAllocator:\n"
          "    def free(self, blocks):\n"
          "        self._free.append(blocks[0])\n")
    assert lint_file("inference/paged_kv.py",
                     rules=select_rules(["DSTPU302"]), src=ok) == []
    # out of scope (not an inference/ file): rule does not apply
    assert lint_file("training/opt.py",
                     rules=select_rules(["DSTPU302"]), src=bad) == []


def test_unpaired_alloc_rule_exit_paths():
    """DSTPU303: every return/raise exit (exception edges included)
    must free the allocation or let it escape to an owner."""
    bad = ("def admit(a):\n"
           "    blocks = a.alloc(3)\n"
           "    if blocks is None:\n"
           "        return None\n"
           "    return 1\n")                    # leaks on this return
    findings = lint_file("inference/serving.py",
                         rules=select_rules(["DSTPU303"]), src=bad)
    assert [f.rule for f in findings] == ["DSTPU303"]
    assert findings[0].line == 5

    bad_edge = ("def admit(a):\n"
                "    blocks = a.alloc(2)\n"
                "    try:\n"
                "        risky()\n"
                "    except RuntimeError:\n"
                "        raise\n"               # exception edge leaks
                "    a.free(blocks)\n")
    findings = lint_file("inference/serving.py",
                         rules=select_rules(["DSTPU303"]), src=bad_edge)
    assert [f.rule for f in findings] == ["DSTPU303"]
    assert findings[0].line == 6

    # clean twin: None-guard exempt, handler frees before re-raising
    # behind a did-the-slot-take-them test, success path escapes
    ok = ("def admit(a):\n"
          "    blocks = a.alloc(2)\n"
          "    if blocks is None:\n"
          "        return None\n"
          "    try:\n"
          "        seat(blocks)\n"
          "    except RuntimeError:\n"
          "        if held() is not blocks:\n"
          "            a.free(blocks)\n"
          "        raise\n"
          "    return blocks\n")
    assert lint_file("inference/serving.py",
                     rules=select_rules(["DSTPU303"]), src=ok) == []


def test_set_once_result_rule():
    """DSTPU304: terminal fields / record create / pop outside the
    declared owners."""
    bad = ("class R:\n"
           "    def hack(self, uid):\n"
           "        self.results[uid] = {}\n"
           "        self.results[uid]['outcome'] = 'OK'\n"
           "        self.results.pop(uid)\n")
    findings = lint_file("inference/router.py",
                         rules=select_rules(["DSTPU304"]), src=bad)
    assert [f.rule for f in findings] == ["DSTPU304"] * 3
    # the declared owners are allowed
    ok = ("class R:\n"
          "    def submit(self, uid):\n"
          "        self.results[uid] = {}\n"
          "    def _finalize(self, rec):\n"
          "        rec['outcome'] = 'OK'\n"
          "    def pop_result(self, uid):\n"
          "        return self.results.pop(uid)\n")
    assert lint_file("inference/router.py",
                     rules=select_rules(["DSTPU304"]), src=ok) == []
    # serving has different owners for the same discipline
    findings = lint_file("inference/serving.py",
                         rules=select_rules(["DSTPU304"]), src=ok)
    assert {f.rule for f in findings} == {"DSTPU304"}


def test_lifecycle_family_selector():
    ids = sorted(r.id for r in select_rules(["DSTPU3xx"]))
    assert ids == ["DSTPU301", "DSTPU302", "DSTPU303", "DSTPU304"]


def test_lifecycle_specs_well_formed():
    """The declarative tables the three layers share: every transition
    target is a declared state, initial is declared, and the runtime
    sanitizer mirrors the kv-block states verbatim."""
    from deepspeed_tpu.analysis.lint import lifecycle as lc
    from deepspeed_tpu.analysis import sanitize as sz
    for fsm in lc.FSMS:
        states = set(fsm["states"])
        assert fsm["initial"] in states
        assert set(fsm["transitions"]) == states
        for frm, tos in fsm["transitions"].items():
            assert set(tos) <= states, (fsm["name"], frm)
    assert (sz.FREE, sz.ALLOCATED, sz.QUARANTINED, sz.SHARED, sz.COW) \
        == lc.KV_BLOCK_FSM["states"]
    assert lc.REPLICA_FSM["transitions"]["DEAD"] == ()   # terminal
    # sharing edges (PR 19): quarantine only from sole-owner allocated
    assert "quarantined" not in lc.KV_BLOCK_FSM["transitions"]["shared"]
    assert lc.KV_BLOCK_FSM["transitions"]["cow"] == ("allocated",)


def test_stale_suppression_warns():
    """DSTPU003: a disable comment whose rule does not fire there is
    itself a (warning) finding; a consumed one is not."""
    stale = "x = 1  # dstpu: disable=DSTPU001\n"
    findings, fired = _rules_fired(stale)
    assert fired == ["DSTPU003"]
    assert findings[0].severity == "warning"
    assert "DSTPU001" in findings[0].message
    consumed = "try:\n    f()\nexcept:  # dstpu: disable=DSTPU001\n    pass\n"
    assert _rules_fired(consumed)[1] == []
    # a rule that did not RUN cannot be judged stale
    _, fired = _rules_fired(stale, rules=["DSTPU002", "DSTPU003"])
    assert fired == []
    # stale file-level suppressions are judged too
    stale_file = "# dstpu: disable-file=DSTPU001\nx = 1\n"
    assert _rules_fired(stale_file)[1] == ["DSTPU003"]


def test_cli_audit_step_serving_lifecycle(devices):
    """`--audit-step serving-lifecycle`: all six sanitizer classes
    demonstrably caught, armed-vs-off jaxpr + token equality on a real
    serving twin, and the full 720-ordering interleave sweep — clean."""
    from deepspeed_tpu.analysis.__main__ import _audit_serving_lifecycle
    findings = _audit_serving_lifecycle()
    assert findings == [], [str(f) for f in findings]
