"""Memory explainability (docs/monitoring.md#memory-explainability):
the memory ledger (``monitor/memory_ledger.py``), the predictive
capacity model (``analysis/capacity.py`` / ``bin/ds_mem``), OOM
forensics, and the memory-family ``ds_bench_diff`` gate.

Flagship acceptance (ISSUE 13): replaying the committed MAXPARAMS.json
through the REAL ``ds_mem`` CLI reproduces the 1.3B rung's recorded
host-RSS HWM within ±10% and brackets the measured ceiling (2.65B fits
the 125 GB host, the 6.7B OOM rung does not, the model's own ceiling
lands in between); a forced RESOURCE_EXHAUSTED run produces a forensic
dump naming the over-budget subsystem; and the compiled train + decode
steps are byte-identical ledger-on vs off.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.analysis import bench_diff as bd
from deepspeed_tpu.analysis import capacity as cap
from deepspeed_tpu.inference import paged_kv as pk
from deepspeed_tpu.inference import Request, ServingConfig, ServingEngine
from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
from deepspeed_tpu.monitor import Monitor, parse_line
from deepspeed_tpu.monitor import gauges as mg
from deepspeed_tpu.monitor import memory_ledger as mled
from deepspeed_tpu.monitor.sinks import EVENTS_FILE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _MLP:
    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        return {"w1": jax.random.normal(k1, (16, 32), jnp.float32),
                "w2": jax.random.normal(k2, (32, 16), jnp.float32)}

    def loss(self, params, batch, rng):
        x, y = batch
        h = jnp.maximum(x.astype(jnp.bfloat16) @ params["w1"], 0)
        p = (h @ params["w2"]).astype(jnp.float32)
        return jnp.mean(jnp.square(p - y))


def _dataset(n=8):
    return [(np.ones((16,), np.float32), np.ones((16,), np.float32))
            for _ in range(n)]


def _engine(tmp_path, *, stage=2, monitor_cfg=None, mesh=None, extra=None):
    cfg = {"train_micro_batch_size_per_gpu": 4,
           "gradient_accumulation_steps": 1,
           "steps_per_print": 10 ** 9,
           "bf16": {"enabled": True},
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": stage},
           "checkpoint": {"dir": str(tmp_path / "ckpt")}}
    if monitor_cfg:
        cfg["monitor"] = monitor_cfg
    if extra:
        cfg.update(extra)
    kw = {"mesh": mesh} if mesh is not None else {}
    return ds.initialize(config=cfg, model=_MLP(),
                         training_data=_dataset(), **kw)[0]


# ---------------------------------------------------------------------------
# the memory ledger
# ---------------------------------------------------------------------------

def test_ledger_attributes_train_state_and_matches_plan(tmp_path,
                                                        mesh_fsdp8):
    """The ledger's TrainState attribution is exact (leaf bytes), and
    the closed-form capacity plan reproduces it subsystem-for-subsystem
    on a sharded z2 mesh — model and measurement share a vocabulary."""
    eng = _engine(tmp_path, stage=2, mesh=mesh_fsdp8)
    try:
        eng.train_batch()
        snap = eng.memory_ledger()
        hbm = snap["hbm"]
        assert hbm["params"] == mled.tree_device_bytes(eng.state.params)
        assert hbm["master_fp32"] == mled.tree_device_bytes(
            eng.state.master)
        assert hbm["opt_moments"] == mled.tree_device_bytes(
            eng.state.opt_state)
        num_params = 16 * 32 + 32 * 16
        plan = cap.train_device_plan(
            num_params, zero_stage=2, n_devices=jax.device_count(),
            fsdp=jax.device_count())
        assert plan["params"] == hbm["params"]
        assert plan["master_fp32"] == hbm["master_fp32"]
        assert plan["opt_moments"] == hbm["opt_moments"]
        # residual is the honest term: RSS minus what the ledger names
        assert snap["host_rss_bytes"] > 0
        assert snap["host_residual_bytes"] == (
            snap["host_rss_bytes"] - snap["host_attributed_bytes"])
        phases = [p["phase"] for p in snap["phases"]]
        assert phases[0] == "init" and "first_compile" in phases
    finally:
        eng.close()


def test_capacity_plan_replication_by_stage():
    """ZeRO layout arithmetic (arXiv 1910.02054): stage 1 shards the
    optimizer states, stage 3 also shards the params; below each
    threshold the subsystem replicates over the mesh."""
    P = 1000
    z0 = cap.train_device_plan(P, zero_stage=0, n_devices=8, fsdp=8)
    z1 = cap.train_device_plan(P, zero_stage=1, n_devices=8, fsdp=8)
    z3 = cap.train_device_plan(P, zero_stage=3, n_devices=8, fsdp=8)
    assert z0["opt_moments"] == 8 * z1["opt_moments"]
    assert z0["params"] == z1["params"] == 8 * z3["params"]
    assert z1["master_fp32"] == z3["master_fp32"]


def test_ledger_attributes_offload_host_tier(tmp_path):
    """The offload tier's host buffers are attributed exactly: fp32
    master + fp32 grad landing + 16-bit image + cpu-tier moments — the
    MAXPARAMS ram-arithmetic table, measured live."""
    eng = _engine(tmp_path, stage=2, extra={
        "zero_optimization": {"stage": 2, "offload_optimizer":
                              {"device": "cpu"}}})
    try:
        eng.train_batch()
        snap = eng.memory_ledger()
        host = snap["host"]
        off = eng._offload
        assert host["host_master_fp32"] == off.master.nbytes
        assert host["host_grad_landing_fp32"] == off._flat32.nbytes
        assert host["host_adam_moments"] == off.m.nbytes + off.v.nbytes
        numel = off.numel
        plan = cap.host_offload_plan(numel / 1e9, moments_tier="cpu")
        assert plan["host_master_fp32"] == pytest.approx(
            host["host_master_fp32"])
        assert plan["host_adam_moments"] == pytest.approx(
            host["host_adam_moments"])
    finally:
        eng.close()


def test_mem_events_stream_and_older_reader_skips(tmp_path):
    """Armed engine emits schema-v3 `mem` events that parse under the
    current reader; a v2-ceiling reader (the pre-ledger build) rejects
    exactly those lines — the per-kind forward-compat contract."""
    mon_dir = tmp_path / "mon"
    eng = _engine(tmp_path, monitor_cfg={
        "enabled": True, "dir": str(mon_dir), "sinks": ["jsonl"],
        "interval": 1, "memory_interval": 1})
    try:
        eng.train_batch()
        eng.train_batch()
        eng.monitor.flush()
        lines = [ln for ln in
                 open(mon_dir / EVENTS_FILE, encoding="utf-8")
                 if ln.strip()]
        events = [parse_line(ln) for ln in lines]
        mems = [e for e in events if e.kind == "mem"]
        assert mems, "no mem events in the armed stream"
        assert all(e.v == 3 for e in mems)
        f = mems[-1].fields
        assert {"params", "master_fp32", "opt_moments"} <= set(f["hbm"])
        assert "host_residual_bytes" in f
        # the v2 reader sees v:3 and raises; v1/v2 kinds still parse
        mem_lines = [ln for ln, e in zip(lines, events)
                     if e.kind == "mem"]
        with pytest.raises(ValueError):
            parse_line(mem_lines[0], max_version=2)
        for ln, e in zip(lines, events):
            if e.kind != "mem":
                parse_line(ln, max_version=2)
    finally:
        eng.close()


def test_mem_cadence_independent_of_monitor_interval(tmp_path):
    """memory_interval alone sets the ledger cadence: an
    interval-thinned monitor (interval=3) must not push mem events to
    the lcm — with memory_interval=2 over 6 steps, steps 2/4/6 all
    emit."""
    mon_dir = tmp_path / "mon_thin"
    eng = _engine(tmp_path, monitor_cfg={
        "enabled": True, "dir": str(mon_dir), "sinks": ["jsonl"],
        "interval": 3, "memory_interval": 2})
    try:
        for _ in range(6):
            eng.train_batch()
        eng.monitor.flush()
        mems = [parse_line(ln) for ln in
                open(mon_dir / EVENTS_FILE, encoding="utf-8")
                if ln.strip()]
        assert [e.step for e in mems if e.kind == "mem"] == [2, 4, 6]
    finally:
        eng.close()


def test_ledger_jaxpr_equality(tmp_path):
    """Compiled train step byte-identical ledger-on vs off (the
    --audit-step mem gate, pinned in tier-1)."""
    from deepspeed_tpu.analysis.jaxpr_audit import train_step_jaxpr_text
    off = _engine(tmp_path)
    armed = _engine(tmp_path, monitor_cfg={
        "enabled": True, "dir": str(tmp_path / "mon2"),
        "sinks": ["jsonl"], "interval": 1, "memory_interval": 1})
    try:
        assert train_step_jaxpr_text(off) == train_step_jaxpr_text(armed)
    finally:
        off.close()
        armed.close()


def test_ds_top_renders_mem_line(tmp_path):
    from deepspeed_tpu.monitor.__main__ import Aggregate, render
    snap = mled.MemoryLedger().snapshot()
    snap["hbm"] = {"params": 1 << 20, "paged_kv_pool": 2 << 20}
    from deepspeed_tpu.monitor.events import Event
    agg = Aggregate()
    agg.feed([Event(kind="mem", name="memory", t=0.0, step=3,
                    fields=snap)])
    out = render(agg, "x")
    assert "mem:" in out and "paged_kv_pool" in out


# ---------------------------------------------------------------------------
# capacity model vs the real preflight / serving engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stage", [1, 2, 3])
def test_capacity_plan_vs_preflight_memory(tmp_path, mesh_fsdp8, stage):
    """The closed-form resident-state bytes agree with BOTH the measured
    ledger (process-total, exact) and the executable's own
    memory_analysis() (per-device): the step's output bytes are the new
    state — they must equal the plan's per-device resident bytes plus a
    small metrics tail, and the projected peak must cover them."""
    extra = None
    if stage == 3:
        extra = {"zero_optimization": {
            "stage": 3, "stage3_param_persistence_threshold": 0}}
    eng = _engine(tmp_path, stage=stage, mesh=mesh_fsdp8, extra=extra)
    try:
        batch = eng._stack_microbatches(
            [next(eng._data_iterator)])
        pre = eng.preflight_memory(batch)
        snap = eng.memory_ledger()
        n = jax.device_count()
        plan = cap.train_device_plan(
            16 * 32 + 32 * 16, zero_stage=stage, n_devices=n, fsdp=n)
        measured_state = (snap["hbm"]["params"]
                          + snap["hbm"].get("master_fp32", 0)
                          + snap["hbm"].get("opt_moments", 0))
        assert plan["resident_bytes"] == measured_state
        if pre is not None:
            plan_per_device = plan["resident_bytes"] // n
            assert plan_per_device <= pre["output_bytes"] \
                <= plan_per_device + 4096
            assert pre["peak_bytes"] >= pre["output_bytes"]
    finally:
        eng.close()


def _tiny_serving(monitor=None, **over):
    cfg = GPT2Config(vocab_size=64, max_seq=32, n_embd=32, n_layer=2,
                     n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    model = GPT2(cfg, dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0))
    scfg = dict(batch_slots=2, block_size=8, max_new_tokens=4,
                preflight=False)
    scfg.update(over)
    return ServingEngine(model=model, params=params, monitor=monitor,
                         config=ServingConfig(**scfg))


def test_serving_plan_matches_pool_and_max_streams():
    """serving_plan mirrors paged_kv.init_pool byte-for-byte (16-bit and
    int8 pools) and max_streams reproduces the engine's own admission
    math from a budget alone."""
    srv = _tiny_serving()
    try:
        mc = srv.model.config
        plan = cap.serving_plan(
            n_layer=mc.n_layer, n_head=mc.n_head, head_dim=mc.head_dim,
            max_seq=mc.max_seq, block_size=srv.config.block_size,
            batch_slots=srv.config.batch_slots, kv_bits=16,
            max_new_tokens=srv.config.max_new_tokens)
        assert plan["num_blocks"] == srv.num_blocks
        assert plan["paged_kv_pool"] == pk.pool_bytes(srv.pool)
        assert plan["blocks_per_request"] == \
            srv.capacity()["blocks_per_request_at_defaults"]
        # a budget exactly covering the pool admits at least the
        # configured slots; a tiny budget admits none
        ms = cap.max_streams(plan, plan["paged_kv_pool"] * 2, safety=1.0)
        assert ms["max_streams"] >= srv.config.batch_slots
        assert cap.max_streams(plan, 1000)["max_streams"] == 0
    finally:
        srv.close()
    # int8 pool: plan equals the real quantized pool too
    plan8 = cap.serving_plan(n_layer=2, n_head=4, head_dim=8, max_seq=32,
                             block_size=8, batch_slots=2, kv_bits=8,
                             quant_block=64)
    pool8 = pk.init_pool(2, plan8["num_blocks"], 8, 4, 8, jnp.bfloat16,
                         kv_bits=8, quant_block=64)
    assert plan8["paged_kv_pool"] == pk.pool_bytes(pool8)


def test_serving_max_streams_vs_preflight_memory():
    """The offline --max-streams answer is consistent with the live
    engine's preflight (per-device accounting): a budget that covers the
    preflighted peak plus the per-device weights and pool admits at
    least the configured slots, and a budget below it admits fewer."""
    srv = _tiny_serving()
    try:
        pre = srv.preflight_memory()
        if pre is None:
            pytest.skip("backend exposes no memory_analysis")
        mc = srv.model.config
        n = jax.device_count()
        weights_pd = mled.tree_device_bytes(srv.engine.params) // n
        plan = cap.serving_plan(
            n_layer=mc.n_layer, n_head=mc.n_head, head_dim=mc.head_dim,
            max_seq=mc.max_seq, block_size=srv.config.block_size,
            batch_slots=srv.config.batch_slots,
            max_new_tokens=srv.config.max_new_tokens,
            weight_bytes=weights_pd)
        budget = int((weights_pd + plan["paged_kv_pool"]
                      + pre["temp_bytes"]) / 0.92) + (1 << 16)
        ms = cap.max_streams(plan, budget,
                             workspace_bytes=pre["temp_bytes"])
        assert ms["max_streams"] >= srv.config.batch_slots
        # the model is monotone and refuses an impossible budget
        tiny = cap.max_streams(plan, weights_pd + 1000)
        assert tiny["max_streams"] == 0
    finally:
        srv.close()


def test_serving_mem_events_and_ledger(tmp_path):
    mon = Monitor(run_dir=str(tmp_path), role="serving")
    srv = _tiny_serving(monitor=mon)
    try:
        srv.run([Request(tokens=np.arange(4), max_new_tokens=18, uid=u)
                 for u in range(2)])
        snap = srv.memory_ledger()
        assert snap["hbm"]["paged_kv_pool"] == pk.pool_bytes(srv.pool)
        assert snap["hbm"]["params"] > 0
        # detail kwargs survive into the snapshot (the in-use block
        # split an operator reads from a pool-exhaustion dump)
        pool_det = snap["detail"]["hbm"]["paged_kv_pool"]
        assert {"blocks", "used_blocks", "free_blocks"} <= set(pool_det)
        assert pool_det["blocks"] == srv.num_blocks
    finally:
        srv.close()
    mems = [parse_line(ln) for ln in
            open(tmp_path / EVENTS_FILE, encoding="utf-8") if ln.strip()]
    mem = next(e for e in mems if e.kind == "mem")
    assert "paged_kv_pool" in mem.fields["hbm"]
    assert "used_blocks" in mem.fields["detail"]["hbm"]["paged_kv_pool"]


def test_serving_honors_monitor_memory_interval_zero(tmp_path):
    """monitor.memory_interval: 0 is the documented off switch — a
    config-built monitor carrying it must silence the serving ledger
    too, while the rest of the serving stream keeps flowing."""
    mon = Monitor(run_dir=str(tmp_path), role="serving",
                  memory_interval=0)
    srv = _tiny_serving(monitor=mon)
    try:
        srv.run([Request(tokens=np.arange(4), max_new_tokens=18, uid=u)
                 for u in range(2)])
    finally:
        srv.close()
    events = [parse_line(ln) for ln in
              open(tmp_path / EVENTS_FILE, encoding="utf-8")
              if ln.strip()]
    assert not any(e.kind == "mem" for e in events)
    assert any(e.kind == "step" for e in events)


def test_serving_static_terms_latched():
    """The hot-loop ledger pass must not re-walk the immutable weights
    or re-scan the compile cache per emission: the latch recomputes
    only when the live program population changes."""
    srv = _tiny_serving()
    try:
        srv.run([Request(tokens=np.arange(4), max_new_tokens=4, uid=0)])
        mled.attribute_serving(srv)
        key, val = srv._mled_static
        # a second pass under the same program population reuses the
        # exact cached tuple (no recompute)
        calls = {"n": 0}
        orig = mled.tree_device_bytes

        def counting(tree):
            calls["n"] += 1
            return orig(tree)
        mled.tree_device_bytes = counting
        try:
            mled.attribute_serving(srv)
            assert calls["n"] == 0          # weights walk skipped
        finally:
            mled.tree_device_bytes = orig
        assert srv._mled_static == (key, val)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# MAXPARAMS replay: the acceptance criterion, via the real CLI
# ---------------------------------------------------------------------------

def test_ds_mem_replay_reproduces_maxparams():
    """``ds_mem --replay`` over the recorded max-params rungs
    (``tests/data/maxparams_rungs.json``; the real CLI, a subprocess):
    the 1.3B rung's recorded 33.81 GB host-RSS HWM reproduces within
    ±10%, every recorded rung is within tolerance, and the model
    BRACKETS the measured ceiling — 2.65B fits the 125 GB host, the
    6.7B OOM rung does not, and the predicted ceiling lands strictly
    between them."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_mem"),
         "--replay", os.path.join(REPO, "tests", "data",
                                  "maxparams_rungs.json"), "--json"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout)
    rungs = {row["rung"]: row for row in rep["rungs"]}
    r13 = rungs["1.3b"]
    assert r13["measured_rss_gb"] == pytest.approx(33.81)
    assert abs(r13["predicted_rss_gb"] - 33.81) / 33.81 <= 0.10
    assert rep["all_within_tolerance"]
    assert rungs["2.7b"]["fits_host"] is True
    assert rungs["6.7b"]["fits_host"] is False
    assert 2.65 < rep["max_params_b"] < 6.7
    # grad_accum_dtype=bf16 (ROADMAP #4's knob) buys headroom
    assert rep["max_params_b_bf16_grad_accum"] > rep["max_params_b"]


def test_fit_host_residual_math():
    # exact line: residual = 2 + 3x must fit with ~zero error
    fit = cap.fit_host_residual([(1.0, 10.0, 5.0), (2.0, 14.0, 6.0),
                                 (4.0, 24.0, 10.0)])
    assert fit["c0_gb"] == pytest.approx(2.0, abs=1e-9)
    assert fit["c1_gb_per_b"] == pytest.approx(3.0, abs=1e-9)
    # degenerate inputs stay well-defined
    assert cap.fit_host_residual([])["c1_gb_per_b"] == 0.0
    one = cap.fit_host_residual([(2.0, 9.0, 4.0)])
    assert one["c0_gb"] == pytest.approx(5.0)


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

def test_forced_resource_exhausted_dumps_forensics(tmp_path):
    """A RESOURCE_EXHAUSTED step produces a forensic dump naming the
    over-budget subsystem and the knob that buys headroom; the original
    error still propagates."""
    eng = _engine(tmp_path)
    try:
        eng.train_batch()

        def boom(*a, **k):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory while trying to "
                "allocate 9876 bytes")
        eng._jit_train_step = boom
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            eng.train_batch()
        dumps = [f for f in os.listdir(tmp_path / "ckpt")
                 if f.startswith("memory_forensics")]
        assert len(dumps) == 1
        doc = json.loads((tmp_path / "ckpt" / dumps[0]).read_text())
        v = doc["verdict"]
        assert v["space"] == "hbm"
        assert v["over_budget_subsystem"] in doc["ledger"]["hbm"]
        assert v["advice"]
        # latched: a second failure does not dump again
        with pytest.raises(RuntimeError):
            eng.train_batch()
        assert len([f for f in os.listdir(tmp_path / "ckpt")
                    if f.startswith("memory_forensics")]) == 1
    finally:
        eng._jit_train_step = None      # close() handles the None
        eng.close()


def test_serving_preflight_failure_dumps_forensics(tmp_path):
    """An impossible HBM budget refuses to serve AND leaves the ledger
    post-mortem on disk (preflight is an admission failure, not just an
    exception message)."""
    srv = _tiny_serving(preflight=True, hbm_budget_bytes=1000,
                        forensic_dir=str(tmp_path))
    try:
        srv.submit(Request(tokens=np.arange(4)))
        with pytest.raises(MemoryError, match="preflight"):
            srv.step()
        dumps = [f for f in os.listdir(tmp_path)
                 if "memory_forensics" in f]
        assert len(dumps) == 1
        doc = json.loads((tmp_path / dumps[0]).read_text())
        assert doc["verdict"]["space"] == "hbm"
        assert "paged_kv_pool" in doc["ledger"]["hbm"]
    finally:
        srv.config.preflight = False     # allow close()'s drain to run
        srv._preflight_done = True
        srv.close()


def test_verdict_space_selection():
    snap = {"hbm": {"params": 100, "paged_kv_pool": 500},
            "host": {"host_master_fp32": 50},
            "host_residual_bytes": 10 ** 9}
    v = cap.verdict_from_snapshot(snap, space="hbm")
    assert v["over_budget_subsystem"] == "paged_kv_pool"
    assert "kv_bits=8" in v["advice"]
    # unset space picks the heavier side (the residual-dominated host)
    v2 = cap.verdict_from_snapshot(snap)
    assert v2["space"] == "host"
    assert v2["over_budget_subsystem"] == "residual"


# ---------------------------------------------------------------------------
# satellites: shared memory_stats helpers, see_memory_usage gauge routing
# ---------------------------------------------------------------------------

def test_shared_memory_stats_helpers():
    assert isinstance(mg.memory_stats(), dict)
    # this container's CPU backend exposes no bytes_limit: the helper
    # returns the documented default instead of crashing/None
    assert mg.hbm_limit_bytes(default=123) == 123
    assert mg.host_rss_bytes() > 0
    # Linux ru_maxrss is KB -> the helper converts to bytes (the HWM can
    # never sit below the current RSS)
    assert mg.host_rss_hwm_bytes() >= mg.host_rss_bytes() // 2
    # the autotuner's previously fallback-less read site now degrades to
    # its documented default on the CPU backend
    from deepspeed_tpu.autotuning.autotuner import (DEFAULT_HBM_BYTES,
                                                    get_hbm_bytes)
    assert get_hbm_bytes() == DEFAULT_HBM_BYTES


def test_see_memory_usage_routes_through_bus():
    from deepspeed_tpu.monitor.bus import MonitorBus
    from deepspeed_tpu.monitor.sinks import RingBufferSink
    from deepspeed_tpu.runtime.utils import see_memory_usage
    sink = RingBufferSink(16)
    bus = MonitorBus([sink])
    see_memory_usage("test point", force=True, bus=bus)
    names = [e.name for e in sink.ring]
    assert "host_rss_hwm" in names
    ev = next(e for e in sink.ring if e.name == "host_rss_hwm")
    assert ev.kind == "gauge" and ev.value > 0
    assert ev.fields["context"] == "test point"
    # force=False stays silent
    sink2 = RingBufferSink(16)
    see_memory_usage("quiet", force=False, bus=MonitorBus([sink2]))
    assert len(list(sink2.ring)) == 0


# ---------------------------------------------------------------------------
# CI/tooling: ds_bench_diff memory family + the two-CLI tier-1 smoke
# ---------------------------------------------------------------------------

def test_bench_diff_gates_memory_family():
    """rss_hwm_gb / pool_bytes / peak_bytes are capacity costs: growth
    beyond band regresses, shrinkage improves."""
    base = {"rss_hwm_gb": 33.8, "serving": {"pool_bytes": 1000},
            "peak_bytes": 5000}
    worse = {"rss_hwm_gb": 50.0, "serving": {"pool_bytes": 2000},
             "peak_bytes": 9000}
    r = bd.compare(base, worse)
    assert len(r["regressions"]) == 3
    assert all(row["direction"] == "lower" for row in r["rows"])
    better = {"rss_hwm_gb": 20.0, "serving": {"pool_bytes": 400},
              "peak_bytes": 2000}
    r2 = bd.compare(base, better)
    assert not r2["regressions"]
    assert {row["verdict"] for row in r2["rows"]} == {"improved"}


def test_cli_smoke_bench_diff_and_ds_mem(tmp_path):
    """Tier-1 smoke over the REAL CLIs: ds_bench_diff gates a
    committed fixture against itself (clean exit), and
    ds_mem renders a synthetic mem-event stream — both executables are
    exercised on every run."""
    fixture = os.path.join(REPO, "tests", "data",
                           "bench_diff_fixture_serving.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_bench_diff"),
         fixture, fixture],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "no regression" in r.stdout

    from deepspeed_tpu.monitor.events import Event
    run = tmp_path / "run"
    run.mkdir()
    snap = {"role": "train", "hbm": {"params": 4 << 20},
            "host": {"host_master_fp32": 8 << 20},
            "hbm_attributed_bytes": 4 << 20,
            "host_attributed_bytes": 8 << 20,
            "host_rss_bytes": 32 << 20, "host_residual_bytes": 24 << 20,
            "rss_hwm_bytes": 40 << 20, "rss_hwm_gb": 0.04,
            "phases": [{"phase": "init", "rss_hwm_bytes": 30 << 20,
                        "delta_bytes": 30 << 20, "t": 0.0}]}
    (run / EVENTS_FILE).write_text(
        Event(kind="mem", name="memory", t=0.0, step=7,
              fields=snap).to_json() + "\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_mem"), str(run)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "host_master_fp32" in r.stdout
    assert "residual" in r.stdout and "phase" in r.stdout
