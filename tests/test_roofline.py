"""Roofline attribution (``analysis/roofline.py`` / ``ds_explain``) and
the ``ds_bench_diff`` perf-regression gate (docs/monitoring.md).

The flagship test replays a recorded b8 paged-decode step (weight and KV
bytes, tokens/s: ``tests/data/bench_diff_fixture_inference.json``)
through a synthetic monitor stream and asserts ``ds_explain`` reproduces
the hand-computed achieved-fraction-of-HBM-bound within 10%, with the
gather-materialization bytes named in the gap."""

import json
import os

import pytest

from deepspeed_tpu.analysis import roofline as rl
from deepspeed_tpu.analysis import bench_diff as bd
from deepspeed_tpu.monitor.events import Event
from deepspeed_tpu.monitor.gauges import CHIP_TABLE, chip_specs
from deepspeed_tpu.monitor.histogram import LogHistogram

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INFERENCE_FIXTURE = os.path.join(REPO, "tests", "data",
                                 "bench_diff_fixture_inference.json")
SERVING_FIXTURE = os.path.join(REPO, "tests", "data",
                               "bench_diff_fixture_serving.json")

V5E = dict(CHIP_TABLE["v5e"], device_kind="TPU v5e", matched="v5e")


# ---------------------------------------------------------------------------
# attribute(): bound selection + gap decomposition
# ---------------------------------------------------------------------------

def test_attribute_picks_the_binding_roofline():
    # compute-bound: FLOPs term dominates
    v = rl.attribute(wall_s=1e-3, flops=150e9, hbm_bytes=1e6,
                     wire_bytes=0, chip=V5E)
    assert v["bound"] == "compute"
    assert v["achieved_frac"] == pytest.approx(
        150e9 / 197e12 / 1e-3, abs=1e-4)    # reported at 4 decimals
    # hbm-bound: bytes term dominates
    v = rl.attribute(wall_s=1e-3, flops=1e9, hbm_bytes=500e6,
                     wire_bytes=0, chip=V5E)
    assert v["bound"] == "hbm"
    # wire-bound: census bytes over the (slower) ICI dominate
    v = rl.attribute(wall_s=1e-3, flops=1e9, hbm_bytes=1e6,
                     wire_bytes=150e6, chip=V5E)
    assert v["bound"] == "wire"
    # gap = wall − the binding term, as a fraction of wall
    t_wire = 150e6 / (200.0 * 1e9)
    assert v["gap"]["host_scheduling_s"] == pytest.approx(1e-3 - t_wire,
                                                          rel=1e-6)
    assert v["gap"]["host_pct"] == pytest.approx(
        100 * (1e-3 - t_wire) / 1e-3, abs=0.1)


def test_attribute_names_gather_bytes_and_scales_chips():
    v = rl.attribute(wall_s=1e-3, hbm_bytes=100e6, gather_bytes=40e6,
                     chip=V5E, n_chips=4)
    g = v["gap"]
    assert g["gather_materialization_bytes"] == 40_000_000
    assert g["gather_materialization_s"] == pytest.approx(
        40e6 / (819e9 * 4), rel=1e-6)
    assert g["gather_pct_of_hbm_bytes"] == pytest.approx(40.0)
    # 4 chips divide every denominator
    assert v["modeled"]["hbm"] == pytest.approx(100e6 / (819e9 * 4),
                                                rel=1e-6)
    with pytest.raises(ValueError):
        rl.attribute(wall_s=0.0, hbm_bytes=1)


def test_chip_specs_resolves_and_falls_back():
    row = chip_specs("TPU v5p chip")
    assert row["matched"] == "v5p" and row["hbm_gb_s"] == 2765.0
    assert chip_specs("TPU v5 lite")["matched"] == "v5 lite"   # the v5e
    nominal = chip_specs("cpu")
    assert nominal["matched"] == "v5e" and nominal.get("nominal") is True
    assert chip_specs().get("nominal") is True     # this backend: the CPU
    # a TPU the table does not know is an error, never priced as a v5e
    with pytest.raises(ValueError, match="no CHIP_TABLE row"):
        chip_specs("TPU v9 mega")
    # every table row carries all three roofline denominators
    for kind, spec in CHIP_TABLE.items():
        assert {"peak_bf16_flops", "hbm_gb_s", "ici_gb_s"} <= set(spec)


# ---------------------------------------------------------------------------
# the flagship acceptance: reproduce the fixture's hand-computed b8
# ---------------------------------------------------------------------------

def _synthetic_stream(tmp_path, bench_point):
    batch = bench_point["batch"]
    wall_ms = batch / bench_point["decode_tokens_per_sec"] * 1e3
    hbm_bytes = (bench_point["roofline"]["weight_bytes_mb"]
                 + bench_point["roofline"]["kv_bytes_per_step_mb"]) * 1e6
    gather = rl.gather_materialization_bytes(
        n_layer=12, batch_slots=batch, nb_max=8, block_size=32,
        n_head=12, head_dim=64, itemsize=2)
    h = LogHistogram()
    for _ in range(64):
        h.add(wall_ms)
    lines = [
        Event(kind="gauge", name="exe_cost", t=1.0, step=1, value=0.0,
              fields={"exe": "serving_step", "flops": 0,
                      "hbm_bytes": int(hbm_bytes), "wire_bytes": 0,
                      "gather_bytes": gather, "tokens_per_step": batch,
                      "device_kind": "TPU v5e", "n_chips": 1}).to_json(),
        Event(kind="hist", name="step_wall_ms", t=2.0, step=64,
              fields=h.to_dict()).to_json(),
    ]
    run = tmp_path / "run"
    run.mkdir()
    (run / "events.jsonl").write_text("\n".join(lines) + "\n")
    return str(run)


def test_ds_explain_reproduces_b8_hbm_fraction(tmp_path, capsys):
    """ds_explain over a monitor stream carrying the b8 paged-decode
    fixture's numbers must land within 10% of its hand-computed
    fraction_of_bound, call it HBM-bound, and name the
    gather-materialization bytes in the gap decomposition."""
    with open(INFERENCE_FIXTURE) as fh:
        bench = json.load(fh)["b8_gather_step"]
    run = _synthetic_stream(tmp_path, bench)
    rc = rl.main([run, "--json"])
    assert rc == 0
    verdicts = json.loads(capsys.readouterr().out)
    v = verdicts["serving_step"]
    hand = bench["roofline"]["fraction_of_bound"]          # 0.481
    assert v["bound"] == "hbm"
    assert abs(v["achieved_frac"] - hand) / hand <= 0.10
    assert v["gap"]["gather_materialization_bytes"] > 0
    # and the human report names the gather term
    rc = rl.main([run])
    out = capsys.readouterr().out
    assert rc == 0 and "HBM-BOUND" in out
    assert "gather materialization" in out


def test_ds_explain_empty_and_missing_stream(tmp_path, capsys):
    run = tmp_path / "empty"
    run.mkdir()
    (run / "events.jsonl").write_text("")
    assert rl.main([str(run)]) == 0
    assert "no priced executables" in capsys.readouterr().out
    assert rl.main([str(tmp_path / "nope")]) == 1


def test_ds_explain_chip_override(tmp_path, capsys):
    with open(INFERENCE_FIXTURE) as fh:
        bench = json.load(fh)["b8_gather_step"]
    run = _synthetic_stream(tmp_path, bench)
    # price the same stream against v5p: 2765/819 ≈ 3.38x more headroom
    rc = rl.main([run, "--chip", "v5p", "--json"])
    assert rc == 0
    v = json.loads(capsys.readouterr().out)["serving_step"]
    assert v["achieved_frac"] == pytest.approx(
        bench["roofline"]["fraction_of_bound"] * 819.0 / 2765.0, rel=0.02)


# ---------------------------------------------------------------------------
# ds_bench_diff: the perf-regression gate
# ---------------------------------------------------------------------------

def _base_doc():
    return {"serving": {"tokens_per_sec": 100.0, "p99_ms": 50.0,
                        "streams": 8},
            "mfu": 0.52, "wire_bytes_per_step": 1000}


def test_bench_diff_detects_regression_and_exits_nonzero(tmp_path,
                                                         capsys):
    base, new = _base_doc(), _base_doc()
    new["serving"]["tokens_per_sec"] = 70.0       # -30% beyond ±20%
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(new))
    assert bd.main([str(a), str(b)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "tokens_per_sec" in out
    # identical inputs: clean exit
    assert bd.main([str(a), str(a)]) == 0


def test_bench_diff_band_semantics():
    base, new = _base_doc(), _base_doc()
    new["serving"]["tokens_per_sec"] = 85.0       # -15%: inside ±20%
    r = bd.compare(base, new)
    assert not r["regressions"]
    assert r["rows"][0]["verdict"] == "info"
    # tighten the band: the same move becomes a regression
    r = bd.compare(base, new, band=0.10)
    assert len(r["regressions"]) == 1
    # direction matters: p99 going DOWN 30% is an improvement, not a
    # regression; tokens/s going UP 30% likewise
    new2 = _base_doc()
    new2["serving"]["p99_ms"] = 35.0
    new2["serving"]["tokens_per_sec"] = 130.0
    r = bd.compare(base, new2)
    assert not r["regressions"]
    assert {row["verdict"] for row in r["rows"]} == {"improved"}


def test_bench_diff_per_metric_band_and_informational():
    base, new = _base_doc(), _base_doc()
    new["serving"]["p99_ms"] = 70.0               # +40%
    r = bd.compare(base, new, bands={"p99_ms": 0.5})
    assert not r["regressions"]                   # widened tail band
    r = bd.compare(base, new)
    assert len(r["regressions"]) == 1             # default band gates it
    # non-perf metrics never gate: streams is config echo
    new2 = _base_doc()
    new2["serving"]["streams"] = 12
    r = bd.compare(base, new2)
    assert not r["regressions"]
    assert r["rows"][0]["direction"] is None
    # wire bytes are a cost: +3x is a regression
    new3 = _base_doc()
    new3["wire_bytes_per_step"] = 3000
    assert len(bd.compare(base, new3)["regressions"]) == 1


def test_bench_diff_zero_baseline_never_gates():
    """A zero baseline makes every relative delta infinite — such rows
    report as informational instead of tripping the gate (a rounded-to-
    0.0 gap_host_pct moving to 0.3 is noise, not a perf cliff)."""
    base = {"gap_host_pct": 0.0, "p99_ms": 0.0}
    new = {"gap_host_pct": 0.3, "p99_ms": 12.5}
    r = bd.compare(base, new)
    assert not r["regressions"]
    assert all(row["verdict"] == "info" and row["direction"] is None
               for row in r["rows"])


def test_bench_diff_against_committed_artifact():
    """The gate runs directly over a committed JSON document."""
    with open(SERVING_FIXTURE) as fh:
        doc = json.load(fh)
    r = bd.compare(doc, doc)
    assert not r["rows"] and not r["regressions"]
    worse = json.loads(json.dumps(doc))
    worse["serving_fixture"]["tokens_per_sec"] *= 0.5
    assert len(bd.compare(doc, worse)["regressions"]) == 1


# ------------------------------------------- paged-attention impl awareness
def test_gather_bytes_reflect_live_impl():
    """The gather term is priced for the IMPLEMENTATION, not the
    layout: the in-place kernel reports exactly 0 (the bytes are gone),
    the gather fallback keeps the modeled written+read copy traffic."""
    kw = dict(n_layer=12, batch_slots=8, nb_max=8, block_size=32,
              n_head=12, head_dim=64, itemsize=2)
    gather = rl.gather_materialization_bytes(paged_impl="gather", **kw)
    assert gather == 4 * 12 * 8 * 8 * 32 * 12 * 64 * 2
    assert rl.gather_materialization_bytes(paged_impl="kernel", **kw) == 0
    with pytest.raises(AssertionError, match="paged_impl"):
        rl.gather_materialization_bytes(paged_impl="magic", **kw)


def test_verdict_names_paged_impl(tmp_path, capsys):
    """A kernel-produced stream's verdict must name the impl AND carry
    an explicit gather_materialization_bytes == 0 — 'the copy is gone'
    is reported evidence, not an absent key (ISSUE 14 acceptance)."""
    v = rl.attribute(wall_s=1e-3, hbm_bytes=100e6, gather_bytes=0,
                     paged_impl="kernel",
                     chip=dict(rl.CHIP_TABLE["v5e"], device_kind="v5e",
                               matched="v5e"))
    assert v["paged_attention_impl"] == "kernel"
    assert v["gap"]["gather_materialization_bytes"] == 0
    assert "paged_attention_impl" not in rl.attribute(
        wall_s=1e-3, hbm_bytes=100e6)        # legacy streams unchanged
    # end to end: a kernel exe_cost event through the real CLI
    h = LogHistogram()
    for _ in range(8):
        h.add(0.5)
    lines = [
        Event(kind="gauge", name="exe_cost", t=1.0, step=1, value=0.0,
              fields={"exe": "serving_step", "flops": 0,
                      "hbm_bytes": 10**8, "wire_bytes": 0,
                      "gather_bytes": 0, "paged_impl": "kernel",
                      "tokens_per_step": 8,
                      "device_kind": "TPU v5e", "n_chips": 1}).to_json(),
        Event(kind="hist", name="step_wall_ms", t=2.0, step=8,
              fields=h.to_dict()).to_json(),
    ]
    run = tmp_path / "kernel_run"
    run.mkdir()
    (run / "events.jsonl").write_text("\n".join(lines) + "\n")
    rc = rl.main([str(run), "--json"])
    assert rc == 0
    v = json.loads(capsys.readouterr().out)["serving_step"]
    assert v["paged_attention_impl"] == "kernel"
    assert v["gap"]["gather_materialization_bytes"] == 0
    rc = rl.main([str(run)])
    out = capsys.readouterr().out
    assert rc == 0 and "in-place Pallas kernel" in out


def test_live_serving_exe_cost_is_impl_aware(devices):
    """The LIVE engine's exe_cost fields: kernel impl → gather_bytes 0
    + impl named; gather impl → the modeled term (the ds_explain feed
    stays honest for whichever path is deployed)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
    from deepspeed_tpu.inference import (ServingEngine, ServingConfig,
                                         Request)
    fields = {}
    for impl in ("kernel", "gather"):
        cfg = GPT2Config(vocab_size=64, max_seq=32, n_embd=32, n_layer=2,
                         n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                         resid_pdrop=0.0, attention_impl="jnp",
                         paged_attention_impl=impl)
        model = GPT2(cfg, dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0))
        srv = ServingEngine(model=model, params=params,
                            config=ServingConfig(batch_slots=2,
                                                 block_size=8,
                                                 max_new_tokens=3,
                                                 preflight=False))
        srv.run([Request(tokens=np.arange(5), max_new_tokens=3)])
        f = srv._exe_cost_fields()
        srv.close()
        if f is None:       # backend without cost analysis: nothing to gate
            pytest.skip("no executable cost analysis on this backend")
        fields[impl] = f
    assert fields["kernel"]["paged_impl"] == "kernel"
    assert fields["kernel"]["gather_bytes"] == 0
    assert fields["gather"]["paged_impl"] == "gather"
    assert fields["gather"]["gather_bytes"] > 0


def test_ds_explain_kernel_b8_projection_meets_bound(tmp_path, capsys):
    """ISSUE 14 acceptance: replaying the fixture's b8 KERNEL entry (a
    projection, named as one) through the real ds_explain CLI must show
    gather_materialization_bytes == 0 for the kernel decode executable
    and an achieved HBM fraction >= 0.8."""
    with open(INFERENCE_FIXTURE) as fh:
        bench = json.load(fh)["b8_kernel_step"]
    batch = bench["batch"]
    wall_ms = batch / bench["decode_tokens_per_sec_modeled"] * 1e3
    hbm_bytes = (bench["roofline"]["weight_bytes_mb"]
                 + bench["roofline"]["kv_bytes_per_step_mb"]) * 1e6
    h = LogHistogram()
    for _ in range(64):
        h.add(wall_ms)
    lines = [
        Event(kind="gauge", name="exe_cost", t=1.0, step=1, value=0.0,
              fields={"exe": "serving_step", "flops": 0,
                      "hbm_bytes": int(hbm_bytes), "wire_bytes": 0,
                      "gather_bytes": 0, "paged_impl": "kernel",
                      "tokens_per_step": batch,
                      "device_kind": "TPU v5e", "n_chips": 1}).to_json(),
        Event(kind="hist", name="step_wall_ms", t=2.0, step=64,
              fields=h.to_dict()).to_json(),
    ]
    run = tmp_path / "run"
    run.mkdir()
    (run / "events.jsonl").write_text("\n".join(lines) + "\n")
    rc = rl.main([str(run), "--json"])
    assert rc == 0
    v = json.loads(capsys.readouterr().out)["serving_step"]
    assert v["bound"] == "hbm"
    assert v["paged_attention_impl"] == "kernel"
    assert v["gap"]["gather_materialization_bytes"] == 0
    assert v["achieved_frac"] >= 0.8
    # and within 5% of the fixture's own fraction
    committed = bench["roofline"]["fraction_of_bound"]
    assert abs(v["achieved_frac"] - committed) <= 0.05
