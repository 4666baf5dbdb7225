"""Each runner end to end at gpt2-tiny on the CPU, through the function
``run.py`` calls (only ``main`` refuses the CPU); the four-chip cell on four
virtual devices.  Speeds mean nothing here: what is checked is the control
flow, the counts, the checks against the reference, and the result's shape.
"""

import copy
import gc
import json

import pytest

from benchmark import harness, run
from benchmark.tests.tiny import TINY

SEED = 2 ** 31 + 77          # the driver's seeds do not fit 32 signed bits


def tiny_traffic(name):
    t = copy.deepcopy(harness.load_traffic(name))
    if t["kind"] == "train":
        t.update(seq=64, trace_seconds=1)
        t["model"]["attention_impl"] = "jnp"
        return t
    cls = t["classes"][0]
    if t["kind"] == "serve_open_loop":
        t["arrivals"]["rate"] = 6.0
        cls["prompt_tokens"].update(median=40, min=8, max=150)
        cls["output_tokens"].update(median=10, min=4, max=24)
    else:
        cls["prompt_tokens"].update(min=60, max=150)
        cls["output_tokens"].update(min=4, max=8)
        t.update(pool_requests=12, queue_depth=4)
    t["serving"]["batch_slots"] = 4
    t["trace_seconds"] = 1
    return t


def run_tiny(name, seconds=2.0, **traffic_overrides):
    bench = harness.load_benchmark()
    cell = harness.cell_by_name(bench, name)
    traffic = {**tiny_traffic(cell["traffic"]), **traffic_overrides}
    result = run.run_cell(bench, cell, seed=SEED, seconds=seconds,
                          trace=False, config=TINY, traffic=traffic,
                          log=lambda msg: None)
    json.dumps(result)                       # the line must serialise
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["device"]["platform"] == "cpu"
    wanted = {m["name"] for m in harness.cell_metrics(
        bench, "end_to_end", name)}
    assert set(result["metrics"]) == wanted
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    return result


@pytest.mark.parametrize("cell, bound_to", [
    ("serve_chat", "tpot_ms_p95"), ("serve_chat_sat", "serve_tokens_per_s")])
def test_serve_open_loop(cell, bound_to):
    """The open-loop runner prints the metric ``BENCHMARK.json`` binds the
    cell to and no other; both are among its facts."""
    r = run_tiny(cell)
    assert set(r["metrics"]) == {bound_to, "setup_s"}
    assert r["metrics"][bound_to]["value"] == r["details"]["facts"][bound_to]
    assert {"tpot_ms_p95", "serve_tokens_per_s", "drain_s"} \
        <= set(r["details"]["facts"])
    assert r["correct"] and r["failed"] == 0
    assert r["attempted"] == 12                  # 6 req/s for 2 s
    c = r["details"]["counters"]
    assert c["completed"] == 12 and c["in_window_compiles"] == 0
    check = r["details"]["facts"]["check"]
    assert check["logit_err"] < 1e-2 and check["blocks_recycled"]


def test_the_rms_limit_decides_correct_where_the_file_states_it():
    """``logit_rms_tol`` is compared only in a cell whose traffic file has
    it; both numbers are printed beside their limits either way."""
    spec = {"slots": 4, "steps": 3, "logit_tol": 0.04}
    sound = run_tiny("serve_chat", check={**spec, "logit_rms_tol": 0.5})
    assert sound["correct"]
    check = sound["details"]["facts"]["check"]
    assert 0 < check["logit_rms_err"] < 0.5 == check["logit_rms_tol"]
    tight = run_tiny("serve_chat", check={**spec, "logit_rms_tol": 1e-9})
    assert not tight["correct"] and tight["failed"] == 0
    assert run_tiny("serve_chat", check=spec)["details"]["facts"]["check"][
        "logit_rms_tol"] is None


def test_the_heap_is_settled_and_the_collector_stays_on():
    from benchmark import serving
    gc.unfreeze()
    try:
        serving.settle_heap()
        assert gc.get_freeze_count() > 10_000     # what set-up left
        assert gc.isenabled()
    finally:
        gc.unfreeze()


def test_serve_offline():
    r = run_tiny("serve_docs_offline")
    assert r["correct"] and r["failed"] == 0
    facts = r["details"]["facts"]
    assert 0 < facts["completed_in_window"] <= r["attempted"]
    assert r["details"]["counters"]["in_window_compiles"] == 0


def test_train_one_chip():
    r = run_tiny("train_z1")
    assert r["correct"] and r["attempted"] >= 2
    check = r["details"]["facts"]["check"]
    assert check["loss_rel_err"] < 1e-3
    assert check["state_share_by_device"] == [1.0]


@pytest.mark.parametrize("lag", [0, 1, 3])
def test_train_reads_every_loss_whatever_the_lag(lag):
    """Steps still in flight when the window closes are drained and
    counted; the first loss (the one checked) is the same at any lag."""
    r = run_tiny("train_z1", seconds=1.0, loss_read_lag=lag)
    assert r["correct"] and r["attempted"] > lag
    assert r["details"]["counters"]["steps"] == r["attempted"]
    first = r["details"]["facts"]["check"]["first_loss"]
    assert first == run_tiny("train_z1", seconds=0.2,
                             loss_read_lag=0)["details"]["facts"][
                                 "check"]["first_loss"]


def test_train_zero3_on_four_devices():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    r = run_tiny("train_z3_x4")
    assert r["correct"] and r["device"]["count"] == 4
    shares = r["details"]["facts"]["check"]["state_share_by_device"]
    assert len(shares) == 4 and all(0.25 <= s <= 0.3 for s in shares)


def test_a_failed_check_is_not_correct():
    """A reference check that fails prints ``correct: false``."""
    bench = harness.load_benchmark()
    cell = harness.cell_by_name(bench, "train_z1")
    traffic = tiny_traffic("train_z1")
    traffic["check"]["loss_rtol"] = 1e-12
    r = run.run_cell(bench, cell, seed=3, seconds=0.5, trace=False,
                     config=TINY, traffic=traffic, log=lambda msg: None)
    assert r["correct"] is False


def test_setup_s_leaves_out_the_call_that_started_the_tpu_runtime():
    """``main`` times its ``jax.devices()`` and hands the seconds in: a
    process that began 100 s ago and spent 90 of them there reports the
    other 10 and what the run itself adds, and says so in ``details``."""
    import time
    bench = harness.load_benchmark()
    cell = harness.cell_by_name(bench, "train_z1")
    kw = dict(seed=3, seconds=0.5, trace=False, config=TINY,
              traffic=tiny_traffic("train_z1"), log=lambda msg: None)
    began = time.monotonic() - 100.0
    whole = run.run_cell(bench, cell, t_process_start=began, **kw)
    less = run.run_cell(bench, cell, t_process_start=began,
                        chip_reach_s=90.0, **kw)
    assert whole["metrics"]["setup_s"]["value"] > 100.0
    assert 10.0 < less["metrics"]["setup_s"]["value"] < 100.0
    assert less["details"]["chip_reach_s"] == 90.0
    assert whole["details"]["chip_reach_s"] == 0.0


def test_main_refuses_the_cpu(capsys):
    rc = run.main(["--workload", "train_z1", "--seed", "1", "--seconds",
                   "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""     # no result line
