"""Serving-resilience chaos tests (docs/serving.md#resilience): the
fault-injection half of the serving layer's fault ladder.

Acceptance oracles:

- **kill-mid-traffic**: ``crash_at=serving.step`` with 12 in-flight
  requests, restart from the journal, and every completed uid's token
  sequence matches the uninterrupted reference exactly (sampling streams
  are pure functions of ``(seed, token_index)``);
- **quarantine**: a ``logit_nan``-poisoned request is evicted with a
  typed ``POISONED`` result while every co-batched request's output is
  bit-identical to a run without it; the circuit breaker trips at the
  configured budget with a forensic dump;
- **bounded journal overhead**: ``io_delay_ms`` on the journal path
  costs O(submits + steps) io-site visits, never O(tokens · records);
- **jaxpr equality**: arming the serving faults leaves the traced decode
  step byte-identical (the poison rides the pool data — the PR-3
  discipline applied to the serving step).
"""

import json
import os
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
from deepspeed_tpu.inference import (ServingEngine, ServingConfig, Request,
                                     CircuitOpenError, OK, POISONED, SHED)

pytestmark = pytest.mark.fault


def _tiny_model():
    cfg = GPT2Config(vocab_size=128, max_seq=64, n_embd=32, n_layer=2,
                     n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    return GPT2(cfg, dtype=jnp.float32)


@pytest.fixture(scope="module")
def tiny_sp():
    model = _tiny_model()
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _mk(model, params, **over):
    base = dict(batch_slots=4, block_size=8, max_new_tokens=4)
    base.update(over)
    return ServingEngine(model=model, params=params,
                         config=ServingConfig(**base))


def _reqs(n, seed0=0, max_new=None):
    """n requests with mixed greedy/sampled decoding (the token-identity
    claims must hold for SAMPLED streams, not just argmax) and mixed
    generation lengths (some complete at prefill, some churn slots)."""
    rng = np.random.default_rng(42)
    return [Request(tokens=rng.integers(0, 128, (4 + i % 5,)),
                    seed=seed0 + i, uid=seed0 + i,
                    max_new_tokens=max_new or (1 + i % 3),
                    do_sample=(i % 2 == 0), temperature=0.8)
            for i in range(n)]


# ---------------------------------------------------------------- kill/replay
def test_kill_mid_traffic_journal_replay_token_identical(
        tiny_sp, tmp_path, fault_harness, devices):
    """ISSUE acceptance: crash_at=serving.step with 12 in-flight
    requests, restart from the journal, every completed uid's tokens
    match the uninterrupted reference run exactly."""
    model, params = tiny_sp
    # uninterrupted reference (no journal)
    ref_srv = _mk(model, params)
    ref = {u: r["tokens"]
           for u, r in ref_srv.run(_reqs(12)).items()}
    ref_srv.close()

    jd = str(tmp_path / "journal")
    srv = _mk(model, params, journal_dir=jd)
    for r in _reqs(12):
        srv.submit(r)
    srv.step()                       # some requests complete pre-crash,
    srv.step()                       # some are mid-flight, some queued
    done_before = [u for u, r in srv.results.items()
                   if r["t_done"] is not None]
    fault_harness.configure("crash_at=serving.step")
    with pytest.raises(fault_harness.InjectedCrash):
        srv.step()
    fault_harness.reset()
    # simulated kill: the crashed engine is abandoned, never close()d

    srv2 = _mk(model, params, journal_dir=jd)
    st = srv2.stats()
    assert st["requeued"] == 12 - len(done_before)
    res = srv2.run()
    for u, toks in ref.items():
        assert res[u]["tokens"] == toks, \
            f"uid {u} diverged after the crash/replay (pre-crash " \
            f"completions: {sorted(done_before)})"
        assert res[u]["outcome"] in (OK, None)   # None = recovered record
    srv2.close()


def test_recovery_sheds_requests_that_no_longer_fit(tiny_sp, tmp_path,
                                                    devices):
    """A restart may run a SMALLER serving configuration (the
    elastic-resize workflows): a journaled pending request that no
    longer fits must finalize as a typed SHED — with a journal finish
    record so the NEXT restart doesn't see it either — instead of
    wedging every restart in __init__."""
    model, params = tiny_sp
    jd = str(tmp_path / "j")
    srv = _mk(model, params, journal_dir=jd)
    srv.submit(Request(tokens=np.arange(30), max_new_tokens=20, uid=1))
    srv.submit(Request(tokens=np.arange(4), max_new_tokens=2, uid=2))
    # simulated kill: nothing served, engine abandoned

    small = ServingConfig(batch_slots=1, block_size=8, num_blocks=4,
                          journal_dir=jd)      # 3 allocatable blocks
    srv2 = ServingEngine(model=model, params=params, config=small)
    assert srv2.results[1]["outcome"] == SHED   # 7 blocks no longer fit
    assert srv2.stats()["requeued"] == 1        # uid 2 still recovers
    res = srv2.run()
    assert res[2]["outcome"] == OK
    srv2.close()

    # srv2 drained CLEAN with nothing pending, so the third generation
    # ROTATES the journal instead of re-materializing served history
    srv3 = ServingEngine(model=model, params=params, config=small)
    assert srv3.stats()["requeued"] == 0        # shed is durable too
    assert srv3.results == {}                   # nothing re-materialized
    assert os.path.getsize(os.path.join(jd, "requests.jsonl")) == 0
    srv3.close()


def test_journal_io_delay_bounded(tiny_sp, tmp_path, fault_harness,
                                  devices):
    """io_delay_ms on the journal path: journal IO is one buffered append
    per scheduler step plus one per submit — O(steps + submits), never
    O(tokens · records) — so an injected per-append delay cannot blow up
    tail latency."""
    model, params = tiny_sp
    fault_harness.configure(io_delay_ms=1.0)
    srv = _mk(model, params, journal_dir=str(tmp_path / "j"))
    res = srv.run(_reqs(6))
    st = srv.stats()
    assert st["outcomes"][OK] == 6 and st["pending"] == 0
    steps = st["decode_steps"]
    hits = fault_harness.plan().hits.get("io.write", 0)
    # 6 eager submit flushes + <= one per step + drain/shutdown slack;
    # the old-style per-record write would be 3-4x this
    assert 0 < hits <= 6 + steps + 4, (hits, steps)
    assert st["latency_ms"]["p99"] > 0
    srv.close()


# ------------------------------------------------------------------ poisoning
def test_poisoned_request_quarantined_neighbors_bit_identical(
        tiny_sp, fault_harness, devices):
    """ISSUE acceptance: a logit_nan request is evicted with a POISONED
    result; every co-batched request's output is bit-identical to a run
    without it; its blocks return to the pool scrubbed (the next tenant
    of those blocks stays finite)."""
    model, params = tiny_sp
    clean_srv = _mk(model, params)
    clean = {u: r["tokens"] for u, r in clean_srv.run(_reqs(4)).items()}
    clean_srv.close()

    bad_uid = 2                              # max_new 3: it decodes
    fault_harness.configure(logit_nan=bad_uid)
    srv = _mk(model, params)
    res = srv.run(_reqs(4))
    rec = res[bad_uid]
    assert rec["outcome"] == POISONED
    # quarantined after its FIRST decode step: only the (clean) prefill
    # token made it out
    assert len(rec["tokens"]) == 1
    for u, toks in clean.items():
        if u != bad_uid:
            assert res[u]["tokens"] == toks, \
                f"neighbor {u} perturbed by the quarantined request"
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    assert srv.stats()["outcomes"][POISONED] == 1
    fault_harness.reset()
    # scrub proof: a fresh request reusing the returned (ex-poisoned)
    # blocks must produce the clean reference stream, not NaN fallout
    probe = _reqs(1, seed0=500, max_new=6)
    again = srv.run(probe)
    assert again[500]["outcome"] == OK
    ref_srv2 = _mk(model, params)
    ref_one = ref_srv2.run(_reqs(1, seed0=500, max_new=6))
    assert again[500]["tokens"] == ref_one[500]["tokens"]
    ref_srv2.close()
    srv.close()


def test_circuit_breaker_trips_with_forensics(tiny_sp, tmp_path,
                                              fault_harness, devices):
    """Poison rate above the budget trips the breaker: submissions are
    refused with CircuitOpenError, in-flight work still completes, and a
    parseable forensic dump (the recent-outcome ring) is written."""
    model, params = tiny_sp
    fault_harness.configure(logit_nan=[0, 1])     # two poisoned uids
    srv = _mk(model, params, poison_budget=1,
              forensic_dir=str(tmp_path / "forensics"))
    res = srv.run(_reqs(4, max_new=3))
    st = srv.stats()
    assert st["outcomes"][POISONED] == 2 and st["breaker_open"]
    # neighbors (uids 2, 3) still completed — the server never dies
    assert res[2]["outcome"] == OK and res[3]["outcome"] == OK
    with pytest.raises(CircuitOpenError, match="breaker is OPEN"):
        srv.submit(Request(tokens=np.arange(4), max_new_tokens=1))
    dump_path = srv._forensic_path
    assert dump_path and os.path.isfile(dump_path)
    with open(dump_path) as f:
        dump = json.load(f)                  # strict JSON (no bare NaN)
    assert dump["event"] == "serving_forensics"
    assert dump["counters"]["poisoned"] == 2
    assert any(r["outcome"] == POISONED for r in dump["recent"])
    srv.close()


def test_poisoned_prefill_quarantined_without_seating(tmp_path, devices):
    """The PREFILL half of the sentinel: a request whose prefill logits
    are already non-finite (here: poisoned model params) must come back
    typed POISONED with no tokens — even at max_new_tokens=1, where it
    would otherwise complete 'ok' with a garbage argmax-over-NaN token —
    and its blocks must return scrubbed."""
    model = _tiny_model()
    params = model.init(jax.random.PRNGKey(1))
    params = dict(params, lnf_scale=params["lnf_scale"] * jnp.nan)
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=2, block_size=8,
                                             max_new_tokens=4,
                                             poison_budget=0,
                                             forensic_dir=str(tmp_path)))
    res = srv.run([Request(tokens=np.arange(4), max_new_tokens=1, uid=0),
                   Request(tokens=np.arange(5), max_new_tokens=4, uid=1)])
    assert res[0]["outcome"] == POISONED and res[0]["tokens"] is None
    assert res[1]["outcome"] == POISONED
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    # budget 0: the second poisoned request tripped the breaker
    assert srv.stats()["breaker_open"]
    with pytest.raises(CircuitOpenError):
        srv.submit(Request(tokens=np.arange(4), max_new_tokens=1))
    srv.close()


# ------------------------------------------------------------------- overload
def test_overload_3x_capacity_latency_bounded(tiny_sp, devices):
    """ISSUE acceptance: at 3x slot capacity under shed_oldest with
    deadlines armed, every admitted request's latency stays within the
    deadline bound (completions finish in time; stragglers are evicted
    AT the deadline, not after), shed requests carry typed results, and
    the queue never grows past the watermark."""
    model, params = tiny_sp
    deadline_ms = 1500.0
    srv = _mk(model, params, batch_slots=2,
              overload="shed_oldest", queue_high_watermark=6,
              queue_low_watermark=4, deadline_ms=deadline_ms)
    # warm the executables OUTSIDE the deadline window: eviction runs at
    # decode-step granularity, so a first step carrying compile/
    # deserialize cost would legitimately blow any ms-scale bound; the
    # warmup itself opts out of the config deadline (inf = no deadline)
    warm = _reqs(1, seed0=900, max_new=8)
    warm[0].deadline_ms = float("inf")
    srv.run(warm)
    srv.reset_stats()
    reqs = _reqs(12, max_new=8)          # 3x the 2+2 slot/queue capacity
    for r in reqs:
        srv.submit(r)
        assert len(srv.queue) <= 6       # bounded: never past the mark
    srv.run()
    st = srv.stats()
    out = st["outcomes"]
    assert out[OK] + out["shed"] + out["deadline"] == 12
    assert out["shed"] >= 1              # the wave DID overload
    for r in reqs:
        assert srv.results[r.uid]["outcome"] in (OK, "shed", "deadline")
    # the latency window covers admitted requests (ok + deadline-evicted):
    # p99 is bounded by the deadline plus at most one decode step of slack
    assert st["latency_ms"]["p99"] <= deadline_ms + 1200.0, st["latency_ms"]
    srv.close()


# -------------------------------------------------------------- program purity
def test_armed_faults_leave_decode_jaxpr_identical(tiny_sp, fault_harness,
                                                   devices):
    """The PR-3 discipline applied to the serving step: arming
    logit_nan + io faults must not change the traced decode program (the
    poison rides the pool data; the sentinel is always compiled in)."""
    model, params = tiny_sp

    def decode_jaxpr():
        srv = _mk(model, params)
        srv._build_decode()
        text = str(jax.make_jaxpr(srv._decode)(*srv._decode_args()))
        srv.close()
        return text

    disarmed = decode_jaxpr()
    fault_harness.configure(
        "logit_nan=3,io_delay_ms=5,crash_at=serving.prefill")
    armed = decode_jaxpr()
    assert disarmed == armed
    # and the sentinel itself is in-graph: the step's jaxpr carries the
    # is_finite reduction (no host round-trip decides quarantine)
    assert "is_finite" in disarmed
