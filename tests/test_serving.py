"""Serving-layer tests: paged KV cache + continuous batching
(docs/serving.md).

Oracles: ``InferenceEngine.generate`` (the sequential per-request path
every serving answer must match token-for-token under greedy decoding)
and the model's contiguous cached decode (logit-level equivalence for
the paged cache)."""

import os
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
from deepspeed_tpu.inference import (InferenceEngine, ServingEngine,
                                     ServingConfig, Request,
                                     ServingError, QueueFullError,
                                     ServingStalledError,
                                     OK, SHED, DEADLINE)
from deepspeed_tpu.inference import paged_kv as pk


def _tiny_model(dtype=jnp.float32, **kw):
    cfg = GPT2Config(vocab_size=128, max_seq=64, n_embd=32, n_layer=2,
                     n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp", **kw)
    return GPT2(cfg, dtype=dtype)


@pytest.fixture(scope="module")
def tiny():
    model = _tiny_model()
    params = model.init(jax.random.PRNGKey(0))
    return model, params


# ------------------------------------------------------------- allocator
def test_block_allocator_alloc_free_reuse():
    a = pk.BlockAllocator(6)              # ids 1..5 (0 = scratch)
    assert a.free_blocks == 5
    got = a.alloc(3)
    assert len(got) == 3 and pk.SCRATCH_BLOCK not in got
    assert a.alloc(3) is None             # all-or-nothing admission
    b2 = a.alloc(2)
    assert set(got).isdisjoint(b2)
    assert a.free_blocks == 0
    a.free(got)
    assert a.free_blocks == 3
    again = a.alloc(3)
    assert set(again) == set(got)         # freed blocks recycle
    # rejections are ValueError (live under python -O), and validate-
    # first: a rejected batch must not partially mutate the free list
    before = (a.free_blocks, a.used_blocks)
    with pytest.raises(ValueError, match="double free"):
        a.free([again[0], again[0]])
    with pytest.raises(ValueError, match="scratch"):
        a.free([pk.SCRATCH_BLOCK])
    assert (a.free_blocks, a.used_blocks) == before
    assert a.is_allocated(again[0])
    assert not a.is_allocated(pk.SCRATCH_BLOCK)


def test_blocks_needed_math():
    assert pk.blocks_needed(1, 8) == 1
    assert pk.blocks_needed(8, 8) == 1
    assert pk.blocks_needed(9, 8) == 2
    assert pk.blocks_needed(0, 8) == 1    # a sequence occupies >= 1 block


# ------------------------------------------- paged decode == contiguous
def test_paged_decode_matches_contiguous_cache(tiny, devices):
    """decode_step_paged over scattered pool blocks must produce the
    SAME logits as the contiguous cached decode (the paged layout is a
    storage change, not a math change)."""
    model, params = tiny
    rng = np.random.default_rng(0)
    B, T, bs = 2, 8, 4
    toks = jnp.asarray(rng.integers(0, 128, (B, T)), jnp.int32)

    cache = model.init_cache(B, 32)
    lg, cache = model.apply_with_cache(params, toks, cache)
    ref = [lg[:, -1]]
    cur = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
    for _ in range(1):
        lg, cache = model.apply_with_cache(params, cur[:, None], cache)
        ref.append(lg[:, -1])
        cur = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)

    c = model.config
    pool = pk.init_pool(c.n_layer, 9, bs, c.n_head, c.head_dim, jnp.float32)
    alloc = pk.BlockAllocator(9)
    tables = np.zeros((B, 4), np.int32)
    for b in range(B):
        blks = alloc.alloc(3)
        tables[b, :3] = blks
        c1 = model.init_cache(1, T)
        _, c1 = model.apply_with_cache(params, toks[b:b + 1], c1)
        pool = pk.write_prefill(pool, jnp.asarray(blks[:T // bs], jnp.int32),
                                c1["k"][:, :, 0], c1["v"][:, :, 0])
    tables = jnp.asarray(tables)
    lengths = jnp.full((B,), T, jnp.int32)
    cur = jnp.argmax(ref[0], -1).astype(jnp.int32)
    step = jax.jit(model.decode_step_paged)   # compile once, not op-by-op
    for i in range(1):
        logits, pool = step(params, cur, pool, tables, lengths)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref[i + 1]),
                                   rtol=1e-5, atol=1e-5)
        cur = jnp.argmax(logits, -1).astype(jnp.int32)
        lengths = lengths + 1


def test_int8_kv_pool_within_tolerance(tiny, devices):
    """int8 KV (block-quantized per head dim) must track the full-width
    pool's logits within the quantizer's error bound."""
    model, params = tiny
    rng = np.random.default_rng(1)
    T, bs = 8, 4
    toks = jnp.asarray(rng.integers(0, 128, (1, T)), jnp.int32)
    c1 = model.init_cache(1, T)
    lg, c1 = model.apply_with_cache(params, toks, c1)
    k, v = c1["k"][:, :, 0], c1["v"][:, :, 0]
    cur = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)

    c = model.config
    step = jax.jit(model.decode_step_paged)
    outs = {}
    for bits in (16, 8):
        pool = pk.init_pool(c.n_layer, 5, bs, c.n_head, c.head_dim,
                            jnp.float32, kv_bits=bits, quant_block=8)
        pool = pk.write_prefill(pool, jnp.asarray([1, 2], jnp.int32), k, v)
        tables = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
        logits, _ = step(params, cur, pool, tables,
                         jnp.asarray([T], jnp.int32))
        outs[bits] = np.asarray(logits)
    scale = np.abs(outs[16]).max()
    err = np.abs(outs[8] - outs[16]).max()
    assert err < 0.02 * scale, (err, scale)    # int8 ~ 1/254 per block


# -------------------------------------------------- continuous batching
def test_serving_matches_sequential_generate(tiny, devices):
    """Greedy answers under continuous batching (slot churn, shared
    decode batch, block reuse) == the sequential engine, per request."""
    model, params = tiny
    rng = np.random.default_rng(2)
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=2, block_size=8,
                                             max_new_tokens=6))
    # 4 requests over 2 slots (slot churn + queueing), but only TWO
    # distinct max_new values — the sequential oracle compiles one
    # decode loop per distinct config, the dominant cost of this test
    reqs = [Request(tokens=rng.integers(0, 128, (5 + i,)),
                    max_new_tokens=3 + (i % 2), seed=i) for i in range(4)]
    res = srv.run(reqs)
    st = srv.stats()
    assert st["completed"] == 4 and st["pending"] == 0
    assert st["latency_ms"]["p99"] >= st["latency_ms"]["p50"] > 0
    assert st["ttft_ms"]["p50"] > 0
    # every block returned to the pool after eviction
    assert srv.allocator.free_blocks == srv.num_blocks - 1

    eng = InferenceEngine(_tiny_model(), params=params)
    for r in reqs:
        out = np.asarray(eng.generate(np.asarray(r.tokens)[None],
                                      max_new_tokens=r.max_new_tokens))
        assert res[r.uid]["tokens"] == out[0, len(r.tokens):].tolist(), \
            f"request {r.uid} diverged from the sequential oracle"

    # drain API: pop_result hands over the record, frees the uid, and
    # the latency aggregates survive (long-running-server hygiene)
    rec = srv.pop_result(reqs[0].uid)
    assert rec["tokens"] and reqs[0].uid not in srv.results
    with pytest.raises(KeyError):
        srv.pop_result(reqs[0].uid)
    assert srv.stats()["completed"] == 4      # aggregates unaffected
    srv.reset_stats()
    assert srv.stats()["completed"] == 0
    assert "latency_ms" not in srv.stats()
    srv.close()


def test_arrival_order_determinism(tiny, devices):
    """The same (sampled!) requests arriving in different orders produce
    identical per-request tokens: each request's RNG stream is keyed on
    (seed, token_index) alone, never on batch composition."""
    model, params = tiny

    def run_order(order):
        srv = ServingEngine(
            model=model, params=params,
            config=ServingConfig(batch_slots=2, block_size=8,
                                 max_new_tokens=5, top_k=8))
        reqs = [Request(tokens=np.arange(3 + i) % 100, max_new_tokens=5,
                        seed=100 + i, do_sample=True, temperature=0.7,
                        uid=i) for i in range(4)]
        out = srv.run([reqs[j] for j in order])
        srv.close()
        return {u: r["tokens"] for u, r in out.items()}

    a = run_order([0, 1, 2, 3])
    b = run_order([3, 1, 0, 2])
    assert a == b


def test_admission_queues_past_capacity(tiny, devices):
    """More streams than slots AND a pool too small for all slots at
    once: requests queue, join as blocks free, and all complete."""
    model, params = tiny
    # 2 slots but only 5 allocatable blocks; each request needs 2 blocks
    # (8 prompt + 4 new over block_size=8) — pool-capacity-bound, with
    # the strict-FIFO queue absorbing the rest
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=2, block_size=8,
                                             num_blocks=6, max_new_tokens=4))
    rng = np.random.default_rng(3)
    reqs = [Request(tokens=rng.integers(0, 128, (8,)), seed=i)
            for i in range(5)]
    res = srv.run(reqs)
    assert all(len(res[r.uid]["tokens"]) == 4 for r in reqs)
    assert srv.allocator.free_blocks == 5
    srv.close()


def test_submit_rejects_oversized_requests(tiny, devices):
    model, params = tiny
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=2, block_size=8,
                                             num_blocks=4))
    with pytest.raises(ValueError, match="max_seq"):
        srv.submit(Request(tokens=np.arange(60), max_new_tokens=30))
    with pytest.raises(ValueError, match="blocks"):
        # fits max_seq (64) but not the 3 allocatable blocks (24 tokens)
        srv.submit(Request(tokens=np.arange(20), max_new_tokens=20))
    with pytest.raises(ValueError, match="empty"):
        srv.submit(Request(tokens=np.zeros((0,), np.int32)))
    with pytest.raises(ValueError, match=">= 1"):
        # max_new_tokens=0 must be rejected, not silently replaced by
        # the config default (falsy-zero trap)
        srv.submit(Request(tokens=np.arange(4), max_new_tokens=0))
    srv.submit(Request(tokens=np.arange(4), max_new_tokens=1, uid=7))
    with pytest.raises(ValueError, match="already submitted"):
        # a duplicate uid would corrupt the in-flight result record
        srv.submit(Request(tokens=np.arange(4), max_new_tokens=1, uid=7))
    srv.close()


def test_prefill_bucket_past_max_seq(devices):
    """A prompt whose block-rounded prefill bucket exceeds max_seq
    (max_seq not a block multiple) must still serve: the forward runs at
    max_seq and the K/V scatter zero-pads the last block."""
    cfg = GPT2Config(vocab_size=64, max_seq=20, n_embd=16, n_layer=1,
                     n_head=2, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    model = GPT2(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(9))
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=1, block_size=8))
    r = Request(tokens=np.arange(19) % 64, max_new_tokens=1, seed=0)
    res = srv.run([r])         # bucket = 24 > max_seq = 20
    eng = InferenceEngine(GPT2(cfg, dtype=jnp.float32), params=params)
    out = np.asarray(eng.generate(np.asarray(r.tokens)[None],
                                  max_new_tokens=1))
    assert res[r.uid]["tokens"] == out[0, 19:].tolist()
    srv.close()


@pytest.mark.slow   # compile-heavy (serving + a generate); the ownership
                    # logic itself is a two-line flag checked here
def test_close_leaves_caller_engine_usable(tiny, devices):
    """close() must not tear down an engine the caller passed in —
    only an internally built one is owned."""
    model, params = tiny
    eng = InferenceEngine(_tiny_model(), params=params)
    srv = ServingEngine(engine=eng,
                        config=ServingConfig(batch_slots=1, block_size=8,
                                             max_new_tokens=2))
    srv.run([Request(tokens=np.arange(4), seed=0)])
    srv.close()
    assert eng.params is not None
    out = np.asarray(eng.generate(np.array([[1, 2]], np.int32),
                                  max_new_tokens=2))
    assert out.shape == (1, 4)
    eng.close()


@pytest.mark.slow   # compile-heavy (two engines); eviction/block-reuse
                    # stays fast-tier via the admission + oracle tests
def test_eos_evicts_early(tiny, devices):
    """A request hitting eos frees its slot + blocks before max_new."""
    model, params = tiny
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=1, block_size=8,
                                             max_new_tokens=8))
    r = Request(tokens=np.arange(4), max_new_tokens=8, seed=0)
    res = srv.run([r])
    toks = res[r.uid]["tokens"]
    # re-run with a token from that greedy stream declared eos: the
    # request must stop at its FIRST occurrence (eos included) and
    # return its blocks
    eos = int(toks[1])
    srv2 = ServingEngine(model=model, params=params,
                         config=ServingConfig(batch_slots=1, block_size=8,
                                              max_new_tokens=8,
                                              eos_token_id=eos))
    r2 = Request(tokens=np.arange(4), max_new_tokens=8, seed=0)
    res2 = srv2.run([r2])
    assert res2[r2.uid]["tokens"] == toks[:toks.index(eos) + 1]
    assert srv2.allocator.free_blocks == srv2.num_blocks - 1
    srv.close()
    srv2.close()


@pytest.mark.slow   # compile-heavy (two quantized engines); int8-in-scan
                    # numerics stay fast-tier in test_inference.py
def test_serving_int8_weights_runs(tiny, devices):
    """int8-quantized weights stream through the fused paged decode (the
    stacked-scan per-layer slice path) and still answer deterministic
    greedy requests."""
    model, params = tiny
    eng = InferenceEngine(_tiny_model(), params=params,
                          quantization_setting=1)
    srv = ServingEngine(engine=eng,
                        config=ServingConfig(batch_slots=2, block_size=8,
                                             max_new_tokens=4))
    reqs = [Request(tokens=np.arange(5 + i), seed=i) for i in range(2)]
    res = srv.run(reqs)
    a = [res[r.uid]["tokens"] for r in reqs]
    eng2 = InferenceEngine(_tiny_model(), params=params,
                           quantization_setting=1)
    for r, got in zip(reqs, a):
        out = np.asarray(eng2.generate(np.asarray(r.tokens)[None],
                                       max_new_tokens=4))
        assert got == out[0, len(r.tokens):].tolist()
    srv.close()


# ------------------------------------------------------ serving resilience
# (overload policy, deadlines, typed errors, drain — docs/serving.md;
#  the chaos/fault-injection half lives in tests/test_serving_resilience.py)

def test_queue_full_is_typed(tiny, devices):
    """submit()'s backpressure raises QueueFullError (a RuntimeError
    subclass — callers can distinguish load shedding from a malformed
    request, which stays ValueError)."""
    model, params = tiny
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=1, block_size=8,
                                             max_queue=1))
    srv.submit(Request(tokens=np.arange(4), max_new_tokens=1))
    with pytest.raises(QueueFullError, match="overload=reject"):
        srv.submit(Request(tokens=np.arange(4), max_new_tokens=1))
    assert issubclass(QueueFullError, RuntimeError)  # backcompat contract
    srv.close()


def test_overload_shed_oldest_hysteresis(tiny, devices):
    """At the high watermark, shed_oldest sheds queue-HEAD requests down
    past the low watermark (one burst, hysteresis) with typed SHED
    results; everything admitted completes."""
    model, params = tiny
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=2, block_size=8,
                                             max_new_tokens=3,
                                             overload="shed_oldest",
                                             queue_high_watermark=3,
                                             queue_low_watermark=2))
    reqs = [Request(tokens=np.arange(5), seed=i, uid=i) for i in range(5)]
    for r in reqs[:3]:
        srv.submit(r)                   # queue: 0,1,2 (at the watermark)
    srv.submit(reqs[3])                 # sheds uids 0,1; queues 3
    assert [r.uid for r in srv.queue] == [2, 3]
    res = srv.run([reqs[4]])
    st = srv.stats()
    assert st["outcomes"][SHED] == 2 and st["outcomes"][OK] == 3
    for uid in (0, 1):
        assert res[uid]["outcome"] == SHED and res[uid]["tokens"] is None
    for uid in (2, 3, 4):
        assert res[uid]["outcome"] == OK and len(res[uid]["tokens"]) == 3
    srv.close()


def test_stalled_scheduler_raises_with_block_math(tiny, devices):
    """The run() livelock class: queue non-empty, zero active slots,
    admission made no progress (here: leaked blocks) — the scheduler must
    raise ServingStalledError carrying the head's block math instead of
    spinning step() hot forever."""
    model, params = tiny
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=1, block_size=8,
                                             num_blocks=4))
    leaked = srv.allocator.alloc(3)     # simulate a block leak
    assert leaked is not None
    srv.submit(Request(tokens=np.arange(4), max_new_tokens=2))
    with pytest.raises(ServingStalledError, match=(
            r"needs 1 block\(s\) over its life.*1 of them at its seat"
            r".*0 free")):
        srv.run(max_steps=10)
    srv.close()


def test_run_overrun_is_typed(tiny, devices):
    model, params = tiny
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=1, block_size=8,
                                             max_new_tokens=4))
    with pytest.raises(ServingStalledError, match="exceeded 1 steps"):
        srv.run([Request(tokens=np.arange(4), seed=0),
                 Request(tokens=np.arange(4), seed=1)], max_steps=1)
    srv.close()


def test_deadline_enforced_at_admit_and_mid_decode(tiny, devices):
    """Both halves of deadline enforcement, one engine.

    Admit half: an expired head, and a head whose remaining budget
    provably cannot cover max_new tokens at the measured step EMA, shed
    with typed DEADLINE results WITHOUT occupying a slot.  Per-step
    half: an ACTIVE slot past its deadline is evicted with its partial
    tokens, freeing the slot + blocks for work that can still meet its
    budget."""
    model, params = tiny
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=1, block_size=8))
    u_expired = srv.submit(Request(tokens=np.arange(4), max_new_tokens=2,
                                   deadline_ms=0.0))
    u_slow = srv.submit(Request(tokens=np.arange(4), max_new_tokens=8,
                                deadline_ms=50.0))
    u_ok = srv.submit(Request(tokens=np.arange(4), max_new_tokens=1))
    time.sleep(0.001)                   # the 0ms deadline is now past
    srv._step_ema_s = 1.0               # white-box: 1 s/token measured
    srv.step()          # admit: sheds both, u_ok completes at prefill
    res = srv.results
    assert res[u_expired]["outcome"] == DEADLINE
    assert res[u_slow]["outcome"] == DEADLINE   # 8 tok · 1 s >> 50 ms
    assert res[u_ok]["outcome"] == OK           # no-deadline head served
    assert srv.stats()["outcomes"][DEADLINE] == 2

    # per-step half: seat a no-deadline request, then force expiry
    uid = srv.submit(Request(tokens=np.arange(4), max_new_tokens=8,
                             seed=0))
    srv.step()                          # admit + first decode step
    assert srv._slots[0] is not None
    srv.results[uid]["deadline"] = time.monotonic() - 1.0  # force expiry
    srv.step()
    rec = srv.results[uid]
    assert rec["outcome"] == DEADLINE
    assert 2 <= len(rec["tokens"]) < 8           # partial output kept
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    st = srv.stats()
    assert st["outcomes"][DEADLINE] == 3 and "latency_ms" in st
    srv.close()


def test_drain_finishes_active_stops_admission(tiny, devices):
    """drain(): active slots run to completion, and admission is
    refused afterwards; WITHOUT a journal the queued leftover gets a
    typed SHED result (no restart will ever serve it — an eternally
    in-flight record would be a lie); close() is idempotent on top."""
    model, params = tiny
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=1, block_size=8,
                                             max_new_tokens=3))
    u_active = srv.submit(Request(tokens=np.arange(4), seed=0))
    u_queued = srv.submit(Request(tokens=np.arange(4), seed=1))
    srv.step()                          # seats u_active only (1 slot)
    summary = srv.drain(timeout_s=60)
    assert summary == {"clean": True, "active": 0, "queued": 1}
    assert srv.results[u_active]["outcome"] == OK
    assert srv.results[u_queued]["outcome"] == SHED     # typed, poppable
    assert srv.pop_result(u_queued)["tokens"] is None
    with pytest.raises(ServingError, match="draining"):
        srv.submit(Request(tokens=np.arange(4), seed=2))
    srv.close()
    srv.close()                         # idempotent


def test_capacity_report(tiny, devices):
    model, params = tiny
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=2, block_size=8,
                                             kv_bits=8))
    cap = srv.capacity()
    assert cap["allocatable_blocks"] == srv.num_blocks - 1
    assert cap["capacity_tokens"] == (srv.num_blocks - 1) * 8
    assert cap["pool_bytes"] == pk.pool_bytes(srv.pool)
    assert cap["kv_bits"] == 8
    srv.close()


# -------------------------------------- request tracing + histograms
# (docs/monitoring.md#request-tracing / #histograms; PR-12 tentpole)

def test_exact_percentiles_vs_truncated_deque_window(tiny, devices):
    """The truncated-window percentile bug, as a regression test: the
    old bounded-deque math silently dropped history under sustained
    traffic — its "p99" diverges from the exact whole-run quantile —
    while the histogram path stats() now uses stays within its 1% bound.

    Drives the REAL accounting seam (the engine's latency histogram),
    with a 10k-completion stream whose early phase is slow and late
    phase fast: a 4096-window deque forgets the slow phase entirely."""
    from collections import deque
    model, params = tiny
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=1, block_size=8))
    rng = np.random.default_rng(0)
    lat = np.concatenate([rng.uniform(900.0, 1100.0, 5000),   # slow era
                          rng.uniform(40.0, 60.0, 5000)])     # fast era
    old_window = deque(maxlen=4096)                  # the replaced math
    for v in lat:
        srv._lat_hist.add(v)
        old_window.append(v)
    exact_p99 = float(np.percentile(np.asarray(lat), 99))
    new_p99 = srv.stats()["latency_ms"]["p99"]
    old_p99 = float(np.percentile(np.asarray(old_window), 99))
    # the deque forgot the 900-1100ms era: its p99 sits in the fast band
    assert abs(old_p99 - exact_p99) / exact_p99 > 0.5
    # the histogram covers the whole run within its documented bound
    # (1% value error + quantile-definition slack on 10k samples)
    assert abs(new_p99 - exact_p99) / exact_p99 < 0.02
    assert srv._lat_hist.count == 10000              # exact count
    srv.close()


def _served_rows(tiny, reqs, **cfg):
    """Serve ``reqs`` with no monitor armed; the rows the process-wide
    recorder gained, and the results."""
    from deepspeed_tpu.monitor import spans as monspans
    model, params = tiny
    rec = monspans.recorder()
    mark = rec.open("test")           # everything recorded while serving
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=2, block_size=8,
                                             **cfg))
    assert not srv.monitor.armed
    res = srv.run(reqs)
    srv.close()
    rows = rec.since(mark)
    rec.discard(mark)
    return rows, res


def test_request_lifecycle_stamps_and_request_row(tiny, devices):
    """With a NullMonitor a served request leaves exactly one
    ``serving.request`` row, and its stamps are ordered: submit <= admit <=
    first token = the first token stamp <= ... <= done, a stamp a token."""
    reqs = [Request(tokens=np.arange(5), max_new_tokens=4, seed=0),
            Request(tokens=np.arange(9), max_new_tokens=3, seed=1,
                    do_sample=True),
            Request(tokens=np.arange(4), max_new_tokens=1, seed=2),
            Request(tokens=np.arange(6), max_new_tokens=5, seed=3)]
    rows, res = _served_rows(tiny, reqs)
    by_uid = {}
    for r in rows:
        if r.name == "serving.request":
            assert r.uid not in by_uid                  # exactly one
            by_uid[r.uid] = r
    assert set(by_uid) == {r.uid for r in reqs}
    for q in reqs:
        rec, row = res[q.uid], by_uid[q.uid]
        stamps = rec["t_tokens"]
        assert len(stamps) == len(rec["tokens"]) == q.max_new_tokens
        assert rec["t_submit"] <= rec["t_admit"] <= rec["t_first"]
        assert rec["t_first"] == stamps[0]
        assert all(a <= b for a, b in zip(stamps, stamps[1:]))
        assert stamps[-1] <= rec["t_done"]
        assert (row.t_start, row.t_end) == (rec["t_submit"], rec["t_done"])
        assert row.parent is None and row.attrs["outcome"] == OK
        assert row.attrs["prompt_len"] == len(q.tokens)
        assert row.attrs["t_admit"] == rec["t_admit"]
        assert row.attrs["t_first"] == rec["t_first"]
        assert row.attrs["t_tokens"] == stamps
    # four requests over two slots: the later ones waited for a seat
    assert res[reqs[3].uid]["t_admit"] > res[reqs[0].uid]["t_first"]


def test_unseated_request_row_has_its_terminal_time_as_admission(tiny,
                                                                 devices):
    from deepspeed_tpu.inference.serving import DEADLINE
    reqs = [Request(tokens=np.arange(5), max_new_tokens=2, seed=0,
                    deadline_ms=0.0)]
    rows, res = _served_rows(tiny, reqs)
    rec = res[reqs[0].uid]
    assert rec["outcome"] == DEADLINE and rec["tokens"] is None
    assert rec["t_admit"] == rec["t_done"] and rec["t_tokens"] is None
    (row,) = [r for r in rows if r.name == "serving.request"]
    assert row.attrs["outcome"] == DEADLINE and row.attrs["t_first"] is None
    # the poll that refused it decoded nothing: no step bracket is kept
    assert not [r for r in rows if r.name == "serving.step"]


def test_serving_step_anatomy(tiny, devices):
    """The spans of a scheduler call, recorded with no monitor: the direct
    children of a ``serving.step`` carry its number, do not overlap, and
    cover it to within its self time; a prefill hangs under ``admit`` with
    its request's uid and its dispatch and read-back as children.  A call
    carries the number of the step it books: it dispatches the next step
    first while a row lives on, sends an admission's prefill to the device
    first and books the step in flight inside the prefill's bracket, reads
    first where no row is left to live through another step, and the first
    call only seats and dispatches (docs/serving.md#one-step-in-flight)."""
    reqs = [Request(tokens=np.arange(5), max_new_tokens=4, seed=0),
            Request(tokens=np.arange(9), max_new_tokens=3, seed=1),
            Request(tokens=np.arange(7), max_new_tokens=5, seed=2)]
    rows, res = _served_rows(tiny, reqs)
    steps = [r for r in rows if r.name == "serving.step"]
    booking = [st for st in steps if st.attrs["t_tokens"] is not None]
    assert [r.step for r in booking] == list(range(1, len(booking) + 1))
    assert steps[0] not in booking and steps[0].step == 1
    assert steps[1:] == booking
    assert all(r.parent == "test" for r in steps)
    dispatching = ["serving.upload", "serving.dispatch"]
    settling = ["serving.readback", "serving.bookkeeping"]
    shapes = set()
    for st in steps:
        kids = [r for r in rows if r.parent == "serving.step"
                and r.step == st.step and st.t_start <= r.t_start
                and r.t_end <= st.t_end]
        kids.sort(key=lambda r: r.t_start)
        names = [k.name for k in kids]
        ahead = [k.attrs["ahead"] for k in kids
                 if k.name == "serving.dispatch"]
        if st not in booking:
            assert names == ["serving.admit"] + dispatching
            assert ahead == [False] and st.attrs["emitted"] == 0
        elif ahead == [True]:
            assert names == dispatching + settling
        elif ahead == [False]:
            # the third request goes in under the step in flight (which
            # carries the second's dead row): its prefill is dispatched,
            # the step read and booked inside the prefill's bracket, then
            # the state goes up and the next step follows
            assert names == ["serving.admit"] + dispatching
            inside = [r.name for r in rows if r.parent == "serving.prefill"
                      and r.step == st.step and st.t_start <= r.t_start]
            assert inside == ["serving.prefill.dispatch"] + settling \
                + ["serving.prefill.readback"]
        else:
            # the last row's last token was in flight: no row would live
            # through another step, so it is read first and nothing follows
            assert names == settling + ["serving.admit"]
        shapes.add((st in booking, tuple(ahead)))
        for a, b in zip(kids, kids[1:]):
            assert a.t_end <= b.t_start                   # no overlap
        covered = sum(k.t_end - k.t_start for k in kids)
        assert 0 <= (st.t_end - st.t_start) - covered     # self time >= 0
        assert st.attrs["n_active"] >= 1
    assert shapes == {(False, (False,)), (True, (True,)), (True, (False,)),
                      (True, ())}
    assert sum(st.attrs["emitted"] for st in steps) == sum(
        len(r["tokens"]) - 1 for r in res.values())
    prefills = [r for r in rows if r.name == "serving.prefill"]
    assert sorted(r.uid for r in prefills) == sorted(q.uid for q in reqs)
    assert [pf.attrs["under_step"] for pf in prefills] == [False, False, True]
    for pf in prefills:
        assert pf.parent == "serving.admit"
        assert pf.step == (3 if pf.attrs["under_step"] else 1)
        assert pf.attrs["prompt_len"] == len(
            next(q for q in reqs if q.uid == pf.uid).tokens)
        assert pf.attrs["bucket"] % 8 == 0
        assert res[pf.uid]["t_admit"] == pf.t_start
        kids = [r for r in rows if r.parent == "serving.prefill"
                and r.uid == pf.uid and r.name.startswith("serving.prefill.")]
        assert [k.name for k in kids] == ["serving.prefill.dispatch",
                                          "serving.prefill.readback"]
        assert pf.t_start <= kids[0].t_start
        assert kids[0].t_end <= kids[1].t_start and kids[1].t_end <= pf.t_end


def test_tracing_emits_spans_and_chrome_export(tiny, devices, tmp_path):
    """trace_sample_rate=1.0 + armed monitor: every request emits a
    schema-v2 `trace` event with monotone non-overlapping queue_wait /
    prefill / decode spans and a TTFT, and --export-trace converts the
    stream to valid Chrome trace-event JSON (one thread per request)."""
    import json as _json
    from deepspeed_tpu.monitor import Monitor, parse_line, EVENTS_FILE
    from deepspeed_tpu.monitor.__main__ import main as ds_top_main
    model, params = tiny
    run_dir = str(tmp_path / "mon")
    srv = ServingEngine(
        model=model, params=params,
        monitor=Monitor(run_dir=run_dir, role="serving"),
        config=ServingConfig(batch_slots=2, block_size=8,
                             trace_sample_rate=1.0))
    reqs = [Request(tokens=np.arange(5), max_new_tokens=4, seed=0),
            Request(tokens=np.arange(9), max_new_tokens=3, seed=1,
                    do_sample=True),
            Request(tokens=np.arange(4), max_new_tokens=2, seed=2)]
    res = srv.run(reqs)
    assert srv.stats()["traces_emitted"] == 3
    srv.close()

    events = []
    with open(os.path.join(run_dir, EVENTS_FILE)) as fh:
        for line in fh:
            if line.strip():
                events.append(parse_line(line))
    traces = {e.fields["uid"]: e for e in events if e.kind == "trace"}
    assert set(traces) == {r.uid for r in reqs}
    for r in reqs:
        f = traces[r.uid].fields
        assert f["outcome"] == OK
        assert f["generated"] == len(res[r.uid]["tokens"])
        assert f["ttft_ms"] and f["ttft_ms"] > 0
        names = [s["name"] for s in f["spans"]]
        assert names[0] == "queue_wait" and names[1] == "prefill"
        # one decode span per post-first token, stamped with its step
        decodes = [s for s in f["spans"] if s["name"] == "decode"]
        assert len(decodes) == f["generated"] - 1
        assert all("step" in s for s in decodes)
        prev_end = 0.0
        for s in f["spans"]:          # monotone, non-overlapping
            assert s["start_ms"] >= prev_end - 1e-6
            assert s["dur_ms"] >= 0.0
            prev_end = max(prev_end, s["start_ms"] + s["dur_ms"])
    # the whole-run histograms rode the same stream (drain-time flush)
    hist_names = {e.name for e in events if e.kind == "hist"}
    assert {"latency_ms", "ttft_ms", "step_wall_ms"} <= hist_names
    # exe_cost pricing for ds_explain rode it too
    assert any(e.kind == "gauge" and e.name == "exe_cost"
               for e in events)

    # --export-trace: valid Chrome trace-event JSON, loadable schema
    out = str(tmp_path / "trace.json")
    rc = ds_top_main([run_dir, "--export-trace", "--out", out])
    assert rc == 0
    with open(out) as fh:
        doc = _json.load(fh)
    assert doc["otherData"]["requests"] == 3
    xs = [ev for ev in doc["traceEvents"] if ev["ph"] == "X"]
    assert xs and all({"name", "ts", "dur", "pid", "tid"} <= set(ev)
                      for ev in xs)
    # per-thread (= per-request) events are monotone non-overlapping
    by_tid = {}
    for ev in xs:
        by_tid.setdefault(ev["tid"], []).append(ev)
    for tid, evs in by_tid.items():
        end = 0.0
        for ev in sorted(evs, key=lambda e: e["ts"]):
            assert ev["ts"] >= end - 1.0      # µs slack
            end = ev["ts"] + ev["dur"]


def test_tracing_disarmed_and_sampling_deterministic(tiny, devices):
    """Rate 0 (default) or a bus-less monitor records nothing; the
    sampling decision is a pure function of the uid."""
    model, params = tiny
    srv = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=1, block_size=8,
                                             trace_sample_rate=1.0))
    # armed rate but NullMonitor (no monitor passed, env off): no traces
    srv.run([Request(tokens=np.arange(4), max_new_tokens=2)])
    assert srv.stats()["traces_emitted"] == 0 and not srv._traces
    # deterministic sampling at a partial rate
    srv.config.trace_sample_rate = 0.25
    picks = [srv._trace_sampled(uid) for uid in range(1000)]
    assert picks == [srv._trace_sampled(uid) for uid in range(1000)]
    assert 0.15 < np.mean(picks) < 0.35
    srv.close()
    with pytest.raises(AssertionError, match="trace_sample_rate"):
        ServingEngine(model=model, params=params,
                      config=ServingConfig(trace_sample_rate=1.5))


def test_tracing_armed_step_jaxpr_identical(tiny, devices):
    """The PR-9/PR-10 equality discipline applied to tracing: arming
    trace_sample_rate=1.0 (with a live monitor) must leave the TRACED
    decode step byte-identical — tracing is host bookkeeping, never
    program content (--audit-step tracing gates the same invariant)."""
    from deepspeed_tpu.monitor import Monitor
    model, params = tiny

    def jaxpr_text(srv):
        srv._build_decode()
        return str(jax.make_jaxpr(srv._decode)(*srv._decode_args()))

    off = ServingEngine(model=model, params=params,
                        config=ServingConfig(batch_slots=2, block_size=8))
    off_jaxpr = jaxpr_text(off)
    off.close()
    ring_mon = Monitor(run_dir=None, sinks=("ring",))
    on = ServingEngine(model=model, params=params, monitor=ring_mon,
                       config=ServingConfig(batch_slots=2, block_size=8,
                                            trace_sample_rate=1.0))
    assert jaxpr_text(on) == off_jaxpr
    on.close()
    # ... and to the recorder's one jax.monitoring listener, which every
    # process has: the step traced without it is the same step
    from jax._src import monitoring as jax_monitoring
    from deepspeed_tpu.monitor import spans as monspans
    jax_monitoring.unregister_event_duration_listener(
        monspans._on_jax_duration)
    try:
        bare = ServingEngine(model=model, params=params,
                             config=ServingConfig(batch_slots=2,
                                                  block_size=8))
        assert jaxpr_text(bare) == off_jaxpr
        bare.close()
    finally:
        jax.monitoring.register_event_duration_secs_listener(
            monspans._on_jax_duration)


# ------------------------------------------------- one token a row a step
def _mixed_reqs():
    """Mixed traffic for the identity test: prompts that repeat and
    prompts that do not, greedy AND sampled decoding, lengths that end
    at different steps, 4 requests over 2 slots (slot churn)."""
    rng = np.random.default_rng(9)
    reqs = []
    for i in range(4):
        if i % 2 == 0:
            toks = np.tile(rng.integers(0, 128, (3 + i,)), 3)
        else:
            toks = rng.integers(0, 128, (5 + i,))
        reqs.append(Request(tokens=toks, max_new_tokens=3 + i,
                            seed=40 + i, uid=i, do_sample=(i % 2 == 1),
                            temperature=0.7))
    return reqs


def test_token_identity_permuted_arrivals(tiny, devices):
    """Each uid's stream is a function of the request alone: permuted
    arrival orders (so other slots, other neighbours, other steps at
    which a slot is re-seated) give every uid the tokens it gets when it
    is served with nothing beside it, greedy and sampled, and every
    stream has the length it asked for."""
    model, params = tiny

    def run(order, slots=2):
        srv = ServingEngine(
            model=model, params=params,
            config=ServingConfig(batch_slots=slots, block_size=8,
                                 max_new_tokens=8, top_k=8))
        reqs = _mixed_reqs()
        out = srv.run([reqs[j] for j in order])
        assert srv.allocator.free_blocks == srv.num_blocks - 1
        srv.close()
        return {u: r["tokens"] for u, r in out.items()}

    alone = {}
    for j in range(4):
        alone.update(run([j], slots=1))
    assert [len(alone[u]) for u in range(4)] == [3, 4, 5, 6]
    for order in ([0, 1, 2, 3], [2, 0, 3, 1], [3, 2, 1, 0]):
        assert run(order) == alone, f"arrival order {order} moved a stream"


def test_a_retired_key_is_refused_by_name(devices):
    """``serving.speculative`` went with PR 45: a configuration that still
    carries it fails at construction and the error names the key, as for
    any key the block does not have."""
    for value in ({"k": 2}, True):
        with pytest.raises(ValueError, match="speculative"):
            ServingConfig.from_dict({"batch_slots": 2, "speculative": value})
    with pytest.raises(TypeError, match="speculative"):
        ServingConfig(speculative={"k": 2})


# ------------------------------------- a serving cell's checks, tiny size
@pytest.mark.parametrize("kv_bits", [16, 8])
def test_served_streams_and_decode_logits_match_oracles(kv_bits, devices):
    """What a short run of a serving cell checks on the chip, held here
    at tiny size through the public API: a bfloat16 model behind
    ``ds.init_inference`` + ``ServingEngine(engine=...)`` with more
    requests than slots finishes every request and recycles every block;
    on a 16-bit pool its greedy streams equal sequential ``generate``
    token for token.  Then a decode step over blocks that
    ``prefill_paged`` wrote (prompt lengths off the block grid): the
    kernel's logits equal the ``gather_kv`` + ``_masked_attend`` oracle's
    on the same pool bit for bit, 16-bit and int8 (the interpreted kernel
    is exact; the int8 pool itself is lossy, so its streams are held to
    completion, not to ``generate``)."""
    import dataclasses
    import deepspeed_tpu as ds
    model = _tiny_model(jnp.bfloat16)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params=params, dtype=jnp.bfloat16)
    assert eng.mesh.size == 1          # one device, not the whole host
    rng = np.random.default_rng(11)
    bs, new = 8, 4
    reqs = [Request(tokens=rng.integers(0, 128, (n,)).astype(np.int32),
                    max_new_tokens=new, seed=i)
            for i, n in enumerate((5, 9, 13, 7))]
    srv = ServingEngine(engine=eng, config={
        "batch_slots": 2, "block_size": bs, "kv_bits": kv_bits})
    res = srv.run(reqs)
    assert srv.stats()["completed"] == len(reqs)
    assert all(r["outcome"] == OK and len(r["tokens"]) == new
               for r in res.values())
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    if kv_bits == 16:
        for r in reqs:
            full = np.asarray(eng.generate(np.asarray(r.tokens)[None],
                                           max_new_tokens=new))
            assert res[r.uid]["tokens"] == full[0, len(r.tokens):].tolist()
    srv.close()

    # ---- one decode step, kernel vs oracle, over prefilled blocks
    c = model.config
    oracle = GPT2(dataclasses.replace(c, paged_attention_impl="gather"),
                  dtype=jnp.bfloat16)
    assert model.paged_attention_impl() == "kernel"
    nb_max = c.max_seq // bs
    pool = model.init_serving_state(len(reqs), 1 + len(reqs) * 2, bs,
                                    kv_bits=kv_bits, quant_block=8)
    tables = np.zeros((len(reqs), nb_max), np.int32)
    cur = []
    prefill = jax.jit(model.prefill_paged)
    for b, r in enumerate(reqs):
        blocks = np.asarray([1 + 2 * b, 2 + 2 * b], np.int32)
        tables[b, :2] = blocks
        toks = np.zeros((1, 2 * bs), np.int32)
        toks[0, :len(r.tokens)] = r.tokens
        row, pool = prefill(params, jnp.asarray(toks), pool,
                            jnp.asarray(blocks), b, len(r.tokens))
        cur.append(int(np.argmax(np.asarray(row)[0])))
    args = (params, jnp.asarray(cur, jnp.int32), pool, jnp.asarray(tables),
            jnp.asarray([len(r.tokens) for r in reqs], jnp.int32))
    got = np.asarray(jax.jit(model.decode_step_paged)(*args)[0])
    want = np.asarray(jax.jit(oracle.decode_step_paged)(*args)[0])
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)
