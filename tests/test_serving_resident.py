"""The decode step's device-resident slot state (docs/serving.md#step-anatomy).

The numpy mirrors stay the scheduler's truth; the compiled step keeps its own
copy of them on the device, advances it in-graph, and the host re-sends it,
as one packed buffer, only after a slot was seated, cleared or ingested.  While
no slot changes one decode step stays dispatched and unread
(docs/serving.md#one-step-in-flight): the device's copy is then the mirrors
advanced by that one step.  These tests hold the copy to the mirrors after
every step, the token streams to a run that settles and uploads on every step,
and the counters and the executable count to what the benchmark's window
relies on."""

import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
from deepspeed_tpu.inference import (InferenceEngine, ServingEngine,
                                     ServingConfig, Request, paged_kv as pk,
                                     OK, DEADLINE, POISONED)

MIRRORS = ("_tables", "_lengths", "_toks", "_seeds", "_ngen", "_temps",
           "_flags")
# "growth": blocks of 4 tokens over a pool that binds, so rows are granted a
# block every fourth step, some with a step in flight, and the head waits
VARIANTS = {"plain": {}, "prefix_sharing": {"prefix_cache": True},
            "growth": {"block_size": 4, "num_blocks": 25}}


@pytest.fixture(scope="module")
def tiny():
    cfg = GPT2Config(vocab_size=128, max_seq=64, n_embd=32, n_layer=2,
                     n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    model = GPT2(cfg, dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0))


def _server(tiny, **cfg):
    model, params = tiny
    return ServingEngine(model=model, params=params, config=ServingConfig(
        **{"batch_slots": 3, "block_size": 8, **cfg}))


def _mixed_requests():
    """Lengths and budgets chosen so that slots turn over at different
    steps, with runs of steps in between in which none does; the prompts
    share a 16-token preamble, so with the prefix cache armed the later
    ones ingest their tail through the decode step."""
    pre = np.arange(16) % 7
    rng = np.random.default_rng(3)
    return [Request(tokens=np.concatenate([pre, rng.integers(0, 128, n)]),
                    max_new_tokens=new, seed=i, do_sample=bool(i % 2),
                    temperature=0.8)
            for i, (n, new) in enumerate(
                [(3, 12), (9, 5), (2, 9), (12, 7), (5, 1), (7, 10)])]


def _mirrors_after_the_unread_step(srv):
    """The mirrors as ``bookkeeping`` will leave them once it has booked the
    step that is dispatched and unread (nothing to advance if none is): a
    seated row's length and index go up by one and its token is the one in
    the buffer on its way down."""
    want = {name: getattr(srv, name).copy() for name in MIRRORS}
    if srv._unread is not None:
        seated = srv._tables[:, 0] != pk.SCRATCH_BLOCK
        want["_lengths"] += seated
        want["_ngen"] += seated
        want["_toks"] = np.where(seated, np.asarray(srv._unread.read)[:, 0],
                                 want["_toks"]).astype(np.int32)
    return want


def _assert_resident_equals_mirrors(srv, arrays, mirrors=None):
    for name, got in zip(MIRRORS, arrays):
        want = getattr(srv, name) if mirrors is None else mirrors[name]
        got = np.asarray(got)
        assert got.dtype == want.dtype and got.shape == want.shape, name
        np.testing.assert_array_equal(got, want, err_msg=name)


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_resident_state_equals_mirrors_after_every_step(
        tiny, fault_harness, devices, variant):
    """(a) Admissions, finishes at eos and at ``max_new``, a poisoned
    request and a deadline eviction: after every ``step()`` the state the
    NEXT dispatch would run on equals the mirrors advanced by the one
    unread step, array for array — read as it stands on the device where no
    slot changed (the in-graph advance against ``bookkeeping``'s); and
    ``_decode_args()`` from outside settles that step and returns operands
    equal to the mirrors (every third step, so that steps run ahead too)."""
    ref = _server(tiny, **VARIANTS[variant])
    clean = ref.run(_mixed_requests())
    ref.close()
    eos = clean[0]["tokens"][6]          # request 0 now ends at eos, early
    fault_harness.configure(logit_nan=2)
    srv = _server(tiny, eos_token_id=int(eos), **VARIANTS[variant])
    uids = [srv.submit(r) for r in _mixed_requests()]
    late = srv.submit(Request(tokens=np.arange(6), max_new_tokens=30,
                              seed=9))
    untouched = calls = 0
    while srv.step():
        calls += 1
        if not srv._state_dirty:
            untouched += 1
            _assert_resident_equals_mirrors(
                srv, srv._resident, _mirrors_after_the_unread_step(srv))
        if calls % 3 == 0:
            args = srv._decode_args()
            assert srv._unread is None
            _assert_resident_equals_mirrors(srv, args[2:])
        rec = srv.results[late]
        if rec["t_first"] is not None and rec["deadline"] is None \
                and len(rec["t_tokens"]) >= 3:
            rec["deadline"] = time.monotonic() - 1.0     # force expiry
    assert untouched >= 5               # the in-graph advance was compared
    assert srv.stats()["steps_ahead"] > 0     # ...with a step in flight too
    # ...and, without the radix cache, tables that grew on the device
    assert (srv.stats()["blocks_grown_total"] > 0) == (
        variant != "prefix_sharing")
    out = {u: srv.results[u]["outcome"] for u in uids + [late]}
    assert out[2] == POISONED and out[late] == DEADLINE
    assert all(out[u] == OK for u in uids if u != 2)
    assert len(srv.results[0]["tokens"]) < len(clean[0]["tokens"])
    assert srv.results[0]["tokens"][-1] == eos
    # nothing leaked: what is still checked out is what the radix cache holds
    cached = srv._prefix_index.cached_blocks if srv._prefix_index else 0
    assert srv.allocator.used_blocks == cached
    srv.close()


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_streams_identical_to_an_upload_on_every_step(tiny, devices, variant):
    """(b) Reusing resident state and keeping a step in flight change no
    token and no outcome: the same requests through an engine whose state
    the TEST marks dirty before every step (so each step is settled before
    the next is dispatched and each uploads, as every step did before this
    state lived on the device) give the same ``results``."""
    def serve(force):
        srv = _server(tiny, **VARIANTS[variant])
        for r in _mixed_requests():
            srv.submit(r)
        while True:
            if force:
                srv._state_dirty = True
            if not srv.step():
                break
        st = srv.stats()
        res = {u: (r["outcome"], r["tokens"]) for u, r in srv.results.items()}
        srv.close()
        return res, st

    got, st = serve(force=False)
    want, st_forced = serve(force=True)
    assert got == want
    assert all(o == OK for o, _ in got.values())
    assert st_forced["state_reused_steps"] == st_forced["steps_ahead"] == 0
    assert st_forced["state_uploads"] == st_forced["decode_steps"]
    assert st["steps_ahead"] > 0
    if variant == "prefix_sharing":
        assert st["prefix_cache"]["requests_hit"] >= 1   # tails were ingested
    assert 0 < st["state_reused_steps"] < st["decode_steps"]


def test_counters_reused_steps_and_uploads(tiny, devices):
    """(c) N steps in which no slot changes record N reused steps, N steps
    ahead and no upload; a seat goes in under the step in flight and records
    exactly one upload; a finish is seen one dispatch late (the step after it
    ran ahead with the dead row) and the upload follows in the next call; the
    ``serving.upload`` and ``serving.dispatch`` spans say which it was."""
    from deepspeed_tpu.monitor import spans as monspans
    srv = _server(tiny)
    srv.submit(Request(tokens=np.arange(5), max_new_tokens=12, seed=0))
    assert srv.step()                    # seat + the first step, in flight
    assert srv.stats()["state_uploads"] == 1
    assert srv.stats()["steps_ahead"] == 0
    srv.reset_stats()
    rec = monspans.recorder()
    mark = rec.open("test")
    for _ in range(6):
        assert srv.step()                # dispatch the next, book the last
    st = srv.stats()
    assert (st["decode_steps"], st["state_reused_steps"], st["steps_ahead"],
            st["state_uploads"]) == (6, 6, 6, 0)
    srv.submit(Request(tokens=np.arange(7), max_new_tokens=2, seed=1))
    assert srv.step()                    # a seat under the step in flight:
    st = srv.stats()                     # prefill, book, one upload, dispatch
    assert (st["state_uploads"], st["admits_under_step"]) == (1, 1)
    assert srv.step()                    # its last token is in flight: the
    assert srv.results[1]["outcome"] == OK           # next step runs ahead
    st = srv.stats()                                 # with the dead row
    assert (st["state_uploads"], st["state_reused_steps"],
            st["steps_ahead"]) == (1, 7, 7)
    assert srv.step()                    # the cleared row goes up
    st = srv.stats()
    assert (st["state_uploads"], st["steps_ahead"]) == (2, 7)
    rows = rec.since(mark)
    rec.discard(mark)
    assert [r.attrs["uploaded"] for r in rows if r.name == "serving.upload"] \
        == [False] * 6 + [True, False, True]
    assert [r.attrs["ahead"] for r in rows if r.name == "serving.dispatch"] \
        == [True] * 6 + [False, True, False]
    assert [r.attrs["under_step"] for r in rows
            if r.name == "serving.prefill"] == [True]
    srv.run()
    assert srv._unread is None
    assert srv.stats()["decode_steps"] == 11         # 12 tokens, one prefilled
    srv.close()


def test_decode_args_between_steps_changes_nothing(tiny, devices):
    """(d) ``_decode_args()`` is the nine live operands at any time: called
    twice between steps (the benchmark's ``check()`` does) it settles the
    unread step, returns equal operands, equal to the mirrors, and no later
    token moves."""
    def serve(peek):
        srv = _server(tiny)
        for r in _mixed_requests():
            srv.submit(r)
        while srv.step():
            if peek:
                one, two = srv._decode_args(), srv._decode_args()
                assert len(one) == len(two) == 9 and srv._unread is None
                assert one[0] is srv.engine.params and one[1] is srv.pool
                for a, b in zip(one[2:], two[2:]):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
                _assert_resident_equals_mirrors(srv, one[2:])
        res = {u: r["tokens"] for u, r in srv.results.items()}
        srv.close()
        return res

    assert serve(peek=True) == serve(peek=False)


def test_one_decode_executable_for_clean_and_dirty_steps(tiny, devices,
                                                         tmp_path):
    """(e) With a compile cache attached the decode executable is acquired
    once: a warm-up in which every step seats or frees a slot (as the
    benchmark's) leaves nothing for the first clean step to build."""
    model, params = tiny
    eng = InferenceEngine(model=model, params=params,
                          compile_cache=str(tmp_path / "cc"))
    srv = ServingEngine(engine=eng, config=ServingConfig(batch_slots=3,
                                                         block_size=8))
    srv.submit(Request(tokens=np.arange(5), max_new_tokens=2, seed=0))
    while srv.step():
        pass
    st = srv.stats()
    assert st["decode_steps"] == st["state_uploads"] == 1
    assert st["state_reused_steps"] == 0
    srv.reset_stats()
    rep = srv.compile_report()
    acquired = rep["hits"] + rep["misses"]
    srv.submit(Request(tokens=np.arange(5), max_new_tokens=9, seed=1))
    srv.submit(Request(tokens=np.arange(5), max_new_tokens=4, seed=2,
                       do_sample=True))
    while srv.step():
        pass
    st = srv.stats()
    assert st["state_reused_steps"] >= 5 and st["state_uploads"] >= 2
    assert len(srv._decode.keys()) == 1
    assert len(srv._unpack.keys()) == 1
    rep = srv.compile_report()
    assert rep["hits"] + rep["misses"] == acquired
    srv.close()


@pytest.mark.parametrize("die_at", [1, 4])
def test_step_that_dies_after_dispatch_is_rerun_from_the_mirrors(
        tiny, devices, die_at):
    """A step that raises between its dispatch and its bookkeeping has
    advanced the device's copy and not the mirrors: the mirrors are the
    truth, so the next step uploads them and the streams do not move.  The
    first call dies with nothing else in flight; the fourth with the step
    before it still unread, which is dropped with it and run again."""
    def serve(die_at):
        srv = _server(tiny)
        for r in _mixed_requests()[:3]:
            srv.submit(r)
        n = 0
        while True:
            n += 1
            if n == die_at:
                def boom():
                    raise RuntimeError("between dispatch and bookkeeping")
                srv._kv_warm_pending, srv._warm_restore_path = True, boom
                # a clean step dies, dispatched ahead of an unread one
                assert (die_at == 1) == srv._state_dirty \
                    == (srv._unread is None)
                with pytest.raises(RuntimeError, match="between"):
                    srv.step()
                assert srv._state_dirty and srv._unread is None
                continue
            if not srv.step():
                break
        res = {u: (r["outcome"], r["tokens"]) for u, r in srv.results.items()}
        srv.close()
        return res

    assert serve(die_at) == serve(None)
