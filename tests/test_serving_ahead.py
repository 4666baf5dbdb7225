"""One decode step always in flight (docs/serving.md#one-step-in-flight).

While no slot changes, ``ServingEngine.step()`` dispatches decode step N+1 on
the device's own advanced state before it reads step N; a finish of any kind
is seen one dispatch late, and an admission's prefill goes to the device
behind the step in flight.  These tests hold the token streams, the outcomes
and the block accounting to a server that is made to settle every step (the
order every step had before), on GPT-2, Jamba and a folded cache; the one row
a late-seen finish computes in vain to its own blocks; a slot re-seated
behind the step that carried its old row to the new stream's own state; the
configurations that must never run ahead to ``steps_ahead == 0``; and every
way of ending a run to "nothing unread, no token missing"."""

import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models import build
from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
from deepspeed_tpu.inference import (ServingEngine, ServingConfig, Request,
                                     paged_kv as pk, OK, DEADLINE, POISONED)
from deepspeed_tpu.inference.serving import TRANSFERRED

JAMBA = {"vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 4,
         "num_attention_heads": 4, "num_key_value_heads": 1,
         "intermediate_size": 128, "attn_layer_period": 4,
         "attn_layer_offset": 1, "mamba_d_state": 8, "mamba_d_conv": 4,
         "mamba_expand": 2, "mamba_dt_rank": 4, "rms_norm_eps": 1e-6,
         "max_position_embeddings": 64}


@pytest.fixture(scope="module")
def families():
    cfg = GPT2Config(vocab_size=128, max_seq=64, n_embd=32, n_layer=2,
                     n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    gpt2 = GPT2(cfg, dtype=jnp.float32)
    jamba = build("jamba-tiny", dtype=jnp.float32, **JAMBA)
    # a cache that folds: windows of 16 tokens, 4 summary rows a window
    folded = build("evabyte-tiny", dtype=jnp.float32)
    return {"gpt2": (gpt2, gpt2.init(jax.random.PRNGKey(0))),
            "jamba": (jamba, jamba.init(jax.random.PRNGKey(3))),
            "folded": (folded, jax.jit(folded.init)(jax.random.PRNGKey(7)))}


def _server(model_params, **cfg):
    model, params = model_params
    if hasattr(model, "cache_fold"):
        cfg = {"block_size": 2, **cfg}   # whole blocks of summary rows
    return ServingEngine(model=model, params=params, dtype=jnp.float32,
                         config=ServingConfig(
        **{"batch_slots": 3, "block_size": 8, **cfg}))


def _step(srv, settle):
    """One scheduler call; with ``settle`` the step it dispatched is read
    and booked before the next call, as every step was before one could
    stay in flight."""
    more = srv.step()
    if settle:
        srv._settle()
    return more


def _mixed_requests(sampled):
    """More requests than slots, with lengths and budgets that turn the
    slots over at different steps and leave runs of steps in between."""
    rng = np.random.default_rng(3)
    return [Request(tokens=rng.integers(0, 128, n), max_new_tokens=new,
                    seed=i, do_sample=sampled, temperature=0.8)
            for i, (n, new) in enumerate(
                [(19, 12), (25, 5), (18, 9), (28, 7), (21, 1), (23, 10)])]


# ----------------------------------------------- (1) the streams do not move
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("family", ["gpt2", "jamba"])
def test_streams_identical_to_settling_every_step(families, fault_harness,
                                                  devices, family, sampled):
    """Admissions mid-run (from the queue and from a late arrival), a finish
    at eos and at ``max_new``, a poisoned request and a deadline eviction:
    the same ``results`` and the same free blocks whether a step stays in
    flight or every step is settled at once."""
    ref = _server(families[family])
    clean = ref.run(_mixed_requests(sampled))
    ref.close()
    eos = clean[0]["tokens"][6]          # request 0 now ends at eos, early

    def serve(settle):
        fault_harness.configure(logit_nan=2)
        srv = _server(families[family], eos_token_id=int(eos))
        uids = [srv.submit(r) for r in _mixed_requests(sampled)]
        late = victim = None
        calls = 0
        while _step(srv, settle):
            calls += 1
            if calls == 4:               # an arrival while slots are busy
                late = srv.submit(Request(tokens=np.arange(11),
                                          max_new_tokens=6, seed=21,
                                          do_sample=sampled))
                victim = srv.submit(Request(tokens=np.arange(6),
                                            max_new_tokens=30, seed=9))
            rec = srv.results.get(victim)
            if rec is not None and rec["t_first"] is not None \
                    and rec["deadline"] is None and len(rec["t_tokens"]) >= 3:
                rec["deadline"] = time.monotonic() - 1.0     # force expiry
        assert srv._unread is None and srv.stats()["pending"] == 0
        st = srv.stats()
        res = {u: (r["outcome"], r["tokens"]) for u, r in srv.results.items()}
        free = srv.allocator.free_blocks, srv.num_blocks - 1
        srv.close()
        fault_harness.reset()
        return res, st, free, (uids, late, victim)

    got, st, free, (uids, late, victim) = serve(settle=False)
    want, st_settled, free_settled, _ = serve(settle=True)
    assert got == want
    assert free == free_settled and free[0] == free[1]
    assert st["steps_ahead"] > 0 and st_settled["steps_ahead"] == 0
    assert st["generated_tokens"] == st_settled["generated_tokens"]
    # every kind of finish was there to be seen one dispatch late, or not
    assert got[2][0] == POISONED and got[victim][0] == DEADLINE
    assert all(got[u][0] == OK for u in uids + [late] if u != 2)
    assert got[0][1][-1] == eos and len(got[0][1]) < len(clean[0]["tokens"])
    assert [len(got[u][1]) for u in uids[3:]] == [7, 1, 10]     # max_new
    assert 3 <= len(got[victim][1]) < 30


# ------------------- (1b) admissions behind the step in flight move no stream
def _backlog(family):
    """Three times as many requests as slots, every one ending at its
    ``max_new_tokens`` and no two of a wave at the same step: every admission
    after the first wave follows a finish the host could have counted."""
    rng = np.random.default_rng(11)
    shapes = [(19, 12), (25, 5), (18, 9), (28, 7), (9, 3), (23, 10),
              (12, 6), (30, 4), (7, 11)]
    if family == "folded":               # prompts and answers that cross
        shapes = [(5, 30), (15, 9), (33, 21), (18, 6), (47, 12), (16, 20),
                  (7, 14), (40, 9), (31, 18)]       # windows of 16 tokens
    return [Request(tokens=rng.integers(0, 128, n), max_new_tokens=new,
                    seed=i, do_sample=family != "folded", temperature=0.8)
            for i, (n, new) in enumerate(shapes)]


@pytest.mark.parametrize("family", ["gpt2", "jamba", "folded"])
def test_admissions_behind_a_counted_finish_move_no_stream(families, devices,
                                                           family):
    """The order that took the place of "a foreseen finish settles first" and
    "an admission settles first": the step after a row's ``max_new_tokens``-th
    token is dispatched with the row still seated, and the head's prefill goes
    to the device behind it.  Streams, outcomes and free blocks are those of
    a server made to settle every step; Jamba's prefill writes the slot's
    recurrent rows whole behind the dead row's advance of them; a step that
    ends a row's window of the folded cache is settled and folded first."""
    from deepspeed_tpu.monitor import spans as monspans

    def serve(settle):
        srv = _server(families[family], sanitize=True)
        mark = monspans.recorder().open("test")
        for r in _backlog(family):
            srv.submit(r)
        while _step(srv, settle):
            pass
        rows = monspans.recorder().since(mark)
        monspans.recorder().discard(mark)
        assert srv._unread is None
        st = srv.stats()
        res = {u: (r["outcome"], r["tokens"]) for u, r in srv.results.items()}
        free = srv.allocator.free_blocks, srv.num_blocks - 1
        srv.close()                      # the sanitizer's leak check
        under = [r.attrs["under_step"] for r in rows
                 if r.name == "serving.prefill"]
        return res, st, free, under

    got, st, free, under = serve(settle=False)
    want, st_settled, free_settled, under_settled = serve(settle=True)
    assert got == want and all(o == OK for o, _ in got.values())
    assert [len(t) for _, t in got.values()] == [
        r.max_new_tokens for r in _backlog(family)]
    assert free == free_settled and free[0] == free[1]
    # the admissions after the first wave went in under a step, but for a
    # step that had to be settled for its own sake (a window's end, a step no
    # row was left to live through) and a second seat of the same call (its
    # slot was freed by the very step the first prefill went under)
    assert sum(under) == st["admits_under_step"] >= 3
    assert len(under) == 9 and not any(under[:3])
    assert st_settled["admits_under_step"] == st_settled["steps_ahead"] == 0
    assert not any(under_settled)
    # the dead row-steps: more steps ran ahead than a server that settles a
    # counted finish first could have run, and no token came of them
    assert st["steps_ahead"] > 0
    assert st["generated_tokens"] == st_settled["generated_tokens"]
    if family == "folded":
        assert st["windows_folded_total"] \
            == st_settled["windows_folded_total"] > 0


def test_a_slot_reseated_behind_its_old_rows_step_keeps_its_own_state(
        families, devices):
    """A slot is re-seated while the step that carried its old row is still
    in flight: the prefill is dispatched behind that step, the step is booked
    before the seat, and the new stream never receives the old row's sample,
    its length or the block the dead row-step was granted."""
    def requests():
        return [Request(tokens=np.arange(9) % 7, max_new_tokens=8, seed=1,
                        do_sample=True),             # the old row: 9 + 8 = 17
                Request(tokens=np.arange(21) % 11, max_new_tokens=30, seed=2),
                Request(tokens=np.arange(13) % 5 + 1, max_new_tokens=6,
                        seed=3, do_sample=True)]     # the new tenant

    solo = _server(families["gpt2"], batch_slots=1)
    alone = [solo.run([r])[i]["tokens"] for i, r in enumerate(requests())]
    solo.close()
    srv = _server(families["gpt2"], batch_slots=2, sanitize=True)
    old, other, new = (srv.submit(r) for r in requests())
    seen = {}
    book, seat = srv._book, srv._seat

    def watch_book(step):
        if srv._slots[0] is None and 0 in step.active and "dead" not in seen:
            # the step that carried the old row's dead row-step: booked
            # while the slot is empty, inside the new tenant's `_start`
            seen["dead"] = (srv.queue[0].uid if srv.queue else None,
                            srv.results[new]["t_admit"] is not None)
        return book(step)

    def watch_seat(slot, req, blocks, *a, **kw):
        if req.uid == new:
            seen["seat"] = (slot, srv._unread is None, "dead" in seen,
                            list(blocks))
        return seat(slot, req, blocks, *a, **kw)
    srv._book, srv._seat = watch_book, watch_seat
    old_blocks = None
    while srv.results[new]["t_first"] is None:
        if srv._slots[0] is not None and srv._slots[0].req.uid == old:
            old_blocks = srv._slots[0].blocks        # the list: it grows
        assert srv.step()
    # the old row's last step opened its third block for the dead write at
    # position 16, which went home with the others before the new seat
    assert len(old_blocks) == 3 == pk.blocks_needed(9 + 8, 8)
    head, prefilling = seen["dead"]
    assert head is None and prefilling   # booked inside the new tenant's start
    slot, settled, after_dead, seat_blocks = seen["seat"]
    assert (slot, settled, after_dead) == (0, True, True)
    s = srv._slots[0]
    assert s.req.uid == new and s.out_tokens == alone[2][:1]
    assert s.blocks == seat_blocks and len(seat_blocks) \
        == pk.blocks_needed(13 + 1, 8) == int(srv._held[0])
    # the mirrors are the seat's own: one prompt, one token, its own first
    # sample (the dead row's is discarded), however the old row stood
    assert (int(srv._lengths[0]), int(srv._ngen[0]), int(srv._toks[0])) \
        == (13, 1, alone[2][0])
    assert srv.stats()["admits_under_step"] == 1
    srv.run()
    assert [srv.results[u]["tokens"] for u in (old, other, new)] == alone
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    srv.close()


# ------------------------------------- (2) the row computed in vain is harmless
def _touched_blocks(a, b):
    """Blocks in which the K/V of two pools differ anywhere."""
    diff = np.zeros(np.asarray(a["k"]).shape[1], bool)
    for name in ("k", "v"):
        x, y = np.asarray(a[name]), np.asarray(b[name])
        diff |= (x != y).any(axis=tuple(i for i in range(x.ndim) if i != 1))
    return set(np.flatnonzero(diff))


def test_dead_row_at_the_context_limit_writes_only_its_own_blocks(families,
                                                                  devices):
    """A request whose prompt plus ``max_new_tokens`` fills its last block
    up to the model's positions ends at eos inside that block, with the next
    step already dispatched: the dead row's write stays inside the request's
    own blocks (or the scratch block), its neighbour's stream does not move,
    and a request seated into the freed blocks right after yields the tokens
    it yields alone."""
    def request_a():
        return Request(tokens=np.arange(40) % 17, max_new_tokens=24, seed=5,
                       do_sample=True, temperature=1.2)

    def request_b():
        return Request(tokens=np.arange(10) + 3, max_new_tokens=40, seed=6)

    def request_c():
        return Request(tokens=(np.arange(37) * 5) % 23, max_new_tokens=8,
                       seed=7, do_sample=True)

    def server(**cfg):
        return _server(families["gpt2"], batch_slots=2, **cfg)

    solo = server()
    alone = solo.run([request_a()])[0]["tokens"]
    solo.close()
    solo = server()
    c_alone = solo.run([request_c()])[0]["tokens"]
    solo.close()
    # the latest token the host cannot foresee as the last, first seen there
    j = next(j for j in range(22, 15, -1) if alone[j] not in alone[:j])
    eos = int(alone[j])

    def serve(settle):
        srv = server(eos_token_id=eos)
        a, b = srv.submit(request_a()), srv.submit(request_b())
        blocks_a = None
        while srv.results[a]["outcome"] is None:
            assert _step(srv, settle)
            if blocks_a is None:
                # the slots' own lists: each grows a block at a time
                blocks_a, blocks_b = srv._slots[0].blocks, srv._slots[1].blocks
        if settle:
            _step(srv, settle)           # the neighbour's step N+1, too
        else:
            assert srv._unread is not None       # N+1 carries the dead row
            srv._settle()
        assert len(srv.results[b]["t_tokens"]) == j + 2    # 1 + steps N+1
        pool = {k: np.asarray(v) for k, v in srv.pool.items()}
        c = srv.submit(request_c())
        while srv.step():
            if srv._slots[0] is not None and srv._slots[0].req.uid == c:
                blocks_c = srv._slots[0].blocks
        res = {u: r["tokens"] for u, r in srv.results.items()}
        free = srv.allocator.free_blocks
        srv.close()
        return res, pool, free, (a, b, c), blocks_a, blocks_c, blocks_b

    got, pool, free, (a, b, c), blocks_a, blocks_c, blocks_b = serve(
        settle=False)
    want, pool_settled, free_settled, _, _, _, b_settled = serve(settle=True)
    assert len(blocks_a) * 8 == 64 == families["gpt2"][0].config.max_seq
    assert got[a] == alone[:j + 1] and (40 + j) // 8 == 7    # the last block
    # the neighbour's own newest block may be another in the two runs: one
    # granted before the dead row's blocks came home, the other after
    touched = _touched_blocks(pool, pool_settled) - (
        set(blocks_b) ^ set(b_settled))
    assert touched and touched <= set(blocks_a) | {pk.SCRATCH_BLOCK}
    assert set(blocks_c) & set(blocks_a)         # seated into freed blocks
    assert got == want and got[c] == c_alone
    assert free == free_settled


@pytest.mark.parametrize("past_the_edge", [0, 1],
                         ids=["fills_its_last_block", "opens_its_last_block"])
def test_dead_row_of_a_counted_finish_writes_inside_its_planned_blocks(
        families, devices, past_the_edge):
    """A row's step after its ``max_new_tokens``-th token is dispatched with
    the row still seated and writes position ``prompt + max_new - 1``: the
    last position of its last block where ``prompt + max_new`` is a multiple
    of the block, and the FIRST of a block no live token ever reaches where
    it is one more, which that dispatch is granted (`_grant_blocks`' assertion
    and the sanitizer armed, the pool as small as the admission rule lets
    both streams be seated: the timeline planned for that block).  What the
    pool holds differs from a settled server's only inside the row's own
    blocks, the neighbour's stream does not move, and every block comes
    home."""
    new = 14 + past_the_edge             # 10 + 14 = 24 = three blocks of 8

    def request_a():
        return Request(tokens=np.arange(10) % 7, max_new_tokens=new, seed=5,
                       do_sample=True, temperature=1.2)

    def request_b():
        return Request(tokens=np.arange(13) + 3, max_new_tokens=30, seed=6)

    life = pk.blocks_needed(10 + new, 8)
    assert life == 3 + past_the_edge

    def serve(settle):
        # the fewest blocks both lives fit in: a grant outside the plan
        # would find the allocator empty
        srv = _server(families["gpt2"], batch_slots=2, sanitize=True,
                      num_blocks=1 + life + pk.blocks_needed(13 + 30, 8))
        a, b = srv.submit(request_a()), srv.submit(request_b())
        blocks_a = None
        while srv.results[a]["outcome"] is None:
            assert _step(srv, settle)
            if blocks_a is None:
                blocks_a, blocks_b = srv._slots[0].blocks, srv._slots[1].blocks
        if settle:
            _step(srv, settle)           # the neighbour's step N+1, too
        else:
            assert srv._unread is not None       # N+1 carries the dead row
            assert srv._slots[0] is None and 0 in srv._unread.active
            srv._settle()
        assert len(srv.results[b]["t_tokens"]) == new + 1
        pool = {k: np.asarray(v) for k, v in srv.pool.items()}
        grown = srv.stats()["blocks_grown_total"]
        srv.run()
        res = {u: (r["outcome"], r["tokens"]) for u, r in srv.results.items()}
        free = srv.allocator.free_blocks, srv.num_blocks - 1
        srv.close()
        return res, pool, free, grown, list(blocks_a), list(blocks_b)

    got, pool, free, grown, blocks_a, blocks_b = serve(settle=False)
    want, pool_settled, free_settled, grown_settled, a_settled, b_settled \
        = serve(settle=True)
    assert got == want and all(o == OK for o, _ in got.values())
    assert free == free_settled and free[0] == free[1]
    # the dead row-step's own block: granted by its dispatch, never by a
    # server that settles first (no live token writes position 24)
    assert len(blocks_a) == life == len(a_settled) + past_the_edge
    assert grown == grown_settled + past_the_edge
    touched = _touched_blocks(pool, pool_settled) - (
        set(blocks_b) ^ set(b_settled))
    assert touched and touched <= {blocks_a[-1], pk.SCRATCH_BLOCK}


# ------------------------------------------- (2b) a granted block settles nothing
@pytest.mark.parametrize("family", ["gpt2", "jamba"])
def test_growth_settles_no_step(families, devices, family):
    """A row gets its next block at the dispatch that first writes into it
    (docs/serving.md#capacity-math--admission-control), through a program of
    its own behind the step in flight: the same requests run as many steps
    ahead, with as many uploads, as over an engine that takes every block at
    the seat (what every engine did before: the parent's count), and the
    streams are the same."""
    def serve(whole_life):
        srv = _server(families[family], block_size=4)
        srv._whole_life = whole_life
        res = srv.run(_mixed_requests(sampled=True))
        st = srv.stats()
        free = srv.allocator.free_blocks, srv.num_blocks - 1
        srv.close()
        return {u: (r["outcome"], r["tokens"]) for u, r in res.items()}, st, free

    got, st, free = serve(False)
    want, st_life, free_life = serve(True)
    assert got == want and free == free_life and free[0] == free[1]
    assert st["blocks_grown_total"] >= 5 and st_life["blocks_grown_total"] == 0
    for name in ("decode_steps", "steps_ahead", "state_uploads",
                 "state_reused_steps"):
        assert st[name] == st_life[name], name
    assert st["steps_ahead"] > 0


# ------------------------------------- (3) what settles every step stays as it was
def _shared_prefix_requests():
    pre = np.arange(16) % 7
    rng = np.random.default_rng(3)
    return [Request(tokens=np.concatenate([pre, rng.integers(0, 128, n)]),
                    max_new_tokens=new, seed=i, do_sample=bool(i % 2),
                    temperature=0.8)
            for i, (n, new) in enumerate(
                [(3, 12), (9, 5), (6, 9), (12, 7), (5, 3), (7, 10)])]


@pytest.mark.parametrize("case", ["kv_snapshot", "transfer_queue_mixed_role",
                                  "draining", "prefix_cache"])
def test_what_settles_every_step(families, devices, tmp_path, case):
    """``_settles_every_step`` by what the engine holds, not by a knob of its
    own: a snapshot cadence, a transfer queue (on a ``mixed`` role too) and a
    drain each read every step in the call that dispatched it; an engine with
    none of them runs ahead and admits under the step in flight.  An armed
    prefix cache runs ahead between admissions and settles before each, as
    every admission did (a finish publishes blocks and an eviction frees
    them, which only the allocator knows).  The streams are the plain
    engine's."""
    plain = _server(families["gpt2"])
    assert not plain._settles_every_step(())
    n = 6 if case == "prefix_cache" else 3           # more than the slots
    want = {u: r["tokens"] for u, r in plain.run(
        _shared_prefix_requests()[:n]).items()}
    assert plain.stats()["steps_ahead"] > 0
    assert (plain.stats()["admits_under_step"] > 0) == (n > 3)
    plain.close()
    if case == "prefix_cache":
        srv = _server(families["gpt2"], prefix_cache=True)
        assert not srv._settles_every_step(())
        for r in _shared_prefix_requests():
            srv.submit(r)
        start, unread_at_a_seat = srv._start, []

        def watch(*a, **kw):
            unread_at_a_seat.append(srv._unread is not None)
            return start(*a, **kw)
        srv._start = watch
        while srv.step():
            pass
        st = srv.stats()
        assert len(unread_at_a_seat) == 6 and not any(unread_at_a_seat)
        assert st["admits_under_step"] == 0 and st["steps_ahead"] > 0
        assert st["prefix_cache"]["requests_hit"] >= 1
        assert {u: r["tokens"] for u, r in srv.results.items()} == want
        srv.close()
        return
    armed = {"kv_snapshot": dict(kv_snapshot={"every_tokens": 4},
                                 journal_dir=str(tmp_path / "journal"),
                                 kv_bits=8),
             "transfer_queue_mixed_role": dict(
                 transfer={"dir": str(tmp_path / "queue")}),
             "draining": {}}[case]
    srv = _server(families["gpt2"], preflight=False, **armed)
    assert srv.role == "mixed"
    for r in _shared_prefix_requests()[:3]:
        srv.submit(r)
    ahead = 0
    if case == "draining":
        assert not srv._settles_every_step(())
        for _ in range(3):
            assert srv.step()
        ahead = srv.stats()["steps_ahead"]
        assert ahead > 0 and srv._unread is not None
        assert srv.drain() == {"clean": True, "active": 0, "queued": 0}
    assert srv._settles_every_step(())
    while srv.step():
        assert srv._unread is None       # read in the call that dispatched
    st = srv.stats()
    assert st["steps_ahead"] == ahead and st["decode_steps"] > 0
    if case == "kv_snapshot":
        # a lossy pool's streams are its own: held to completion
        assert all(len(srv.results[u]["tokens"]) == len(want[u])
                   for u in want)
        assert st["kv_snapshot"]["snapshots"] > 0
    else:
        assert {u: r["tokens"] for u, r in srv.results.items()} == want
    srv.close()


def test_a_row_ingesting_a_shared_prefix_is_never_run_ahead_of(families,
                                                               devices):
    def serve(settle):
        srv = _server(families["gpt2"], prefix_cache=True)
        for r in _shared_prefix_requests():
            srv.submit(r)
        ingesting_calls = 0
        while True:
            ingesting = any(s is not None and s.pending is not None
                            for s in srv._slots)
            before = srv.stats()["steps_ahead"]
            if not _step(srv, settle):
                break
            if ingesting:
                ingesting_calls += 1
                assert srv.stats()["steps_ahead"] == before
        st = srv.stats()
        res = {u: (r["outcome"], r["tokens"]) for u, r in srv.results.items()}
        srv.close()
        return res, st, ingesting_calls

    got, st, ingesting_calls = serve(settle=False)
    want, st_settled, _ = serve(settle=True)
    assert got == want and all(o == OK for o, _ in got.values())
    assert st["prefix_cache"]["requests_hit"] >= 1 and ingesting_calls >= 3
    assert st_settled["steps_ahead"] == 0


def test_prefill_and_decode_roles_never_run_ahead(families, devices,
                                                  tmp_path):
    model, params = families["gpt2"]

    def cfg(name, **kw):
        return ServingConfig(batch_slots=2, block_size=8, kv_bits=8,
                             journal_dir=str(tmp_path / name),
                             preflight=False, **kw)

    def requests():
        return [Request(tokens=np.arange(5 + 6 * i) % 11, max_new_tokens=9,
                        seed=i, do_sample=bool(i % 2), uid=i)
                for i in range(4)]

    mixed = ServingEngine(model=model, params=params, config=cfg("mixed"))
    want = {u: list(r["tokens"]) for u, r in mixed.run(requests()).items()}
    assert mixed.stats()["steps_ahead"] > 0
    mixed.close()
    qdir = str(tmp_path / "queue")
    pre = ServingEngine(model=model, params=params, config=cfg(
        "pre", role="prefill", transfer={"dir": qdir}))
    dec = ServingEngine(model=model, params=params, config=cfg(
        "dec", role="decode", transfer={"dir": qdir}))
    for r in requests():
        pre.submit(r)
    for _ in range(200):
        pre.step()
        dec.step()
        assert pre._unread is None and dec._unread is None
        if all(dec.results.get(u, {}).get("outcome") for u in want):
            break
    assert all(pre.results[u]["outcome"] == TRANSFERRED for u in want)
    assert {u: list(dec.results[u]["tokens"]) for u in want} == want
    assert pre.stats()["steps_ahead"] == dec.stats()["steps_ahead"] == 0
    assert dec.stats()["decode_steps"] > 0
    pre.close()
    dec.close()


# ----------------------------------------------- (4) every way a run ends
@pytest.mark.parametrize("end", ["run", "loop", "drain", "close"])
def test_no_way_of_ending_leaves_a_step_unread(families, devices, end):
    srv = _server(families["gpt2"])
    reqs = _mixed_requests(sampled=True)[:3 if end in ("drain", "close")
                                         else 6]
    uids = [srv.submit(r) for r in reqs]
    if end == "run":
        srv.run()
    elif end == "loop":
        while srv.step():
            pass
    else:
        for _ in range(3):
            assert srv.step()
        assert srv._unread is not None   # ...and ended with one in flight
        summary = srv.drain() if end == "drain" else srv.close()
        assert end == "close" or summary == {"clean": True, "active": 0,
                                             "queued": 0}
    assert srv._unread is None
    st = srv.stats()
    assert st["pending"] == 0 and st["completed"] == len(reqs)
    assert st["steps_ahead"] > 0
    for u, r in zip(uids, reqs):
        rec = srv.results[u]
        assert rec["outcome"] == OK
        assert len(rec["tokens"]) == len(rec["t_tokens"]) == r.max_new_tokens
    assert not srv.step()                # nothing left, nothing unread
    srv.close()
