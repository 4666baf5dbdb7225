"""Admission by the pool's timeline (docs/serving.md#capacity-math--admission-control).

A seat takes the blocks the prompt and the first decode write touch; every
later block is granted at the dispatch that first writes into it; the queue's
head is seated only if the blocks all seated streams will hold never exceed
the pool at any coming step.  The invariant these tests hold: a seated stream
never waits for a block and is never preempted, and the head is never
overtaken.  The sum alone against a walk over the steps; random backlogs
through a tiny engine whose pool binds, every stream against the same request
served alone; more streams seated than a reservation for life would seat;
every way of leaving early brings every granted block home; a block granted
out of a dead row's hands; a head planned under the step in flight beside
blocks that just came home; and the operands an outside reader gets."""

import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
from deepspeed_tpu.inference import (ServingEngine, ServingConfig, Request,
                                     paged_kv as pk, OK, SHED, DEADLINE,
                                     POISONED)


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
        **{"batch_slots": 4, "block_size": 8, "sanitize": True, **cfg}))


def _alone(tiny, requests, **cfg):
    """Each request through one slot over a pool nothing else touches."""
    srv = _server(tiny, batch_slots=1, **cfg)
    out = {}
    for r in requests:
        out[r.uid] = srv.run([r])[r.uid]["tokens"]
    srv.close()
    return out


# ------------------------------------------------------ (1) the sum alone
def _walk(written, ends, held, bs):
    """The same peak, by walking every step."""
    peak = 0
    for t in range(int(max(e - w for w, e in zip(written, ends)))):
        peak = max(peak, sum(
            max(h, pk.blocks_needed(min(w + t + 1, e), bs))
            for w, e, h in zip(written, ends, held) if t < e - w))
    return peak


@pytest.mark.parametrize("whole", [False, True], ids=["grown", "some_whole"])
@pytest.mark.parametrize("bs", [16, 64])
def test_timeline_peak_equals_a_walk_over_the_steps(bs, whole):
    rng = np.random.default_rng(bs + whole)
    for _ in range(200):
        n = int(rng.integers(1, 20))
        prompts = rng.integers(1, 40 * bs // 8, n)
        ends = prompts + rng.integers(1, 30 * bs // 8, n)
        written = np.minimum(prompts + rng.integers(0, 400, n), ends - 1)
        # a stream holds what it has written; with ``whole`` one in three
        # holds more (a restored stream holds its whole life from the seat)
        held = np.array([pk.blocks_needed(w + 1, bs) for w in written])
        life = np.array([pk.blocks_needed(e, bs) for e in ends])
        held = np.where((rng.integers(0, 3, n) == 0) & whole, life, held)
        assert pk.timeline_peak(written, ends, held, bs) == _walk(
            written, ends, held, bs)
    assert pk.timeline_peak([], [], [], bs) == 0
    # one stream alone peaks at its life, a seat that holds more at that
    assert pk.timeline_peak([5], [5 * bs], [1], bs) == 5
    assert pk.timeline_peak([5], [5 * bs], [7], bs) == 7


# --------------------------- (1b) a cache that folds: holdings that fall
def _walk_folded(written, ends, fold):
    """The peak of a folded cache's sum, by walking every step: a stream
    holds ``fold.charge(n)`` blocks in the step that brings it to ``n``."""
    peak = 0
    for t in range(int(max(max(e - w for w, e in zip(written, ends)), 0))):
        peak = max(peak, sum(int(fold.charge(min(w + t + 1, e)))
                             for w, e in zip(written, ends) if t < e - w))
    return peak


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("window, chunk, bs", [
    (16, 4, 4), (16, 4, 2), (64, 8, 8), (2048, 16, 64)])
def test_the_folded_sum_equals_a_walk_over_every_step(window, chunk, bs,
                                                      seed):
    """Random sawtooth streams (a stream gives ``window / bs`` blocks back
    and takes ``window / chunk / bs`` at every window's end, having held both
    for a step): the sum read at the window ends and the finishes is the
    walk's peak, and stands at a window's end, not at a finish, in a good
    part of the draws."""
    fold = pk.WindowFold(window, chunk, bs)
    rng = np.random.default_rng([seed, window, bs])
    at_a_fold = 0
    for _ in range(60 if window < 2048 else 6):
        n = int(rng.integers(1, 9))
        written = rng.integers(0, 4 * window, n)
        ends = written + rng.integers(-2, 3 * window, n)   # some not live
        got = pk.timeline_peak(written, ends, np.zeros(n), bs, fold=fold)
        assert got == _walk_folded(written, ends, fold)
        finishes = [sum(int(fold.charge(min(w + t + 1, e)))
                        for w, e in zip(written, ends) if t < e - w)
                    for t in {int(e - w - 1) for w, e in zip(written, ends)
                              if e > w}]
        at_a_fold += got > max(finishes, default=0)
    assert at_a_fold > 0
    assert pk.timeline_peak([], [], [], bs, fold=fold) == 0
    # one stream alone: the fold of its last whole window
    assert pk.timeline_peak([0], [3 * window + 5], [0], bs, fold=fold) \
        == fold.life_peak(3 * window + 5) \
        == 3 * fold.summary_blocks + fold.window_blocks


@pytest.mark.parametrize("whole", [False, True], ids=["grown", "some_whole"])
@pytest.mark.parametrize("bs", [16, 64])
def test_a_window_no_stream_reaches_is_the_growing_table(bs, whole):
    """Monotone holdings, case by case: a fold whose window no stream's end
    reaches holds ``blocks_needed`` at every length, and its sum is the
    growing table's, which stays the default and reads what it read."""
    fold = pk.WindowFold(1 << 20, bs, bs)
    rng = np.random.default_rng(bs + whole)
    for _ in range(100):
        n = int(rng.integers(1, 20))
        prompts = rng.integers(1, 40 * bs // 8, n)
        ends = prompts + rng.integers(1, 30 * bs // 8, n)
        written = np.minimum(prompts + rng.integers(0, 400, n), ends - 1)
        held = np.array([pk.blocks_needed(w + 1, bs) for w in written])
        grown = pk.timeline_peak(written, ends, held, bs)
        assert grown == _walk(written, ends, held, bs)
        assert grown == pk.timeline_peak(written, ends, held, bs, fold=fold)
        assert all(int(fold.held(m)) == pk.blocks_needed(m, bs)
                   for m in (1, bs, bs + 1, 7 * bs))
        if whole:
            # what a seat holds beyond its length is the default rule's alone
            more = held + 3
            assert pk.timeline_peak(written, ends, more, bs) == _walk(
                written, ends, more, bs)


# ------------------------------------- (2) random backlogs over a pool that binds
def _backlog(seed, n=14):
    rng = np.random.default_rng(seed)
    return [Request(tokens=rng.integers(0, 128, int(rng.integers(3, 30))),
                    max_new_tokens=int(rng.integers(2, 30)), seed=i, uid=i,
                    do_sample=bool(i % 2), temperature=0.9)
            for i in range(n)]


@pytest.mark.parametrize("eos", [None, "early"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_no_stream_waits_is_preempted_or_overtaken(tiny, devices, seed, eos):
    """Fourteen random requests through four slots over twelve blocks (a
    life is up to eight): the grants never fail (``_grant_blocks`` asserts
    it), every request is seated once and in the order it came, its tokens
    are those of the same request served alone, and every block comes home;
    some answers end at an eos the rule could not foresee."""
    eos_id = None
    if eos:
        first = _alone(tiny, _backlog(seed)[:1])
        eos_id = int(first[0][len(first[0]) // 2])
    want = _alone(tiny, _backlog(seed), eos_token_id=eos_id)
    srv = _server(tiny, num_blocks=13, eos_token_id=eos_id)
    reqs = _backlog(seed)
    seats, orig = [], srv._start

    def start(slot, req, *a, **kw):
        seats.append(req.uid)
        return orig(slot, req, *a, **kw)
    srv._start = start
    for r in reqs[:9]:
        srv.submit(r)
    calls = seated_most = waited = 0
    while srv.step():
        calls += 1
        if calls == 5:
            for r in reqs[9:]:
                srv.submit(r)
        seated_most = max(seated_most, sum(s is not None for s in srv._slots))
        waited += bool(srv.queue) and any(s is None for s in srv._slots)
        # what is checked out is what the seated streams hold, and never
        # more than the rule promised at the last seat
        assert srv.allocator.used_blocks == int(srv._held.sum()) \
            == sum(len(s.blocks) for s in srv._slots if s is not None)
        assert srv.allocator.used_blocks <= srv._promised <= 12
    assert seats == [r.uid for r in reqs]            # once each, in order
    assert {u: r["tokens"] for u, r in srv.results.items()} == want
    assert all(r["outcome"] == OK for r in srv.results.values())
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    st = srv.stats()
    assert st["blocks_grown_total"] > 0 and st["steps_ahead"] > 0
    assert waited and seated_most >= 3               # the pool did bind
    srv.close()                                      # the sanitizer's leak check


def test_without_the_timeline_a_seated_stream_would_wait(tiny, devices):
    """The teeth of the test above: a rule that seats whatever the free
    blocks hold NOW runs the same backlog into a seated stream that cannot
    have its next block, which ``_grant_blocks`` refuses to let pass."""
    srv = _server(tiny, num_blocks=13)
    srv._plan = lambda written, total, seat: (
        seat if seat <= srv.allocator.free_blocks else None)
    with pytest.raises(AssertionError, match="timeline broke its promise"):
        srv.run(_backlog(0))
    # the drain meets the same row again; the teardown runs all the same
    with pytest.raises(AssertionError, match="timeline broke its promise"):
        srv.close()


# ----------------------- (3) more streams than a reservation for life would seat
def test_seats_more_streams_than_whole_life_reservation(tiny, devices):
    """Seven blocks, streams of four each (8 + 24 tokens over blocks of 8):
    a reservation for life seats one at a time; the timeline seats the second
    once the first is far enough along that both peaks fit, so two decode
    side by side and the backlog takes fewer steps."""
    def serve(whole_life):
        srv = _server(tiny, num_blocks=8, batch_slots=3)
        srv._whole_life = whole_life
        reqs = [Request(tokens=np.arange(8) + i, max_new_tokens=24, seed=i,
                        uid=i) for i in range(4)]
        for r in reqs:
            srv.submit(r)
        most = 0
        while srv.step():
            most = max(most, sum(s is not None for s in srv._slots))
        st = srv.stats()
        res = {u: r["tokens"] for u, r in srv.results.items()}
        assert srv.allocator.free_blocks == 7
        srv.close()
        return most, st, res

    most, st, res = serve(False)
    most_life, st_life, res_life = serve(True)
    assert (most, most_life) == (2, 1)
    assert res == res_life
    assert st["decode_steps"] < st_life["decode_steps"] == 4 * 23
    assert st["blocks_grown_total"] == 4 * 2 and \
        st_life["blocks_grown_total"] == 0


def test_a_seat_takes_the_prompts_blocks_and_the_first_write(tiny, devices):
    srv = _server(tiny, batch_slots=2)
    srv.submit(Request(tokens=np.arange(15), max_new_tokens=30, uid=0))
    srv.submit(Request(tokens=np.arange(16), max_new_tokens=30, uid=1))
    srv._admit()
    assert [len(s.blocks) for s in srv._slots] == [2, 3]
    assert srv._pool_state([0, 1])["blocks_in_use"] == 5
    assert srv._promised == pk.blocks_needed(45, 8) + pk.blocks_needed(46, 8)
    assert srv.capacity()["blocks_per_request_at_defaults"] == 8   # a life
    srv.run()
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    srv.close()


# ------------------------------ (4) every early exit brings grown blocks home
@pytest.mark.parametrize("exit", ["eos", "deadline", "poison", "drain",
                                  "prefill_raises"])
def test_early_exits_return_every_grown_block(tiny, fault_harness, devices,
                                              exit):
    reqs = [Request(tokens=np.arange(5 + 3 * i) % 11, max_new_tokens=40,
                    seed=i, uid=i, do_sample=bool(i % 2)) for i in range(4)]
    eos = None
    if exit == "eos":
        eos = int(_alone(tiny, reqs[:1])[0][20])
    if exit == "poison":
        fault_harness.configure(logit_nan=1)
    srv = _server(tiny, batch_slots=3, eos_token_id=eos)
    for r in reqs:
        srv.submit(r)
    if exit == "prefill_raises":
        orig = srv._prefill_fn

        def prefill_fn(bucket):
            if srv._prefills and bucket not in srv._prefills:
                raise RuntimeError("the second bucket's executable died")
            return orig(bucket)
        srv._prefill_fn = prefill_fn
        with pytest.raises(RuntimeError, match="second bucket"):
            srv.step()
        srv._prefill_fn = orig
        assert srv.allocator.used_blocks == sum(
            len(s.blocks) for s in srv._slots if s is not None)
    calls = 0
    while srv.step():
        calls += 1
        if exit == "deadline" and calls == 20:
            srv.results[2]["deadline"] = time.monotonic() - 1.0
        if exit == "drain" and calls == 20:
            grown = srv.stats()["blocks_grown_total"]
            assert grown > 0
            assert srv.drain(timeout_s=0.0)["active"] == 3
            break
    st = srv.stats()
    assert st["blocks_grown_total"] > 0
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    out = {u: r["outcome"] for u, r in srv.results.items()}
    want = {"eos": {0: OK}, "deadline": {2: DEADLINE}, "poison": {1: POISONED},
            "drain": {0: SHED, 1: SHED, 2: SHED, 3: SHED},
            "prefill_raises": {}}[exit]
    assert all(out[u] == o for u, o in want.items())
    if exit == "eos":
        assert len(srv.results[0]["tokens"]) <= 21
    if exit == "prefill_raises":
        # the request whose prefill died was taken off the queue: the others
        assert sorted(u for u, o in out.items() if o == OK) == [0, 1, 3]
    fault_harness.reset()
    srv.close()


# -------------------- (5) a block granted out of a dead row's hands
def _garbage(srv, blocks):
    """Fill ``blocks`` of the pool with finite garbage (1e4 in K and V)."""
    ids = jnp.asarray(blocks)
    srv.pool = {k: v.at[:, ids].set(jnp.asarray(1e4, v.dtype))
                for k, v in srv.pool.items()}


def test_block_granted_beside_a_dead_row_in_flight(tiny, devices):
    """A row ends at an eos the host sees one dispatch late: its blocks go
    home while a step dispatched for it is still in flight, and the very next
    dispatch grants the last of them to its neighbour.  Device order saves
    it: the dead row's write lands before the new owner's first, and a row
    reads only positions it wrote.  Held over a pool of GARBAGE: every block
    that is free holds 1e4 in K and V, those that come home are filled with it
    at once, so a stream that read one position it had not written would lose
    its tokens.  (Finite garbage, as every block that comes home holds: NaN
    would pass through a masked position as 0 x NaN, which is why a quarantine
    scrubs and why `_set_blocks(poison=True)` is no use here.)"""
    def request_a():
        return Request(tokens=np.arange(20) % 17, max_new_tokens=30, seed=5,
                       uid=0, do_sample=True, temperature=1.2)

    alone = _alone(tiny, [request_a()])[0]
    # a token the host cannot foresee as the last, first seen at index j
    j = next(j for j in range(22, 8, -1) if alone[j] not in alone[:j])
    eos = int(alone[j])
    # the neighbour opens a block at dispatch j + 2, the first after the
    # dead row's blocks came home: it writes position T_b + j + 1 there
    t_b = next(t for t in range(9, 17) if (t + j + 1) % 8 == 0)

    def request_b():
        return Request(tokens=np.arange(t_b) + 3, max_new_tokens=40, seed=6,
                       uid=1)

    b_alone = _alone(tiny, [request_b()], eos_token_id=eos)[1]
    srv = _server(tiny, batch_slots=2, num_blocks=14, eos_token_id=eos)
    _garbage(srv, list(srv.allocator._free))
    orig, homes, grants = srv._finish, [], []

    def finish(slot, outcome=OK):
        blocks = list(srv._slots[slot].blocks)
        in_flight = dispatched[0] > srv._steps      # booked: `_steps`
        orig(slot, outcome)
        _garbage(srv, blocks)             # behind the step that is in flight
        homes.append((blocks, in_flight))
    srv._finish = finish
    grant = srv._grant_blocks

    def grant_blocks(ahead):
        out = grant(ahead)
        if out is not None:
            grants.append((srv._steps, [int(b) for b in out if b >= 0]))
        return out
    srv._grant_blocks = grant_blocks
    dispatch, dispatched = srv._dispatch, [0]

    def count_dispatch(active, ahead):
        dispatched[0] += 1
        return dispatch(active, ahead)
    srv._dispatch = count_dispatch
    a, b = srv.submit(request_a()), srv.submit(request_b())
    while srv.results[a]["outcome"] is None:
        assert srv.step()
    blocks_a, in_flight = homes[0]
    assert in_flight                     # a step was in flight for the dead row
    # the next dispatch grants the neighbour the last of the dead row's blocks
    assert srv.step()
    assert grants[-1] == (j + 1, [blocks_a[-1]])
    assert srv._slots[1].blocks[-1] == blocks_a[-1]
    srv.run()
    assert srv.results[a]["tokens"] == alone[:j + 1]
    assert srv.results[b]["outcome"] == OK
    assert srv.results[b]["tokens"] == b_alone
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    srv.close()


# ----- (6) a head planned with a step unread and a dead row's blocks just home
def test_head_planned_under_a_step_beside_blocks_just_returned(tiny, devices):
    """The pool binds (two slots, and the three lives fill it to the last
    block only once the first has gone): a row ends
    at its ``max_new_tokens`` with the next step already dispatched for it,
    its blocks go home, and the head is planned at once, with that step still
    unread (every live row one token past its mirror), seated into the blocks
    that just came home, its prefill dispatched behind the dead row's write
    into one of them.  The timeline keeps its promise (`_grant_blocks`'
    assertion never fires, what is checked out never passes what the rule
    promised), every stream yields the tokens it yields alone, and every
    block comes home.  Held over a pool of GARBAGE, as the test above."""
    def requests():
        return [Request(tokens=np.arange(20) % 17, max_new_tokens=13, seed=5,
                        uid=0, do_sample=True, temperature=1.2),  # 33: 5 blocks
                Request(tokens=np.arange(11) + 3, max_new_tokens=36, seed=6,
                        uid=1),                                   # 47: 6
                Request(tokens=(np.arange(26) * 5) % 23, max_new_tokens=9,
                        seed=7, uid=2, do_sample=True)]           # 35: 5

    want = _alone(tiny, requests())
    # 5 + 6 blocks seat the first two; the third's 5 are the first's
    srv = _server(tiny, batch_slots=2, num_blocks=12)
    _garbage(srv, list(srv.allocator._free))
    orig, homes = srv._finish, []

    def finish(slot, outcome=OK):
        blocks = list(srv._slots[slot].blocks)
        in_flight = dispatched[0] > srv._steps      # booked: `_steps`
        orig(slot, outcome)
        _garbage(srv, blocks)             # behind the step that is in flight
        homes.append((blocks, in_flight))
    srv._finish = finish
    dispatch, dispatched = srv._dispatch, [0]

    def count_dispatch(active, ahead):
        dispatched[0] += 1
        return dispatch(active, ahead)
    srv._dispatch = count_dispatch
    head_plan, plans = srv._head_plan, []

    def watch_plan(req, shared=0):
        out = head_plan(req, shared)
        plans.append((req.uid, srv._unread is not None,
                      srv.allocator.free_blocks, out[0][2]))
        return out
    srv._head_plan = watch_plan
    start, seats = srv._start, []

    def watch_start(slot, req, blocks, *a, **kw):
        seats.append((req.uid, srv._unread is not None, list(blocks),
                      [int(x) for x in srv._lengths]))
        return start(slot, req, blocks, *a, **kw)
    srv._start = watch_start
    for r in requests():
        srv.submit(r)
    while srv.step():
        assert srv.allocator.used_blocks == int(srv._held.sum()) \
            <= srv._promised <= 11
    blocks_a, in_flight = homes[0]
    assert in_flight and len(blocks_a) == 5      # the dead row's own block too
    # the head's plan that passed was made with that step unread, over
    # exactly the blocks that had just come home, and the seat took them
    uid, unread, blocks_c, lengths = seats[2]
    assert (uid, unread) == (2, True) and set(blocks_c) <= set(blocks_a)
    # (free: all but the neighbour's three; the plan's peak: the whole pool,
    # which the dead row's five blocks had to come home for)
    assert [p[1:] for p in plans if p[0] == 2][-1] == (True, 11 - 3, 11)
    assert lengths[1] == 11 + 12         # the neighbour's mirror: twelve steps
    #                                      booked, the thirteenth in flight
    assert srv.stats()["admits_under_step"] == 1
    assert {u: r["tokens"] for u, r in srv.results.items()} == want
    assert all(r["outcome"] == OK for r in srv.results.values())
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    srv.close()                          # the sanitizer's leak check


# ---------------- (7) the operands an outside reader gets cover the next write
def test_decode_args_cover_every_seated_rows_next_write(tiny, devices):
    """At any point between steps ``_decode_args()[2]`` names a block at
    column ``lengths[i] // block_size`` for every seated row (the benchmark's
    check passes the five operands straight to the model), a second call
    returns the same, the decode executable accepts them, and no token
    moves."""
    def serve(peek):
        srv = _server(tiny, batch_slots=3, num_blocks=16)
        for r in _backlog(4, n=7):
            srv.submit(r)
        while srv.step():
            if not peek:
                continue
            args = srv._decode_args()
            tables, lengths = np.asarray(args[2]), np.asarray(args[3])
            np.testing.assert_array_equal(tables, srv._tables)
            np.testing.assert_array_equal(lengths, srv._lengths)
            seated = [i for i, s in enumerate(srv._slots) if s is not None]
            assert all(
                tables[i, lengths[i] // 8] != pk.SCRATCH_BLOCK
                and tables[i, lengths[i] // 8] == srv._slots[i].blocks[-1]
                or lengths[i] // 8 < len(srv._slots[i].blocks) - 1
                for i in seated)
            assert all((tables[i] != pk.SCRATCH_BLOCK).sum()
                       == len(srv._slots[i].blocks) for i in seated)
            again = srv._decode_args()
            np.testing.assert_array_equal(np.asarray(again[2]), tables)
            with jax.set_mesh(srv.engine.mesh):
                srv._decode.executable(*again)
        res = {u: r["tokens"] for u, r in srv.results.items()}
        grown = srv.stats()["blocks_grown_total"]
        assert srv.allocator.free_blocks == srv.num_blocks - 1
        srv.close()
        return res, grown

    got, grown = serve(True)
    want, grown_unseen = serve(False)
    assert got == want and grown == grown_unseen > 0


# ------------------------------------ what keeps a reservation for life
@pytest.mark.parametrize("armed", ["prefix_cache", "kv_snapshot", "transfer"])
def test_armed_features_keep_whole_life_reservation(tiny, devices, tmp_path,
                                                    armed):
    """Where something other than a seated stream holds or reads blocks (the
    radix cache, a snapshot image, the transfer queue) a seat takes the
    stream's whole life, as before, and nothing grows."""
    cfg = {"prefix_cache": dict(prefix_cache=True),
           "kv_snapshot": dict(kv_snapshot={"every_tokens": 4}, kv_bits=8,
                               journal_dir=str(tmp_path / "j")),
           "transfer": dict(transfer={"dir": str(tmp_path / "q")})}[armed]
    srv = _server(tiny, batch_slots=2, preflight=False, **cfg)
    assert srv._whole_life
    srv.submit(Request(tokens=np.arange(9), max_new_tokens=30, uid=0))
    srv._admit()
    assert len(srv._slots[0].blocks) == pk.blocks_needed(39, 8)
    srv.run()
    assert srv.stats()["blocks_grown_total"] == 0
    srv.close()


@pytest.mark.parametrize("allocatable, restored", [(6, False), (7, True)])
def test_a_restore_asks_the_same_rule(tiny, devices, tmp_path, allocatable,
                                      restored):
    """A stream restored from an image holds its whole life from the seat
    (four blocks here, and it leaves when its neighbour, which still grows
    to four, holds three): with six blocks the timeline has no room and the
    restore degrades to the recompute queue, with seven it is seated and
    counted at what it holds;
    either way the seated stream gets every block it was promised and both
    get the tokens they get alone."""
    import os
    import shutil
    from deepspeed_tpu.checkpoint import atomic
    from deepspeed_tpu.inference.serving import stream_snapshot_dir

    def request(uid):
        return Request(tokens=np.arange(1, 9), max_new_tokens=24, seed=7 + uid,
                       do_sample=True, temperature=0.9, uid=uid)

    def cfg(name, **kw):
        return dict(batch_slots=2, kv_bits=8, preflight=False,
                    journal_dir=str(tmp_path / name), **kw)
    sa = _server(tiny, **cfg("a", kv_snapshot={"every_tokens": 4}))
    sa.submit(request(5))
    for _ in range(11):
        sa.step()
    saved = str(tmp_path / "copy")
    shutil.copytree(stream_snapshot_dir(str(tmp_path / "a"), 5), saved)
    oracle = {5: sa.run()[5]["tokens"]}
    oracle[6] = sa.run([request(6)])[6]["tokens"]
    sa.close()

    sb = _server(tiny, **cfg("b", num_blocks=allocatable + 1))
    assert not sb._whole_life
    sb.submit(request(6))
    for _ in range(3):
        sb.step()
    out = sb.submit_restored(request(5), os.path.join(
        saved, atomic.find_latest_valid(saved)))
    assert out["restored"] == restored
    if restored:
        assert len(sb._slots[1].blocks) == 4 == sb._held[1]
        assert sb._promised == 7
    else:
        assert "timeline has no room" in out["reason"] and len(sb.queue) == 1
    sb.run()
    assert {u: sb.results[u]["tokens"] for u in (5, 6)} == oracle
    assert sb.allocator.free_blocks == allocatable
    sb.close()


def test_step_span_carries_the_timelines_counters(tiny, devices):
    from deepspeed_tpu.monitor import spans as monspans
    rec = monspans.recorder()
    mark = rec.open("test")
    srv = _server(tiny, batch_slots=2, num_blocks=9)
    srv.run([Request(tokens=np.arange(7), max_new_tokens=20, uid=0)])
    rows = [r.attrs for r in rec.since(mark) if r.name == "serving.step"]
    rec.discard(mark)
    assert all(a["kv_token_room"] == 8 * 8 for a in rows)
    assert all(a["blocks_promised"] == pk.blocks_needed(27, 8) for a in rows)
    assert sum(a["blocks_grown"] for a in rows) == 3 \
        == srv.stats()["blocks_grown_total"]
    # a dispatch finds what the one before it left: the seat's block first
    assert [a["blocks_in_use"] for a in rows][:2] == [1, 1]
    assert max(a["blocks_in_use"] for a in rows) == 4
    srv.close()
