"""Multi-tenant KV prefix sharing (docs/serving.md#prefix-sharing).

Layers under test, bottom up:

- **refcounted allocator**: incref/free holder accounting, double-free
  and incref-of-free rejections, released-vs-retained reporting;
- **radix index** (`paged_kv.PrefixIndex`): chained content keys,
  full-content collision demotion, same-content dedup, COW donors,
  LRU leaf-only eviction that can never reclaim a referenced block;
- **serving engine**: token-identical outputs shared vs unshared under
  permuted arrivals, copy-on-write at the first divergent token,
  admission charging UNIQUE blocks via the one capacity function the
  ds_mem CLI and the memory ledger also call, quarantine scrubbing
  only sole-owner blocks, eviction under pool pressure, and a decode
  jaxpr that stays byte-identical with the cache armed;
- **migration**: restore re-establishes sharing against the survivor's
  own index (or degrades loudly to a private import), and a crash
  mid-restore never tears a refcount;
- **tooling**: ds_bench_diff classifies the sharing metrics, ds_report
  prints the resolved policy.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.checkpoint import atomic
from deepspeed_tpu.inference import paged_kv as pk
from deepspeed_tpu.inference.serving import (ServingEngine, ServingConfig,
                                             Request, PrefixCacheConfig,
                                             describe_prefix_cache,
                                             stream_snapshot_dir,
                                             OK, POISONED)
from deepspeed_tpu.analysis.capacity import (request_unique_blocks,
                                             serving_plan, max_streams)
from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny96():
    """max_seq=96: room for a 40-token shared preamble + suffix + new."""
    cfg = GPT2Config(vocab_size=128, max_seq=96, n_embd=32, n_layer=2,
                     n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    model = GPT2(cfg, dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0))


RNG = np.random.default_rng(7)
PRE = RNG.integers(0, 128, (40,))          # the shared preamble
SUFFIX = [RNG.integers(0, 128, (6,)) for _ in range(5)]


def _reqs(n=5, mnt=8):
    """n requests sharing the 40-token preamble, 6-token unique tails,
    alternating greedy and sampled."""
    return [Request(tokens=np.concatenate([PRE, SUFFIX[i]]),
                    max_new_tokens=mnt, seed=100 + i, uid=i,
                    do_sample=(i % 2 == 1), temperature=0.7)
            for i in range(n)]


def _mk(model, params, prefix=True, **kw):
    cfg = ServingConfig(batch_slots=4, block_size=8, max_new_tokens=8,
                        top_k=8, prefix_cache=prefix, **kw)
    return ServingEngine(model=model, params=params, config=cfg)


# ===================================================================
# refcounted allocator
# ===================================================================

def test_allocator_refcount_share_and_release():
    a = pk.BlockAllocator(6)
    got = a.alloc(3)
    assert [a.refcount(b) for b in got] == [1, 1, 1]
    a.incref(got[:2])
    assert a.shared_blocks == 2 and a.logical_blocks == 5
    # first free drops one holder: only the sole-owner block releases
    released = a.free(got)
    assert released == [got[2]]
    assert a.free_blocks == 3 and a.used_blocks == 2
    # second free releases the ex-shared pair
    assert sorted(a.free(got[:2])) == sorted(got[:2])
    assert a.free_blocks == 5 and a.shared_blocks == 0


def test_allocator_rejects_incref_of_free_and_double_free():
    a = pk.BlockAllocator(4)
    got = a.alloc(2)
    a.free(got)
    before = (a.free_blocks, a.used_blocks)
    with pytest.raises(ValueError, match="not in use"):
        a.incref([got[0]])
    with pytest.raises(ValueError, match="double free"):
        a.free([got[0]])
    # validate-first: a rejected batch must not partially decref
    held = a.alloc(2)
    a.incref(held)                          # refcount 2 each
    with pytest.raises(ValueError, match="double free"):
        a.free(held + [99])                 # 99 was never allocated
    assert all(a.refcount(b) == 2 for b in held)
    a.free(held), a.free(held)
    assert (a.free_blocks, a.used_blocks) == before


# ===================================================================
# radix index
# ===================================================================

def _index(num_blocks=10, **kw):
    alloc = pk.BlockAllocator(num_blocks)
    return alloc, pk.PrefixIndex(alloc, **kw)


def test_block_key_is_chained_and_content_sensitive():
    k1 = pk.block_key(None, [1, 2, 3, 4])
    assert k1 == pk.block_key(None, [1, 2, 3, 4])
    assert k1 != pk.block_key(None, [1, 2, 3, 5])
    # chaining: the same tokens under a different parent key apart —
    # one flat dict IS a radix tree
    assert pk.block_key(k1, [9] * 4) != pk.block_key(None, [9] * 4)


def test_index_insert_match_roundtrip_takes_refcount():
    alloc, idx = _index()
    b = alloc.alloc(2)
    toks = list(range(16))
    k0 = idx.insert(None, toks[:8], b[0])
    k1 = idx.insert(k0, toks[8:], b[1])
    assert k1 is not None and len(idx) == 2
    assert alloc.refcount(b[0]) == 2        # inserter + cache
    m = idx.match(toks + [99, 98], 8)       # trailing partial chunk
    assert m["blocks"] == b and m["keys"] == [k0, k1]
    assert m["donor"] is None
    # limit_blocks clamps the walk (the caller's write-safety clamp)
    assert idx.match(toks, 8, limit_blocks=1)["blocks"] == [b[0]]
    # inserter finishes: the cache's reference keeps both blocks live
    assert alloc.free(b) == []
    assert alloc.used_blocks == 2 and idx.holds(b[0])


def test_hash_collision_demotes_to_miss(monkeypatch):
    """A forced SHA collision must degrade to a cache miss — never to
    serving another prefix's K/V."""
    alloc, idx = _index()
    b = alloc.alloc(2)
    monkeypatch.setattr(pk, "block_key", lambda parent, toks: "SAMEKEY")
    assert idx.insert(None, [1] * 8, b[0]) == "SAMEKEY"
    # same key, different content: insert refuses (first writer wins)
    assert idx.insert(None, [2] * 8, b[1]) is None
    assert alloc.refcount(b[1]) == 1        # no refcount taken
    # lookup of the colliding content misses with the counter bumped
    m = idx.match([2] * 8, 8)
    assert m["blocks"] == [] and idx.collisions >= 1


def test_insert_dedupes_same_content():
    """Two tenants publishing identical content race cleanly: the first
    block stays authoritative, the second keeps only its own holders."""
    alloc, idx = _index()
    b = alloc.alloc(2)
    k0 = idx.insert(None, [5] * 8, b[0])
    assert idx.insert(None, [5] * 8, b[1]) == k0    # same key returned
    assert idx.holds(b[0]) and not idx.holds(b[1])
    assert alloc.refcount(b[1]) == 1
    assert len(idx) == 1


def test_insert_rejects_scratch_and_broken_chain():
    alloc, idx = _index()
    b = alloc.alloc(1)
    assert idx.insert(None, [1] * 8, pk.SCRATCH_BLOCK) is None
    assert idx.insert("no-such-parent", [1] * 8, b[0]) is None
    assert alloc.refcount(b[0]) == 1


def test_cow_donor_at_first_divergent_token():
    alloc, idx = _index()
    b = alloc.alloc(2)
    k0 = idx.insert(None, list(range(8)), b[0])
    idx.insert(k0, [10, 11, 12, 13, 14, 15, 16, 17], b[1])
    # diverges at the 3rd token of block 1: donor shares j=2
    probe = list(range(8)) + [10, 11, 99, 99, 99, 99, 99, 99]
    m = idx.match(probe, 8)
    assert m["blocks"] == [b[0]]
    assert m["donor"] == (b[1], 2)
    # no shared token at all -> no donor
    m2 = idx.match(list(range(8)) + [70] * 8, 8)
    assert m2["donor"] is None


def test_eviction_never_reclaims_referenced_blocks():
    alloc, idx = _index()
    b = alloc.alloc(3)
    k0 = idx.insert(None, [1] * 8, b[0])
    idx.insert(k0, [2] * 8, b[1])           # b0 is interior, b1 leaf
    idx.insert(None, [3] * 8, b[2])         # b2 leaf
    alloc.incref([b[2]])                    # a live reader holds b2
    for bb in b:
        alloc.free([bb])                    # inserters let go
    # want everything: only b1 (cold leaf) then b0 (now a leaf) can go;
    # b2 is referenced and must survive any demand
    released = idx.evict(10)
    assert set(released) == {b[0], b[1]}
    assert idx.holds(b[2]) and alloc.is_allocated(b[2])
    assert idx.evict(1) == []               # still pinned
    alloc.free([b[2]])                      # reader lets go
    assert idx.evict(1) == [b[2]]
    assert alloc.free_blocks == alloc.num_blocks - 1


def test_max_blocks_cap_evicts_lru_leaf():
    alloc, idx = _index(num_blocks=12)
    b = alloc.alloc(3)
    idx.insert(None, [1] * 8, b[0])
    idx.insert(None, [2] * 8, b[1])
    alloc.free(b)                           # cache holds the only refs
    cap_idx = pk.PrefixIndex(alloc, max_blocks=2)
    assert cap_idx.max_blocks == 2
    c = alloc.alloc(3)
    cap_idx.insert(None, [4] * 8, c[0])
    cap_idx.insert(None, [5] * 8, c[1])
    alloc.free([c[0], c[1]])
    assert cap_idx.insert(None, [6] * 8, c[2]) is not None
    assert len(cap_idx) == 2 and not cap_idx.holds(c[0])   # LRU victim


def test_clear_reports_dropped_vs_released():
    alloc, idx = _index()
    b = alloc.alloc(2)
    idx.insert(None, [1] * 8, b[0])
    idx.insert(None, [2] * 8, b[1])
    alloc.free([b[0]])                      # only cache holds b0 now
    dropped, released = idx.clear()
    assert sorted(dropped) == sorted(b)
    assert released == [b[0]]               # b1 still has its inserter
    assert alloc.is_allocated(b[1]) and not alloc.is_allocated(b[0])


# ===================================================================
# serving: identity, COW, unified capacity, scrub, eviction, jaxpr
# ===================================================================

def test_shared_prefix_token_identical_under_permuted_arrivals(
        tiny96, devices):
    """The acceptance bar: outputs with the cache armed are
    token-identical to the unshared engine, for greedy AND sampled
    requests, under both arrival orders — and the cache actually
    shares (hit on every co-tenant after the first)."""
    model, params = tiny96

    def run(prefix, order):
        srv = _mk(model, params, prefix=prefix)
        out = srv.run([_reqs()[j] for j in order])
        st = srv.stats()
        srv.close()
        assert srv.allocator.free_blocks == srv.num_blocks - 1, \
            "close() left cache references behind"
        return {u: r["tokens"] for u, r in out.items()}, st

    base, st0 = run(None, range(5))
    assert "prefix_cache" not in st0        # off = absent, not zeroed
    on, st1 = run(True, range(5))
    perm, st2 = run(True, [3, 1, 4, 0, 2])
    assert on == base, "armed cache changed a request's tokens"
    assert perm == base, "arrival order leaked into shared outputs"
    for st in (st1, st2):
        pc = st["prefix_cache"]
        # co-batched sharing: prompt blocks publish at seat time, so
        # every request after the first hits even in one admission wave
        assert pc["requests"] == 5 and pc["requests_hit"] == 4
        assert pc["hit_rate"] == pytest.approx(0.8)
        # 4 co-tenants x 4 shared blocks (clamp: (46-1)//8 = 5, but
        # the preamble covers exactly 5 full blocks and the 6th chunk
        # spans preamble+suffix, so the chain match is 5 for uid 0's
        # twin and 5 for all — assert the attached total instead
        assert pc["shared_blocks_attached"] == 20
        assert pc["unique_blocks_in_use"] <= pc["logical_blocks"]
        assert pc["index"]["collisions"] == 0
        assert pc["policy"]["enabled"] is True


def test_cow_clones_at_first_divergent_token(tiny96, devices):
    """Request B shares A's preamble for 5 full blocks and diverges at
    token 45 — mid-block: the cached sibling block is CLONED (one
    copy), the copied run is not re-ingested, and B's tokens still
    match the unshared oracle exactly."""
    model, params = tiny96
    rng = np.random.default_rng(3)
    pre48 = rng.integers(0, 128, (48,))
    a = Request(tokens=pre48.copy(), max_new_tokens=6, seed=1, uid=0)
    b_toks = pre48.copy()
    b_toks[45:] = (b_toks[45:] + 1) % 128          # diverge at 45
    b = Request(tokens=b_toks, max_new_tokens=6, seed=2, uid=1,
                do_sample=True, temperature=0.8)

    oracle_srv = _mk(model, params, prefix=None)
    oracle = {u: r["tokens"]
              for u, r in oracle_srv.run([a, b]).items()}
    oracle_srv.close()

    srv = _mk(model, params, prefix=True)
    got = {u: r["tokens"] for u, r in srv.run([a, b]).items()}
    st = srv.stats()["prefix_cache"]
    srv.close()
    assert got == oracle
    assert st["cow_copies"] == 1
    assert st["requests_hit"] == 1          # b hit a's published chain
    assert srv.allocator.free_blocks == srv.num_blocks - 1


def test_admission_charges_unique_blocks_one_function(tiny96, devices):
    """Satellite regression: serving admission, the capacity planner
    and ds_mem --max-streams all pin to request_unique_blocks() on the
    SAME synthetic mix — prompt 40, max_new 8, block 8, shared head 32
    tokens -> 6 total, 4 shared, 2 unique."""
    ub = request_unique_blocks(prompt_tokens=40, max_new_tokens=8,
                               block_size=8, shared_prefix_tokens=32)
    assert ub == {"total_blocks": 6, "shared_blocks": 4,
                  "unique_blocks": 2}
    # the write-safety clamp: a whole-prompt "hit" still keeps the
    # final prompt token's block private
    clamped = request_unique_blocks(prompt_tokens=40, max_new_tokens=8,
                                    block_size=8, shared_prefix_tokens=40)
    assert clamped["shared_blocks"] == 4

    # the planner carries the same split...
    plan = serving_plan(n_layer=2, n_head=4, head_dim=8, max_seq=96,
                        block_size=8, batch_slots=4, max_new_tokens=8,
                        prompt_tokens=40, shared_prefix_tokens=32)
    assert plan["shared_prefix_blocks"] == 4
    assert plan["unique_blocks_per_request"] == 2
    # ...and max_streams charges the shared head ONCE
    budget = plan["per_block_bytes"] * 20 / 0.92
    ms = max_streams(plan, budget)
    assert ms["allocatable_blocks"] == 19
    assert ms["max_streams"] == (19 - 4) // 2
    unshared = serving_plan(n_layer=2, n_head=4, head_dim=8, max_seq=96,
                            block_size=8, batch_slots=4, max_new_tokens=8,
                            prompt_tokens=40)
    assert max_streams(unshared, budget)["max_streams"] == 19 // 6
    # sharing must never price WORSE than unshared
    assert ms["max_streams"] >= max_streams(unshared, budget)["max_streams"]

    # the serving engine's own admission: warm the cache with request
    # A, then admitting its twin must allocate exactly unique_blocks
    model, params = tiny96
    srv = _mk(model, params, prefix=True)
    try:
        srv.run([Request(tokens=PRE.copy(), max_new_tokens=8, seed=9,
                         uid=0)])
        used_before = srv.allocator.used_blocks
        srv.submit(Request(tokens=PRE.copy(), max_new_tokens=8, seed=9,
                           uid=1))
        srv._admit()
        assert srv.allocator.used_blocks - used_before == \
            ub["unique_blocks"]
        s = srv._slots[[i for i, sl in enumerate(srv._slots)
                        if sl is not None][0]]
        assert s.shared_blocks == ub["shared_blocks"]
        while srv.results[1]["outcome"] is None:
            srv.step()
    finally:
        srv.close()


def test_ds_mem_cli_max_streams_shared_prefix():
    """The REAL CLI answers the capacity question with the same math."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_mem"),
         "--max-streams", "--layers", "2", "--heads", "4",
         "--head-dim", "8", "--max-seq", "96", "--block-size", "8",
         "--max-new", "8", "--prompt-tokens", "40",
         "--shared-prefix-tokens", "32", "--budget-gb", "0.001",
         "--json"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout)
    assert out["shared_prefix_blocks"] == 4
    assert out["unique_blocks_per_request"] == 2
    assert out["max_streams"] == \
        (out["allocatable_blocks"] - 4) // 2


def test_memory_ledger_reports_shared_unique_split(tiny96, devices):
    """ds_mem's serving attribution: with co-tenants live, the ledger's
    paged-KV detail splits physical (unique) vs logical blocks and
    prices the sharing dividend in bytes."""
    from deepspeed_tpu.monitor import memory_ledger as mled
    model, params = tiny96
    srv = _mk(model, params, prefix=True)
    try:
        srv.run([Request(tokens=PRE.copy(), max_new_tokens=8, seed=9,
                         uid=0)])
        for i in (1, 2):
            srv.submit(Request(tokens=np.concatenate([PRE, SUFFIX[i]]),
                               max_new_tokens=8, seed=9 + i, uid=i))
        srv._admit()
        snap = mled.attribute_serving(srv).snapshot()
        detail = snap["detail"]["hbm"]["paged_kv_pool"]
        assert detail["shared_blocks"] > 0
        assert detail["logical_blocks"] > detail["unique_blocks"]
        per_block = snap["hbm"]["paged_kv_pool"] // detail["blocks"]
        assert detail["shared_saved_bytes"] == \
            (detail["logical_blocks"] - detail["unique_blocks"]) \
            * per_block
        while any(srv.results[i]["outcome"] is None for i in (1, 2)):
            srv.step()
    finally:
        srv.close()


def test_poisoned_cotenant_scrubs_only_private_blocks(
        tiny96, fault_harness, devices):
    """Chaos-poison a prefix-HIT request: only its PRIVATE blocks are
    poisoned and scrubbed (a shared-block scrub is DSTPU316), the
    publisher's cached prefix survives clean, and a later twin request
    reusing the cache still matches the oracle."""
    model, params = tiny96
    reqs = _reqs(3)
    oracle_srv = _mk(model, params, prefix=None)
    oracle = {u: r["tokens"] for u, r in oracle_srv.run(reqs).items()}
    oracle_srv.close()

    fault_harness.configure(logit_nan=1)    # uid 1 is a HIT co-tenant
    srv = _mk(model, params, prefix=True, sanitize=True)
    res = srv.run(reqs)
    assert res[1]["outcome"] == POISONED
    for u in (0, 2):
        assert res[u]["outcome"] == OK and res[u]["tokens"] == oracle[u]
    fault_harness.reset()
    # the cached prefix is still clean: a fresh twin hits and matches
    again = srv.run([Request(tokens=np.concatenate([PRE, SUFFIX[2]]),
                             max_new_tokens=8, seed=102, uid=9)])
    assert again[9]["tokens"] == oracle[2]
    assert srv.stats()["sanitizer"]["findings"] == 0
    srv.close()
    assert srv.allocator.free_blocks == srv.num_blocks - 1


def test_pool_pressure_evicts_cache_not_live_streams(tiny96, devices):
    """A pool sized so cached chains must be evicted to admit fresh
    traffic: admission's retry path reclaims LRU cache entries, all
    requests complete correctly, nothing leaks."""
    model, params = tiny96
    # 13 blocks: one 46-token request costs 6; its published chain (5
    # full blocks at finish) must be partially evicted to admit two
    # different-prefix requests back to back
    rng = np.random.default_rng(11)
    other = [Request(tokens=rng.integers(0, 128, (46,)),
                     max_new_tokens=8, seed=50 + i, uid=10 + i)
             for i in range(2)]
    oracle_srv = _mk(model, params, prefix=None, num_blocks=13)
    oracle = {u: r["tokens"]
              for u, r in oracle_srv.run([_reqs(1)[0]] + other).items()}
    oracle_srv.close()

    srv = _mk(model, params, prefix=True, num_blocks=13)
    got = {}
    for r in [_reqs(1)[0]] + other:         # sequential: pressure peaks
        got.update({u: rec["tokens"]
                    for u, rec in srv.run([r]).items()})
    st = srv.stats()["prefix_cache"]
    srv.close()
    assert got == oracle
    assert st["evicted_blocks"] > 0
    assert srv.allocator.free_blocks == srv.num_blocks - 1


def test_prefix_cache_decode_jaxpr_identical(tiny96, devices):
    """Arming the cache must leave the TRACED decode step
    byte-identical: sharing is host-side block-table bookkeeping, and
    COW uses a separate tiny executable (PR-9 equality discipline)."""
    model, params = tiny96

    def jaxpr_text(prefix):
        srv = _mk(model, params, prefix=prefix)
        srv._build_decode()
        jx = str(jax.make_jaxpr(srv._decode)(*srv._decode_args()))
        srv.close()
        return jx

    assert jaxpr_text(None) == jaxpr_text(True)


# ===================================================================
# migration under sharing
# ===================================================================

def _snap_cfg(journal_dir, **kw):
    return ServingConfig(batch_slots=2, block_size=8, max_new_tokens=24,
                         kv_bits=8, journal_dir=journal_dir,
                         preflight=False,
                         kv_snapshot={"every_tokens": 4, "keep_n": 2},
                         **kw)


MIG_PROMPT = np.arange(1, 17, dtype=np.int32)    # two full blocks


def _mig_req(uid=5):
    return Request(tokens=MIG_PROMPT.copy(), max_new_tokens=24,
                   do_sample=True, temperature=0.9, seed=7, uid=uid)


def _deep_snapshot(model, params, root):
    """Run uid 5 deep on a snapshotting engine; return (snapshot copy
    dir, full oracle tokens)."""
    ja = os.path.join(root, "ja")
    sa = ServingEngine(model=model, params=params,
                       config=_snap_cfg(ja, prefix_cache=True))
    sa.submit(_mig_req())
    for _ in range(11):
        sa.step()
    saved = os.path.join(root, "crashcopy")
    shutil.copytree(stream_snapshot_dir(ja, 5), saved)
    while sa.results[5]["outcome"] is None:
        sa.step()
    oracle = list(sa.results[5]["tokens"])
    sa.close()
    return saved, oracle


def test_restore_reestablishes_sharing_on_warm_survivor(tiny96, tmp_path):
    """The survivor's own radix index already holds the prompt's
    blocks: restore shares them instead of importing duplicates — the
    image's shared head is never re-imported, the stream completes
    token-identical, and the snapshot meta records the sharing."""
    model, params = tiny96
    saved, oracle = _deep_snapshot(model, params, str(tmp_path))
    tag = atomic.find_latest_valid(saved)
    _, meta = pk.load_block_image(os.path.join(saved, tag))
    assert meta["stream"]["shared_blocks"] == 0   # source seated plainly

    sb = ServingEngine(model=model, params=params,
                       config=_snap_cfg(str(tmp_path / "jb"),
                                        prefix_cache=True))
    # warm the survivor: a finished twin publishes the prompt blocks
    sb.run([_mig_req(uid=11)])
    cached = sb._prefix_index.cached_blocks
    assert cached >= MIG_PROMPT.size // 8
    used_before = sb.allocator.used_blocks
    out = sb.submit_restored(_mig_req(), os.path.join(saved, tag))
    assert out["restored"] and out["tokens_saved"] > 0
    # both full prompt blocks shared -> only the private tail imported
    nb = pk.blocks_needed(MIG_PROMPT.size + 24, 8)
    assert sb.allocator.used_blocks - used_before == nb - 2
    while sb.results[5]["outcome"] is None:
        sb.step()
    assert list(sb.results[5]["tokens"]) == oracle
    sb.close()
    assert sb.allocator.free_blocks == sb.num_blocks - 1


def test_restore_degrades_loudly_on_cold_survivor(tiny96, tmp_path):
    """No local prefix match: restore WARNS and imports every block
    privately — degraded, never torn, still token-identical."""
    import logging
    model, params = tiny96
    saved, oracle = _deep_snapshot(model, params, str(tmp_path))
    sb = ServingEngine(model=model, params=params,
                       config=_snap_cfg(str(tmp_path / "jb"),
                                        prefix_cache=True))
    # cold cache is EMPTY -> the quiet classic import path; seed one
    # unrelated entry so the degradation path (match attempted, none
    # found) is the one that runs
    sb.run([Request(tokens=np.arange(30, 46, dtype=np.int32),
                    max_new_tokens=4, seed=3, uid=70)])
    used_before = sb.allocator.used_blocks
    # the package logger does not propagate: tap it directly
    records = []
    tap = logging.Handler()
    tap.emit = records.append
    lg = logging.getLogger("deepspeed_tpu")
    lg.addHandler(tap)
    try:
        out = sb.submit_restored(
            _mig_req(),
            os.path.join(saved, atomic.find_latest_valid(saved)))
    finally:
        lg.removeHandler(tap)
    assert out["restored"]
    assert any(r.levelno == logging.WARNING
               and "no local prefix match" in r.getMessage()
               for r in records)
    # every block imported privately: the full per-request cost
    nb = pk.blocks_needed(MIG_PROMPT.size + 24, 8)
    assert sb.allocator.used_blocks - used_before == nb
    while sb.results[5]["outcome"] is None:
        sb.step()
    assert list(sb.results[5]["tokens"]) == oracle
    sb.close()
    assert sb.allocator.free_blocks == sb.num_blocks - 1


def test_crash_during_restore_with_sharing_never_tears_refcount(
        tiny96, tmp_path, fault_harness):
    """The fault-site proof for torn refcounts: crash AFTER the shared
    borrow is taken and fresh blocks are allocated — on the surviving
    engine every fresh block goes home, the cache's own references are
    intact (refcount back to exactly 1), the sanitizer finds nothing,
    and the engine still serves hits."""
    model, params = tiny96
    saved, oracle = _deep_snapshot(model, params, str(tmp_path))
    sb = ServingEngine(model=model, params=params,
                       config=_snap_cfg(str(tmp_path / "jb"),
                                        prefix_cache=True,
                                        sanitize=True))
    sb.run([_mig_req(uid=11)])
    cached_ids = [b for b in range(1, sb.num_blocks)
                  if sb._prefix_index.holds(b)]
    assert cached_ids
    free_before = sb.allocator.free_blocks
    fault_harness.configure("crash_at=serving.crash_during_restore")
    with pytest.raises(fault_harness.InjectedCrash):
        sb.submit_restored(_mig_req(),
                           os.path.join(saved,
                                        atomic.find_latest_valid(saved)))
    fault_harness.reset()
    assert sb.allocator.free_blocks == free_before
    for b in cached_ids:
        assert sb.allocator.refcount(b) == 1, \
            f"torn refcount on cached block {b}"
    # the engine is whole: the journaled uid drains, a twin still HITS
    while sb.results[5]["outcome"] is None:
        sb.step()
    out = sb.run([_mig_req(uid=12)])
    assert out[12]["outcome"] == "ok"
    assert sb.stats()["prefix_cache"]["requests_hit"] >= 1
    assert sb.stats()["sanitizer"]["findings"] == 0
    sb.close()
    assert sb.allocator.free_blocks == sb.num_blocks - 1


# ===================================================================
# tooling: bench_diff classification, ds_report policy echo
# ===================================================================

def test_bench_diff_classifies_prefix_metrics():
    from deepspeed_tpu.analysis.bench_diff import classify, compare
    assert classify("prefix_hit_rate") == "higher"
    assert classify("max_streams") == "higher"
    assert classify("unique_block_frac") == "lower"
    res = compare({"m": {"prefix_hit_rate": 0.8, "unique_block_frac": 0.4}},
                  {"m": {"prefix_hit_rate": 0.2, "unique_block_frac": 0.9}})
    assert {r["path"] for r in res["regressions"]} == \
        {"m.prefix_hit_rate", "m.unique_block_frac"}


def test_describe_prefix_cache_and_report(capsys):
    off = describe_prefix_cache(None)
    assert off["enabled"] is False
    assert off["defaults_when_armed"]["min_prefix_blocks"] == \
        PrefixCacheConfig().min_prefix_blocks
    on = describe_prefix_cache({"max_blocks": 64, "min_prefix_blocks": 2})
    assert on["enabled"] and on["max_blocks"] == 64
    with pytest.raises(ValueError, match="unknown"):
        describe_prefix_cache({"bogus": 1})

    from deepspeed_tpu.env_report import prefix_cache_report
    prefix_cache_report()
    text = capsys.readouterr().out
    assert "prefix sharing" in text.lower()
    assert "copy-on-write" in text and "eviction" in text
    assert "--shared-prefix-tokens" in text


# ===================================================================
# interleaving explorer: the refcount protocol under every ordering
# ===================================================================

def test_prefix_interleave_sweep_is_clean():
    """All 720 orderings of publish/attach/finish/evict/clear over the
    real allocator + radix cache conserve the pool and never tear a
    refcount (docs/static-analysis.md#interleave, DSTPU321)."""
    from deepspeed_tpu.analysis.interleave import (explore,
                                                   prefix_sharing_scenario)
    rep = explore(prefix_sharing_scenario())
    assert rep["explored"] == rep["total_permutations"] == 720
    assert rep["ok"], "\n".join(str(f) for f in rep["findings"][:5])


def test_prefix_interleave_reports_seeded_violation():
    """Detector integrity: a scenario whose event leaks a block must
    produce DSTPU321 findings — a sweep that cannot see a seeded leak
    proves nothing about the clean one above."""
    from deepspeed_tpu.analysis import interleave as il

    def build(workdir):
        return {"alloc": pk.BlockAllocator(4), "violations": []}

    def ev_leak(w):
        w["alloc"].alloc(1)     # never freed; settle does not clean up

    def check(w):
        viol = list(w["violations"])
        if w["alloc"].used_blocks:
            viol.append(f"{w['alloc'].used_blocks} block(s) leaked")
        return viol

    rep = il.explore({"name": "seeded-leak", "build": build,
                      "events": [("leak", ev_leak)],
                      "settle": lambda w: None, "check": check,
                      "rule": il.PREFIX_INTERLEAVE_VIOLATION})
    assert not rep["ok"]
    assert rep["findings"][0].rule == "DSTPU321"


def test_cli_smoke_bench_diff_gates_prefix_bench(tmp_path):
    """Tier-1 smoke over the REAL CLI: ds_bench_diff gates the
    committed prefix-sharing fixture against itself (clean exit), and a
    degraded twin — hit rate halved, unique-block fraction doubled —
    regresses on exactly the prefix-sharing metrics."""
    artifact = os.path.join(REPO, "tests", "data",
                            "bench_diff_fixture_prefix.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_bench_diff"),
         artifact, artifact],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "no regression" in r.stdout

    with open(artifact) as f:
        doc = json.load(f)
    rung = doc["serving_shared_prefix"]
    worse = json.loads(json.dumps(doc))
    worse["serving_shared_prefix"]["shared"]["prefix_hit_rate"] = \
        rung["shared"]["prefix_hit_rate"] / 2
    worse["serving_shared_prefix"]["shared"]["unique_block_frac"] = \
        min(1.0, rung["shared"]["unique_block_frac"] * 2)
    bad = tmp_path / "worse.json"
    bad.write_text(json.dumps(worse))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_bench_diff"),
         artifact, str(bad), "--json"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    regressed = {row["path"] for row in
                 json.loads(r.stdout)["regressions"]}
    assert regressed == {
        "serving_shared_prefix.shared.prefix_hit_rate",
        "serving_shared_prefix.shared.unique_block_frac"}
