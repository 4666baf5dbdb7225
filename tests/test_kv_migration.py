"""Crash-consistent KV migration (docs/serving.md#kv-migration).

Layers under test, bottom up:

- **block images** (`paged_kv.export_block_image` family): int8 pools
  round-trip bit-exact (the token-identity guarantee), full-width pools
  quantize within tolerance, per-block digests catch tampering, the
  atomic save/load protocol makes torn writes invisible and corrupt
  payloads detectable (`serving.kv_snapshot_torn`,
  `serving.kv_image_corrupt` fault sites);
- **serving engine**: cadence snapshots + keep_n rotation, the armed
  config leaves the traced decode step byte-identical, cross-engine
  `submit_restored` resumes token-identical, every restore defect
  degrades loudly to recompute, `crash_during_restore` leaks nothing,
  and retention deletes images at finish while close() keeps only
  still-pending uids;
- **router**: restore-first handoff from a dead replica (migrated
  stream token-identical, counters populated), fallback requeue when no
  manifest-valid tag exists;
- **tooling**: ds_bench_diff classifies the migration counters,
  ds_report prints the resolved snapshot policy.
"""

import os
import shutil

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.checkpoint import atomic
from deepspeed_tpu.inference import paged_kv as pk
from deepspeed_tpu.inference.serving import (ServingEngine, ServingConfig,
                                             Request, KVSnapshotConfig,
                                             describe_kv_snapshot,
                                             stream_snapshot_dir)
from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config


@pytest.fixture(scope="module")
def tiny():
    cfg = GPT2Config(vocab_size=64, max_seq=64, n_embd=32, n_layer=2,
                     n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    model = GPT2(cfg, dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0))


PROMPT = np.arange(1, 9, dtype=np.int32)


def _cfg(journal_dir, kv_snapshot=None, **kw):
    return ServingConfig(batch_slots=2, block_size=8, max_new_tokens=24,
                         kv_bits=8, journal_dir=journal_dir,
                         preflight=False, kv_snapshot=kv_snapshot, **kw)


def _req(uid=None, mnt=24):
    return Request(tokens=PROMPT.copy(), max_new_tokens=mnt,
                   do_sample=True, temperature=0.9, seed=7, uid=uid)


# ===================================================================
# block images: round-trip, digests, atomic save/load, fault sites
# ===================================================================

def _int8_pool(num_blocks=6, rng=None):
    rng = rng or np.random.default_rng(3)
    pool = pk.init_pool(2, num_blocks, 8, 4, 8, jnp.float32, kv_bits=8)
    filled = {}
    for name in ("k", "v"):
        filled[name] = jnp.asarray(rng.integers(
            -127, 128, pool[name].shape, dtype=np.int8))
        sname = f"{name}_scale"
        filled[sname] = jnp.asarray(rng.uniform(
            0.01, 1.0, pool[sname].shape).astype(np.float32))
    return dict(pool, **filled)


def test_block_image_int8_roundtrip_bit_exact():
    """int8 pool -> image -> int8 pool is a pass-through: the restored
    blocks are byte-identical, which is what makes a restored stream
    token-identical to the dead replica's."""
    src = _int8_pool()
    dst = pk.init_pool(2, 6, 8, 4, 8, jnp.float32, kv_bits=8)
    img = pk.export_block_image(src, [2, 4])
    assert int(img["source_bits"]) == 8
    assert len(img["block_sha256"]) == 2
    dst = pk.import_block_image(dst, [1, 3], img)
    for name in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(
            np.asarray(src[name][:, [2, 4]]),
            np.asarray(dst[name][:, [1, 3]]))


def test_block_image_fp_pool_quantizes_within_tolerance():
    rng = np.random.default_rng(11)
    src = pk.init_pool(2, 5, 8, 4, 8, jnp.float32, kv_bits=16)
    src = dict(src,
               k=jnp.asarray(rng.normal(size=src["k"].shape)
                             .astype(np.float32)),
               v=jnp.asarray(rng.normal(size=src["v"].shape)
                             .astype(np.float32)))
    dst = pk.init_pool(2, 5, 8, 4, 8, jnp.float32, kv_bits=16)
    img = pk.export_block_image(src, [1, 2])
    assert int(img["source_bits"]) == 16
    dst = pk.import_block_image(dst, [1, 2], img)
    for name in ("k", "v"):
        a = np.asarray(src[name][:, [1, 2]])
        b = np.asarray(dst[name][:, [1, 2]])
        err = np.abs(a - b).max()
        assert 0 < err < 0.05, f"{name}: quant err {err}"


def test_block_image_pad_to_only_touches_scratch():
    """pad_to pins the scatter shape; the padding lanes write zeros
    into SCRATCH_BLOCK only — every allocatable block is untouched."""
    src = _int8_pool()
    base = pk.init_pool(2, 6, 8, 4, 8, jnp.float32, kv_bits=8)
    img = pk.export_block_image(src, [2])
    plain = pk.import_block_image(base, [3], img)
    padded = pk.import_block_image(base, [3], img, pad_to=5)
    for name in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(
            np.asarray(plain[name][:, 1:]),
            np.asarray(padded[name][:, 1:]))


def test_block_image_digest_catches_tamper():
    src = _int8_pool()
    img = pk.export_block_image(src, [1, 3])
    img["k"] = np.array(img["k"], copy=True)
    img["k"][0, 1, 0, 0] ^= 0x7F
    assert pk.verify_block_image(img) == [1]
    dst = pk.init_pool(2, 6, 8, 4, 8, jnp.float32, kv_bits=8)
    with pytest.raises(pk.BlockImageError, match="digest"):
        pk.import_block_image(dst, [1, 3], img)


def test_block_image_geometry_and_count_checked():
    src = _int8_pool()
    img = pk.export_block_image(src, [1, 3])
    dst = pk.init_pool(2, 6, 8, 4, 8, jnp.float32, kv_bits=8)
    with pytest.raises(pk.BlockImageError, match="blocks"):
        pk.import_block_image(dst, [1], img)
    narrow = pk.init_pool(2, 6, 4, 4, 8, jnp.float32, kv_bits=8)
    with pytest.raises(pk.BlockImageError, match="geometry"):
        pk.import_block_image(narrow, [1, 3], img)


def test_save_load_atomic_commit(tmp_path):
    src = _int8_pool()
    img = pk.export_block_image(src, [2, 4])
    d = str(tmp_path / "snaps")
    pk.save_block_image(d, "snap-000004", img, meta={"stream": {"uid": 9}})
    assert atomic.find_valid_tags(d) == ["snap-000004"]
    got, meta = pk.load_block_image(os.path.join(d, "snap-000004"))
    assert meta["stream"]["uid"] == 9
    assert pk.verify_block_image(got) == []
    np.testing.assert_array_equal(np.asarray(img["k"]),
                                  np.asarray(got["k"]))


def test_torn_snapshot_is_never_restorable(tmp_path, fault_harness):
    """A kill between staging and commit leaves only a ``.tmp`` dir:
    invisible to find_valid_tags, so a survivor restores the OLDER
    committed tag instead of half an image."""
    fault = fault_harness
    src = _int8_pool()
    img = pk.export_block_image(src, [2, 4])
    d = str(tmp_path / "snaps")
    pk.save_block_image(d, "snap-000004", img, meta={})
    fault.configure("crash_at=serving.kv_snapshot_torn")
    with pytest.raises(fault.InjectedCrash):
        pk.save_block_image(d, "snap-000008", img, meta={})
    assert os.path.isdir(os.path.join(d, "snap-000008.tmp"))
    assert atomic.find_valid_tags(d) == ["snap-000004"]
    assert atomic.find_latest_valid(d) == "snap-000004"


def test_corrupt_image_detected_at_load(tmp_path, fault_harness):
    """``corrupt_at=serving.kv_image_corrupt`` flips a committed byte
    AFTER the rename — the manifest sha catches it at load, and the
    caller's contract is a typed error, never a garbage restore."""
    fault = fault_harness
    src = _int8_pool()
    img = pk.export_block_image(src, [2, 4])
    d = str(tmp_path / "snaps")
    fault.configure("corrupt_at=serving.kv_image_corrupt")
    pk.save_block_image(d, "snap-000004", img, meta={})
    with pytest.raises(pk.BlockImageError):
        pk.load_block_image(os.path.join(d, "snap-000004"), verify="full")


# ===================================================================
# serving engine: cadence, rotation, jaxpr identity, restore paths
# ===================================================================

def _run_until_deep(srv, uid, steps=11):
    srv.submit(_req(uid=uid))
    for _ in range(steps):
        srv.step()


def test_engine_snapshot_cadence_and_rotation(tiny, tmp_path):
    model, params = tiny
    srv = ServingEngine(model=model, params=params,
                        config=_cfg(str(tmp_path / "j"),
                                    {"every_tokens": 4, "keep_n": 2}))
    _run_until_deep(srv, 5)
    sdir = stream_snapshot_dir(str(tmp_path / "j"), 5)
    tags = atomic.find_valid_tags(sdir)
    assert tags, "no snapshot written at cadence"
    assert len(tags) <= 2, f"keep_n=2 violated: {tags}"
    st = srv.stats()["kv_snapshot"]
    assert st["snapshots"] >= 2
    assert st["policy"]["every_tokens"] == 4
    srv.close()


def test_kv_snapshot_armed_jaxpr_identical(tiny, tmp_path):
    """Arming kv_snapshot must leave the TRACED decode step
    byte-identical: snapshots are host-side exports, never program
    content (the sanitizer's PR-9 equality discipline)."""
    model, params = tiny

    def jaxpr_text(kv):
        srv = ServingEngine(model=model, params=params,
                            config=_cfg(str(tmp_path / f"jx-{bool(kv)}"),
                                        kv))
        srv._build_decode()
        jx = str(jax.make_jaxpr(srv._decode)(*srv._decode_args()))
        srv.close()
        return jx

    assert jaxpr_text(None) == jaxpr_text({"every_tokens": 4})


def test_cross_engine_restore_token_identical(tiny, tmp_path):
    """The acceptance path end to end: engine A snapshots at cadence
    and dies (simulated by copying its snapshot dir aside); engine B
    seats the image and re-decodes only the suffix — the final tokens
    match A's own completion exactly (int8 images are pass-through)."""
    model, params = tiny
    ja = str(tmp_path / "ja")
    sa = ServingEngine(model=model, params=params,
                       config=_cfg(ja, {"every_tokens": 4, "keep_n": 2}))
    _run_until_deep(sa, 5)
    saved = str(tmp_path / "crashcopy")
    shutil.copytree(stream_snapshot_dir(ja, 5), saved)
    while sa.results[5]["outcome"] is None:
        sa.step()
    oracle = list(sa.results[5]["tokens"])
    sa.close()

    sb = ServingEngine(model=model, params=params,
                       config=_cfg(str(tmp_path / "jb")))
    tag = atomic.find_latest_valid(saved)
    out = sb.submit_restored(_req(uid=5), os.path.join(saved, tag))
    assert out["restored"] and out["tokens_saved"] > 0
    while sb.results[5]["outcome"] is None:
        sb.step()
    assert list(sb.results[5]["tokens"]) == oracle
    st = sb.stats()["kv_snapshot"]
    assert st["migrated_streams"] == 1
    assert st["recompute_tokens_saved"] == out["tokens_saved"]
    sb.close()


def test_restore_fallback_on_corrupt_image(tiny, tmp_path):
    """A corrupt committed image degrades loudly: submit_restored
    returns restored=False with a reason, counts a migration_fallback,
    and the stream still completes token-identical via recompute —
    never lost, never garbage."""
    model, params = tiny
    ja = str(tmp_path / "ja")
    sa = ServingEngine(model=model, params=params,
                       config=_cfg(ja, {"every_tokens": 4, "keep_n": 2}))
    _run_until_deep(sa, 5)
    saved = str(tmp_path / "crashcopy")
    shutil.copytree(stream_snapshot_dir(ja, 5), saved)
    while sa.results[5]["outcome"] is None:
        sa.step()
    oracle = list(sa.results[5]["tokens"])
    sa.close()

    for tag in atomic.find_valid_tags(saved):
        npz = os.path.join(saved, tag, "image.npz")
        blob = bytearray(open(npz, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        open(npz, "wb").write(bytes(blob))

    sb = ServingEngine(model=model, params=params,
                       config=_cfg(str(tmp_path / "jb")))
    tag = atomic.find_latest_valid(saved, level="size")
    out = sb.submit_restored(_req(uid=5), os.path.join(saved, tag))
    assert not out["restored"] and out["reason"]
    while sb.results[5]["outcome"] is None:
        sb.step()
    assert list(sb.results[5]["tokens"]) == oracle
    assert sb.stats()["kv_snapshot"]["migration_fallbacks"] == 1
    sb.close()


def test_crash_during_restore_leaks_nothing(tiny, tmp_path,
                                            fault_harness):
    """``crash_during_restore`` fires after block allocation: the
    exception propagates (a real kill dies here), but on a SURVIVING
    engine the blocks must go back — the allocator is whole, the
    armed sanitizer finds nothing, and the engine still serves."""
    fault = fault_harness
    model, params = tiny
    ja = str(tmp_path / "ja")
    sa = ServingEngine(model=model, params=params,
                       config=_cfg(ja, {"every_tokens": 4, "keep_n": 2}))
    _run_until_deep(sa, 5)
    saved = str(tmp_path / "crashcopy")
    shutil.copytree(stream_snapshot_dir(ja, 5), saved)
    while sa.results[5]["outcome"] is None:
        sa.step()
    sa.close()

    sb = ServingEngine(model=model, params=params,
                       config=_cfg(str(tmp_path / "jb"), sanitize=True))
    free_before = sb.allocator.free_blocks
    tag = atomic.find_latest_valid(saved)
    fault.configure("crash_at=serving.crash_during_restore")
    with pytest.raises(fault.InjectedCrash):
        sb.submit_restored(_req(uid=5), os.path.join(saved, tag))
    assert sb.allocator.free_blocks == free_before
    # the uid survived in the queue (journaled before the attempt):
    # drain it, then prove the engine is still whole
    while sb.results[5]["outcome"] is None:
        sb.step()
    out = sb.run([_req(uid=77, mnt=4)])
    assert out[77]["outcome"] == "ok"
    assert sb.stats()["sanitizer"]["findings"] == 0
    sb.close()


def test_retention_finish_deletes_close_keeps_pending(tiny, tmp_path):
    """The retention fix, both halves: a finished uid's images are
    deleted at _finish (nothing ever restores a completed uid), and
    close() deletes every non-pending dir but KEEPS a still-pending
    uid's images — the crash-recovery asset (the leak regression).
    ``drain_timeout_s=0`` wedges the drain so stream 6 is still
    journaled in-flight at close — the restorable case."""
    model, params = tiny
    jd = str(tmp_path / "j")
    srv = ServingEngine(model=model, params=params,
                        config=_cfg(jd, {"every_tokens": 4, "keep_n": 2},
                                    drain_timeout_s=0.0))
    # stream 5 runs to completion; stream 6 stays mid-flight at close
    srv.run([_req(uid=5)])
    assert not os.path.isdir(stream_snapshot_dir(jd, 5))
    _run_until_deep(srv, 6)
    assert atomic.find_valid_tags(stream_snapshot_dir(jd, 6))
    srv.close()
    assert os.path.isdir(stream_snapshot_dir(jd, 6)), \
        "close() deleted a pending uid's snapshots — the restore asset"
    root = os.path.join(jd, "kv_snapshots")
    assert sorted(os.listdir(root)) == [
        os.path.basename(stream_snapshot_dir(jd, 6))]


# ===================================================================
# router: restore-first handoff, fallback on unusable images
# ===================================================================

def _router_pair(model, params, root, kv=None):
    from deepspeed_tpu.inference.router import (ReplicaRouter,
                                                RouterConfig, LocalReplica)
    kv = kv or {"every_tokens": 4, "keep_n": 2}
    engines = {n: ServingEngine(model=model, params=params,
                                config=_cfg(os.path.join(root, n), kv))
               for n in ("a", "b")}
    router = ReplicaRouter(
        [LocalReplica(n, e) for n, e in engines.items()],
        config=RouterConfig())
    return router, engines


def _solo_oracle(model, params, root):
    srv = ServingEngine(model=model, params=params,
                        config=_cfg(os.path.join(root, "oracle")))
    try:
        return list(srv.run([_req(uid=5)])[5]["tokens"])
    finally:
        srv.close()


def test_router_restore_first_handoff(tiny, tmp_path):
    from deepspeed_tpu.inference.router import DEAD
    model, params = tiny
    oracle = _solo_oracle(model, params, str(tmp_path))
    router, engines = _router_pair(model, params, str(tmp_path))
    uid = router.submit(_req(uid=5))
    for _ in range(12):
        router.pump()
    owner = "a" if router.states()["a"]["assigned"] else "b"
    router._set_state(router._replicas[owner], DEAD, router._clock(),
                      "test kill")
    out = router.run(timeout_s=60)
    assert out[uid]["outcome"] == "ok"
    assert list(out[uid]["tokens"]) == oracle
    s = router.stats()
    assert s["migrated_streams"] == 1 and s["migrated_uids"] == [uid]
    assert s["migration_fallbacks"] == 0
    assert s["recompute_tokens_saved"] > 0 and s["restore_ms"]
    assert s["lost"] == 0 and s["duplicates_suppressed"] == 0
    router.close()


def test_router_fallback_without_valid_tag(tiny, tmp_path):
    """Snapshot dir exists but holds no manifest-valid tag (all torn):
    the handoff counts a migration_fallback, emits the typed event,
    and the requeued recompute still lands token-identical."""
    from deepspeed_tpu.inference.router import DEAD
    model, params = tiny
    oracle = _solo_oracle(model, params, str(tmp_path))
    router, engines = _router_pair(model, params, str(tmp_path))
    uid = router.submit(_req(uid=5))
    for _ in range(12):
        router.pump()
    owner = "a" if router.states()["a"]["assigned"] else "b"
    sdir = stream_snapshot_dir(os.path.join(str(tmp_path), owner), uid)
    for tag in os.listdir(sdir):         # tear every committed tag
        mf = os.path.join(sdir, tag, "manifest.json")
        if os.path.exists(mf):
            os.unlink(mf)
    router._set_state(router._replicas[owner], DEAD, router._clock(),
                      "test kill")
    out = router.run(timeout_s=60)
    assert out[uid]["outcome"] == "ok"
    assert list(out[uid]["tokens"]) == oracle
    s = router.stats()
    assert s["migrated_streams"] == 0
    assert s["migration_fallbacks"] == 1
    assert s["requeued_total"] == 1 and s["lost"] == 0
    router.close()


# ===================================================================
# tooling: bench_diff classification, ds_report policy echo
# ===================================================================

def test_bench_diff_classifies_migration_counters():
    from deepspeed_tpu.analysis.bench_diff import classify, compare
    assert classify("migrated_streams") == "higher"
    assert classify("recompute_tokens_saved") == "higher"
    assert classify("migration_fallbacks") == "lower"
    assert classify("restore_ms") == "lower"       # the _ms suffix rule
    res = compare({"m": {"migrated_streams": 4, "migration_fallbacks": 1,
                         "restore_ms": 10.0}},
                  {"m": {"migrated_streams": 1, "migration_fallbacks": 3,
                         "restore_ms": 10.0}})
    bad = {r["path"] for r in res["regressions"]}
    assert bad == {"m.migrated_streams", "m.migration_fallbacks"}


def test_bench_diff_zero_contract_still_gates_router_counters():
    from deepspeed_tpu.analysis.bench_diff import compare
    res = compare({"lost_requests": 0, "duplicate_answers": 0},
                  {"lost_requests": 1, "duplicate_answers": 2})
    assert {r["path"] for r in res["regressions"]} == \
        {"lost_requests", "duplicate_answers"}


def test_describe_kv_snapshot_and_report(capsys):
    off = describe_kv_snapshot(None)
    assert off["enabled"] is False
    assert off["defaults_when_armed"]["every_tokens"] == \
        KVSnapshotConfig().every_tokens
    on = describe_kv_snapshot({"every_tokens": 8, "keep_n": 3})
    assert on["enabled"] and on["every_tokens"] == 8 and on["keep_n"] == 3

    from deepspeed_tpu.env_report import kv_snapshot_report
    kv_snapshot_report()
    text = capsys.readouterr().out
    assert "KV snapshot" in text and "cadence" in text
    assert "retention" in text and "handoff" in text
