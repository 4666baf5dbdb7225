"""AFMoE (models/afmoe.py, Arcee Trinity): window and global attention layers
over TWO kinds of paged block, a gated grouped-query attention with rope on
the window layers only, sigmoid top-k routing with a selection bias, of which
a chip holds a share.  Every number is held against the benchmark's plain
reference (``benchmark/reference/afmoe.py``), which shares no code with the
program and knows no cache, no ring and no kernel.

Tiny model at widths that keep the ratios: 5 layers (one dense, four expert
layers; the last of them global, the others window), hidden 64, 4 query
heads over 2 K/V heads of 16, 16 experts (top-4) of width 32 and a shared
one; a WINDOW OF 8 over BLOCKS OF 4, so a 40-token stream wraps its ring of
3 blocks several times; seeded weights, float32 (a wrong ring entry or a key
that should have slid out stands orders above the rounding), the projections
that feed the scores enlarged (``sharp``).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.analysis import sanitize
from deepspeed_tpu.analysis.lint import lifecycle
from deepspeed_tpu.inference import Request, ServingEngine, paged_kv as pk
from deepspeed_tpu.models import afmoe, build
from deepspeed_tpu.models.jamba import swiglu
from deepspeed_tpu.moe import dropless
from benchmark.reference import afmoe as reference

PRESET = afmoe.PRESETS["afmoe-tiny"]
WINDOW, BLOCK = PRESET["sliding_window"], 4
RING = pk.ring_blocks(WINDOW, BLOCK)
TOL = 1e-3          # of the largest reference logit; float32 reads ~1e-6


def tiny(dtype=jnp.float32, impl="kernel", **overrides):
    return build("afmoe-tiny", dtype=dtype, paged_attention_impl=impl,
                 **{"max_position_embeddings": 64, **overrides})


def ref_cfg(model, **extra):
    """The reference's configuration (published key names) of ``model``."""
    c = model.config
    return {"num_attention_heads": c.n_head,
            "num_key_value_heads": c.n_kv_head, "head_dim": c.head_dim,
            "hidden_size": c.hidden_size, "rms_norm_eps": c.rms_norm_eps,
            "sliding_window": c.sliding_window, "rope_theta": c.rope_theta,
            "num_hidden_layers": c.num_hidden_layers,
            "num_dense_layers": c.num_dense_layers,
            "layer_types": list(c.types), "mup_enabled": c.mup_enabled,
            "score_func": c.score_func, "route_norm": c.route_norm,
            "route_scale": c.route_scale,
            "num_experts_per_tok": c.num_experts_per_tok, **extra}


def sharp(params):
    """q and k enlarged: scores of order 1 and a softmax far from uniform
    (at the initialisation's 0.02 attention is nearly an average and a wrong
    position hardly moves a logit)."""
    attn = dict(params["attn"])
    attn.update(q_w=8.0 * attn["q_w"], k_w=8.0 * attn["k_w"])
    return dict(params, attn=attn)


@pytest.fixture(scope="module")
def model_params():
    m = tiny()
    return m, sharp(m.init(jax.random.PRNGKey(3)))


def tokens(seed, *shape, hi=PRESET["vocab_size"]):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                         hi), np.int32)


def rel_err(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


# --------------------------------------------------------- (a) whole model
def test_logits_match_the_reference_past_the_window(model_params):
    m, params = model_params
    toks = jnp.asarray(tokens(1, 2, 40))            # 5 windows long
    got = jax.jit(m.apply)(params, toks)
    ref = jax.jit(lambda p: reference.logits(ref_cfg(m), p, toks))(params)
    assert rel_err(got, ref) < TOL


@pytest.mark.parametrize("T", [6, 40], ids=["under_the_window", "5_windows"])
def test_logits_match_the_reference_through_the_flash_forward(
        model_params, monkeypatch, T):
    """What a TPU runs (``flash_attention_available`` forced true, the
    kernels interpreted, the band's blocks 16 so that 40 tokens are three):
    every sliding layer's prompt goes through the windowed forward, one shorter
    than the window (the causal triangle) as one five windows long, every
    full layer's through the dense causal call, and the logits match the
    reference as the ``jax.numpy`` band's do."""
    import importlib
    import deepspeed_tpu.ops as ops
    fa = importlib.import_module(
        "deepspeed_tpu.ops.transformer.flash_attention")
    monkeypatch.setattr(ops, "flash_attention_available", lambda: True)
    monkeypatch.setattr(fa, "_WINDOW_BLOCK", 16)
    calls = {"band": 0, "causal": 0}
    for name, key in (("_window_fwd", "band"), ("_fwd", "causal")):
        def counted(*a, _inner=getattr(fa, name), _key=key, **kw):
            calls[_key] += 1
            return _inner(*a, **kw)
        monkeypatch.setattr(fa, name, counted)
    m, params = model_params
    toks = jnp.asarray(tokens(1, 2, T))
    got = jax.jit(m.apply)(params, toks)
    ref = jax.jit(lambda p: reference.logits(ref_cfg(m), p, toks))(params)
    assert rel_err(got, ref) < TOL
    assert calls == {"band": len(m.window_layers),
                     "causal": len(m.global_layers)}


def test_loss_matches_the_reference(model_params):
    m, params = model_params
    batch = jnp.asarray(tokens(2, 2, 25))
    ref = reference.loss(ref_cfg(m), params, batch)
    assert abs(float(m.loss(params, batch)) - float(ref)) < 1e-4 * float(ref)


@pytest.mark.parametrize("change, where", [
    ("sliding_window", "program"),       # a window of 9: one key too many
    ("rope_on_the_global_layer", "reference"),
    ("bias_in_the_weights", "reference"),
    ("no_gate", "program")])
def test_another_forward_fails(model_params, monkeypatch, change, where):
    """The comparison sees each of the mechanisms: a program or a reference
    that computes it otherwise stands far above ``TOL``."""
    m, params = model_params
    # a bias large enough to move weights, not picks alone
    params = dict(params, moe=dict(params["moe"], expert_bias=20.0
                                   * params["moe"]["expert_bias"]))
    cfg = ref_cfg(m)
    if change == "sliding_window":
        m = tiny(sliding_window=WINDOW + 1)
    elif change == "rope_on_the_global_layer":
        cfg["layer_types"] = ["sliding_attention"] * len(cfg["layer_types"])
        cfg["sliding_window"] = 10 ** 6
        # all layers rotate and none is cut: the global layer alone differs
        m = tiny(sliding_window=10 ** 6)
    elif change == "bias_in_the_weights":
        monkeypatch.setattr(reference, "route", lambda cfg, s, x: (
            lambda w: w / w.sum(-1, keepdims=True) * cfg["route_scale"])(
            jnp.where(reference.picks(cfg, x), x, 0.0)))
    elif change == "no_gate":
        # ``gate_proj`` read as zeros is a gate of one half everywhere,
        # which the norm after ``o_proj`` takes out: no gate
        after = afmoe.Afmoe._after_attention
        monkeypatch.setattr(
            afmoe.Afmoe, "_after_attention",
            lambda self, params, p, *rest: after(self, params, dict(
                p, gate_w=jnp.zeros_like(p["gate_w"])), *rest))
    toks = jnp.asarray(tokens(1, 2, 40))
    got = m.apply(params, toks)
    ref = reference.logits(cfg, params, toks)
    assert rel_err(got, ref) > 10 * TOL, change


# ------------------------------------------- (b) both kinds of block, served
PROMPTS = (5, 21, 13, 30, 9, 26)       # none on a 4-token bucket's edge
NEW = (30, 20, 6, 12, 28, 4)           # streams of 35, 41, 19, 42, 37, 30


def live_logit_error(srv, params, cfg):
    """The benchmark's check: the NEXT decode step's logits through the
    paged path (both kinds of block), against the reference's full forward
    over each live slot's history."""
    p, pool, tables, lengths, toks = srv._decode_args()[:5]
    if not hasattr(srv, "_next_logits"):
        srv._next_logits = jax.jit(lambda p, t, pl, tb, ln:
                                   srv.model.decode_step_paged(
                                       p, t, pl, tb, ln)[0])
        srv._reference = jax.jit(lambda p, t, pos: reference.logits_at(
            cfg, p, t, pos))
    got = np.asarray(srv._next_logits(p, toks, pool, tables, lengths))
    live = [i for i, s in enumerate(srv._slots) if s is not None]
    if not live:                    # settling the step freed the last slot
        return 0.0, 0
    rows = np.zeros((len(live), 64), np.int32)
    last = []
    for n, i in enumerate(live):
        s = srv._slots[i]
        hist = np.concatenate([np.asarray(s.req.tokens),
                               np.asarray(s.out_tokens)])
        rows[n, :len(hist)] = hist
        last.append(len(hist) - 1)
    ref = srv._reference(params, jnp.asarray(rows), jnp.asarray(last))
    return rel_err(got[live], ref), max(last) + 1


def serve(model, params, n, slots, every=1, **config):
    eng = ds.init_inference(model, params=params, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={
        "batch_slots": slots, "block_size": BLOCK, "sanitize": True,
        **config})
    uids = [srv.submit(Request(tokens=tokens(20 + i, PROMPTS[i]),
                               max_new_tokens=NEW[i])) for i in range(n)]
    worst, longest, k = 0.0, 0, 0
    cfg = ref_cfg(model)
    while srv.step():
        k += 1
        if k % every == 0 and any(s is not None for s in srv._slots):
            err, length = live_logit_error(srv, params, cfg)
            worst, longest = max(worst, err), max(longest, length)
    assert [len(srv.results[u]["tokens"]) for u in uids] == list(NEW[:n])
    return worst, longest, srv


@pytest.mark.parametrize("impl, n, slots, every", [
    ("gather", 6, 3, 1), ("kernel", 2, 2, 6)])
def test_serving_matches_the_reference(model_params, impl, n, slots, every):
    """Prefill, then decoding through the growing table and the ring, far
    past the window (streams of up to 42 tokens over a ring of 3 blocks of
    4): the logits are the reference's full forward's; every block of both
    kinds comes home."""
    _, params = model_params
    worst, longest, srv = serve(tiny(impl=impl), params, n, slots, every)
    assert worst < TOL and longest > 4 * WINDOW
    st = srv.stats()
    assert st["completed"] == n
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    assert srv.window_allocator.free_blocks == srv.window_num_blocks - 1
    assert srv.model.paged_attention_impl() == impl
    assert (st["window_ring_blocks"], st["sliding_window"]) == (RING, WINDOW)
    assert st["sanitizer"]["findings"] == 0
    assert st["window_sanitizer"]["findings"] == 0
    assert st["window_sanitizer"]["live_blocks"] == 0
    srv.close()


def test_cached_decoding_matches_the_full_forward(model_params):
    m, params = model_params
    toks = jnp.asarray(tokens(4, 2, 30))
    full = m.apply(params, toks)
    cache = m.init_cache(2, 32, dtype=jnp.float32)
    head, cache = m.apply_with_cache(params, toks[:, :11], cache)
    rows = [head]
    for t in range(11, 30):
        row, cache = m.apply_with_cache(params, toks[:, t:t + 1], cache)
        rows.append(row)
    assert rel_err(jnp.concatenate(rows, axis=1), full) < TOL


def test_a_long_prompt_in_chunks_is_the_same_forward(model_params,
                                                     monkeypatch):
    """What works a token at a time runs over a long prompt in chunks
    (``_over_tokens``); chunks of 16 and of 7 (the last one padded) give
    the whole prompt's logits and counters."""
    m, params = model_params
    toks = jnp.asarray(tokens(6, 1, 40))
    whole = m.apply(params, toks)
    for size in (16, 7):
        monkeypatch.setattr(afmoe, "_CHUNK_TOKENS", size)
        assert rel_err(m.apply(params, toks), whole) < 1e-5


# ------------------------------------------------- (c) one chip's share
def test_the_eight_shares_of_an_expert_layer_add_up_to_the_whole():
    """Eight chips with 2 of the 16 experts each: their routed parts, and
    the shared expert counted once, add up to the uncut reference layer."""
    m = tiny()
    params = m.init(jax.random.PRNGKey(5))
    pm = params["moe"]
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 24, 64), jnp.float32)
    cfg = ref_cfg(m)
    whole, _ = reference._experts(
        cfg, jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), pm), 1,
        u[0])
    shared = swiglu({"gate_w": pm["shared_gate_w"][1],
                     "up_w": pm["shared_up_w"][1],
                     "down_w": pm["shared_down_w"][1]}, u[0])
    total, pairs = shared, 0
    for first in range(0, 16, 2):
        share = tiny(experts_held=(first, 2))
        part = dict(pm, **{k: pm[k][:, first:first + 2]
                           for k in ("gate_w", "up_w", "down_w")})
        y, counts, _ = share._moe(part, u, 1)
        total = total + (y[0] - shared)
        pairs += int(counts[0])
    assert rel_err(total, whole) < 1e-5
    assert pairs == 24 * 4                  # every pair fell to one share


def test_the_program_with_one_share_equals_the_reference_with_it():
    m = tiny(experts_held=(4, 6), vocab_held=(128, 256))
    params = sharp(m.init(jax.random.PRNGKey(7)))
    toks = jnp.asarray(tokens(8, 2, 20) % 256 + 128)
    cfg = ref_cfg(m, experts_held=[4, 6], vocab_held=[128, 256])
    assert rel_err(m.apply(params, toks),
                   reference.logits(cfg, params, toks)) < TOL


def test_a_decode_step_reports_the_experts_it_routed_to(model_params):
    """``with_routes``: the picks of each slot's token in every expert
    layer, the reference's picks from its own scores."""
    m, params = model_params
    _, _, srv = serve(m, params, 2, 2, every=10 ** 6)
    srv.submit(Request(tokens=tokens(30, 19), max_new_tokens=8))
    srv.step(), srv.step()
    p, pool, tables, lengths, toks = srv._decode_args()[:5]
    logits, _, routes = srv.model.decode_step_paged(
        p, toks, pool, tables, lengths, with_routes=True)
    s = srv._slots[0]
    hist = np.concatenate([np.asarray(s.req.tokens),
                           np.asarray(s.out_tokens)])
    cfg = ref_cfg(m)
    ref, scores = reference.logits_and_scores_at(
        cfg, params, jnp.asarray(hist[None]), jnp.asarray([len(hist) - 1]))
    assert rel_err(logits[0], ref[0]) < TOL
    assert routes.shape == (4, 2, 4)
    want = np.asarray(reference.picks(cfg, scores[0]))       # (layers, E)
    for layer in range(4):
        assert set(np.asarray(routes[layer, 0]).tolist()) == set(
            np.nonzero(want[layer])[0].tolist())
    srv.close()


# -------------------------------------------------------- (d) the sizes
def test_parameter_count_is_the_shapes_init_makes():
    for kw in ({}, {"experts_held": (2, 4), "vocab_held": (0, 128)}):
        m = tiny(**kw)
        shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
        assert m.num_params() == sum(
            int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    # the published widths, one chip's share of eight (ISSUE 39)
    cut = build("afmoe-tiny", hidden_size=3072, intermediate_size=12288,
                moe_intermediate_size=3072, num_hidden_layers=5,
                num_dense_layers=1, num_attention_heads=48,
                num_key_value_heads=8, head_dim=128, num_experts=256,
                vocab_size=200192, sliding_window=4096,
                experts_held=(0, 32), vocab_held=(0, 25024),
                layer_types=("sliding_attention",) * 4
                + ("full_attention",), max_position_embeddings=17408)
    assert cut.num_params() == 4_321_903_872
    shapes = jax.eval_shape(cut.init, jax.random.PRNGKey(0))
    assert cut.num_params() == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))


# ------------------------------------------- (e) the two kinds of block
def blocks_of(srv, uid):
    s = next(s for s in srv._slots if s is not None and s.req.uid == uid)
    return len(s.blocks), len(s.wblocks)


def test_a_seat_reserves_both_kinds_and_a_short_one_only_what_it_fills(
        model_params):
    m, params = model_params
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={"batch_slots": 3,
                                            "block_size": BLOCK})
    assert srv.window_num_blocks == 1 + 3 * RING     # auto: a ring a slot
    short = srv.submit(Request(tokens=tokens(1, 3), max_new_tokens=3))
    long = srv.submit(Request(tokens=tokens(2, 30), max_new_tokens=20))
    srv.step()
    # 6 tokens: 2 ring blocks for its life, and of the growing table what
    # the prompt and the first write touch (4 tokens: 1 block); 50 tokens:
    # the ring's 3, and 8 global blocks for 31 tokens, 13 at its end
    assert blocks_of(srv, short) == (1, 2)
    assert blocks_of(srv, long) == (8, RING)
    assert srv._promised == 2 + 13  # both lives fit at once: the rule's sum
    assert RING * BLOCK <= WINDOW + BLOCK            # the cap
    row = srv._tables[1]
    assert (row[srv.nb_max:] != pk.SCRATCH_BLOCK).sum() == RING
    assert (srv._tables[0, srv.nb_max:] != pk.SCRATCH_BLOCK).sum() == 2
    cap = srv.capacity()
    assert cap["window_ring_blocks"] == RING
    assert cap["window_free_blocks"] == srv.window_num_blocks - 1 - 2 - RING
    while srv.step():
        pass
    assert srv.window_allocator.free_blocks == srv.window_num_blocks - 1
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    srv.close()


@pytest.mark.parametrize("short_kind, config", [
    ("window", {"num_blocks": 40, "window_num_blocks": 1 + RING + 1}),
    ("global", {"num_blocks": 1 + 13 + 2, "window_num_blocks": 20})])
def test_admission_waits_on_whichever_kind_is_short_and_says_which(
        model_params, short_kind, config):
    m, params = model_params
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={
        "batch_slots": 3, "block_size": BLOCK, **config})
    first = srv.submit(Request(tokens=tokens(2, 30), max_new_tokens=20))
    second = srv.submit(Request(tokens=tokens(3, 30), max_new_tokens=20))
    t0 = srv._spans.rows()[-1].t_end if srv._spans.rows() else 0.0
    srv.step(), srv.step()
    assert sum(s is not None for s in srv._slots) == 1     # one seated
    rows = [r.attrs for r in srv._spans.rows()
            if r.name == "serving.step" and r.t_start >= t0 and r.attrs]
    assert rows[-1]["waits_for_blocks"]
    assert rows[-1]["waits_for_window_blocks"] == (short_kind == "window")
    assert rows[-1]["waits_for_global_blocks"] == (short_kind == "global")
    assert rows[-1]["window_blocks_in_use"] == RING
    assert rows[-1]["window_kv_tokens"] == WINDOW
    assert rows[-1]["window_capped_tokens"] == rows[-1]["kv_tokens"] - WINDOW
    while srv.step():
        pass
    assert {srv.results[u]["outcome"] for u in (first, second)} == {"ok"}
    assert srv.window_allocator.free_blocks == srv.window_num_blocks - 1
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    srv.close()


def test_a_request_no_pool_can_hold_is_refused_at_submit(model_params):
    m, params = model_params
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={
        "batch_slots": 2, "block_size": BLOCK, "window_num_blocks": RING})
    with pytest.raises(ValueError, match="window blocks"):
        srv.submit(Request(tokens=tokens(2, 30), max_new_tokens=20))
    srv.submit(Request(tokens=tokens(2, 3), max_new_tokens=3))   # 2 blocks
    srv.close()


def test_drain_and_a_poisoned_stream_recycle_both_kinds(model_params,
                                                        monkeypatch):
    m, params = model_params
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={
        "batch_slots": 2, "block_size": BLOCK, "sanitize": True})
    bad = srv.submit(Request(tokens=tokens(2, 14), max_new_tokens=20))
    good = srv.submit(Request(tokens=tokens(3, 9), max_new_tokens=25))
    srv.step(), srv.step()
    # poison the first stream's ring: its window layers read NaN, the
    # stream is quarantined, the ring scrubbed and both kinds freed
    s = next(s for s in srv._slots if s.req.uid == bad)
    srv._set_blocks(s.wblocks, poison=True, window=True)
    report = srv.drain()
    assert report["clean"]
    assert srv.results[bad]["outcome"] == "poisoned"
    assert srv.results[good]["outcome"] == "ok"
    assert srv.window_allocator.free_blocks == srv.window_num_blocks - 1
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    assert np.isfinite(np.asarray(srv.pool["wk"], np.float32)).all()
    assert srv.stats()["window_sanitizer"]["findings"] == 0
    srv.close()


def test_an_evicted_stream_gives_both_kinds_back(model_params):
    """A seated stream past its deadline is evicted mid-decode with its
    partial tokens: its table's blocks and its ring's go back, and the
    stream that waited for window blocks is seated."""
    import time
    m, params = model_params
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={
        "batch_slots": 2, "block_size": BLOCK, "num_blocks": 40,
        "window_num_blocks": 1 + RING + 1, "sanitize": True})
    late = srv.submit(Request(tokens=tokens(2, 30), max_new_tokens=20))
    waits = srv.submit(Request(tokens=tokens(3, 30), max_new_tokens=6))
    srv.step(), srv.step()
    assert sum(s is not None for s in srv._slots) == 1
    assert srv.window_allocator.free_blocks == 1
    srv.results[late]["deadline"] = time.monotonic() - 1.0   # force expiry
    while srv.step():
        pass
    assert srv.results[late]["outcome"] == "deadline"
    assert 2 <= len(srv.results[late]["tokens"]) < 20
    assert srv.results[waits]["outcome"] == "ok"
    assert srv.window_allocator.free_blocks == srv.window_num_blocks - 1
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    assert srv.stats()["window_sanitizer"]["findings"] == 0
    srv.close()


def test_the_sanitizer_and_the_lint_know_the_second_table():
    shadow = sanitize.ShadowSanitizer(8, halt=False, kind="window")
    shadow.on_alloc([1, 2], uid=7)
    shadow.on_attach(7, [1, 2])
    shadow.on_free([1], uid=8)                   # still in uid 7's ring
    assert len(shadow.findings) == 1
    assert shadow.findings[0].message.startswith("[window blocks]")
    assert shadow.findings[0].extra["kind"] == "window"
    assert "wblocks" in lifecycle.PROTECTED_ATTRS
    from deepspeed_tpu.analysis import lint_file, select_rules
    bad = ("def steal(slot):\n"
           "    slot.wblocks.append(3)\n")
    found = lint_file("inference/serving.py",
                      rules=select_rules(["DSTPU302"]), src=bad)
    assert [f.rule for f in found] == ["DSTPU302"]
    import deepspeed_tpu.inference.serving as serving_file
    assert lint_file(serving_file.__file__, rules=select_rules(
        ["DSTPU301", "DSTPU302", "DSTPU303", "DSTPU304"])) == []


# ------------------------------------------------------ (f) refused by name
# (what is refused over a ring of window blocks: tests/test_serving_refusals.py)
@pytest.mark.parametrize("key, value", [
    ("rope_scaling", {"type": "linear", "factor": 4.0}),
    ("n_group", 2), ("hidden_act", "gelu"), ("score_func", "softmax_v3"),
    ("layer_types", ("sliding_attention", "chunked_attention"))])
def test_what_the_model_does_not_run_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match={"score_func": "scoring_func"}.get(
            key, key)):
        tiny(**{key: value})


def test_an_int8_ring_and_a_model_of_one_kind_are_refused():
    m = tiny()
    with pytest.raises(ValueError, match="kv_bits"):
        m.init_serving_state(2, 8, BLOCK, kv_bits=8)
    with pytest.raises(ValueError, match="both kinds"):
        tiny(layer_types=("sliding_attention",) * 5).init_serving_state(
            2, 8, BLOCK)


# --------------------------------------------- (g) moe/dropless.py's route
LOGITS = jax.random.normal(jax.random.PRNGKey(11), (64, 16)) * 2
BIAS = 0.3 * jax.random.normal(jax.random.PRNGKey(12), (16,))


def by_hand(logits, k, scoring, bias, norm, scale, both):
    """The route in float64 numpy: ``(experts as sets, weights by expert)``."""
    x = np.asarray(logits, np.float64)
    s = (np.exp(x - x.max(-1, keepdims=True)) / np.exp(
        x - x.max(-1, keepdims=True)).sum(-1, keepdims=True)
        if scoring == "softmax" else 1.0 / (1.0 + np.exp(-x)))
    pick = s if bias is None else s + np.asarray(bias, np.float64)
    experts = np.argsort(-pick, axis=-1, kind="stable")[:, :k]
    w = np.take_along_axis(s, experts, -1)
    if norm:
        w = w / w.sum(-1, keepdims=True) * (scale if both else 1.0)
    else:
        w = w * scale
    out = np.zeros_like(s)
    np.put_along_axis(out, experts, w, -1)
    return out


@pytest.mark.parametrize("scoring, bias, norm, scale, both", [
    ("sigmoid", None, False, 1.0, False),       # sigmoid scores
    ("sigmoid", BIAS, False, 1.0, False),       # the bias picks only
    ("sigmoid", BIAS, True, 2.448, True),       # the family's: all three
    ("sigmoid", None, True, 2.448, True),       # normalise, THEN scale
    ("softmax", None, True, 16.0, False),       # one or the other, as before
    ("softmax", BIAS, False, 16.0, False)],
    ids=["sigmoid", "bias", "afmoe", "norm_then_scale", "norm_only",
         "softmax_bias"])
def test_route_by_hand(scoring, bias, norm, scale, both):
    experts, weights = dropless.route(
        LOGITS, 4, scoring_func=scoring, bias=bias, norm_topk_prob=norm,
        routed_scaling_factor=scale, scale_normed=both)
    got = np.zeros(LOGITS.shape)
    np.put_along_axis(got, np.asarray(experts), np.asarray(weights), -1)
    np.testing.assert_allclose(
        got, by_hand(LOGITS, 4, scoring, bias, norm, scale, both), rtol=1e-5)


def test_a_bias_changes_the_pick_and_not_the_weight():
    plain_e, plain_w = dropless.route(LOGITS, 4, scoring_func="sigmoid")
    e, w = dropless.route(LOGITS, 4, scoring_func="sigmoid", bias=BIAS)
    changed = (np.sort(e, 1) != np.sort(plain_e, 1)).any(1)
    assert 8 < changed.sum() < 64                # many picks moved, not all
    s = np.asarray(jax.nn.sigmoid(LOGITS))
    np.testing.assert_array_equal(np.asarray(w),
                                  np.take_along_axis(s, np.asarray(e), 1))
    # the reference's route (by rank, not by a sort) picks the same
    cfg = {"score_func": "sigmoid", "num_experts_per_tok": 4,
           "route_norm": False}
    ref = np.asarray(reference.route(cfg, jnp.asarray(s), jnp.asarray(s)
                                     + BIAS))
    got = np.zeros_like(ref)
    np.put_along_axis(got, np.asarray(e), np.asarray(w), 1)
    np.testing.assert_allclose(got, ref, rtol=1e-6)


def test_a_biased_pick_stays_inside_the_kept_groups():
    e, _ = dropless.route(LOGITS, 4, topk_method="group_limited_greedy",
                          n_group=4, topk_group=2, scoring_func="sigmoid",
                          bias=BIAS - 2.0)      # every biased score negative
    x = np.asarray(jax.nn.sigmoid(LOGITS)) + np.asarray(BIAS) - 2.0
    for n in range(64):
        groups = set(np.argsort(-x[n].reshape(4, 4).max(1))[:2])
        assert {i // 4 for i in np.asarray(e)[n]} <= groups


@pytest.mark.parametrize("kw", [
    {}, {"norm_topk_prob": True},
    {"routed_scaling_factor": 16.0},
    {"topk_method": "group_limited_greedy", "n_group": 8, "topk_group": 3,
     "routed_scaling_factor": 16.0}],
    ids=["plain", "norm", "scale", "group_limited"])
def test_the_softmax_route_is_bit_for_bit_what_it_was(kw):
    """The arithmetic of the route before sigmoid scores and the bias came
    (PR 34's, written out again here), on the same logits: equal to the
    bit."""
    def before(logits, k, topk_method="greedy", n_group=1, topk_group=1,
               norm_topk_prob=False, routed_scaling_factor=1.0):
        N, E = logits.shape
        scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        pick_from = scores
        if topk_method == "group_limited_greedy":
            best = scores.reshape(N, n_group, E // n_group).max(axis=-1)
            _, groups = jax.lax.top_k(best, topk_group)
            kept = jnp.zeros((N, n_group), bool).at[
                jnp.arange(N)[:, None], groups].set(True)
            pick_from = jnp.where(jnp.repeat(kept, E // n_group, axis=1),
                                  scores, 0.0)
        weights, experts = jax.lax.top_k(pick_from, k)
        if k > 1 and norm_topk_prob:
            weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
        else:
            weights = weights * routed_scaling_factor
        return experts.astype(jnp.int32), weights
    for got, want in zip(jax.jit(lambda x: dropless.route(x, 6, **kw))(LOGITS),
                         jax.jit(lambda x: before(x, 6, **kw))(LOGITS)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
