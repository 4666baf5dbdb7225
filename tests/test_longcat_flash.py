"""LongCat-Flash (models/longcat_flash.py): the shortcut-connected double
block (two latent-attention sub-layers, two dense FFNs, one expert layer that
joins late), a router wider than its experts (identity experts), a chip's
share of the real experts.  Every number is held against the benchmark's
plain reference (``benchmark/reference/longcat_flash.py``), which shares no
code with the program and knows the expanded attention only.

Tiny model at widths that keep the ratios: 2 double layers (4 sub-layers),
hidden 64, 4 heads of 16 + 8 (v 16), q rank 48, kv rank 32, a router of 16
real and 8 identity experts, top-6, a selection bias; seeded weights, float32
(a wrong cache row or a wrong route stands orders above the rounding), the
projections that feed the scores enlarged (``sharp``).
"""

import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.inference import Request, ServingEngine, paged_kv as pk
from deepspeed_tpu.models import build, longcat_flash as lcf
from deepspeed_tpu.moe import dropless
from benchmark import control_longcat
from benchmark.reference import longcat_flash as reference

CFG = {"model_type": "longcat_flash", **lcf.PRESETS["longcat-flash-tiny"],
       "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
       "routed_scaling_factor": 6.0, "rms_norm_eps": 1e-5,
       "zero_expert_type": "identity"}
L, E, Z, K = (CFG["num_layers"], CFG["n_routed_experts"],
              CFG["zero_expert_num"], CFG["moe_topk"])
TOL = 1e-3          # of the largest reference logit; float32 reads ~1e-6


def tiny(dtype=jnp.float32, impl="kernel", **overrides):
    """``impl="gather"``: the ``jax.numpy`` oracle of the latent kernel, set
    on the instance (the model has no such option: it serves the kernel)."""
    m = build("longcat-flash-tiny", dtype=dtype, **overrides)
    if impl != "kernel":
        m.paged_attention_impl = lambda: impl
    return m


def sharp(params):
    """The projections that feed the scores enlarged: scores of order 1 and
    a softmax far from uniform (at the initialisation's 0.02 attention is
    nearly an average and a wrong cache row hardly moves a logit), and the
    output projections enlarged, so that what a sub-layer adds stands beside
    the identity experts' part (a weight near 2 on a unit-norm input)."""
    attn = dict(params["attn"])
    attn.update(q_nope_w=12.0 * attn["q_nope_w"],
                q_pe_w=12.0 * attn["q_pe_w"], k_up_w=4.0 * attn["k_up_w"],
                kv_a_w=6.0 * attn["kv_a_w"], v_up_w=6.0 * attn["v_up_w"],
                o_w=8.0 * attn["o_w"])
    dense = dict(params["dense"], down_w=8.0 * params["dense"]["down_w"])
    moe = dict(params["moe"], down_w=8.0 * params["moe"]["down_w"])
    return dict(params, attn=attn, dense=dense, moe=moe)


@pytest.fixture(scope="module")
def model_params():
    m = tiny()
    return m, sharp(m.init(jax.random.PRNGKey(3)))


def tokens(seed, *shape, lo=0, hi=CFG["vocab_size"]):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape, lo,
                                         hi), np.int32)


def rows_since(srv, t0, name):
    return [r.attrs for r in srv._spans.rows()
            if r.name == name and r.t_start >= t0]


def rel_err(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


TOKS = jnp.asarray(tokens(1, 2, 40))


def apply_error(model, params, cfg=CFG, toks=TOKS):
    """``apply``'s logits against the reference's, every position."""
    got = jax.jit(model.apply)(params, toks)
    return rel_err(got, jax.jit(
        lambda p: reference.logits(cfg, p, toks))(params))


# --------------------------------------------------------- (a) whole model
def test_parameter_count_and_the_published_defaults(model_params):
    m, params = model_params
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == m.num_params()
    raw = m.init(jax.random.PRNGKey(3))["moe"]
    assert raw["router_w"].shape == (L, 64, E + Z)
    assert float(raw["router_w"].std() * np.sqrt(64)) == \
        pytest.approx(2, rel=0.1)
    assert float(raw["router_bias"].std()) == pytest.approx(0.01, rel=0.3)
    big = lcf.LongcatFlashConfig()           # the published defaults
    assert (big.n_layer, big.kv_layers, big.n_head, big.n_kv_head,
            big.head_dim, big.max_seq, big.held, big.router_width) == (
        28, 56, 64, 1, 192, 131072, (0, 512), 768)
    share = lcf.LongcatFlash(lcf.LongcatFlashConfig(
        num_layers=4, experts_held=(0, 16), vocab_held=(0, 16384)))
    assert share.num_params() == 5_172_749_312
    assert share._mla.q_scale == 2.0
    assert share._mla.kv_scale == pytest.approx(3.4641, 1e-4)
    assert share._sm_scale == pytest.approx(192 ** -0.5)
    for key, value in [("zero_expert_type", "constant"),
                       ("attention_method", "MHA"),
                       ("attention_bias", True)]:
        with pytest.raises(ValueError, match=key):
            tiny(**{key: value})


def test_logits_match_the_reference(model_params):
    m, params = model_params
    assert apply_error(m, params) < 1e-4


@pytest.mark.parametrize("length, chunk, size, narrow", [
    (40, 16, 16, False), (40, 30, 27, False), (40, 64, 40, False),
    (37, 16, 15, False), (40, 30, 27, True), (40, 64, 40, True)])
def test_a_long_prompts_expert_layer_in_chunks_is_the_same(
        monkeypatch, length, chunk, size, narrow):
    """The expert layer gathers at most ``_MOE_CHUNK`` tokens' pairs at once
    (2 rows of 40 tokens here: 5 chunks of 16, 3 of 27 and 2 of 40; 2 rows of
    37 in 5 chunks of 15): where the count does not divide the tokens the
    last chunk is filled up with rows that no held expert takes, and the
    logits are the whole prompt's.  ``narrow``: a share of the experts (4 of
    16 real, 24 outputs) with the tile of rows and the threshold shrunk, so
    that a chunk's call has a narrow width and its ``cond`` sits inside the
    ``lax.map`` over chunks; the counters count a call a chunk."""
    m = tiny(experts_held=(4, 4)) if narrow else tiny()
    params = sharp(m.init(jax.random.PRNGKey(3)))
    if narrow:
        monkeypatch.setattr(dropless, "_GMM_ROWS", 8)
        monkeypatch.setattr(dropless, "_COMPACT_MIN_PAIRS", 64)
    seen = []
    sound = dropless.held_experts
    monkeypatch.setattr(lcf, "_MOE_CHUNK", chunk)
    monkeypatch.setattr(dropless, "held_experts", lambda x, *a, **kw: (
        seen.append(x.shape[0]), sound(x, *a, **kw))[1])
    cfg = dict(CFG, experts_held=[4, 4]) if narrow else CFG
    assert apply_error(m, params, cfg=cfg, toks=TOKS[:, :length]) < 1e-4
    assert set(seen) == {size}
    if narrow:
        u = jax.random.normal(jax.random.PRNGKey(8), (2, length, 64))
        pm = jax.tree_util.tree_map(lambda w: w[0], params["moe"])
        _, counts, _ = m._moe(pm, u)
        n = dict(zip(m.step_counters, counts.tolist()))
        assert n["calls_compacted"] + n["calls_whole"] == -(-2 * length // size)
        assert n["calls_compacted"] > 0


def test_the_bias_moves_the_pick_and_never_the_weight(model_params):
    """The selection bias changes WHICH twelve (six here) for a stated share
    of tokens, and the weights are the unbiased scores of whichever were
    picked: ``dropless.route``'s and the reference's alike."""
    m, params = model_params
    u = jax.random.normal(jax.random.PRNGKey(7), (400, 64))
    pm = params["moe"]
    s, x = reference.selection(CFG, pm, 0, u)
    logits = u @ pm["router_w"][0]
    experts, weights = dropless.route(
        logits, K, routed_scaling_factor=6.0, bias=pm["router_bias"][0])
    plain, _ = dropless.route(logits, K, routed_scaling_factor=6.0)
    moved = np.mean([set(a) != set(b) for a, b in
                     zip(np.asarray(experts), np.asarray(plain))])
    assert 0.05 < moved < 0.6, moved
    want = np.asarray(reference.route(CFG, s, x))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(experts), np.asarray(weights), 1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(weights),
        6.0 * np.take_along_axis(np.asarray(s), np.asarray(experts), 1),
        rtol=1e-5)


# ------------------------------------------------------ (b) the shares add up
def one_layer(params, l=0):
    """Layer ``l``'s leaves as a one-layer tree (2 sub-layers)."""
    cut = lambda tree, a, b: {k: w[a:b] for k, w in tree.items()}
    return dict(params, attn=cut(params["attn"], 2 * l, 2 * l + 2),
                dense=cut(params["dense"], 2 * l, 2 * l + 2),
                moe=cut(params["moe"], l, l + 1))


def test_the_shares_of_a_layer_add_up_to_the_whole(model_params):
    """One double layer over a stream: the four ``experts_held`` shares (four
    real experts each), the identity experts' part and the dense path (both
    attentions, both dense FFNs) counted once, sum to the uncut reference's
    layer; each share equals the reference given the same share; and the
    counters of the shares add up to every pair."""
    m, params = model_params
    lay = one_layer(params)
    h = jax.random.normal(jax.random.PRNGKey(6), (50, 64)) * 0.5
    cos, sin = reference._tables(CFG, 50)
    with jax.default_matmul_precision("highest"):
        whole = reference.layer(CFG, lay, 0, h, cos, sin)
    causal = jnp.tril(jnp.ones((50, 50), bool))

    def run(model, p):
        out, _, counts, _ = model._layers(
            p, h[None], (), jnp.arange(50),
            lambda pa, qn, qp, ckv, kpe, i, carry: (
                model._mla.attend_expanded(pa, qn, qp, ckv, kpe, causal,
                                           model._sm_scale), carry))
        return out[0], counts
    one = tiny(num_layers=1)
    got, counts = run(one, lay)
    assert rel_err(got, whole) < 1e-5
    # routed + elsewhere + zero = k x tokens; nothing is elsewhere when all
    # are held; every real expert is held
    assert counts.tolist()[:2] == [50 * K - int(counts[2]), 0]
    assert int(counts[2]) > 0 and int(counts[3]) + int(counts[4]) == E
    # the dense path and the identity part, alone: a share that holds no
    # expert a token picked adds exactly this
    zeroed = dict(lay, moe=dict(lay["moe"], **{
        k: jnp.zeros_like(lay["moe"][k]) for k in ("down_w",)}))
    common, _ = run(one, zeroed)
    total, pairs = common, 0
    for first in range(0, E, 4):
        part = tiny(num_layers=1, experts_held=(first, 4))
        held = dict(lay, moe=dict(lay["moe"], **{
            k: lay["moe"][k][:, first:first + 4]
            for k in ("gate_w", "up_w", "down_w")}))
        out, n = run(part, held)
        with jax.default_matmul_precision("highest"):
            ref = reference.layer({**CFG, "experts_held": [first, 4]}, held,
                                  0, h, cos, sin)
        assert rel_err(out, ref) < 1e-5
        assert int(n[0]) + int(n[1]) + int(n[2]) == 50 * K
        assert int(n[2]) == int(counts[2])       # the identity pairs: whole
        total = total + (out - common)
        pairs += int(n[0])
    assert pairs == 50 * K - int(counts[2])
    assert rel_err(total, whole) < 1e-5
    # and the real experts' part is no rounding
    assert rel_err(common, whole) > TOL


def test_the_program_with_one_share_equals_the_reference_with_it():
    m = tiny(experts_held=(4, 4), vocab_held=(128, 256))
    params = sharp(m.init(jax.random.PRNGKey(3)))
    assert params["moe"]["gate_w"].shape[:2] == (L, 4)
    assert params["moe"]["router_w"].shape[-1] == E + Z   # routes over all
    assert params["wte"].shape == params["head"].shape == (256, 64)
    cfg = {**CFG, "experts_held": [4, 4], "vocab_held": [128, 256]}
    toks = jnp.asarray(tokens(2, 2, 40, lo=128, hi=384))
    assert apply_error(m, params, cfg, toks) < 1e-4


@pytest.mark.parametrize("case", ["all_identity", "all_held",
                                  "all_elsewhere", "mixed"])
def test_a_token_whose_picks_are_all_of_one_kind(case):
    """The expert layer over hand-made picks: held real experts 4..7 of 16,
    identity experts 16..23.  What comes out is the by-hand sum of the held
    experts' SwiGLUs and the token's own input times the identity weights;
    the three kinds of pair are counted apart."""
    N, D, F, first, count = 30, 16, 24, 4, 4
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(k[0], (N, D))
    gate, up = (jax.random.normal(k[i], (count, D, F)) * .3 for i in (1, 2))
    down = jax.random.normal(k[3], (count, F, D)) * .3
    weights = jax.random.uniform(k[4], (N, K)) + 0.5
    ids = {"all_identity": [16, 17, 18, 21, 22, 23],
           "all_held": [4, 5, 6, 7, 4, 5],        # (a pick twice: a test's)
           "all_elsewhere": [0, 1, 2, 3, 8, 15],
           "mixed": [0, 4, 7, 12, 16, 23]}[case]
    experts = jnp.tile(jnp.asarray(ids), (N, 1))
    routed = dropless.held_experts(x, experts, weights, gate, up, down, first)
    zero = dropless.zero_experts(x, experts, weights, 16)
    assert zero.dtype == jnp.float32
    X, W = np.asarray(x, np.float64), np.asarray(weights, np.float64)
    want = np.zeros_like(X)
    for n in range(N):
        for e, w in zip(ids, W[n]):
            if e >= 16:
                want[n] += w * X[n]
            elif first <= e < first + count:
                g = X[n] @ np.asarray(gate[e - first], np.float64)
                u = X[n] @ np.asarray(up[e - first], np.float64)
                want[n] += w * ((g / (1 + np.exp(-g)) * u)
                                @ np.asarray(down[e - first], np.float64))
    got = np.asarray(routed, np.float64) + np.asarray(zero, np.float64)
    assert np.abs(got - want).max() < 1e-4 * max(1.0, np.abs(want).max())
    if case == "all_elsewhere":
        assert float(jnp.abs(routed).max()) == float(jnp.abs(zero).max()) == 0
    if case == "all_identity":
        assert float(jnp.abs(routed).max()) == 0.0
        np.testing.assert_allclose(np.asarray(zero),
                                   X * W.sum(1, keepdims=True), rtol=1e-5)
    held = sum(first <= e < first + count for e in ids)
    z = sum(e >= 16 for e in ids)
    base = dropless.route_counters(experts, first, count).tolist()
    assert base[0] == held * N and base[1] == (K - held) * N
    assert int(dropless.zero_pairs(experts, 16)) == z * N
    assert base[4] == (N if held == 0 else 0)      # no held REAL expert
    live = jnp.arange(N) < 10
    assert int(dropless.zero_pairs(experts, 16, live)) == z * 10


# ------------------------------------------------------------- (c) serving
PROMPTS = (13, 21, 9, 30, 17, 26)      # none on an 8-token bucket's edge
NEW = (5, 9, 3, 12, 7, 4)              # so slots free at different steps

_REFERENCE = jax.jit(lambda p, t, pos: reference.logits_at(CFG, p, t, pos))


def live_logit_error(srv, params):
    """The benchmark's check: the NEXT decode step's logits through the
    paged path (absorbed), against the reference's full expanded forward
    over each live slot's history."""
    p, pool, tables, lengths, toks = srv._decode_args()[:5]
    if not hasattr(srv, "_next_logits"):
        srv._next_logits = jax.jit(lambda p, t, pl, tb, ln:
                                   srv.model.decode_step_paged(
                                       p, t, pl, tb, ln)[0])
    got = np.asarray(srv._next_logits(p, toks, pool, tables, lengths))
    live = [i for i, s in enumerate(srv._slots) if s is not None]
    worst = 0.0
    for i in live:
        s = srv._slots[i]
        hist = np.concatenate([np.asarray(s.req.tokens),
                               np.asarray(s.out_tokens)]).astype(np.int32)
        row = np.zeros((1, 64), np.int32)      # one shape, one compile
        row[0, :len(hist)] = hist
        ref = _REFERENCE(params, jnp.asarray(row),
                         jnp.asarray([len(hist) - 1]))
        worst = max(worst, rel_err(got[i], ref[0]))
    return worst, len(live)


def serve_and_compare(model, params, n=6, slots=3):
    eng = ds.init_inference(model, params=params, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={"batch_slots": slots,
                                            "block_size": 8})
    uids = [srv.submit(Request(tokens=tokens(20 + i, PROMPTS[i]),
                               max_new_tokens=NEW[i])) for i in range(n)]
    worst, seen = 0.0, 0
    while srv.step():
        if any(s is not None for s in srv._slots):
            err, live = live_logit_error(srv, params)
            worst, seen = max(worst, err), seen + live
    assert seen > 3 * n
    assert [len(srv.results[u]["tokens"]) for u in uids] == list(NEW[:n])
    return worst, srv


@pytest.mark.parametrize("impl, n, slots, model", [
    ("gather", 6, 3, {}),
    ("kernel", 3, 2, {"max_position_embeddings": 48})])
def test_serving_matches_the_reference(model_params, impl, n, slots, model):
    """Prefill (expanded) then decoding (absorbed) through the latent pool,
    two rows a token a layer, against the reference's full forward."""
    _, params = model_params
    worst, srv = serve_and_compare(tiny(impl=impl, **model), params, n, slots)
    assert worst < TOL
    st = srv.stats()
    assert st["completed"] == n
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    assert srv.model.paged_attention_impl() == impl and not srv._recurrent
    assert (st["experts_held"], st["experts_total"], st["zero_experts"],
            st["latent_rows_per_token"], st["kv_layers"]) == (
        E, E, Z, 2 * L, 2 * L)
    assert st["kv_bytes_per_token"] == 2 * L * st["latent_row_bytes"]
    assert srv.pool["latent"].shape[0] == 2 * L


def test_cached_decoding_matches_the_full_forward(model_params):
    m, params = model_params
    toks = jnp.asarray(tokens(4, 2, 30))
    full = jax.jit(m.apply)(params, toks)
    cache = m.init_cache(2, 32)
    assert cache["latent"].shape == (2 * L, 2, 32, 32 + 8)
    cached = jax.jit(m.apply_with_cache)
    got, cache = cached(params, toks[:, :21], cache)         # expanded
    assert rel_err(got, full[:, :21]) < 1e-4
    for t in range(21, 30):                                  # absorbed
        step, cache = cached(params, toks[:, t:t + 1], cache)
        assert rel_err(step[:, 0], full[:, t]) < 1e-4
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    out = eng.generate(np.asarray(toks[:, :10]), max_new_tokens=4)
    assert out.shape == (2, 14)


def decode_error(model, params, cfg=CFG, with_routes=False):
    """One prompt prefilled into the pool and one token decoded over it,
    against the reference's full forward; slot 0 is empty."""
    hist = tokens(9, 21)
    pool = model.init_serving_state(2, 9, 8, dtype=jnp.float32)
    prompt = np.zeros((1, 16), np.int32)
    prompt[0, :13] = hist[:13]
    _, pool = jax.jit(model.prefill_paged)(
        params, jnp.asarray(prompt), pool, jnp.asarray([1, 2], jnp.int32),
        jnp.int32(1), jnp.int32(13))
    args = (params, jnp.asarray([0, hist[13]]), pool,
            jnp.asarray([[0, 0, 0], [1, 2, 3]], jnp.int32),
            jnp.asarray([0, 13]))
    got, state, routes = jax.jit(
        lambda *a: model.decode_step_paged(*a, with_routes=True))(*args)
    row = np.zeros((1, 64), np.int32)
    row[0, :14] = hist[:14]
    ref, x = reference.logits_and_scores_at(
        cfg, params, jnp.asarray(row), jnp.asarray([13]))
    err = rel_err(got[1], ref[0])
    return (err, state, routes, x) if with_routes else err


def test_a_decode_step_reports_its_routes_and_counts_three_kinds_of_pair(
        model_params):
    _, params = model_params
    model = tiny(experts_held=(4, 4), impl="gather")
    held = jax.tree_util.tree_map(lambda w: w[:, 4:8], {
        k: params["moe"][k] for k in ("gate_w", "up_w", "down_w")})
    params = dict(params, moe=dict(params["moe"], **held))
    cfg = dict(CFG, experts_held=[4, 4])
    err, state, routes, x = decode_error(model, params, cfg, with_routes=True)
    assert err < TOL
    assert routes.shape == (L, 2, K) and routes.dtype == jnp.int32
    picked = np.asarray(reference.picks(cfg, x[0]))
    for i in range(L):
        assert sorted(np.asarray(routes[i, 1]).tolist()) == \
            np.nonzero(picked[i])[0].tolist()
    mine = np.asarray(routes[:, 1])
    n = dict(zip(model.step_counters, state["counters"].tolist()))
    assert n["routed_pairs"] == ((mine >= 4) & (mine < 8)).sum()
    assert n["zero_pairs"] == (mine >= E).sum() > 0
    assert n["routed_pairs"] + n["pairs_elsewhere"] + n["zero_pairs"] \
        == K * 1 * L                                   # slot 0 is empty
    assert n["experts_touched"] + n["experts_idle"] == 4 * L


def test_the_counters_reach_the_step_rows():
    m = tiny(experts_held=(2, 2), impl="gather")
    params = sharp(m.init(jax.random.PRNGKey(3)))
    t0 = time.monotonic()
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={"batch_slots": 3,
                                            "block_size": 8})
    for i in range(3):
        srv.submit(Request(tokens=tokens(30 + i, PROMPTS[i]),
                           max_new_tokens=6))
    while srv.step():
        pass
    steps = [a for a in rows_since(srv, t0, "serving.step") if a["emitted"]]
    assert len(steps) >= 4
    assert all(a["routed_pairs"] + a["pairs_elsewhere"] + a["zero_pairs"]
               == K * a["n_active"] * L for a in steps)
    assert all(a["experts_touched"] + a["experts_idle"] == 2 * L
               for a in steps)
    assert sum(a["zero_pairs"] for a in steps) > 0
    assert sum(a["pairs_elsewhere"] for a in steps) \
        > sum(a["routed_pairs"] for a in steps) > 0
    assert sum(a["tokens_unrouted"] for a in steps) > 0
    # a prefill counts its prompt's tokens, not its bucket's pad
    prefills = rows_since(srv, t0, "serving.prefill")
    assert [a["routed_pairs"] + a["pairs_elsewhere"] + a["zero_pairs"]
            for a in prefills] == [K * L * t for t in PROMPTS[:3]]


# ----------------------------------------- (d) each control fails at float32
@pytest.mark.parametrize("fault", control_longcat.FAULTS)
def test_a_planted_fault_stands_orders_above_the_rounding(model_params,
                                                          fault):
    """``benchmark/control_longcat.py``'s faults, each against the sound
    reference, on the full forward and on a prefill and a decoded token
    through the pool: float32 reads 1e-6 or less, a fault 1e-3 or more."""
    _, params = model_params
    assert apply_error(tiny(), params) < 1e-5
    unplant = control_longcat.plant(fault)
    try:
        faulty = tiny(impl="gather")
        assert apply_error(faulty, params) > 10 * TOL
        assert decode_error(faulty, params) > TOL
    finally:
        unplant()
    assert decode_error(tiny(impl="gather"), params) < 1e-4


def test_without_the_kv_scale_the_cached_row_is_another(model_params):
    """``mla_scale_kv_lora`` is applied BEFORE the row is cached: with the
    flag off the pool holds other rows, by the factor, and k_pe the same."""
    _, params = model_params
    rows = {}
    for flag in (True, False):
        m = tiny(mla_scale_kv_lora=flag)
        pool = m.init_serving_state(1, 4, 8, dtype=jnp.float32)
        prompt = jnp.asarray(tokens(5, 1, 8))
        _, pool = jax.jit(m.prefill_paged)(
            params, prompt, pool, jnp.asarray([1], jnp.int32), jnp.int32(0),
            jnp.int32(8))
        rows[flag] = np.asarray(pool[pk.LATENT][0, 1])
    np.testing.assert_allclose(rows[True][:, :32],
                               rows[False][:, :32] * np.sqrt(64 / 32),
                               rtol=1e-5)
    np.testing.assert_allclose(rows[True][:, 32:40], rows[False][:, 32:40],
                               rtol=1e-6)


# ------------------------------------------------ (e) what the engine refuses
def test_an_int8_latent_pool_is_refused():
    eng = ds.init_inference(tiny(), dtype=jnp.float32)
    with pytest.raises(ValueError, match="kv_bits"):
        ServingEngine(engine=eng, config={"batch_slots": 2, "kv_bits": 8})


def test_prefix_sharing_over_the_latent_pool_is_refused_by_name():
    eng = ds.init_inference(tiny(), dtype=jnp.float32)
    with pytest.raises(ValueError, match="latent"):
        ServingEngine(engine=eng, config={"batch_slots": 2,
                                          "prefix_cache": True})


# --------------------------------------------------- (f) loss and gradients
def test_loss_and_gradients_match_the_reference(model_params):
    m, params = model_params
    batch = jnp.asarray(tokens(2, 2, 25))
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: m.loss(p, batch, None)))(params)
    ref, g_ref = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(CFG, p, batch)))(params)
    assert float(got) == pytest.approx(float(ref), rel=1e-5)
    for path in (("attn", "q_a_w"), ("dense", "down_w"), ("moe", "down_w"),
                 ("moe", "router_w")):
        a, b = g_got[path[0]][path[1]], g_ref[path[0]][path[1]]
        assert rel_err(a, b) < 1e-3, path
