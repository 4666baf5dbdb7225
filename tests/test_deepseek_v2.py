"""DeepSeek-V2 (models/deepseek_v2.py): latent attention in two forms (a
prompt expanded, a decoded token absorbed over a paged latent pool), routed
experts of which a chip holds a share, shared experts.  Every number is held
against the benchmark's plain reference (``benchmark/reference/
deepseek_v2.py``), which shares no code with the program and knows the
expanded form only.

Tiny model at widths that keep the ratios: 3 layers (one dense, two expert
layers), hidden 64, 4 heads of 16 + 8 (v 16), q rank 48, kv rank 32, 16
routed experts in 8 groups (top-3 groups, top-6) of width 32, 2 shared,
YaRN; seeded weights, float32 (a wrong cache row or a wrong route stands
orders above the rounding), the projections that feed the scores enlarged
(``sharp``) so that attention is far from an average.
"""

import itertools
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.analysis import capacity
from deepspeed_tpu.inference import Request, ServingEngine, paged_kv as pk
from deepspeed_tpu.models import build, deepseek_v2 as dsv2, mla
from deepspeed_tpu.moe import dropless
from benchmark.reference import deepseek_v2 as reference

CFG = {"model_type": "deepseek_v2",
       **dsv2.PRESETS["deepseek-v2-tiny"],
       "first_k_dense_replace": 1, "moe_layer_freq": 1,
       "topk_method": "group_limited_greedy", "scoring_func": "softmax",
       "norm_topk_prob": False, "routed_scaling_factor": 16.0,
       "rms_norm_eps": 1e-6, "rope_theta": 10000}
L, E, K = CFG["num_hidden_layers"], CFG["n_routed_experts"], 6
TOL = 1e-3          # of the largest reference logit; float32 reads ~1e-6


def tiny(dtype=jnp.float32, impl="kernel", **overrides):
    """``impl="gather"``: the ``jax.numpy`` oracle of the latent kernel, set
    on the instance (the model has no such option: it serves the kernel)."""
    keys = {k: v for k, v in CFG.items() if k != "model_type"}
    m = build("deepseek-v2-tiny", dtype=dtype, **{**keys, **overrides})
    if impl != "kernel":
        m.paged_attention_impl = lambda: impl
    return m


def sharp(params):
    """The projections that feed the scores enlarged: scores of order 1 and
    a softmax far from uniform.  At the initialisation's 0.02 attention is
    nearly an average and a wrong cache row hardly moves a logit."""
    attn = dict(params["attn"])
    attn.update(q_nope_w=12.0 * attn["q_nope_w"],
                q_pe_w=12.0 * attn["q_pe_w"], k_up_w=12.0 * attn["k_up_w"],
                kv_a_w=6.0 * attn["kv_a_w"], v_up_w=6.0 * attn["v_up_w"])
    return dict(params, attn=attn)


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
    # the routed experts' down projections are drawn like the shared ones';
    # the router's logits have a spread near 2
    raw = m.init(jax.random.PRNGKey(3))["moe"]
    assert float(raw["shared_down_w"].std() / raw["down_w"].std()) == \
        pytest.approx(1.0, rel=0.05)
    assert float(raw["router_w"].std() * np.sqrt(64)) == pytest.approx(2, rel=0.1)
    big = dsv2.DeepseekV2Config()            # the published defaults
    assert (big.kv_layers, big.n_head, big.n_kv_head, big.head_dim,
            big.max_seq, big.held) == (60, 128, 1, 192, 163840, (0, 160))
    whole = dsv2.DeepseekV2(big)
    assert whole.num_params() == 235_741_434_880
    share = dsv2.DeepseekV2(dsv2.DeepseekV2Config(
        num_hidden_layers=7, experts_held=(0, 20), vocab_held=(0, 12800)))
    assert share.num_params() == 4_483_671_040
    # m = 0.1 * 0.707 * ln 40 + 1, and the softmax scale carries its square
    yarn = dict(type="yarn", factor=40, beta_fast=32, beta_slow=1,
                mscale=0.707, mscale_all_dim=0.707,
                original_max_position_embeddings=4096)
    pub = dsv2.DeepseekV2(dsv2.DeepseekV2Config(
        num_hidden_layers=2, rope_scaling=yarn, max_position_embeddings=64))
    assert pub._sm_scale == pytest.approx(192 ** -0.5 * 1.26081 ** 2, 1e-5)


def test_logits_match_the_reference(model_params):
    m, params = model_params
    assert apply_error(m, params) < 1e-4


def test_yarn_frequencies_are_the_references():
    from deepspeed_tpu.models import rotary
    sc = CFG["rope_scaling"]
    got = rotary.yarn_inv_freq(64, 10000.0, 40, 4096, 32, 1)
    f, m, table = reference._yarn({"qk_rope_head_dim": 64, "rope_theta": 1e4,
                                   "rope_scaling": dict(
                                       sc, original_max_position_embeddings=4096)})
    np.testing.assert_allclose(got, f, rtol=1e-12)
    assert (m, table) == (pytest.approx(1.26081, 1e-5), 1.0)
    # fast pairs keep their frequency, slow ones are divided by the factor
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert got[0] == plain[0] and got[-1] == pytest.approx(plain[-1] / 40)
    assert 0 < np.sum((got < plain * 0.999) & (got > plain / 40 * 1.001))


# ------------------------------------------------------ (b) the shares add up
def test_the_eight_shares_of_an_expert_layer_add_up_to_the_whole():
    """One expert layer's routed output: the eight ``experts_held`` shares
    (two experts each: one routing group a chip, as the deployment) sum to
    the whole layer's, the shared experts counted once; and each equals the
    reference given the same share."""
    m = tiny()
    pm = jax.tree_util.tree_map(lambda w: w[0], m.init(
        jax.random.PRNGKey(5))["moe"])
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 50, 64))
    whole, counts, _ = m._moe(pm, u)
    assert counts.tolist() == [50 * K, 0, E, 0, 0, 0, 0]
    stacked = jax.tree_util.tree_map(lambda w: w[None], pm)
    ref_whole = reference._experts(CFG, stacked, 0, u[0])
    assert rel_err(whole[0], ref_whole) < 1e-5
    shared = dsv2.swiglu({"gate_w": pm["shared_gate_w"],
                          "up_w": pm["shared_up_w"],
                          "down_w": pm["shared_down_w"]}, u)
    total = shared
    pairs = 0
    for first in range(0, E, 2):
        part = tiny(experts_held=(first, 2))
        held = dict(pm, **{k: pm[k][first:first + 2]
                           for k in ("gate_w", "up_w", "down_w")})
        out, n, _ = part._moe(held, u)
        ref = reference._experts(
            {**CFG, "experts_held": [first, 2]},
            jax.tree_util.tree_map(lambda w: w[None], held), 0, u[0])
        assert rel_err(out[0], ref) < 1e-5
        total = total + (out - shared)
        pairs += int(n[0])
        assert int(n[0]) + int(n[1]) == 50 * K
    assert pairs == 50 * K
    assert rel_err(total, whole) < 1e-5


def test_the_program_with_one_share_equals_the_reference_with_it():
    m = tiny(experts_held=(4, 2), vocab_held=(128, 256))
    params = sharp(m.init(jax.random.PRNGKey(3)))
    assert params["moe"]["gate_w"].shape[:2] == (L - 1, 2)
    assert params["moe"]["router_w"].shape[-1] == E      # routes over all
    assert params["wte"].shape == params["head"].shape == (256, 64)
    cfg = {**CFG, "experts_held": [4, 2], "vocab_held": [128, 256]}
    toks = jnp.asarray(tokens(2, 2, 40, lo=128, hi=384))
    assert apply_error(m, params, cfg, toks) < 1e-4
    # and it is not the whole model's answer
    whole = tiny(vocab_held=(128, 256))
    assert rel_err(jax.jit(m.apply)(params, toks), jax.jit(whole.apply)(
        dict(params, moe=sharp(whole.init(jax.random.PRNGKey(3)))["moe"]),
        toks)) > 10 * TOL


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
    """``n`` requests of unequal length through ``slots`` slots: prompts of
    unequal length decode in one batch, every slot is seated, freed and
    seated again.  Returns the worst logit error seen at any decoded
    position and the engine (drained)."""
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
    _, params = model_params
    worst, srv = serve_and_compare(
        tiny(impl=impl, **model), params, n, slots)
    assert worst < TOL
    st = srv.stats()
    assert st["completed"] == n
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    assert srv.model.paged_attention_impl() == impl and not srv._recurrent
    assert (st["experts_held"], st["experts_total"]) == (E, E)


def test_cached_decoding_matches_the_full_forward(model_params):
    m, params = model_params
    toks = jnp.asarray(tokens(4, 2, 30))
    full = jax.jit(m.apply)(params, toks)
    cache = m.init_cache(2, 32)
    assert cache["latent"].shape == (L, 2, 32, 32 + 8)
    cached = jax.jit(m.apply_with_cache)
    got, cache = cached(params, toks[:, :21], cache)         # expanded
    assert rel_err(got, full[:, :21]) < 1e-4
    for t in range(21, 30):                                  # absorbed
        step, cache = cached(params, toks[:, t:t + 1], cache)
        assert rel_err(step[:, 0], full[:, t]) < 1e-4
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    out = eng.generate(np.asarray(toks[:, :10]), max_new_tokens=4)
    assert out.shape == (2, 14)


def test_the_latent_kernel_is_the_gathered_arithmetic():
    from deepspeed_tpu.ops.transformer.paged_latent_attention import (
        paged_latent_attention)
    pool = pk.init_latent_pool(2, 9, 8, 32, 16, jnp.float32)
    assert pool["latent"].shape == (2, 9, 8, 128)     # 48 values, one tile
    pool = {"latent": jax.random.normal(jax.random.PRNGKey(0),
                                        pool["latent"].shape)}
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 0]],
                         jnp.int32)
    lengths = jnp.asarray([17, 9, 23], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(1), (3, 4, 128))
    out = paged_latent_attention(q, pool, tables, lengths, 1,
                                 value_width=32, sm_scale=0.3)
    rows = pk.gather_latent(pool, 1, tables, jnp.float32)
    s = jnp.einsum("bhr,btr->bht", q, rows) * 0.3
    valid = jnp.arange(rows.shape[1])[None, None, :] <= lengths[:, None, None]
    ref = jnp.einsum("bht,btc->bhc", jax.nn.softmax(
        jnp.where(valid, s, -jnp.inf), -1), rows[..., :32])
    assert rel_err(out, ref) < 1e-5



# The walk (PR 48): blocks of 64 rows of 128 (a latent of 32 + 16) under 4
# heads.  A case is (chunk, tail tile, table, rows): the file's constants
# shrunk to chunks of 256 tokens and tail tiles of 128, so that a table of 10
# blocks is two whole chunks and one of 2 blocks and a last chunk's products
# are 128 or 256 wide, or (None) left as served, where a table of 20 blocks
# is one chunk of 1,024 tokens and one of 4 blocks, at widths of 256 to 1,024.
# A row is the query token's position, or None for an empty slot.
WALK_BS = 64
WALK_ROWS = {
    # one before, on and after a block's edge, a tail tile's, a chunk's,
    # and the table's last position
    "edges": (256, 128, 10, [62, 63, 64, 126, 127, 128, 254, 255, 256, 383,
                             384, 510, 511, 512, 639]),
    # a table filled to nb_max, and a position past it (clamped)
    "full_table": (256, 128, 10, [639, 639, 650, 639]),
    # a table shorter than one chunk: one chunk of 3 blocks
    "short_table": (None, None, 3, [0, 191, None, 40, 128]),
    # dead rows first, between live rows and last
    "dead_rows": (256, 128, 10, [None, 40, None, None, 600, 5, None]),
    "all_dead": (256, 128, 10, [None, None, None]),
    # every ring hand-over: one-chunk rows in a run (the fetch two positions
    # ahead belongs to the row after next) between three-chunk rows
    "handover": (256, 128, 10, [511, 0, 3, 639, 127, None, 513, 1, 255, 256]),
    "as_served": (None, None, 20, [1023, 1024, 1279, 300, None, 767, 768, 0]),
}


@pytest.mark.parametrize("case", list(WALK_ROWS))
def test_the_latent_walk_does_live_work_only(case, monkeypatch):
    """Live rows equal the gathered arithmetic wherever they stand in the
    batch and wherever their length ends; dead rows are exactly zero;
    nothing is NaN though the scratch block, every block no table names and
    every position past a row's length is (the interpreter fills the VMEM
    ring with NaN too, so a block that was not fetched fails in P @ V)."""
    import importlib
    pla = importlib.import_module(
        "deepspeed_tpu.ops.transformer.paged_latent_attention")
    chunk, tile, nb_max, rows = WALK_ROWS[case]
    if chunk is not None:
        monkeypatch.setattr(pla, "_CHUNK_TOKENS", chunk)
        monkeypatch.setattr(pla, "_TAIL_TOKENS", tile)
    rng = np.random.default_rng(7)
    B = len(rows)
    lat = np.full((2, 1 + B * nb_max + 2, WALK_BS, 128), np.nan, np.float32)
    tables = np.zeros((B, nb_max), np.int32)
    lengths = np.zeros((B,), np.int32)
    for b, length in enumerate(rows):
        if length is None:
            continue
        n = min(length // WALK_BS + 1, nb_max)          # the blocks it holds
        tables[b, :n] = 1 + b * nb_max + np.arange(n)
        lengths[b] = length
        mine = lat[1, tables[b, :n]].reshape(n * WALK_BS, 128)
        mine[:length + 1] = rng.standard_normal(mine[:length + 1].shape)
        lat[1, tables[b, :n]] = mine.reshape(n, WALK_BS, 128)
    pool = {"latent": jnp.asarray(lat)}
    q = jnp.asarray(rng.standard_normal((B, 4, 128)), jnp.float32)
    out = np.asarray(jax.jit(lambda q, pool: pla.paged_latent_attention(
        q, pool, tables, lengths, 1, value_width=32, sm_scale=0.3))(q, pool))
    live = tables[:, 0] != pk.SCRATCH_BLOCK
    assert np.isfinite(out).all()
    assert not out[~live].any()
    if live.any():
        stored = np.nan_to_num(np.asarray(pk.gather_latent(
            pool, 1, jnp.asarray(tables), jnp.float32)))
        s = np.einsum("bhr,btr->bht", np.asarray(q), stored) * 0.3
        valid = np.arange(stored.shape[1])[None, None, :] <= lengths[
            :, None, None]
        ref = np.einsum("bht,btc->bhc", np.asarray(jax.nn.softmax(
            jnp.where(valid, s, -jnp.inf), -1)), stored[..., :32])
        assert rel_err(out[live], ref[live]) < 1e-5


# ------------------------------------------------------ (d) negative controls
def decode_error(model, params):
    """A prompt through ``prefill_paged`` and four tokens through
    ``decode_step_paged`` (gathered), against the reference."""
    hist = tokens(9, 21)
    pool = model.init_serving_state(2, 9, 8, dtype=jnp.float32)
    prompt = np.zeros((1, 16), np.int32)
    prompt[0, :13] = hist[:13]
    _, pool = jax.jit(model.prefill_paged)(
        params, jnp.asarray(prompt), pool, jnp.asarray([1, 2], jnp.int32),
        jnp.int32(1), jnp.int32(13))
    tables = jnp.asarray([[0, 0, 0], [1, 2, 3]], jnp.int32)
    step = jax.jit(model.decode_step_paged)
    worst = 0.0
    for t in range(13, 17):
        got, pool = step(params, jnp.asarray([0, hist[t]]), pool, tables,
                         jnp.asarray([0, t]))
        row = np.zeros((1, 64), np.int32)
        row[0, :t + 1] = hist[:t + 1]
        ref = _REFERENCE(params, jnp.asarray(row), jnp.asarray([t]))
        worst = max(worst, rel_err(got[1], ref[0]))
    return worst


def test_the_paged_path_alone_is_sound(model_params):
    _, params = model_params
    assert decode_error(tiny(impl="gather"), params) < TOL


def test_a_decode_step_reports_the_experts_it_routed_to(model_params):
    """``with_routes``: the same logits, and the experts of each slot's
    token in every expert layer: in float32 the reference's own picks from
    its scores of that token, and what the step's counters count."""
    _, params = model_params
    model = tiny(experts_held=(4, 4), impl="gather")
    held = jax.tree_util.tree_map(lambda w: w[:, 4:8], {
        k: params["moe"][k] for k in ("gate_w", "up_w", "down_w")})
    params = dict(params, moe=dict(params["moe"], **held))
    cfg = dict(CFG, experts_held=[4, 4])
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
    plain, _ = jax.jit(model.decode_step_paged)(*args)
    got, state, routes = jax.jit(
        lambda *a: model.decode_step_paged(*a, with_routes=True))(*args)
    assert np.array_equal(np.asarray(plain), np.asarray(got))
    assert routes.shape == (L - 1, 2, K) and routes.dtype == jnp.int32
    row = np.zeros((1, 64), np.int32)
    row[0, :14] = hist[:14]
    ref, scores = reference.logits_and_scores_at(
        cfg, params, jnp.asarray(row), jnp.asarray([13]))
    assert rel_err(got[1], ref[0]) < TOL
    picked = np.asarray(reference.picks(cfg, scores[0]))
    for i in range(L - 1):
        assert sorted(np.asarray(routes[i, 1]).tolist()) == \
            np.nonzero(picked[i])[0].tolist()
    here = ((np.asarray(routes[:, 1]) >= 4) & (np.asarray(routes[:, 1]) < 8))
    assert int(state["counters"][0]) == here.sum()        # slot 0 is empty


def test_k_pe_cached_before_rope_fails(model_params, monkeypatch):
    _, params = model_params
    sound = mla.apply_rotary_pos_emb
    monkeypatch.setattr(
        mla, "apply_rotary_pos_emb",
        lambda x, *a, **k: x if x.shape[-2] == 1 else sound(x, *a, **k))
    assert decode_error(tiny(impl="gather"),
                        params) > 10 * TOL


def test_c_kv_cached_before_its_norm_fails(model_params, monkeypatch):
    _, params = model_params
    sound = mla._rms
    monkeypatch.setattr(
        mla, "_rms", lambda x, w, eps: x if w.shape[-1] == 32 and
        x.shape[-1] == 32 else sound(x, w, eps))
    assert decode_error(tiny(impl="gather"),
                        params) > 10 * TOL


def test_the_softmax_scale_without_m_squared_fails(model_params):
    m, params = model_params
    plain = tiny()
    plain._sm_scale = m.config.head_dim ** -0.5
    assert m._sm_scale == pytest.approx(plain._sm_scale * 1.26081 ** 2, 1e-5)
    assert apply_error(plain, params) > 10 * TOL


@pytest.mark.parametrize("change", [
    {"topk_method": "greedy"},              # plain top-6 over all experts
    {"norm_topk_prob": True},               # weights renormalised
    {"routed_scaling_factor": 1.0}])        # the factor left out
def test_another_route_fails(model_params, change):
    _, params = model_params
    assert apply_error(tiny(**change), params) > 10 * TOL
    # and the reference follows the key, so it is the ROUTE that differs
    assert apply_error(tiny(**change), params, {**CFG, **change}) < 1e-4


# -------------------------------------- (e) nothing is dropped; the counters
def by_hand(x, experts, weights, gate, up, down, first):
    x, gate, up, down = (np.asarray(a, np.float64)
                         for a in (x, gate, up, down))
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        for e, w in zip(np.asarray(experts)[n], np.asarray(weights)[n]):
            if first <= e < first + gate.shape[0]:
                g, u = x[n] @ gate[e - first], x[n] @ up[e - first]
                out[n] += w * ((g / (1 + np.exp(-g)) * u) @ down[e - first])
    return out


@pytest.mark.parametrize("case", ["all_to_one", "none_held", "mixed"])
def test_no_token_is_dropped_whatever_the_imbalance(case):
    N, D, F, count, first = 40, 16, 24, 4, 8
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(k[0], (N, D))
    gate, up = (jax.random.normal(k[i], (count, D, F)) * .3 for i in (1, 2))
    down = jax.random.normal(k[3], (count, F, D)) * .3
    weights = jax.random.uniform(k[4], (N, K)) + 0.5
    if case == "all_to_one":            # every token's first pick: expert 9
        experts = jnp.tile(jnp.asarray([9, 0, 1, 2, 3, 4]), (N, 1))
        want = [N, (K - 1) * N, 1, count - 1, 0]
    elif case == "none_held":           # no token to any held expert
        experts = jnp.tile(jnp.asarray([0, 1, 2, 3, 4, 5]), (N, 1))
        want = [0, K * N, 0, count, N]
    else:
        experts = jax.vmap(lambda key: jax.random.permutation(key, 16)[:K])(
            jax.random.split(k[5], N))
        held = (np.asarray(experts) >= first) & (np.asarray(experts) < 12)
        want = [held.sum(), K * N - held.sum(),
                len(set(np.asarray(experts)[held])),
                count - len(set(np.asarray(experts)[held])),
                int((~held.any(1)).sum())]
    out = dropless.held_experts(x, experts, weights, gate, up, down, first)
    ref = by_hand(x, experts, weights, gate, up, down, first)
    assert np.isfinite(np.asarray(out)).all()
    assert np.abs(np.asarray(out) - ref).max() < 1e-4 * max(
        1.0, np.abs(ref).max())
    if case == "none_held":
        assert float(jnp.abs(out).max()) == 0.0
    assert dropless.route_counters(experts, first, count).tolist() == [
        *want, 0, 0]                    # one path: no call counted
    # rows left out of the count (pad, empty slots) are still computed
    live = jnp.arange(N) < 10
    n = dropless.route_counters(experts, first, count, live).tolist()
    assert n[0] + n[1] == K * 10 and n[2] + n[3] == count
    # the stacked form (a layer of a stack, in place) is the same product
    stack = lambda w: jnp.stack([jnp.zeros_like(w), w, jnp.ones_like(w)])
    np.testing.assert_allclose(
        dropless.held_experts(x, experts, weights, stack(gate), stack(up),
                              stack(down), first, layer=jnp.int32(1)),
        out, atol=1e-6)


def test_group_limited_routing_by_hand():
    logits = jax.random.normal(jax.random.PRNGKey(2), (64, 16)) * 2
    experts, weights = dropless.route(
        logits, K, topk_method="group_limited_greedy", n_group=8,
        topk_group=3, routed_scaling_factor=16.0)
    scores = np.asarray(jax.nn.softmax(logits, -1), np.float64)
    for n in range(64):
        best = scores[n].reshape(8, 2).max(1)
        groups = set(np.argsort(-best)[:3])
        assert {e // 2 for e in np.asarray(experts)[n]} == groups  # all six
        np.testing.assert_allclose(np.asarray(weights)[n],
                                   16 * scores[n][np.asarray(experts)[n]],
                                   rtol=1e-5)
    # the reference's route (by rank, not by a sort) picks the same
    ref = np.asarray(reference.route(CFG, jnp.asarray(scores, jnp.float32)))
    got = np.zeros_like(ref)
    np.put_along_axis(got, np.asarray(experts), np.asarray(weights), 1)
    np.testing.assert_allclose(got, ref, rtol=1e-5)
    plain, _ = dropless.route(logits, K)
    assert (np.sort(plain, 1) != np.sort(experts, 1)).any()


def plain_run(model, params):
    """Three requests through three slots, driven by ``step`` alone: every
    step is booked inside the call that carries its span."""
    t0 = time.monotonic()
    eng = ds.init_inference(model, params=params, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={"batch_slots": 3,
                                            "block_size": 8})
    for i in range(3):
        srv.submit(Request(tokens=tokens(30 + i, PROMPTS[i]),
                           max_new_tokens=6))
    while srv.step():
        pass
    steps = [a for a in rows_since(srv, t0, "serving.step") if a["emitted"]]
    assert len(steps) >= 4
    return srv, steps, rows_since(srv, t0, "serving.prefill")


def test_the_whole_model_routes_every_pair_here(model_params):
    m, params = model_params
    srv, steps, prefills = plain_run(tiny(impl="gather"),
                                     params)
    assert all(a["routed_pairs"] == K * a["n_active"] * (L - 1)
               and a["pairs_elsewhere"] == 0 and a["tokens_unrouted"] == 0
               and a["experts_touched"] + a["experts_idle"] == E * (L - 1)
               for a in steps)
    # a prefill counts its prompt's tokens, not its bucket's pad
    assert [a["routed_pairs"] for a in prefills] == [
        K * (L - 1) * t for t in PROMPTS[:3]]


def test_a_prompt_says_which_branch_its_expert_layers_took(monkeypatch):
    """PR 57: a prompt's expert layers run over the held pairs compacted to
    the narrow width or fall back, and the ``serving.prefill`` row says how
    many calls did which; a decode step's calls are under the threshold and
    count 0 and 0.  Tiny sizes: the tile of rows and the threshold shrunk so
    that a 16-token bucket's 96 pairs have a narrow width (24 of them, twice
    the even share 2 / 16); the layer's output is the whole-width one."""
    monkeypatch.setattr(dropless, "_GMM_ROWS", 8)
    monkeypatch.setattr(dropless, "_COMPACT_MIN_PAIRS", 64)
    m = tiny(experts_held=(2, 2), impl="gather")
    params = sharp(m.init(jax.random.PRNGKey(3)))
    srv, steps, prefills = plain_run(m, params)
    assert all(a["calls_compacted"] == a["calls_whole"] == 0 for a in steps)
    assert all(a["calls_compacted"] + a["calls_whole"] == L - 1
               for a in prefills)
    assert sum(a["calls_compacted"] for a in prefills) > 0
    pm = jax.tree_util.tree_map(lambda w: w[0], params["moe"])
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 64, 64))
    narrow, counts, _ = m._moe(pm, u)
    assert counts.tolist()[5:] == [1, 0]
    monkeypatch.setattr(dropless, "_COMPACT_MIN_PAIRS", 10 ** 6)
    whole, counts, _ = m._moe(pm, u)
    assert counts.tolist()[5:] == [0, 0]
    assert float(jnp.abs(narrow - whole).max()) < 1e-5 * max(
        1.0, float(jnp.abs(whole).max()))
    assert float(jnp.abs(whole).max()) > 0


def test_a_share_counts_what_falls_elsewhere():
    """One routing group held (2 of 16): most pairs fall elsewhere, some
    tokens have no expert here, and the step's span says so."""
    m = tiny(experts_held=(2, 2), impl="gather")
    srv, steps, _ = plain_run(m, sharp(m.init(jax.random.PRNGKey(3))))
    assert all(a["routed_pairs"] + a["pairs_elsewhere"]
               == K * a["n_active"] * (L - 1) for a in steps)
    assert all(a["experts_touched"] + a["experts_idle"] == 2 * (L - 1)
               for a in steps)
    assert sum(a["pairs_elsewhere"] for a in steps) \
        > sum(a["routed_pairs"] for a in steps) > 0
    assert sum(a["tokens_unrouted"] for a in steps) > 0
    st = srv.stats()
    assert (st["experts_held"], st["experts_total"]) == (2, E)


# ------------------------------------------ (f) what the pool and a token cost
def test_the_pool_row_is_the_latent_and_a_token_costs_seven_of_them():
    m = tiny(num_hidden_layers=7)            # the cell's depth
    eng = ds.init_inference(m, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={"batch_slots": 2,
                                            "block_size": 16,
                                            "num_blocks": 12})
    assert set(srv.pool) == {"latent", "counters"}
    assert srv.pool["latent"].shape == (7, 12, 16, 128)
    st = srv.stats()
    assert st["latent_row_bytes"] == 128 * 4 == pk.latent_row_bytes(srv.pool)
    assert st["kv_layers"] == 7
    assert st["kv_bytes_per_token"] == 7 * st["latent_row_bytes"]
    assert st["kv_pool_bytes"] == 12 * 16 * st["kv_bytes_per_token"]
    assert srv.capacity()["capacity_tokens"] == 11 * 16
    plan = capacity.serving_plan(
        n_layer=7, n_head=m.config.n_head, head_dim=m.config.head_dim,
        max_seq=256, num_blocks=12, kv_row_bytes=st["latent_row_bytes"])
    assert plan["paged_kv_pool"] == st["kv_pool_bytes"]
    # at the published widths: 512 + 64 values in five 128-lane tiles
    assert pk.latent_row_width(512, 64) == 640
    big = pk.init_latent_pool(1, 2, 64, 512, 64)
    assert pk.latent_row_bytes(big) == 1280


def test_an_int8_latent_pool_is_refused():
    """(What moves a stream by its K/V blocks is refused over a latent pool
    in tests/test_serving_refusals.py.)"""
    eng = ds.init_inference(tiny(), dtype=jnp.float32)
    with pytest.raises(ValueError, match="kv_bits"):
        ServingEngine(engine=eng, config={"batch_slots": 2, "kv_bits": 8})


# --------------------------------------------------- (g) loss and gradients
def test_loss_and_gradients_match_the_reference(model_params):
    m, params = model_params
    batch = jnp.asarray(tokens(2, 2, 25))
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: m.loss(p, batch, None)))(params)
    ref, g_ref = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(CFG, p, batch)))(params)
    assert abs(float(got) - float(ref)) < 1e-5 * abs(float(ref))
    flat_ref = dict(jax.tree_util.tree_leaves_with_path(g_ref))
    for path, g in jax.tree_util.tree_leaves_with_path(g_got):
        r = flat_ref[path]
        scale = float(jnp.abs(r).max()) + 1e-12
        assert float(jnp.abs(g - r).max()) < 2e-3 * scale + 1e-9, path


def test_a_tiny_one_trains_through_ds_initialize():
    engine, *_ = ds.initialize(
        model=tiny(), config={"train_micro_batch_size_per_gpu": 2,
                              "optimizer": {"type": "Adam",
                                            "params": {"lr": 1e-2}},
                              "zero_optimization": {"stage": 0}})
    feed = itertools.repeat(tokens(5, 16, 33))
    losses = [float(engine.train_batch(feed)) for _ in range(8)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


# ------------------------------------------------------ (h) refused by name
@pytest.mark.parametrize("key, value", [
    ("topk_method", "noaux_tc"),
    ("scoring_func", "sigmoid"),
    ("rope_scaling", {"type": "linear", "factor": 4.0})])
def test_what_the_model_does_not_run_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        tiny(**{key: value})
    if key != "rope_scaling":
        # moe/dropless.py itself scores by sigmoid since PR 39 (another
        # family's): what IT refuses by name is a function it has not
        unknown = {"scoring_func": "tanh"}.get(key, value)
        with pytest.raises(ValueError, match=key):
            dropless.route(jnp.zeros((2, 16)), 2, **{key: unknown})
