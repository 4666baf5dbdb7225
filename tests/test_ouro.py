"""Ouro (models/ouro.py): one stack of layers run ``total_ut_steps`` times
over shared weights, every loop of every layer with its own K/V, rotary
positions in the paged path.  Every number is held against the benchmark's
plain reference (``benchmark/reference/ouro.py``), which shares no code with
the program.

Tiny model: 3 layers looped 4 times (12 layer-applications), hidden 128, 4
heads of 32, seeded weights, float32 (so that a wrong position or a wrong
loop's cache stands orders above the rounding), the query and key
projections enlarged (``sharp``) so that attention is far from an average.
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
from deepspeed_tpu.models import build, gpt2, jamba, ouro as ouro_mod
from benchmark.reference import ouro as reference

CFG = {"model_type": "ouro", "vocab_size": 512, "hidden_size": 128,
       "num_hidden_layers": 3, "num_attention_heads": 4,
       "num_key_value_heads": 4, "head_dim": 32, "intermediate_size": 256,
       "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
       "max_position_embeddings": 256, "total_ut_steps": 4,
       "early_exit_threshold": 1}
L, R = CFG["num_hidden_layers"], CFG["total_ut_steps"]
TOL = 1e-3          # of the largest reference logit; float32 reads ~1e-6


def tiny(dtype=jnp.float32, **overrides):
    keys = {k: v for k, v in CFG.items() if k != "model_type"}
    return build("ouro-tiny", dtype=dtype, **{**keys, **overrides})


def sharp(params):
    """The query and key projections six times larger: scores of order 1, a
    softmax far from uniform.  At the initialisation's 0.02 attention is
    nearly an average and a wrong position hardly moves a logit."""
    blocks = dict(params["blocks"])
    blocks.update(q_w=6.0 * blocks["q_w"], k_w=6.0 * blocks["k_w"])
    return dict(params, blocks=blocks)


@pytest.fixture(scope="module")
def model_params():
    m = tiny()
    return m, sharp(m.init(jax.random.PRNGKey(3)))


def tokens(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                         CFG["vocab_size"]), np.int32)


def rows_since(srv, t0, name):
    """The attributes of this engine's ``name`` spans: the recorder is the
    process's, and holds earlier tests' rows too."""
    return [r.attrs for r in srv._spans.rows()
            if r.name == name and r.t_start >= t0]


def rel_err(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


# ------------------------------------------------ (a) forward, gates, loops
def test_parameter_count_and_the_published_defaults(model_params):
    m, params = model_params
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == m.num_params()
    big = ouro_mod.OuroConfig()             # the published 2.6B defaults
    assert (big.kv_layers, big.n_head, big.head_dim, big.max_seq) == (
        192, 16, 128, 65536)
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert 48 * layer + 2 * 49152 * 2048 + 2048 + 2049 == 2_667_974_657


@pytest.mark.parametrize("loops", [1, 2, 4])
def test_logits_and_gates_match_the_reference(model_params, loops):
    _, params = model_params                # the weights do not depend on R
    m = tiny(total_ut_steps=loops)
    cfg = {**CFG, "total_ut_steps": loops}
    toks = jnp.asarray(tokens(1, 2, 40))
    logits, lam = jax.jit(
        lambda p: m.apply(p, toks, return_gates=True))(params)
    assert lam.shape == (loops, 2, 40)
    ref_at = jax.jit(lambda p, pos: reference.logits_at(cfg, p, toks, pos))
    for position in (0, 17, 39):
        ref = ref_at(params, jnp.full((2,), position))
        assert rel_err(logits[:, position], ref) < 1e-4
    ref_lam = jax.jit(lambda p: reference.gates(cfg, p, toks))(params)
    assert float(jnp.abs(lam - ref_lam).max()) < 1e-5
    # the gate is not a constant: it moves from token to token
    assert float(jnp.std(ref_lam)) > 1e-3


def test_two_loops_are_not_four(model_params):
    _, params = model_params
    toks = jnp.asarray(tokens(1, 2, 40))
    four = jax.jit(tiny().apply)(params, toks)
    two = jax.jit(tiny(total_ut_steps=2).apply)(params, toks)
    assert rel_err(two, four) > 100 * TOL


# --------------------------------------------------- (f) loss and gradients
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
        if "exit" in jax.tree_util.keystr(path):
            # the gate enters no logit at threshold 1: no gradient
            assert float(jnp.abs(g).max()) == float(jnp.abs(r).max()) == 0.0
            continue
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


def test_cached_decoding_matches_the_full_forward(model_params):
    m, params = model_params
    toks = jnp.asarray(tokens(4, 2, 30))
    full = jax.jit(m.apply)(params, toks)
    cache = m.init_cache(2, 32)
    assert cache["k"].shape == (R * L, 2, 32, 4, 32)
    cached = jax.jit(m.apply_with_cache)
    got, cache = cached(params, toks[:, :21], cache)
    assert rel_err(got, full[:, :21]) < 1e-4
    for t in range(21, 30):
        step, cache = cached(params, toks[:, t:t + 1], cache)
        assert rel_err(step[:, 0], full[:, t]) < 1e-4
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    out = eng.generate(np.asarray(toks[:, :10]), max_new_tokens=4)
    assert out.shape == (2, 14)


# ------------------------------------------------------------- (b) serving
PROMPTS = (13, 21, 9, 30, 17, 26)      # none on an 8-token bucket's edge
NEW = (5, 9, 3, 12, 7, 4)              # so slots free at different steps

_REFERENCE = jax.jit(lambda p, t, pos: reference.logits_at(CFG, p, t, pos))


def live_logit_error(srv, params):
    """The benchmark's check (``benchmark/serving.py::check``): the NEXT
    decode step's logits through the paged path, against the reference's
    full forward over each live slot's history."""
    p, pool, tables, lengths, toks = srv._decode_args()[:5]
    if not hasattr(srv, "_next_logits"):        # traced once an engine
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


def serve_and_compare(params, impl="gather", n=6, slots=3, **model):
    """``n`` requests of unequal length through ``slots`` slots: every slot
    is seated, freed and seated again.  Returns the worst logit error seen
    at any decoded position, the engine (drained) and the requests' ids."""
    eng = ds.init_inference(tiny(paged_attention_impl=impl, **model),
                            params=params, dtype=jnp.float32)
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
    return worst, srv, uids


# the interpreted kernel takes seconds a call (12 calls a decode step): its
# run is three requests through two slots over a 48-position table
@pytest.mark.parametrize("impl, n, slots, model", [
    ("gather", 6, 3, {}),
    ("kernel", 3, 2, {"max_position_embeddings": 48})])
def test_serving_matches_the_reference(model_params, impl, n, slots, model):
    _, params = model_params
    worst, srv, uids = serve_and_compare(params, impl, n, slots, **model)
    assert worst < TOL
    assert srv.stats()["completed"] == n
    assert [len(srv.results[u]["tokens"]) for u in uids] == list(NEW[:n])
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    assert srv.model.paged_attention_impl() == impl and not srv._recurrent


def test_a_decode_window_is_its_tokens_one_after_another(model_params):
    """A (B, W) window (chunked prefill's to take, ROADMAP D17): window
    token i sits at position ``lengths + i``."""
    _, params = model_params
    m = tiny(paged_attention_impl="gather")
    pool = m.init_serving_state(2, 9, 8, dtype=jnp.float32)
    prompt = jnp.asarray(tokens(7, 1, 16))
    _, pool = m.prefill_paged(params, prompt, pool,
                              jnp.asarray([1, 2], jnp.int32), jnp.int32(1),
                              jnp.int32(13))
    tables = jnp.asarray([[0, 0, 0], [1, 2, 3]], jnp.int32)
    lengths = jnp.asarray([0, 13])
    window, _ = m.decode_step_paged(params, jnp.asarray([[0, 0], [5, 9]]),
                                    pool, tables, lengths)
    one, pool = m.decode_step_paged(params, jnp.asarray([0, 5]), pool,
                                    tables, lengths)
    two, _ = m.decode_step_paged(params, jnp.asarray([0, 9]), pool, tables,
                                 lengths + jnp.asarray([0, 1]))
    assert rel_err(window[1, 0], one[1]) < 1e-5
    assert rel_err(window[1, 1], two[1]) < 1e-5


# ------------------------------------------------------ (c) negative controls
def test_positions_off_by_one_in_decode_fail(model_params, monkeypatch):
    """Rotary positions from ``lengths + 1``: the new token's query and key
    are turned one position too far against the prompt's keys."""
    _, params = model_params
    sound = ouro_mod.apply_rotary_pos_emb

    def late(x, cos, sin, positions, *a, **k):
        if positions.ndim == 2:             # decode: (B, W) from lengths
            positions = positions + 1
        return sound(x, cos, sin, positions, *a, **k)
    monkeypatch.setattr(ouro_mod, "apply_rotary_pos_emb", late)
    worst, _, _ = serve_and_compare(params)
    assert worst > 10 * TOL


def test_loops_that_share_one_cache_fail(model_params, monkeypatch):
    """Loop r reading loop R-1's K/V (the cheaper "shared cache" decode):
    other logits, so a different result and not a faster one."""
    _, params = model_params
    sound = pk.gather_kv

    def last_loops(pool, layer, *a, **k):
        return sound(pool, (R - 1) * L + layer % L, *a, **k)
    monkeypatch.setattr(pk, "gather_kv", last_loops)
    worst, _, _ = serve_and_compare(params)
    assert worst > 10 * TOL


# ------------------------------------------- (d) what the pool and a token cost
def test_the_pool_has_a_layer_for_every_loop_of_every_layer(model_params):
    m, params = model_params
    t0 = time.monotonic()
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={"batch_slots": 2,
                                            "block_size": 16,
                                            "num_blocks": 12})
    assert srv.pool["k"].shape == (R * L, 12, 16, 4 * 32)
    st = srv.stats()
    assert st["kv_layers"] == R * L == 12 and st["loop_steps"] == R
    assert st["kv_bytes_per_token"] == R * L * 2 * 4 * 32 * 4
    assert st["kv_pool_bytes"] == 12 * 16 * st["kv_bytes_per_token"]
    plan = capacity.serving_plan(
        n_layer=L, kv_layers=m.config.kv_layers, n_head=4, head_dim=32,
        max_seq=256, num_blocks=12)
    # the plan prices 16-bit cells; this pool is float32
    assert 2 * plan["paged_kv_pool"] == st["kv_pool_bytes"]
    srv.submit(Request(tokens=tokens(1, 20), max_new_tokens=3))
    while srv.step():
        pass
    (pre,) = rows_since(srv, t0, "serving.prefill")
    assert (pre["kv_layers"], pre["loop_steps"], pre["bucket"]) == (12, 4, 32)
    steps = rows_since(srv, t0, "serving.step")
    assert [a["blocks_in_use"] for a in steps] == [2, 2, 0]
    assert [a["kv_tokens"] for a in steps] == [20, 20, 0]
    assert not any(a["waits_for_blocks"] for a in steps)


@pytest.mark.parametrize("config, want", [
    (gpt2.GPT2Config(n_layer=7), 7),
    (jamba.JambaConfig(), 2),
    (ouro_mod.OuroConfig(), 192)])
def test_every_family_says_how_many_layers_keep_kv(config, want):
    assert config.kv_layers == want


def test_other_families_spans_and_stats_carry_the_new_fields():
    m = build("gpt2-tiny", dtype=jnp.float32)
    t0 = time.monotonic()
    eng = ds.init_inference(m, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={"batch_slots": 2,
                                            "block_size": 8})
    srv.submit(Request(tokens=tokens(1, 11), max_new_tokens=3))
    while srv.step():
        pass
    st = srv.stats()
    assert (st["kv_layers"], st["loop_steps"]) == (m.config.n_layer, 1)
    assert st["kv_bytes_per_token"] == m.config.n_layer * 2 * 128 * 4
    steps = rows_since(srv, t0, "serving.step")
    assert max(a["blocks_in_use"] for a in steps) == 2
    assert max(a["kv_tokens"] for a in steps) == 11
    assert not any(a["waits_for_blocks"] for a in steps)


def test_one_layers_prefill_write_is_the_whole_writes_layer():
    pool = pk.init_pool(3, 6, 8, 2, 16, jnp.float32)
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((3, 16, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((3, 16, 2, 16)), jnp.float32)
    blocks = jnp.asarray([4, 2], jnp.int32)
    whole = pk.write_prefill(pool, blocks, k, v)
    one = pool
    for layer in range(3):
        one = pk.write_prefill(one, blocks, k[layer], v[layer],
                               layer=jnp.int32(layer))
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(one[name]),
                                      np.asarray(whole[name]))
    qpool = pk.init_pool(3, 6, 8, 2, 16, jnp.bfloat16, kv_bits=8,
                         quant_block=8)
    whole = pk.write_prefill(qpool, blocks, k, v)
    one = pk.write_prefill(qpool, blocks, k[1], v[1], layer=1)
    for name in whole:
        np.testing.assert_array_equal(np.asarray(one[name][1]),
                                      np.asarray(whole[name][1]))


# -------------------------------------------- (e) the pool fills before the slots
def test_a_request_waits_for_blocks_beside_a_free_slot(model_params):
    m, params = model_params
    t0 = time.monotonic()
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    # 7 allocatable blocks of 8 tokens; each request reserves 24 + 8 tokens
    # = 4 blocks: one seats, the second waits for blocks though 3 slots are
    # free
    srv = ServingEngine(engine=eng, config={"batch_slots": 4,
                                            "block_size": 8,
                                            "num_blocks": 8})
    uids = [srv.submit(Request(tokens=tokens(40 + i, 24), max_new_tokens=8))
            for i in range(3)]
    most = 0
    while srv.step():
        most = max(most, sum(s is not None for s in srv._slots))
    assert most == 1
    steps = rows_since(srv, t0, "serving.step")
    waited = [a["waits_for_blocks"] for a in steps]
    # while the first two decode, the next one waits; the last has no one
    # behind it
    assert waited == [True] * 14 + [False] * 8
    assert all(a["n_active"] == 1 and a["blocks_in_use"] in (0, 4)
               for a in steps)
    assert max(a["kv_tokens"] for a in steps) == 29
    assert all(len(srv.results[u]["tokens"]) == 8 for u in uids)
    assert srv.allocator.free_blocks == srv.num_blocks - 1


# ------------------------------------------------------ (g) refused by name
@pytest.mark.parametrize("key, value", [
    ("early_exit_threshold", 0.9),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0})])
def test_what_the_model_does_not_run_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        tiny(**{key: value})
