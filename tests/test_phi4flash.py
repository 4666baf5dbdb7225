"""Phi4Flash (models/phi4flash.py, Phi-4-mini-flash-reasoning): a
decoder-hybrid-decoder whose serving state is THREE kinds at once — one
growing paged cache that the full layer writes and every cross layer reads
again, rings for the window layers, recurrent rows for the Mamba layers — with
differential attention through every attention call and a prefill that runs
the cross-decoder at the prompt's last position alone.  Every number is held
against the benchmark's plain reference (``benchmark/reference/phi4flash.py``),
which shares no code with the program and knows no cache, no padding of
queries and no kernel.

Tiny model: 8 layers (all five kinds: Mamba, window, Mamba that keeps ``m``,
full, GMU, cross), hidden 64, 4 query / 2 K/V heads of 16, a window of 8 that
a 40-token stream slides several times, state 4, vocabulary 128; seeded
weights, float32, the projections that feed the scores enlarged and the norms'
weights and biases drawn (``sharp``), so that attention is no average and a
dropped bias shows.
"""

import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.inference import Request, ServingEngine
from deepspeed_tpu.inference import paged_kv as pk
from deepspeed_tpu.models import build, phi4flash
from deepspeed_tpu.ops import selective_scan as ss
from benchmark import control_phi4flash
from benchmark.families import phi4flash as family
from benchmark.reference import phi4flash as reference

PRESET = phi4flash.PRESETS["phi4flash-tiny"]
TOL = 1e-3          # of the largest reference logit; float32 reads ~1e-6
BLOCK = 8


def tiny(dtype=jnp.float32, **overrides):
    return build("phi4flash-tiny", dtype=dtype,
                 **{"max_position_embeddings": 64, **overrides})


def ref_cfg(model, **extra):
    """The reference's configuration (published key names) of ``model``."""
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "sliding_window", "layer_norm_eps",
            "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank")
    return {**{k: getattr(model.config, k) for k in keys}, **extra}


def sharp(params):
    """The query projections enlarged (scores of order 1: at the tiny width
    0.02 sqrt(64) is a sixth, and attention nearly an average), ``in_proj``
    likewise (x, B and C of order 1, or ``S C`` is nothing beside ``D x``),
    and the norms' weights and biases drawn (ones and zeros would hide a
    dropped bias)."""
    def jig(path, x):
        name = path[-1].key
        key = jax.random.fold_in(jax.random.PRNGKey(11),
                                 hash(tuple(p.key for p in path)) % (2 ** 31))
        if name in ("qkv_w", "q_w"):
            return 8.0 * x
        if name == "in_w" and path[0].key == "mamba":
            return 6.0 * x
        if name == "x_w":          # B and C of order 1, as 0.02 sqrt(5120) is
            return 8.0 * x
        if name in ("ln_b", "lnf_b", "conv_b"):
            return 0.1 * jax.random.normal(key, x.shape)
        if name in ("ln_w", "lnf_w", "sub_w"):
            return 1.0 + 0.1 * jax.random.normal(key, x.shape)
        return x
    return jax.tree_util.tree_map_with_path(jig, params)


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


# ------------------------------------------------ (a) forward, loss, the count
def test_layer_kinds_and_parameter_count(model_params):
    m, params = model_params
    c = m.config
    assert [c.kind(l) for l in range(8)] == [
        "mamba", "window", "mamba", "window", "mamba", "full", "gmu", "cross"]
    assert (c.memory_layer, c.full_layer, c.shared_kv_readers) == (4, 5, 2)
    leaves = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert leaves == m.num_params() == family.parameters(
        {**ref_cfg(m), "vocab_size": 128, "intermediate_size": 96})
    assert m.has_recurrent_state and m.has_window_layers


def test_the_published_count_is_the_closed_form_and_the_programs_shapes():
    from benchmark import harness
    cfg = harness.read_json("configs", "phi-4-mini-flash-reasoning.json")
    m = family.build(cfg, jnp.bfloat16)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    leaves = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert leaves == m.num_params() == family.parameters(cfg) \
        == cfg["parameters"] == 3_852_562_944
    c = m.config
    kinds = [c.kind(l) for l in range(32)]
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert (c.memory_layer, c.full_layer, c.shared_kv_readers) == (16, 17, 8)
    # a prefill of 100 tokens leaves 14 layers out at 99 positions
    assert m.prefill_attrs(100) == {"positions_skipped": 99 * 14}
    assert m.ring_entries(64) == 9 and m.state_step_bytes() == 9 * 2 * 327680


@pytest.mark.parametrize("position", [0, 5, 17, 39])
def test_forward_logits_match_the_reference(model_params, position):
    m, params = model_params
    toks = tokens(1, 2, 40)
    got = m.apply(params, jnp.asarray(toks))[:, position]
    want = reference.logits_at(ref_cfg(m), params, jnp.asarray(toks),
                               jnp.full((2,), position))
    assert rel_err(got, want) < 1e-5


def test_loss_matches_the_reference(model_params):
    m, params = model_params
    batch = jnp.asarray(tokens(2, 2, 25))
    got = float(m.loss(params, batch))
    want = float(reference.loss(ref_cfg(m), params, batch))
    assert abs(got - want) < 1e-5 * abs(want)
    grads = jax.grad(m.loss)(params, batch)
    for kind in ("mamba", "attn", "gmu", "cross", "mlp"):
        assert all(float(jnp.abs(g).max()) > 0
                   for g in jax.tree_util.tree_leaves(grads[kind])), kind


def test_cached_decoding_matches_the_full_forward(model_params):
    m, params = model_params
    toks = jnp.asarray(tokens(3, 2, 30))
    full = m.apply(params, toks)
    cache = m.init_cache(2, 32, jnp.float32)
    first, cache = m.apply_with_cache(params, toks[:, :21], cache)
    assert rel_err(first, full[:, :21]) < 1e-5
    for t in range(21, 30):                     # the window slides twice more
        one, cache = m.apply_with_cache(params, toks[:, t:t + 1], cache)
        assert rel_err(one[:, 0], full[:, t]) < 1e-5, t


@pytest.mark.parametrize("overrides", [
    dict(num_hidden_layers=6), dict(mb_per_layer=1), dict(hidden_act="gelu"),
    dict(tie_word_embeddings=False), dict(mlp_bias=True)])
def test_what_the_file_does_not_compute_is_refused(overrides):
    with pytest.raises(AssertionError):
        tiny(**overrides)


# --------------------------------------- (b) the paged path, a call at a time
def seat(m, params, pool, prompt, slot, blocks, wblocks):
    """``prefill_paged`` for one prompt as the engine calls it: padded to its
    bucket, the table's blocks then the ring's."""
    T = len(prompt)
    nb = pk.blocks_needed(T, BLOCK)
    toks = np.zeros((1, nb * BLOCK), np.int32)
    toks[0, :T] = prompt
    ring = m.ring_entries(BLOCK)
    blk = np.zeros((nb + ring,), np.int32)
    blk[:nb] = blocks[:nb]
    blk[nb:nb + len(wblocks)] = wblocks
    return m.prefill_paged(params, jnp.asarray(toks), pool, jnp.asarray(blk),
                           slot, T)


def paged_logits(params, prompts, steps=6, model=None):
    """Two streams seated together through the three kinds of state and
    ``steps`` teacher-forced decode steps.  Returns ``(logits (steps + 1, 2,
    V), histories)``: row 0 of the logits is the prefill's."""
    m = model or tiny()
    nb_max, ring = 8, m.ring_entries(BLOCK)
    pool = m.init_serving_state(2, 1 + 2 * nb_max, BLOCK, dtype=jnp.float32)
    tables = np.zeros((2, nb_max + ring), np.int32)
    rows = []
    for s, prompt in enumerate(prompts):
        blocks = 1 + s * nb_max + np.arange(nb_max)
        wblocks = 1 + s * ring + np.arange(
            min(ring, pk.blocks_needed(len(prompt) + steps, BLOCK)))
        tables[s, :nb_max] = blocks
        tables[s, nb_max:nb_max + len(wblocks)] = wblocks
        row, pool = seat(m, params, pool, prompt, s, blocks, wblocks)
        rows.append(row[0])
    out = [jnp.stack(rows)]
    hist = [list(p) for p in prompts]
    lengths = np.array([len(p) for p in prompts], np.int32)
    step = jax.jit(m.decode_step_paged)
    for i in range(steps):
        nxt = tokens(90 + i, 2)
        logits, pool = step(params, jnp.asarray(nxt), pool,
                            jnp.asarray(tables), jnp.asarray(lengths))
        out.append(logits)
        for s in range(2):
            hist[s].append(int(nxt[s]))
        lengths = lengths + 1
    return np.asarray(jnp.stack(out)), hist, pool


def reference_logits(params, hist, n_prompt, steps):
    """The SOUND reference's full forward over each history, read where the
    paged path produced logits: the prompt's end, then every fed token."""
    cfg = SOUND_CFG
    want = []
    for h, n in zip(hist, n_prompt):
        row = np.zeros((1, 64), np.int32)
        row[0, :len(h)] = h
        rows = np.repeat(row, steps + 1, axis=0)
        want.append(reference.logits_at(
            cfg, params, jnp.asarray(rows), jnp.arange(n - 1, n + steps)))
    return np.stack([np.asarray(w) for w in want], axis=1)


SOUND_CFG = ref_cfg(tiny())       # read before any fault is planted

# one stream shorter than the window (its ring holds scratch entries), one
# several windows long
PROMPTS = (5, 37)


def paged_error(params, model=None, steps=6):
    m = model or tiny()
    prompts = [tokens(40 + i, n) for i, n in enumerate(PROMPTS)]
    got, hist, pool = paged_logits(params, prompts, steps, model=m)
    want = reference_logits(params, hist, PROMPTS, steps)
    return rel_err(got[1:], want[1:]), rel_err(got[0], want[0]), pool


@pytest.fixture(scope="module")
def sound(model_params):
    return paged_error(model_params[1])


def test_the_paged_path_matches_the_reference_past_the_window(sound):
    decode, prefill, pool = sound
    # 1e-5 of float32's own rounding through 8 layers; the tolerance of the
    # served comparison (TOL) is for a bfloat16's worth and is the faults'
    assert decode < 1e-5 and prefill < 1e-5
    assert pool["ssm"].dtype == jnp.float32
    assert pool["k"].shape == (1, 17, BLOCK, 32)        # ONE layer, [k1 | k2]
    assert pool["wk"].shape == (2, 5, BLOCK, 32)        # the window layers
    assert pool["conv"].shape == (3, 2, 3, 128)
    assert pool["ssm"].shape == (3, 2, 4, 128)
    # a decode step ran all 8 layers for both rows
    assert [int(x) for x in pool["counters"]] == [2 * 6, 2 * 2]


def test_the_prefill_runs_the_cross_decoder_at_one_position(model_params):
    m, params = model_params
    prompt = tokens(7, 21)                       # bucket 24: three pad tokens
    pool = m.init_serving_state(1, 9, BLOCK, dtype=jnp.float32)
    row, pool = seat(m, params, pool, prompt, 0, 1 + np.arange(8),
                     1 + np.arange(2))
    full = m.apply(params, jnp.asarray(prompt[None]))
    assert rel_err(row[0], full[0, 20]) < 1e-5
    # what the dispatch counted: 6 layers over the prompt, 2 at one position
    assert [int(x) for x in pool["counters"]] == [6 * 21, 2 * 1]
    assert m.prefill_attrs(21) == {"positions_skipped": 20 * 2}
    assert 6 * 21 + 2 + 20 * 2 == 8 * 21


def test_an_inactive_row_keeps_its_recurrent_rows(model_params):
    m, params = model_params
    pool = m.init_serving_state(2, 5, BLOCK, dtype=jnp.float32)
    pool = dict(pool, ssm=pool["ssm"] + 1.0, conv=pool["conv"] + 2.0)
    tables = jnp.asarray([[1, 2, 1, 2], [0, 0, 0, 0]], jnp.int32)
    _, new = m.decode_step_paged(params, jnp.asarray([3, 4]), pool, tables,
                                 jnp.asarray([5, 0], jnp.int32))
    assert float(jnp.abs(new["ssm"][:, 1] - 1.0).max()) == 0.0
    assert float(jnp.abs(new["conv"][:, 1] - 2.0).max()) == 0.0
    assert float(jnp.abs(new["ssm"][:, 0] - 1.0).max()) > 0.0
    assert [int(x) for x in new["counters"]] == [6, 2]        # 1 live row


# ---------------------------------------------------------- (c) planted faults
# bfloat16 rounding of the state reads 6e-5: a hundred times float32 own 5e-7
FAULT_FLOORS = dict.fromkeys(control_phi4flash.FAULTS, 10 * TOL)
FAULT_FLOORS.update(ssm_bf16=TOL / 50)


@pytest.mark.parametrize("fault", control_phi4flash.FAULTS)
def test_each_planted_fault_fails_at_float32(model_params, sound, fault):
    """``benchmark/control_phi4flash.py``'s faults, each through the paged
    path against the sound reference at float32, where nothing hides below
    the precision served."""
    _, params = model_params
    unplant = control_phi4flash.plant(fault)
    try:
        # the worse of the decode steps' rows and the prefills' own
        worst = max(paged_error(params, model=tiny())[:2])
    finally:
        unplant()
    assert sound[0] < TOL / 100
    assert worst > max(FAULT_FLOORS[fault], 30 * sound[0]), (fault, worst)


def test_a_cross_layer_reading_kv_of_its_own_fails(model_params, monkeypatch):
    """The one fault the serving path cannot hold (a cross layer has no
    cache): in the full forward, each cross layer projects K and V from its
    OWN input with the full layer's weights."""
    m, params = model_params
    toks = jnp.asarray(tokens(1, 2, 40))
    want = reference.logits_at(ref_cfg(m), params, toks, jnp.full((2,), 39))
    cross_q = phi4flash.Phi4Flash._cross_q
    seen = {}

    def noting(self, p, u):
        seen["u"] = u
        return cross_q(self, p, u)

    def own_kv(self, params, h, m_, cross_fn):
        full = jax.tree_util.tree_map(lambda x: x[-1], params["attn"])

        def attend(q):
            _, k, v = self._qkv(full, seen["u"])
            return phi4flash.banded_attention(q, k, v)
        return decoder(self, params, h, m_, attend)
    decoder = phi4flash.Phi4Flash._cross_decoder
    monkeypatch.setattr(phi4flash.Phi4Flash, "_cross_q", noting)
    monkeypatch.setattr(phi4flash.Phi4Flash, "_cross_decoder", own_kv)
    got = tiny().apply(params, toks)[:, 39]
    assert rel_err(got, want) > 10 * TOL


# ---------------------------------------------- (d) through the serving engine
SERVED = (5, 27, 13, 40, 9, 33)          # prompts: under and over the window
NEW = (6, 8, 5, 7, 9, 4)


def live_logit_error(srv, params, ref):
    """The benchmark's check: the NEXT decode step's logits through both
    pools and the recurrent rows, against the reference's full forward over
    each live slot's history."""
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
        want = ref(params, jnp.asarray(row), jnp.asarray([len(hist) - 1]))
        worst = max(worst, rel_err(got[i], want[0]))
    return worst, len(live)


def test_serving_matches_the_reference(model_params):
    """Six requests through three slots: every slot is seated, freed and
    seated again by a second stream (no state may leak), streams shorter and
    several times longer than the window seated together."""
    m, params = model_params
    cfg = ref_cfg(m)
    ref = jax.jit(lambda p, t, pos: reference.logits_at(cfg, p, t, pos))
    eng = ds.init_inference(tiny(), params=params, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={"batch_slots": 3,
                                            "block_size": BLOCK})
    t0 = time.monotonic()      # the recorder is the process's: other tests'
    #                            rows lie before this
    uids = [srv.submit(Request(tokens=tokens(20 + i, n), max_new_tokens=new))
            for i, (n, new) in enumerate(zip(SERVED, NEW))]
    worst, seen = 0.0, 0
    while srv.step():
        if any(s is not None for s in srv._slots):
            err, n = live_logit_error(srv, params, ref)
            worst, seen = max(worst, err), seen + n
    assert seen > 20 and worst < 1e-5
    st = srv.stats()
    assert st["completed"] == 6 and st["state_seats"] == 6   # slots reused
    assert [len(srv.results[u]["tokens"]) for u in uids] == list(NEW)
    # both allocators' blocks came home
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    assert srv.window_allocator.free_blocks == srv.window_num_blocks - 1
    # what the donated pytree holds, by kind
    per_stream = 3 * (4 * 4 * 128 + 4 * 3 * 128)
    assert st["recurrent_state_bytes"] == 3 * per_stream
    assert st["state_bytes_per_stream"] == per_stream
    assert st["kv_bytes_per_token"] == 2 * 32 * 4            # ONE layer
    assert (st["shared_kv_readers"], st["window_layers"],
            st["mamba_layers"], st["kv_layers"]) == (2, 2, 3, 1)
    assert st["window_ring_blocks"] == 2
    assert st["window_pool_bytes"] == 2 * srv.pool["wk"].nbytes
    # the spans' attributes
    rows = [r for r in srv._spans.rows() if r.t_start >= t0]
    for pre in (r.attrs for r in rows if r.name == "serving.prefill"):
        T = pre["prompt_len"]
        assert pre["scan_tokens"] == T
        assert (pre["positions_self"], pre["positions_cross"],
                pre["positions_skipped"]) == (6 * T, 2, 2 * (T - 1))
    steps = [r.attrs for r in rows if r.name == "serving.step" and r.attrs]
    assert steps and max(a["seated_slots"] for a in steps) == 3
    for a in steps:
        assert a["seated_slots"] + a["free_slots"] == 3
        assert a["window_blocks_in_use"] + a["window_blocks_free"] == 6
        assert a["window_kv_tokens"] + a["window_capped_tokens"] \
            == a["kv_tokens"]
        assert a["state_bytes"] == a["seated_slots"] * 3 * 2 * 4 * 4 * 128
    # (a step that `live_logit_error` settled from outside books no counters:
    # one more stream, left to the engine's own loop)
    srv.submit(Request(tokens=tokens(30, 11), max_new_tokens=4))
    while srv.step():
        pass
    counted = [r.attrs for r in srv._spans.rows() if r.t_start >= t0
               and r.name == "serving.step" and "positions_self" in (r.attrs or {})]
    assert counted and all((a["positions_self"], a["positions_cross"])
                           == (6, 2) for a in counted)


FEATURES = {"prefix_cache": True, "kv_snapshot": {"every_tokens": 4},
            "transfer": {"dir": "/nonexistent"}, "role": "prefill"}


@pytest.mark.parametrize("name", list(FEATURES))
def test_what_needs_blocks_alone_is_refused_by_both_kinds_names(name):
    eng = ds.init_inference(tiny(), dtype=jnp.float32)
    said = (rf"^serving\.{name}=.* cannot serve a model with recurrent state "
            r"and sliding-window layers: .+; .+ \(docs/serving\.md"
            r"#recurrent-state, docs/serving\.md#window-layers\)$")
    with pytest.raises(ValueError, match=said):
        ServingEngine(engine=eng, config={
            "batch_slots": 2, "block_size": BLOCK,
            "journal_dir": "/nonexistent", name: FEATURES[name]})


def test_a_ragged_table_refuses_by_the_kinds_that_list_the_feature(monkeypatch):
    """A feature that the FIRST shown kind does not list is still refused,
    by the kind that lists it alone, and a kind that lacks one raises no
    ``KeyError``."""
    from deepspeed_tpu.inference import serving
    table = dict(serving._NEEDS_BLOCKS_ALONE)
    what, why = table["recurrent-state"]
    table["recurrent-state"] = (what, {k: v for k, v in why.items()
                                       if k != "prefix_cache"})
    monkeypatch.setattr(serving, "_NEEDS_BLOCKS_ALONE", table)
    eng = ds.init_inference(tiny(), dtype=jnp.float32)
    said = (r"^serving\.prefix_cache=True cannot serve a model with "
            r"sliding-window layers: [^;]+ \(docs/serving\.md#window-layers\)$")
    with pytest.raises(ValueError, match=said):
        ServingEngine(engine=eng, config={
            "batch_slots": 2, "block_size": BLOCK, "prefix_cache": True})


def test_an_int8_pool_is_refused_by_name():
    with pytest.raises(ValueError, match="kv_bits = 8"):
        tiny().init_serving_state(2, 5, BLOCK, kv_bits=8)


# ----------------------------------------------- (e) the scan's ungated output
def scan_operands(T=16, Bt=2, Di=1024, N=4, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    return dict(x=n(k[0], Bt, T, Di), delta=jax.nn.softplus(n(k[1], Bt, T, Di)),
                A=-jnp.exp(n(k[2], N, Di)), B=n(k[3], Bt, T, N),
                C=n(k[4], Bt, T, N), D=n(k[5], Di), z=n(k[6], Bt, T, Di))


def parent_scan(x, delta, A, B, C, D, z, h0=None):
    """``selective_scan_jnp`` as the parent commit had it, gate fused."""
    f32 = jnp.float32
    Bt, T, Di = x.shape
    if h0 is None:
        h0 = jnp.zeros((Bt, A.shape[0], Di), f32)

    def step(S, inp):
        x_t, d_t, b_t, c_t = inp
        dA = jnp.exp(d_t[:, None, :] * A[None])
        S = dA * S + (d_t * x_t)[:, None, :] * b_t[:, :, None]
        return S, jnp.einsum("bnd,bn->bd", S, c_t)
    xs = tuple(a.astype(f32).swapaxes(0, 1) for a in (x, delta, B, C))
    S, y = jax.lax.scan(step, h0.astype(f32), xs)
    y = y.swapaxes(0, 1) + D.astype(f32) * x.astype(f32)
    zf = z.astype(f32)
    return (y * zf * jax.nn.sigmoid(zf)).astype(x.dtype), S


@pytest.mark.parametrize("impl", ["jnp", "kernel", "step"])
def test_the_ungated_output_is_the_gated_one_before_its_gate(impl):
    o = scan_operands()
    if impl == "step":
        S0 = jax.random.normal(jax.random.PRNGKey(9), (2, 4, 1024))
        one = {k: (v[:, 0] if v.ndim == 3 and k not in "A" else v)
               for k, v in o.items()}
        args = (one["x"], one["delta"], o["A"], one["B"], one["C"], o["D"])
        gated, Sg = ss.selective_step(*args, one["z"], S0)
        plain, Sp = ss.selective_step(*args, None, S0)
        z = one["z"]
    else:
        args = (o["x"], o["delta"], o["A"], o["B"], o["C"], o["D"])
        gated, Sg = ss.selective_scan(*args, o["z"], impl=impl)
        plain, Sp = ss.selective_scan(*args, None, impl=impl)
        z = o["z"]
    np.testing.assert_array_equal(np.asarray(Sg), np.asarray(Sp))
    np.testing.assert_allclose(np.asarray(plain * jax.nn.silu(z)),
                               np.asarray(gated), rtol=2e-6, atol=1e-6)
    want = parent_scan(**o)[0] / jax.nn.silu(o["z"])
    if impl != "step":
        np.testing.assert_allclose(np.asarray(plain), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_gated_scan_is_the_parents_bit_for_bit(dtype):
    """What Jamba's calls compute did not move: the gated ``lax.scan`` form
    and the one-token step against the parent's functions written out, and
    the kernel's gated call traces to the same equations whether or not the
    ungated form exists (one pallas_call, three token operands)."""
    o = {k: (v.astype(dtype) if k in ("x", "z") else v)
         for k, v in scan_operands(seed=1).items()}
    got, S = ss.selective_scan_jnp(**o)
    want, S_want = parent_scan(**o)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(S), np.asarray(S_want))
    text = str(jax.make_jaxpr(lambda **kw: ss.selective_scan_kernel(
        **kw, interpret=True))(**o))
    assert text.count("pallas_call") == 1
    ungated = str(jax.make_jaxpr(lambda **kw: ss.selective_scan_kernel(
        **{**kw, "z": None}, interpret=True))(**{k: v for k, v in o.items()
                                                 if k != "z"}))
    assert ungated.count("pallas_call") == 1 and "logistic" in text \
        and "logistic" not in ungated


def test_jambas_forward_is_what_the_parents_scan_gives(monkeypatch):
    from deepspeed_tpu.models import jamba
    m = build("jamba-tiny", dtype=jnp.float32)
    params = m.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(tokens(5, 2, 24, hi=512))
    now = m.apply(params, toks, scan_impl="jnp")
    monkeypatch.setattr(jamba.ss, "selective_scan",
                        lambda x, d, A, B, C, D, z, h0=None, impl="auto":
                        parent_scan(x, d, A, B, C, D, z, h0))
    np.testing.assert_array_equal(np.asarray(now), np.asarray(
        m.apply(params, toks, scan_impl="jnp")))
