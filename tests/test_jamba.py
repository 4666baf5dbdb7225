"""Jamba (models/jamba.py): Mamba mixers beside multi-query attention, and
the serving engine's second kind of state.  Every number is held against the
benchmark's plain reference (``benchmark/reference/jamba.py``), which shares
no code with the program.

Tiny model: 4 layers with attention at ``l % 4 == 1``, hidden 128, state 16,
conv 4, 4 query heads over 1 K/V head, seeded weights, float32 (so that a
wrong hand-off of state stands three orders above the rounding).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.inference import Request, ServingEngine, paged_kv as pk
from deepspeed_tpu.models import build
from deepspeed_tpu.models import jamba as jamba_mod
from deepspeed_tpu.ops import selective_scan as ss
from deepspeed_tpu.ops.transformer.paged_attention import paged_attention
from benchmark.reference import jamba as reference

CFG = {"model_type": "jamba", "vocab_size": 512, "hidden_size": 128,
       "num_hidden_layers": 4, "num_attention_heads": 4,
       "num_key_value_heads": 1, "intermediate_size": 256,
       "attn_layer_period": 4, "attn_layer_offset": 1, "mamba_d_state": 16,
       "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 8,
       "rms_norm_eps": 1e-6, "max_position_embeddings": 256}
TOL = 1e-3          # of the largest reference logit; float32 reads ~1e-6


def tiny(dtype=jnp.float32, **overrides):
    keys = {k: v for k, v in CFG.items() if k != "model_type"}
    return build("jamba-tiny", dtype=dtype, **{**keys, **overrides})


@pytest.fixture(scope="module")
def model_params():
    m = tiny()
    return m, m.init(jax.random.PRNGKey(3))


def tokens(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                         CFG["vocab_size"]), np.int32)


def rel_err(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


# ------------------------------------------------ (a) forward, loss, gradients
def test_layer_order_and_parameter_count(model_params):
    m, params = model_params
    c = m.config
    assert c.attn_layers == (1,) and c.n_mamba_layer == 3
    assert [s[0] for s in c.segments()] == ["mamba", "attn", "mamba"]
    big = jamba_mod.JambaConfig()           # the published 3B defaults
    assert big.attn_layers == (7, 21)
    assert [(k, l1 - l0) for k, l0, l1, _ in big.segments()] == [
        ("mamba", 7), ("attn", 1), ("mamba", 13), ("attn", 1), ("mamba", 6)]
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == m.num_params()


@pytest.mark.parametrize("position", [0, 17, 39])
def test_forward_logits_match_the_reference(model_params, position):
    m, params = model_params
    toks = tokens(1, 2, 40)
    got = m.apply(params, toks)[:, position]
    ref = reference.logits_at(CFG, params, jnp.asarray(toks),
                              jnp.full((2,), position))
    assert rel_err(got, ref) < 1e-4


def test_loss_and_gradients_match_the_reference(model_params):
    m, params = model_params
    batch = jnp.asarray(tokens(2, 2, 33))
    got, g_got = jax.value_and_grad(lambda p: m.loss(p, batch, None))(params)
    ref, g_ref = jax.value_and_grad(
        lambda p: reference.loss(CFG, p, batch))(params)
    assert abs(float(got) - float(ref)) < 1e-5 * abs(float(ref))
    flat_got = jax.tree_util.tree_leaves_with_path(g_got)
    flat_ref = dict(jax.tree_util.tree_leaves_with_path(g_ref))
    for path, g in flat_got:
        r = flat_ref[path]
        scale = float(jnp.abs(r).max()) + 1e-12
        assert float(jnp.abs(g - r).max()) < 2e-3 * scale + 1e-9, path


def test_a_tiny_one_trains_through_ds_initialize():
    m = tiny()
    engine, *_ = ds.initialize(
        model=m, config={"train_micro_batch_size_per_gpu": 2,
                         "optimizer": {"type": "Adam",
                                       "params": {"lr": 1e-2}},
                         "zero_optimization": {"stage": 0}})
    import itertools
    feed = itertools.repeat(tokens(5, 16, 33))
    losses = [float(engine.train_batch(feed)) for _ in range(8)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_cached_decoding_matches_the_full_forward(model_params):
    m, params = model_params
    toks = jnp.asarray(tokens(4, 2, 30))
    full = m.apply(params, toks)
    cache = m.init_cache(2, 32)
    got, cache = m.apply_with_cache(params, toks[:, :21], cache)
    assert rel_err(got, full[:, :21]) < 1e-4
    for t in range(21, 30):
        step, cache = m.apply_with_cache(params, toks[:, t:t + 1], cache)
        assert rel_err(step[:, 0], full[:, t]) < 1e-4
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    out = eng.generate(np.asarray(toks[:, :10]), max_new_tokens=4)
    assert out.shape == (2, 14)


# --------------------------------------------------- (c) the chunked scan
def scan_operands(T, Di=1024, N=16, Bt=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (Bt, T, Di))
    z = jax.random.normal(k[1], (Bt, T, Di))
    delta = jax.nn.softplus(jax.random.normal(k[2], (Bt, T, Di)) - 3.0)
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None],
                          (N, Di))
    B = jax.random.normal(k[3], (Bt, T, N))
    C = jax.random.normal(k[4], (Bt, T, N))
    return x, delta, A, B, C, jnp.ones((Di,)), z


@pytest.mark.parametrize("T, t_real", [(64, 64), (72, 70), (128, 64),
                                       (136, 65), (16, 9), (8, 1)])
def test_chunked_scan_matches_the_plain_scan(T, t_real):
    """Interpreted.  Chunk edges (64 tokens a chunk), a last chunk that is
    not whole, and ``t_real`` inside a chunk: the state handed back is the
    state after token ``t_real - 1``, which is the plain scan over the
    prefix alone."""
    x, delta, *rest = scan_operands(T)
    masked = ss.mask_delta(delta, t_real)
    y_k, s_k = ss.selective_scan_kernel(x, masked, *rest, interpret=True)
    y_j, s_j = ss.selective_scan_jnp(x, masked, *rest)
    assert float(jnp.abs(y_k - y_j).max()) < 1e-4
    assert float(jnp.abs(s_k - s_j).max()) < 1e-5
    cut = lambda a: a[:, :t_real] if a.ndim == 3 else a
    _, s_prefix = ss.selective_scan_jnp(*(cut(a) for a in (x, delta, *rest)))
    assert float(jnp.abs(s_k - s_prefix).max()) < 1e-5


def test_scan_dispatch_and_the_one_token_step():
    x, delta, A, B, C, D, z = scan_operands(12, Di=256)
    assert not ss.kernel_supports(12, 256)            # 12 tokens, 256 wide
    assert ss.kernel_supports(48, 5120)
    y, S = ss.selective_scan(x, delta, A, B, C, D, z)         # plain, CPU
    # a scan continued from a state equals the scan over the whole
    y1, S1 = ss.selective_scan(x[:, :7], delta[:, :7], A, B[:, :7],
                               C[:, :7], D, z[:, :7])
    y2, S2 = ss.selective_scan(x[:, 7:], delta[:, 7:], A, B[:, 7:],
                               C[:, 7:], D, z[:, 7:], h0=S1)
    assert float(jnp.abs(jnp.concatenate([y1, y2], 1) - y).max()) < 1e-5
    assert float(jnp.abs(S2 - S).max()) < 1e-6
    # and the one-token update is one step of it
    y3, S3 = ss.selective_step(x[:, 7], delta[:, 7], A, B[:, 7], C[:, 7], D,
                               z[:, 7], S1)
    assert float(jnp.abs(y3 - y[:, 7]).max()) < 1e-5
    with pytest.raises(AssertionError, match="zero state"):
        ss.selective_scan(x, delta, A, B, C, D, z, h0=S1, impl="kernel")


def test_causal_conv_carries_its_tail():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    b = jnp.arange(8.0)
    whole, padded = ss.causal_conv(x, w, b)
    first, p1 = ss.causal_conv(x[:, :6], w, b)
    tail = ss.conv_tail_at(p1, 6, 3)
    assert float(jnp.abs(tail - x[:, 3:6]).max()) == 0.0
    second, _ = ss.causal_conv(x[:, 6:], w, b, tail)
    assert float(jnp.abs(jnp.concatenate([first, second], 1)
                         - whole).max()) < 1e-6
    # a prompt shorter than the kernel: the tail keeps its leading zeros
    short = ss.conv_tail_at(padded, 2, 3)
    assert float(jnp.abs(short[:, 0]).max()) == 0.0
    assert float(jnp.abs(short[:, 1:] - x[:, :2]).max()) == 0.0


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


def serve_and_compare(params, model=None):
    """Six requests through three slots: every slot is seated, freed and
    seated again.  Returns the worst logit error seen at any step and the
    engine (drained)."""
    m = model or tiny()
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={
        "batch_slots": 3, "block_size": 8})
    uids = [srv.submit(Request(tokens=tokens(20 + i, n), max_new_tokens=new))
            for i, (n, new) in enumerate(zip(PROMPTS, NEW))]
    worst, seen = 0.0, 0
    while srv.step():
        if any(s is not None for s in srv._slots):
            err, n = live_logit_error(srv, params)
            worst, seen = max(worst, err), seen + n
    assert seen > 20
    return worst, srv, uids


def test_serving_matches_the_reference(model_params):
    _, params = model_params
    worst, srv, uids = serve_and_compare(params)
    assert worst < TOL
    st = srv.stats()
    assert st["completed"] == 6 and st["state_seats"] == 6   # slots reused
    assert [len(srv.results[u]["tokens"]) for u in uids] == list(NEW)
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    # what the donated pytree holds, by kind
    c = srv.model.config
    per_stream = c.n_mamba_layer * (c.mamba_d_state * c.d_inner * 4
                                    + (c.mamba_d_conv - 1) * c.d_inner * 4)
    assert st["recurrent_state_bytes"] == 3 * per_stream
    assert srv.pool["k"].shape == (1, srv.num_blocks, 8, 32)   # 1 x 32 wide
    assert st["kv_pool_bytes"] == 2 * srv.pool["k"].nbytes
    # the new attributes of the prefill span
    rows = srv._spans.rows()
    pre = [r for r in rows if r.name == "serving.prefill"][-1].attrs
    assert pre["scan_tokens"] == pre["prompt_len"]
    assert pre["pad_tokens"] == pre["bucket"] - pre["prompt_len"] > 0


def test_an_inactive_row_keeps_its_recurrent_rows(model_params):
    m, params = model_params
    pool = m.init_serving_state(2, 5, 8, dtype=jnp.float32)
    pool = dict(pool, ssm=pool["ssm"] + 1.0, conv=pool["conv"] + 2.0)
    tables = jnp.asarray([[1, 2], [0, 0]], jnp.int32)       # row 1: scratch
    _, new = m.decode_step_paged(params, jnp.asarray([3, 4]), pool, tables,
                                 jnp.asarray([5, 0], jnp.int32))
    assert float(jnp.abs(new["ssm"][:, 1] - 1.0).max()) == 0.0
    assert float(jnp.abs(new["conv"][:, 1] - 2.0).max()) == 0.0
    assert float(jnp.abs(new["ssm"][:, 0] - 1.0).max()) > 0.0


# ---------------------------------------------------------- (d) sensitivity
def test_state_taken_at_the_buckets_end_fails(model_params, monkeypatch):
    """The pad after the prompt enters the recurrence: what this PR is most
    likely to get wrong, and the check sees it."""
    _, params = model_params
    monkeypatch.setattr(ss, "mask_delta", lambda delta, t_real: delta)
    monkeypatch.setattr(ss, "conv_tail_at",
                        lambda padded, t_real, width: padded[:, -width:])
    worst, _, _ = serve_and_compare(params)
    assert worst > 10 * TOL


def test_a_seat_that_keeps_the_previous_rows_fails(model_params, monkeypatch):
    _, params = model_params
    sound = jamba_mod.Jamba.prefill_paged

    def keeps_rows(self, params, toks, pool, blocks, slot, t_real):
        row, new = sound(self, params, toks, pool, blocks, slot, t_real)
        return row, dict(new, conv=pool["conv"], ssm=pool["ssm"])
    monkeypatch.setattr(jamba_mod.Jamba, "prefill_paged", keeps_rows)
    worst, _, _ = serve_and_compare(params)
    assert worst > 10 * TOL


# ------------------------------------------------- (e) multi-query paged path
MQ = dict(L=2, NB=16, BS=8, H=4, HD=16)
MQ_TABLES = np.asarray([[1, 2, 3, 4], [5, 6, 7, 0], [8, 9, 0, 0],
                        [10, 0, 0, 0], [0, 0, 0, 0]], np.int32)
MQ_LENGTHS = np.asarray([31, 23, 8, 0, 0], np.int32)


def mq_pool(dtype, kv_bits=16, n_kv=1):
    rng = np.random.default_rng(0)
    pool = pk.init_pool(MQ["L"], MQ["NB"], MQ["BS"], MQ["H"], MQ["HD"],
                        dtype if kv_bits == 16 else jnp.bfloat16,
                        kv_bits=kv_bits, quant_block=8, n_kv_head=n_kv)
    shape = (MQ["L"], MQ["NB"] * MQ["BS"], n_kv, MQ["HD"])
    k = jnp.asarray(rng.standard_normal(shape), dtype)
    v = jnp.asarray(rng.standard_normal(shape), dtype)
    return pk.write_prefill(pool, jnp.arange(MQ["NB"], dtype=jnp.int32),
                            k, v), k, v


def dense_attention(q, k, v, tables, lengths, layer, n_kv):
    """Plain attention over each slot's tokens, read back block by block."""
    B, W, H, hd = q.shape
    out = np.zeros((B, W, H * hd), np.float64)
    k, v = np.asarray(k, np.float64), np.asarray(v, np.float64)
    for b in range(B):
        n = int(lengths[b]) + 1
        rows = [int(tables[b, p // MQ["BS"]]) * MQ["BS"] + p % MQ["BS"]
                for p in range(n)]
        for h in range(H):
            kv = h // (H // n_kv)
            s = np.asarray(q[b, 0, h], np.float64) @ k[layer, rows, kv].T
            s = s / np.sqrt(hd)
            p = np.exp(s - s.max())
            out[b, 0, h * hd:(h + 1) * hd] = (p / p.sum()) @ v[layer, rows, kv]
    return out


@pytest.mark.parametrize("n_kv", [1, 2])
@pytest.mark.parametrize("mode", ["exact", "online"])
def test_grouped_paged_attention_matches_dense(mode, n_kv):
    pool, k, v = mq_pool(jnp.float32, n_kv=n_kv)
    assert pool["k"].shape[-1] == n_kv * MQ["HD"]
    q = jax.random.normal(jax.random.PRNGKey(1),
                          (5, 1, MQ["H"], MQ["HD"]), jnp.float32)
    got = paged_attention(q, pool, jnp.asarray(MQ_TABLES),
                          jnp.asarray(MQ_LENGTHS), 1, mode=mode,
                          interpret=True)
    ref = dense_attention(q, k, v, MQ_TABLES, MQ_LENGTHS, 1, n_kv)
    live = MQ_TABLES[:, 0] != 0
    assert np.abs(np.asarray(got)[live] - ref[live]).max() < 2e-5


def test_grouped_gather_and_int8_pool_match_dense():
    pool, k, v = mq_pool(jnp.float32)
    keys, vals = pk.gather_kv(pool, 0, jnp.asarray(MQ_TABLES), jnp.float32, 1)
    assert keys.shape == (5, 32, 1, MQ["HD"])
    q = jax.random.normal(jax.random.PRNGKey(2),
                          (5, 1, MQ["H"], MQ["HD"]), jnp.float32)
    valid = (jnp.arange(32)[None, :] <= jnp.asarray(MQ_LENGTHS)[:, None])
    got = jamba_mod.grouped_attention(q, keys, vals,
                                      valid[:, None, None, None, :])
    ref = dense_attention(q, k, v, MQ_TABLES, MQ_LENGTHS, 0, 1)
    live = MQ_TABLES[:, 0] != 0
    assert np.abs(np.asarray(got)[live] - ref[live]).max() < 2e-5
    # an int8 multi-query pool through both kernel modes, against its own
    # gathered (dequantized) view
    qpool, _, _ = mq_pool(jnp.bfloat16, kv_bits=8)
    qk, qv = pk.gather_kv(qpool, 0, jnp.asarray(MQ_TABLES), jnp.float32, 1)
    want = jamba_mod.grouped_attention(q, qk, qv,
                                       valid[:, None, None, None, :])
    for mode in ("exact", "online"):
        got = paged_attention(q.astype(jnp.bfloat16), qpool,
                              jnp.asarray(MQ_TABLES),
                              jnp.asarray(MQ_LENGTHS), 0, mode=mode,
                              interpret=True)
        assert np.abs(np.asarray(got, np.float32)[live]
                      - np.asarray(want)[live]).max() < 5e-2


def test_decode_step_kernel_and_gather_paths_agree(model_params):
    _, params = model_params
    outs = []
    for impl in ("kernel", "gather"):
        m = tiny(paged_attention_impl=impl)
        pool = m.init_serving_state(2, 9, 8, dtype=jnp.float32)
        toks = jnp.asarray(tokens(7, 1, 16))
        blocks = jnp.asarray([1, 2], jnp.int32)
        _, pool = m.prefill_paged(params, toks, pool, blocks, jnp.int32(1),
                                  jnp.int32(13))
        tables = jnp.asarray([[0, 0, 0], [1, 2, 3]], jnp.int32)
        logits, _ = m.decode_step_paged(params, jnp.asarray([0, 5]), pool,
                                        tables, jnp.asarray([0, 13]))
        outs.append(np.asarray(logits[1]))
    assert rel_err(outs[0], outs[1]) < 1e-5


# ------------------------------------- (f) the served context limit
# (what is refused over recurrent state: tests/test_serving_refusals.py)
def test_the_models_positions_are_the_served_context_limit():
    """No positional parameter: ``max_position_embeddings`` sizes a slot's
    block table and the pool, and nothing else."""
    m = tiny(max_position_embeddings=48)
    eng = ds.init_inference(m, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={"batch_slots": 2,
                                            "block_size": 8})
    assert srv.max_seq == 48 and srv._tables.shape == (2, 6)
    assert srv.num_blocks == 1 + 2 * 6
    with pytest.raises(ValueError, match="max_seq"):
        srv.submit(Request(tokens=tokens(1, 40), max_new_tokens=16))


# ------------------------------------- (g) GPT-2 behind the state protocol
def test_gpt2_decode_step_is_the_program_it_was():
    """The pool GPT-2 hands the engine is ``paged_kv.init_pool``'s of its
    own sizes, and the decode step traced over it is, letter for letter,
    the step traced over a pool built the way the engine built it before
    the model owned its serving state."""
    m = build("gpt2-tiny", dtype=jnp.float32)
    eng = ds.init_inference(m, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={"batch_slots": 3,
                                            "block_size": 8})
    mc = m.config
    legacy = pk.init_pool(mc.n_layer, srv.num_blocks, 8, mc.n_head,
                          mc.head_dim, jnp.float32, kv_bits=16,
                          quant_block=64)
    same = lambda a, b: (jax.tree_util.tree_structure(a)
                         == jax.tree_util.tree_structure(b)
                         and all(x.shape == y.shape and x.dtype == y.dtype
                                 for x, y in zip(jax.tree_util.tree_leaves(a),
                                                 jax.tree_util.tree_leaves(b))))
    assert same(srv.pool, legacy)
    assert srv.max_seq == mc.max_seq and not srv._recurrent
    srv._build_decode()
    args = srv._decode_args()
    step = lambda *a: m.decode_step_paged(a[0], a[4], a[1], a[2], a[3])
    now = jax.make_jaxpr(step)(*args)
    then = jax.make_jaxpr(step)(args[0], legacy, *args[2:])
    assert str(now) == str(then)
    st = srv.stats()
    assert st["recurrent_state_bytes"] == 0 and st["state_seats"] == 0
    assert st["kv_pool_bytes"] == pk.pool_bytes(legacy)
    # and its prefill keeps the eight operands it always had
    assert len(srv._prefill_args(np.zeros((1, 8), np.int32),
                                 np.zeros((1,), np.int32), 0, 1, 0, 1.0,
                                 False)) == 8
