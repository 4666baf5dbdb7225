"""Qwen3-Next (models/qwen3_next.py, Qwen3-Next-80B-A3B): Gated DeltaNet
mixers whose delta-rule state lives in the serving engine's recurrent rows,
gated attention layers with a partial rotary over the paged pool, an expert
layer with a gated shared expert after EVERY mixer, of whose experts a chip
holds a share.  Every number is held against the benchmark's plain reference
(``benchmark/reference/qwen3_next.py``), which shares no code with the program
and knows no cache, no chunk and no kernel: its delta rule is a ``lax.scan``
over tokens.

Tiny model at widths that keep the ratios: ONE period of 4 layers (3 DeltaNet
+ 1 attention; the preset has two, and compiling them doubles this file's
time), hidden 64, 2 key heads and 4 value heads of 8 (``Hv / Hk``
2), chunks of 16 that a 40-token stream crosses twice, 4 query heads over 1
K/V head of 16 with rotary on a quarter of it, 16 experts (top-4) of width 32
and a shared one of 32; seeded weights, float32 (a wrong hand-off of state
stands orders above the rounding), the projections that feed the attention
scores and the rule enlarged (``sharp``).
"""

import functools
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.inference import Request, ServingEngine
from deepspeed_tpu.models import build, qwen3_next
from deepspeed_tpu.ops import gated_delta as gd
from benchmark.reference import qwen3_next as reference

PRESET = qwen3_next.PRESETS["qwen3-next-tiny"]
DELTA, ATTENTION = qwen3_next.DELTA, qwen3_next.ATTENTION
TOL = 1e-3          # of the largest reference logit; float32 reads ~1e-6


def tiny(dtype=jnp.float32, **overrides):
    return build("qwen3-next-tiny", dtype=dtype,
                 **{"max_position_embeddings": 64, "num_hidden_layers": 4,
                    **overrides})


def ref_cfg(model, **extra):
    """The reference's configuration (published key names) of ``model``."""
    c = model.config
    keys = ("num_hidden_layers", "full_attention_interval",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps")
    return {**{k: getattr(c, k) for k in keys}, **extra}


def sharp(params):
    """q and k enlarged: scores of order 1 and a softmax far from uniform (at
    the initialisation's 0.02 attention is nearly an average and a rotation
    that should not be there hardly moves a logit).  ``in_proj_qkvz`` and
    ``in_proj_ba`` enlarged to what the published width gives them (0.02
    sqrt(2048) is 0.9; 0.02 sqrt(64) a sixth): v and z of order 1, beta and
    the decays spread over their range, or the state hardly moves a logit."""
    attn, delta = dict(params["attn"]), dict(params["delta"])
    attn.update(q_w=8.0 * attn["q_w"], k_w=8.0 * attn["k_w"])
    delta.update(qkvz_w=6.0 * delta["qkvz_w"], ba_w=6.0 * delta["ba_w"])
    return dict(params, attn=attn, delta=delta)


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


# ------------------------------------------------ (a) forward, loss, refusals
def test_layer_kinds_and_parameter_count(model_params):
    m, params = model_params
    assert m.layers == [(DELTA, 0), (DELTA, 1), (DELTA, 2), (ATTENTION, 0)]
    assert build("qwen3-next-tiny").layers[4:] == [
        (DELTA, 3), (DELTA, 4), (DELTA, 5), (ATTENTION, 1)]
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == m.num_params()
    big = qwen3_next.Qwen3NextConfig()          # the published defaults
    assert (big.count(DELTA), big.count(ATTENTION)) == (36, 12)
    assert (big.key_dim, big.value_dim, big.conv_dim, big.rotary_dim) == (
        2048, 4096, 8192, 64)
    assert big.state_bytes_per_layer == 2_097_152
    assert qwen3_next.Qwen3Next(big).num_params() == 79_674_391_296


@pytest.mark.parametrize("position", [0, 17, 39])
def test_forward_logits_match_the_reference(model_params, position):
    m, params = model_params
    toks = tokens(1, 2, 40)
    got = m.apply(params, toks)[:, position]
    ref = reference.logits_at(ref_cfg(m), params, jnp.asarray(toks),
                              jnp.full((2,), position))
    assert rel_err(got, ref) < 1e-4


def test_loss_matches_the_reference_and_a_step_lowers_it(model_params):
    m, params = model_params
    batch = jnp.asarray(tokens(2, 2, 33))
    got, grads = jax.jit(jax.value_and_grad(m.loss))(params, batch, None)
    ref = reference.loss(ref_cfg(m), params, batch)
    assert abs(float(got) - float(ref)) < 1e-5 * abs(float(ref))
    # the chunked rule is differentiable: one step of plain descent
    stepped = jax.tree_util.tree_map(lambda p, g: p - 0.05 * g, params, grads)
    assert float(m.loss(stepped, batch, None)) < float(got)


def test_cached_decoding_matches_the_full_forward(model_params):
    m, params = model_params
    toks = jnp.asarray(tokens(4, 2, 30))
    full = m.apply(params, toks)
    cache = m.init_cache(2, 32)
    forward = jax.jit(m.apply_with_cache)
    got, cache = forward(params, toks[:, :21], cache)
    assert rel_err(got, full[:, :21]) < 1e-4
    for t in range(21, 30):
        step, cache = forward(params, toks[:, t:t + 1], cache)
        assert rel_err(step[:, 0], full[:, t]) < 1e-4
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    out = eng.generate(np.asarray(toks[:, :10]), max_new_tokens=4)
    assert out.shape == (2, 14)


@pytest.mark.parametrize("overrides, named", [
    (dict(mlp_only_layers=(0,)), "mlp_only_layers"),
    (dict(decoder_sparse_step=2), "decoder_sparse_step"),
    (dict(hidden_act="gelu"), "gelu"),
])
def test_what_the_file_does_not_compute_is_refused_by_name(overrides, named):
    with pytest.raises(ValueError, match=named):
        tiny(**overrides)


def test_a_long_prompt_in_segments_is_the_prompt_whole(model_params,
                                                       monkeypatch):
    """The DeltaNet mixer of a long prompt runs in segments that hand the
    convolution's tail and the state on (``_CHUNK_TOKENS``: 4,096 as served);
    a segment that holds only pad leaves both as they were."""
    m, params = model_params
    p0 = jax.tree_util.tree_map(lambda a: a[0], params["delta"])
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 64))
    whole = m._delta(p0, h, t_real=29)
    monkeypatch.setattr(qwen3_next, "_CHUNK_TOKENS", 16)
    cut = m._delta(p0, h, t_real=29)          # 3 segments; the last all pad
    assert rel_err(cut[0][:, :29], whole[0][:, :29]) < 1e-5     # the stream
    assert rel_err(cut[1], whole[1]) < 1e-5                     # the tail
    assert rel_err(cut[2], whole[2]) < 1e-5                     # the state
    monkeypatch.setattr(qwen3_next, "_CHUNK_TOKENS", 20)   # 48 = 3 x 16
    again = m._delta(p0, h, t_real=29)
    assert rel_err(again[2], whole[2]) < 1e-5


def test_prompt_attention_at_head_256_through_the_flash_forward(monkeypatch):
    """What a TPU runs for an attention layer's prompt
    (``flash_attention_available`` forced true, the kernel interpreted) at
    the PUBLISHED head geometry, 16 query heads over 2 K/V heads of 256: one
    flash call a K/V head's group of 8, equal to the ``jax.numpy`` band the
    CPU takes."""
    import deepspeed_tpu.ops as ops
    from deepspeed_tpu.models.afmoe import banded_attention
    from deepspeed_tpu.models.nemotron_h import causal_prompt_attention
    k = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(k[0], (1, 200, 16, 256)) / 4
    kk = jax.random.normal(k[1], (1, 200, 2, 256)) / 4
    v = jax.random.normal(k[2], (1, 200, 2, 256))
    want = banded_attention(q, kk, v)
    monkeypatch.setattr(ops, "flash_attention_available", lambda: True)
    got = jax.jit(causal_prompt_attention)(q, kk, v)
    assert got.shape == (1, 200, 16 * 256)
    assert rel_err(got, want) < 1e-5


# ------------------------------------------------------- (b) the delta rule
def rule_operands(T, B=2, H=4, dk=8, dv=8, seed=0, alike=0.5):
    """As the mixer hands them over: unit keys that are ALIKE (silu leaves
    them mostly positive), decays from 1 down to e^-1.5 a token."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(k[0], (B, T, H, dk))) / np.sqrt(dk)
    kk = unit(jax.random.normal(k[1], (B, T, H, dk)) + alike)
    v = jax.random.normal(k[2], (B, T, H, dv))
    g = -1.5 * jax.random.uniform(k[3], (B, T, H))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (B, T, H)))
    S0 = jax.random.normal(k[5], (B, H, dk, dv))
    return q, kk, v, g, beta, S0


def chunked(impl, **kw):
    """``gd.delta_chunk`` in one form, jitted: ``"kernel"`` is the Pallas
    kernel ``gated_delta_chunk_scan`` interpreted (no chip here)."""
    return jax.jit(functools.partial(gd.delta_chunk, impl=impl,
                                     interpret=True, **kw))


@pytest.mark.parametrize("impl", ["jnp", "kernel"])
@pytest.mark.parametrize("T, chunk, t_real, with_state", [
    (45, 16, None, True),         # not a multiple of the chunk, from a state
    (45, 64, None, False),        # one chunk, cut to 64
    (128, 64, None, True),        # two whole chunks of the served size
    (48, 16, 30, True),           # the state after token 29 of a 48 bucket
    (40, 16, 40, False), (5, 16, None, True), (1, 16, None, True),
])
def test_the_chunked_rule_matches_the_token_recurrence(T, chunk, t_real,
                                                       with_state, impl):
    q, k, v, g, beta, S0 = rule_operands(T)
    S0 = S0 if with_state else None
    n = T if t_real is None else t_real
    want_o, want_S = gd.delta_scan_jnp(q[:, :n], k[:, :n], v[:, :n],
                                       g[:, :n], beta[:, :n], S0)
    got_o, got_S = chunked(impl, chunk=chunk)(q, k, v, g, beta, S0,
                                              t_real=t_real)
    assert got_o.shape == v.shape and got_S.dtype == jnp.float32
    assert rel_err(got_o[:, :n], want_o) < 1e-5
    assert rel_err(got_S, want_S) < 1e-5


@pytest.mark.parametrize("shape, T, chunk, t_real", [
    # the served chunk and head size, a length that is no multiple of 64,
    # from a state: three grid steps of one pair, the last one padded
    (dict(B=1, H=2, dk=128, dv=128), 150, 64, None),
    # four pairs a grid step and two groups of heads, each head its own decay
    (dict(B=2, H=16, dk=8, dv=16), 48, 16, None),
    # the bucket's real tokens end inside the FIRST chunk
    (dict(B=1, H=4, dk=8, dv=8), 48, 16, 5),
    (dict(B=1, H=2, dk=128, dv=128), 130, 64, 3),
], ids=["150_tokens_at_128", "sixteen_heads", "t_real_5", "t_real_3_at_128"])
def test_the_chunk_kernel_where_only_a_kernel_can_go_wrong(shape, T, chunk,
                                                           t_real):
    q, k, v, g, beta, S0 = rule_operands(T, seed=3, **shape)
    H = shape["H"]
    g = g * jnp.linspace(0.05, 2.0, H)       # a head's decay is its own
    n = T if t_real is None else t_real
    want_o, want_S = gd.delta_scan_jnp(q[:, :n], k[:, :n], v[:, :n],
                                       g[:, :n], beta[:, :n], S0)
    got_o, got_S = chunked("kernel", chunk=chunk)(q, k, v, g, beta, S0,
                                                  t_real=t_real)
    assert got_o.shape == v.shape
    assert rel_err(got_o[:, :n], want_o) < 1e-5
    assert rel_err(got_S, want_S) < 1e-5
    # head by head: a pair's two halves and a step's two pairs kept apart
    for h in range(H):
        assert rel_err(got_S[:, h], want_S[:, h]) < 2e-5, h


def _rms(a, b):
    return float(jnp.sqrt(jnp.mean(jnp.square(a.astype(jnp.float32) - b))
                          / jnp.mean(jnp.square(b))))


@pytest.mark.parametrize("alike, sure, slow, state_tol", [
    (0.5, 0.0, 1.0, 5e-3), (16.0, 2.5, 0.05, 2e-2),
], ids=["keys_alike", "a_token_repeated"])
def test_the_chunk_kernel_at_bfloat16(alike, sure, slow, state_tol):
    """bfloat16 operands as served, against the float32 recurrence over the
    same rounded operands; and the kernel against ``"jnp"``, which rounds
    at the same places and inverts at the same precision, closer than either
    is to the recurrence.  The second case is the one the module docstring
    warns of: keys all but equal (a cosine of 0.996: a token repeated),
    ``beta`` near 1 and hardly any decay, where the powers inside the
    16 x 16 blocks are far larger than ``T``; bfloat16 products leave the
    state 1.4e-2 off there in either form, and in float32 the kernel stays
    at 1e-5."""
    q, k, v, g, beta, S0 = rule_operands(150, B=1, H=2, dk=128, dv=128,
                                         seed=4, alike=alike)
    g, beta = slow * g, jax.nn.sigmoid(jax.scipy.special.logit(beta) + sure)
    want_o, want_S = gd.delta_scan_jnp(q, k, v, g, beta, S0)
    o, S = chunked("kernel")(q, k, v, g, beta, S0)
    assert _rms(o, want_o) < 2e-5 and _rms(S, want_S) < 2e-5
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    want_o, want_S = gd.delta_scan_jnp(q, k, v.astype(jnp.float32), g, beta,
                                       S0)
    got = {impl: chunked(impl)(q, k, v, g, beta, S0)
           for impl in ("jnp", "kernel")}
    for o, S in got.values():
        assert o.dtype == jnp.bfloat16 and S.dtype == jnp.float32
        assert _rms(o, want_o) < 5e-3 and _rms(S, want_S) < state_tol
    between = (_rms(got["kernel"][0], got["jnp"][0].astype(jnp.float32)),
               _rms(got["kernel"][1], got["jnp"][1]))
    assert between[0] < 0.5 * _rms(got["jnp"][0], want_o), between
    assert between[1] < 0.5 * _rms(got["jnp"][1], want_S), between


def test_the_chunk_kernel_is_named_and_differentiated_and_the_chips():
    """The kernel's name (``breakdown.device_ops`` and the scope map read
    it); ``jax.grad`` through it, which has no backward kernel and takes the
    ``jax.numpy`` form's (the model's ``loss`` calls the entry with no
    ``impl``); and ``impl="auto"``: off the chip today's ``jax.numpy`` body to
    the bit, on it the kernel at widths Mosaic tiles and the ``jax.numpy``
    body at a tiny model's."""
    q, k, v, g, beta, S0 = rule_operands(40, B=1, H=2)
    text = jax.jit(functools.partial(gd.delta_chunk, chunk=16, impl="kernel",
                                     interpret=True)).trace(
        q, k, v, g, beta, S0).jaxpr.pretty_print(name_stack=True)
    assert gd.CHUNK_KERNEL == "gated_delta_chunk_scan" and gd.CHUNK_KERNEL in text

    def loss(impl, *operands):
        o, S = chunked(impl, chunk=16)(*operands, t_real=30)
        return (o * o).sum() + (S * jnp.cos(S)).sum()
    every = tuple(range(6))
    want = jax.grad(functools.partial(loss, "jnp"), every)(q, k, v, g, beta, S0)
    got = jax.grad(functools.partial(loss, "kernel"), every)(q, k, v, g, beta, S0)
    for a, b in zip(got, want):
        assert a.shape == b.shape and rel_err(a, b) < 1e-5
    assert float(jnp.abs(got[3][:, 30:]).max()) == 0.0      # a pad's g
    with pytest.raises(ValueError, match="pairs"):
        gd.delta_chunk(q[:, :, :1], k[:, :, :1], v[:, :, :1], g[:, :, :1],
                       beta[:, :, :1], impl="kernel", interpret=True)
    auto = gd.delta_chunk(q, k, v, g, beta, S0, chunk=16, t_real=20)
    body = gd.delta_chunk_jnp(q, k, v, g, beta, S0, chunk=16, t_real=20)
    assert all(bool((a == b).all()) for a, b in zip(auto, body))
    assert gd._chunk_kernel_lowers(32, 64, 128, 128)
    assert not gd._chunk_kernel_lowers(4, 16, 8, 8)
    assert not gd._chunk_kernel_lowers(3, 64, 128, 128)


@pytest.mark.parametrize("C, scale", [(64, 0.2), (64, 0.6), (16, 0.6),
                                      (8, 0.5), (128, 0.3)])
def test_the_triangular_inverse_by_blocks(C, scale):
    """``(I + L)^-1`` for keys that are alike (every entry of ``L`` near
    ``scale``: the six plain products over 64 would pass through powers of
    a thousand to a million times the result)."""
    noise = jax.random.uniform(jax.random.PRNGKey(C), (3, C, C), jnp.float32,
                               0.8, 1.0)
    L = jnp.tril(scale * noise, -1)
    got = gd.unit_lower_inverse(L)
    want = np.linalg.inv(np.eye(C) + np.asarray(L, np.float64))
    assert np.abs(np.asarray(got) - want).max() < 5e-5 * np.abs(want).max()
    assert float(jnp.abs(jnp.triu(got, 1)).max()) == 0.0


@pytest.mark.parametrize("impl", ["jnp", "kernel"])
def test_a_pad_enters_neither_the_state_nor_the_decay(impl):
    q, k, v, g, beta, S0 = rule_operands(32)
    _, S = chunked(impl, chunk=16)(q, k, v, g, beta, S0, t_real=0)
    assert float(jnp.abs(S - S0).max()) == 0.0


@pytest.mark.parametrize("dims, slots", [
    ((4, 8, 8), 3),              # one slot a grid step
    ((2, 16, 128), 8),           # four slots a step, two steps
    ((32, 8, 8), 16),            # eight a step: the cell's split
])
def test_one_token_update_in_place_and_a_dead_slot_untouched(dims, slots):
    H, dk, dv = dims
    q, k, v, g, beta, _ = rule_operands(slots, B=1, H=H, dk=dk, dv=dv, seed=2)
    q, k, v, g, beta = (x[0] for x in (q, k, v, g, beta))
    state = jax.random.normal(jax.random.PRNGKey(9), (3, slots, H, dk, dv))
    active = jnp.arange(slots) % 3 != 1
    want_o, want_S = gd.delta_scan_jnp(q[:, None], k[:, None], v[:, None],
                                       g[:, None], beta[:, None], state[1])
    for impl in ("kernel", "jnp"):
        o, new = jax.jit(functools.partial(
            gd.delta_step, impl=impl, interpret=True))(
                state, 1, q, k, v, g, beta, active=active)
        assert o.dtype == new.dtype == jnp.float32
        live = np.asarray(active)
        assert rel_err(o[live], want_o[live, 0]) < 1e-5
        assert rel_err(new[1][live], want_S[live]) < 1e-5
        # a dead slot's rows, and every other layer's, bit for bit
        assert float(jnp.abs(new[1][~live] - state[1][~live]).max()) == 0.0
        assert float(jnp.abs(new[0] - state[0]).max()) == 0.0
        assert float(jnp.abs(new[2] - state[2]).max()) == 0.0


def test_the_update_aliases_the_state():
    """The kernel's state is its output: the call donates the leaf."""
    state = jnp.zeros((2, 8, 2, 8, 128), jnp.float32)
    args = (jnp.zeros((8, 2, 8)),) * 2 + (jnp.zeros((8, 2, 128)),) \
        + (jnp.zeros((8, 2)),) * 2
    text = jax.jit(functools.partial(gd.delta_step, impl="kernel",
                                     interpret=True),
                   donate_argnums=(0,)).lower(state, 0, *args).as_text()
    assert gd.STEP_KERNEL == "gated_delta_state_update"
    assert "tf.aliasing_output" in text or "jax.buffer_donor" in text


def test_the_gated_norm_norms_first():
    o = jax.random.normal(jax.random.PRNGKey(0), (5, 4, 8))
    z = jax.random.normal(jax.random.PRNGKey(1), (5, 4, 8))
    w = jnp.linspace(0.5, 1.5, 8)
    got = qwen3_next.gated_head_norm(o, z, w, 1e-6)
    normed = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + 1e-6) * w
    assert rel_err(got, normed * jax.nn.silu(z)) < 1e-6
    gated = o * jax.nn.silu(z)
    other = gated / jnp.sqrt((gated * gated).mean(-1, keepdims=True) + 1e-6)
    assert rel_err(got, other * w) > 0.1


# -------------------------------------------------------- (c) the chip's share
def test_the_shares_add_up_to_the_whole_layer(model_params):
    """One expert layer over the same stream, held four ways: the four
    shares' routed parts, with the gated shared expert (which every chip
    computes alike) counted once, equal the UNCUT reference's whole layer;
    the weights are renormalised over ALL picks, held or not."""
    m, params = model_params
    pm = params["moe"]
    layer = 2
    # a small stream: the layer's output (5e-4) is read off ``h + y``
    h = 0.01 * jax.random.normal(jax.random.PRNGKey(7), (1, 24, 64))
    u = reference._rms0(h[0], pm["ln2"][layer], 1e-6)
    whole, _ = reference.experts(ref_cfg(m), pm, layer, u)
    shared = reference._sigmoid(u @ pm["shared_gate"][layer])[:, None] \
        * reference._swiglu(u, pm["shared_gate_w"][layer],
                            pm["shared_up_w"][layer],
                            pm["shared_down_w"][layer])
    total = 0.0
    for first in (0, 4, 8, 12):
        share = tiny(experts_held=(first, 4))
        cut = dict(pm, gate_w=pm["gate_w"][:, first:first + 4],
                   up_w=pm["up_w"][:, first:first + 4],
                   down_w=pm["down_w"][:, first:first + 4])
        out, counts, _ = share._moe(cut, h, layer)
        total = total + (out[0] - h[0]) - shared
        # and the reference, given the same share, leaves the same out
        mine, _ = reference.experts(
            ref_cfg(m, experts_held=[first, 4]), cut, layer, u)
        assert rel_err(out[0] - h[0], mine) < 1e-4
        assert int(counts[0] + counts[1]) == 24 * 4
    assert rel_err(total + shared, whole) < 1e-4


# ------------------------------------------------------------- (d) serving
PROMPTS = (13, 21, 9, 30, 17, 26)      # none on an 8-token bucket's edge
NEW = (5, 9, 3, 12, 7, 4)              # so slots free at different steps


def live_logit_error(srv, params, ref):
    """The benchmark's check: the NEXT decode step's logits through the paged
    path and the recurrent rows, against the reference's full forward over
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


@functools.lru_cache(maxsize=None)
def jitted_reference(cfg_items):
    """The reference's ``logits_at`` under one jit a configuration: every
    served comparison of this file reads the same executable."""
    cfg = dict(cfg_items)
    return jax.jit(lambda p, t, pos: reference.logits_at(cfg, p, t, pos))


def serve_and_compare(params, model=None, light=False):
    """Six requests through three slots: every slot is seated, freed and
    seated again by a second stream (no state may leak).  Returns the worst
    logit error seen at any step and the engine (drained).  ``light`` (the
    planted faults, each of which compiles the served path anew): the first
    four requests through two slots, two prefill buckets where six have
    three."""
    m = model or tiny()
    ref = jitted_reference(tuple(sorted(ref_cfg(m).items())))
    prompts, slots = ((13, 21, 9, 17), 2) if light else (PROMPTS, 3)
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={
        "batch_slots": slots, "block_size": 8})
    uids = [srv.submit(Request(tokens=tokens(20 + i, n), max_new_tokens=new))
            for i, (n, new) in enumerate(zip(prompts, NEW))]
    worst, seen = 0.0, 0
    while srv.step():
        if any(s is not None for s in srv._slots):
            err, n = live_logit_error(srv, params, ref)
            worst, seen = max(worst, err), seen + n
    assert seen > (10 if light else 20)
    return worst, srv, uids


def test_serving_matches_the_reference(model_params):
    _, params = model_params
    t0 = time.monotonic()      # the recorder is the process's: this run's rows
    worst, srv, uids = serve_and_compare(params)
    assert worst < TOL
    st = srv.stats()
    assert st["completed"] == 6 and st["state_seats"] == 6   # slots reused
    assert [len(srv.results[u]["tokens"]) for u in uids] == list(NEW)
    assert srv.allocator.free_blocks == srv.num_blocks - 1
    # what the donated pytree holds, by kind
    c = srv.model.config
    per_stream = c.count(DELTA) * (
        c.state_bytes_per_layer
        + (c.linear_conv_kernel_dim - 1) * c.conv_dim * 4)
    assert st["recurrent_state_bytes"] == 3 * per_stream
    assert st["state_bytes_per_stream"] == per_stream
    assert srv.pool["k"].shape == (1, srv.num_blocks, 8, 16)   # 1 x 16 wide
    assert srv.pool["delta"].shape == (3, 3, 4, 8, 8)
    assert srv.pool["delta"].dtype == jnp.float32
    assert st["kv_pool_bytes"] == 2 * srv.pool["k"].nbytes
    assert (st["delta_layers"], st["attention_layers"], st["experts_held"],
            st["experts_total"]) == (3, 1, 16, 16)
    # the new attributes of the spans
    rows = [r for r in srv._spans.rows() if r.t_start >= t0]
    pre = [r for r in rows if r.name == "serving.prefill"][-1].attrs
    assert pre["delta_tokens"] == pre["scan_tokens"] == pre["prompt_len"]
    assert pre["delta_chunks"] == -(-pre["prompt_len"] // c.chunk_size)
    assert pre["routed_pairs"] + pre["pairs_elsewhere"] == \
        4 * 4 * pre["prompt_len"]
    steps = [r.attrs for r in rows if r.name == "serving.step" and r.attrs]
    assert steps and max(a["seated_slots"] for a in steps) == 3
    for a in steps:
        assert a["seated_slots"] + a["free_slots"] == 3
        assert a["state_bytes"] == a["seated_slots"] * 3 * 2 \
            * c.state_bytes_per_layer
        if "routed_pairs" in a:       # 10 x live x layers, here 4 x live x 4
            assert a["routed_pairs"] + a["pairs_elsewhere"] == \
                4 * a["n_active"] * 4
            assert a["experts_touched"] + a["experts_idle"] == 16 * 4


@pytest.mark.parametrize("path", ["update_kernel", "gather"])
def test_serving_through_the_other_paths(model_params, monkeypatch, path):
    """The one-token update's Pallas kernel (interpreted) in the decode step
    where the CPU takes the ``jax.numpy`` form; and the ``gather`` oracle of
    the paged kernel."""
    _, params = model_params
    if path == "update_kernel":
        monkeypatch.setattr(gd, "delta_step", functools.partial(
            gd.delta_step, impl="kernel", interpret=True))
        worst, _, _ = serve_and_compare(params)
    else:
        worst, _, _ = serve_and_compare(
            params, tiny(paged_attention_impl="gather"))
    assert worst < TOL


def test_prefix_sharing_is_refused_by_name(model_params):
    m, params = model_params
    assert m.has_recurrent_state
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    with pytest.raises(ValueError, match="prefix_cache.*recurrent"):
        ServingEngine(engine=eng, config={"batch_slots": 2, "block_size": 8,
                                          "prefix_cache": True})


def test_an_inactive_row_keeps_its_recurrent_rows(model_params):
    m, params = model_params
    pool = m.init_serving_state(2, 5, 8, dtype=jnp.float32)
    pool = dict(pool, delta=pool["delta"] + 1.0, conv=pool["conv"] + 2.0)
    tables = jnp.asarray([[1, 2], [0, 0]], jnp.int32)       # row 1: scratch
    _, new, routes = m.decode_step_paged(
        params, jnp.asarray([3, 4]), pool, tables,
        jnp.asarray([5, 0], jnp.int32), with_routes=True)
    assert routes.shape == (4, 2, 4)
    assert float(jnp.abs(new["delta"][:, 1] - 1.0).max()) == 0.0
    assert float(jnp.abs(new["conv"][:, 1] - 2.0).max()) == 0.0
    assert float(jnp.abs(new["delta"][:, 0] - 1.0).max()) > 0.0
    assert int(new["counters"][0] + new["counters"][1]) == 4 * 4   # 1 live
