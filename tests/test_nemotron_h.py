"""Nemotron-H (models/nemotron_h.py, NVIDIA Nemotron-3-Nano): Mamba-2 mixers
whose state lives in the serving engine's recurrent rows, attention layers
with no position over the paged pool, non-gated relu2 experts of which a chip
holds a share; a layer is ONE of the three.  Every number is held against the
benchmark's plain reference (``benchmark/reference/nemotron_h.py``), which
shares no code with the program and knows no cache, no chunk and no kernel:
its recurrence is a ``lax.scan`` over tokens.

Tiny model at widths that keep the ratios: pattern ``MEM*EM`` (all three
kinds), hidden 64, 4 Mamba heads of 8 in 2 groups over a state of 16, chunks
of 8 that a 40-token stream crosses several times, 4 query heads over 2 K/V
heads of 16, 16 experts (top-4) of width 32 and a shared one of 48; seeded
weights, float32 (a wrong hand-off of state stands orders above the
rounding), the projections that feed the attention scores and the recurrence
enlarged (``sharp``).
"""

import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.inference import Request, ServingEngine
from deepspeed_tpu.models import build, nemotron_h
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops import mamba2 as m2
from benchmark import control_nemotron
from benchmark.reference import nemotron_h as reference

PRESET = nemotron_h.PRESETS["nemotron-h-tiny"]
TOL = 1e-3          # of the largest reference logit; float32 reads ~1e-6


def tiny(dtype=jnp.float32, **overrides):
    return build("nemotron-h-tiny", dtype=dtype,
                 **{"max_position_embeddings": 64, **overrides})


def ref_cfg(model, **extra):
    """The reference's configuration (published key names) of ``model``."""
    c = model.config
    keys = ("hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
            "n_groups", "ssm_state_size", "conv_kernel",
            "layer_norm_epsilon", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor")
    return {**{k: getattr(c, k) for k in keys}, **extra}


def sharp(params):
    """q and k enlarged: scores of order 1 and a softmax far from uniform (at
    the initialisation's 0.02 attention is nearly an average and a position
    that should not be there hardly moves a logit).  ``in_proj`` enlarged to
    what the published width gives it (0.02 sqrt(2688) is 1; 0.02 sqrt(64)
    a sixth): x, B and C of order 1, or ``S C`` is a ten-thousandth of ``D
    x`` and a state left behind hardly moves a logit."""
    attn, mamba = dict(params["attn"]), dict(params["mamba"])
    attn.update(q_w=8.0 * attn["q_w"], k_w=8.0 * attn["k_w"])
    mamba.update(in_w=6.0 * mamba["in_w"])
    return dict(params, attn=attn, mamba=mamba)


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
    assert m.layers == [("M", 0), ("E", 0), ("M", 1), ("*", 0), ("E", 1),
                        ("M", 2)]
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == m.num_params()
    big = nemotron_h.NemotronHConfig()          # the published defaults
    assert (big.count("M"), big.count("E"), big.count("*")) == (23, 23, 6)
    assert big.d_inner == 4096 and big.conv_dim == 6144
    assert big.state_bytes_per_layer == 2_097_152
    assert nemotron_h.NemotronH(big).num_params() == 31_577_940_288


@pytest.mark.parametrize("position", [0, 17, 39])
def test_forward_logits_match_the_reference(model_params, position):
    m, params = model_params
    toks = tokens(1, 2, 40)
    got = m.apply(params, toks)[:, position]
    ref = reference.logits_at(ref_cfg(m), params, jnp.asarray(toks),
                              jnp.full((2,), position))
    assert rel_err(got, ref) < 1e-4


def test_loss_matches_the_reference(model_params):
    m, params = model_params
    batch = jnp.asarray(tokens(2, 2, 33))
    got = m.loss(params, batch, None)
    ref = reference.loss(ref_cfg(m), params, batch)
    assert abs(float(got) - float(ref)) < 1e-5 * abs(float(ref))


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


@pytest.mark.parametrize("overrides, named", [
    (dict(hybrid_override_pattern="ME-*EM"), "hybrid_override_pattern"),
    (dict(mlp_hidden_act="gelu"), "gelu"),
    (dict(n_group=2), "n_group"),
    (dict(mamba_hidden_act="relu"), "mamba_hidden_act"),
])
def test_what_the_file_does_not_compute_is_refused_by_name(overrides, named):
    with pytest.raises(ValueError, match=named):
        tiny(**overrides)


# --------------------------------------------------- (b) the state-space ops
def scan_operands(T, Bt=2, H=4, P=8, G=2, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    f32 = jnp.float32
    return (jax.random.normal(k[0], (Bt, T, H, P), f32),
            jax.nn.softplus(jax.random.normal(k[1], (Bt, T, H), f32)) * 0.3,
            -jnp.exp(jax.random.uniform(k[2], (H,), f32, 0.0, 2.5)),
            jax.random.normal(k[3], (Bt, T, G, N), f32),
            jax.random.normal(k[4], (Bt, T, G, N), f32),
            jax.random.normal(k[5], (H,), f32),
            jax.random.normal(k[6], (Bt, H, P, N), f32))


def recurrence(x, dt, A, B, C, D, h0):
    """Token by token, with the one-token update."""
    S, ys = h0, []
    for t in range(x.shape[1]):
        y, S = m2.ssm_step_jnp(x[:, t], dt[:, t], A, B[:, t], C[:, t], D, S)
        ys.append(y)
    return jnp.stack(ys, 1), S


@pytest.mark.parametrize("T, t_real, form", [
    (16, 16, "jnp"), (32, 32, "kernel"), (37, 37, "jnp"), (37, 37, "kernel"),
    (5, 5, "kernel"), (48, 41, "jnp"), (48, 41, "kernel"), (24, 1, "kernel"),
    (40, 40, "initial state")])
def test_chunked_scan_matches_the_token_by_token_recurrence(T, t_real, form):
    """Chunks of 16: lengths that are and are not multiples of the chunk, a
    padded tail frozen by ``dt = 0`` (the state handed back is the state
    after token ``t_real - 1``), and a scan continued from a state.  The
    kernel runs interpreted."""
    x, dt, A, B, C, D, h0 = scan_operands(T)
    masked = m2.mask_delta(dt, t_real)
    if form == "initial state":
        y, S = m2.ssd_scan(x, masked, A, B, C, D, h0=h0, chunk=16)
    else:
        h0 = jnp.zeros_like(h0)
        scan = m2.ssd_scan_jnp if form == "jnp" else (
            lambda *a, **kw: m2.ssd_scan_kernel(*a, **kw, interpret=True))
        y, S = scan(x, masked, A, B, C, D, chunk=16)
    cut = lambda a: a[:, :t_real]
    y_ref, S_ref = recurrence(cut(x), cut(dt), A, cut(B), cut(C), D, h0)
    assert float(jnp.abs(y[:, :t_real] - y_ref).max()) < 2e-5
    assert float(jnp.abs(S - S_ref).max()) < 1e-5


def test_scan_dispatch_refuses_a_kernel_with_an_initial_state():
    x, dt, A, B, C, D, h0 = scan_operands(8)
    with pytest.raises(AssertionError, match="zero state"):
        m2.ssd_scan(x, dt, A, B, C, D, h0=h0, impl="kernel")


def low_bits_share(S):
    """The share of ``S``'s elements whose 16 low mantissa bits are not all
    zero: about 1 for float32 arithmetic, 0 after a pass through bfloat16."""
    bits = jax.lax.bitcast_convert_type(S, jnp.uint32) & 0xFFFF
    return float((bits != 0).mean())


@pytest.mark.parametrize("dims, slots", [
    (dict(H=4, P=8, G=2, N=16), [True, False, True, True, False]),
    # slots in pairs, so the kernel's reads and writes take three turns
    (dict(H=4, P=8, G=2, N=16), [True, False, True, True, False, True]),
    # ONE slot and layer at the published shape: 3 x 3 x 2 MB
    (dict(H=64, P=64, G=8, N=128), [False, True, False]),
])
def test_one_token_update_in_place_and_a_dead_slot_untouched(dims, slots):
    """The kernel (interpreted) against the ``jax.numpy`` form over layer 1
    of a three-layer state in the update's own layout: the other layers and
    the dead slots' rows keep every bit, and what is written is float32."""
    live = jnp.asarray(slots)
    x, dt, A, B, C, D, h0 = scan_operands(1, Bt=len(slots), **dims)
    h0 = m2.to_step_layout(0.1 * h0, dims["G"])
    assert h0.shape[1:] == m2.step_layout(**dims)
    ssm = jnp.stack([0.5 * h0, h0, 2.0 * h0])
    args = (x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D)
    y_j, s_j = m2.ssm_step(ssm, 1, *args, active=live, impl="jnp")
    y_k, s_k = m2.ssm_step(ssm, 1, *args, active=live, impl="kernel",
                           interpret=True)
    assert float(jnp.abs(y_j - y_k)[live].max()) < 1e-5
    assert float(jnp.abs(s_j - s_k).max()) < 1e-6
    for s in (s_j, s_k):
        assert bool((s[1][~live] == ssm[1][~live]).all())
        assert bool((s[0] == ssm[0]).all()) and bool((s[2] == ssm[2]).all())
        assert float(jnp.abs(s[1][live] - ssm[1][live]).max()) > 0.0
        assert low_bits_share(s[1][live]) > 0.99


def test_the_updates_layout_is_the_states_with_n_on_the_sublanes():
    """``to_step_layout``: head ``h``'s channel ``p`` and column ``n`` lie at
    group ``h // (H / G)``, sublane ``n``, lane ``(h % (H / G)) P + p``; the
    way back is its inverse, and one update through the leaf is
    ``ssm_step_jnp`` over the published orientation."""
    x, dt, A, B, C, D, h0 = scan_operands(1, Bt=3, H=6, P=4, G=3, N=8)
    leaf = m2.to_step_layout(h0, 3)
    assert leaf.shape == (3,) + m2.step_layout(6, 4, 8, 3) == (3, 3, 8, 8)
    for h, p, n in [(0, 0, 0), (1, 3, 7), (4, 2, 5), (5, 1, 6)]:
        assert float(leaf[2, h // 2, n, (h % 2) * 4 + p]) == \
            float(h0[2, h, p, n])
    assert bool((m2.from_step_layout(leaf, 6) == h0).all())
    args = (x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D)
    y, new = m2.ssm_step(leaf[None], 0, *args, impl="jnp")
    y_ref, s_ref = m2.ssm_step_jnp(*args, h0)
    assert bool((y == y_ref).all())
    assert bool((m2.from_step_layout(new[0], 6) == s_ref).all())


def test_grouped_gated_norm_is_not_one_mean_square():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    y = jax.random.normal(k[0], (3, 32)) * jnp.repeat(
        jnp.asarray([0.1, 1.0, 3.0, 9.0]), 8)      # groups of unlike size
    z, w = jax.random.normal(k[1], (3, 32)), 1.0 + jax.random.normal(
        k[2], (32,)) * 0.1
    grouped = m2.gated_group_norm(y, z, w, 4, 1e-5)
    g = (y * jax.nn.silu(z)).reshape(3, 4, 8)
    want = (g / jnp.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 32) * w
    assert float(jnp.abs(grouped - want).max()) < 1e-5
    whole = m2.gated_group_norm(y, z, w, 1, 1e-5)
    assert float(jnp.abs(grouped - whole).max()) > 0.5


# ------------------------------------------------------- (c) the expert layer
def parent_held_experts(x, experts, weights, gate_w, up_w, down_w, first,
                        layer=None):
    """``moe/dropless.py::held_experts`` as it stood before the non-gated
    form came (PR 41's tree), operation for operation."""
    N, k = experts.shape
    count = gate_w.shape[-3]
    local = experts.reshape(-1) - first
    held = (local >= 0) & (local < count)
    local = jnp.where(held, local, count)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.zeros((count,), jnp.int32).at[local].add(1, mode="drop")
    if layer is not None:
        n = gate_w.shape[0] * count
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n,), jnp.int32), sizes, (layer * count,))
        gate_w, up_w, down_w = (w.reshape((n,) + w.shape[2:])
                                for w in (gate_w, up_w, down_w))
    rows = x[order // k]
    dt = x.dtype
    h = jax.nn.silu(jax.lax.ragged_dot(rows, gate_w.astype(dt), sizes)) \
        * jax.lax.ragged_dot(rows, up_w.astype(dt), sizes)
    out = jax.lax.ragged_dot(h, down_w.astype(dt), sizes)
    back = jnp.zeros((N * k,), jnp.int32).at[order].set(
        jnp.arange(N * k, dtype=jnp.int32))
    out = out[back].reshape(N, k, -1).astype(jnp.float32)
    w = jnp.where(held.reshape(N, k), weights, 0.0)[..., None]
    return jnp.where(w != 0, out * w, 0.0).sum(axis=1).astype(dt)


def expert_operands(dtype, layers=None, N=24, D=16, F=12, E=8, k=3, seed=0):
    key = jax.random.split(jax.random.PRNGKey(seed), 6)
    lead = () if layers is None else (layers,)
    x = jax.random.normal(key[0], (N, D)).astype(dtype)
    logits = jax.random.normal(key[1], (N, E))
    w = lambda kk, *shape: jax.random.normal(kk, lead + shape) * 0.3
    return (x, logits, w(key[2], 4, D, F), w(key[3], 4, D, F),
            w(key[4], 4, F, D))


@pytest.mark.parametrize("family, route_kw, layer, dtype", [
    ("deepseek_v2", dict(topk_method="group_limited_greedy", n_group=4,
                         topk_group=2, routed_scaling_factor=16.0), None,
     jnp.float32),
    ("deepseek_v2", dict(topk_method="group_limited_greedy", n_group=4,
                         topk_group=2, routed_scaling_factor=16.0), 1,
     jnp.bfloat16),
    ("afmoe", dict(scoring_func="sigmoid", norm_topk_prob=True,
                   routed_scaling_factor=2.448, scale_normed=True), 2,
     jnp.bfloat16),
    ("afmoe", dict(scoring_func="sigmoid", norm_topk_prob=True,
                   routed_scaling_factor=2.448, scale_normed=True), None,
     jnp.float32),
])
def test_the_gated_path_is_what_it_was_bit_for_bit(family, route_kw, layer,
                                                   dtype):
    """The held experts 2..5 of 8, as ``deepseek_v2`` and ``afmoe`` call the
    layer: the same result as the parent's function, to the bit, and the
    same operations in the same order (one jaxpr)."""
    x, logits, gate, up, down = expert_operands(
        dtype, layers=None if layer is None else 3)
    experts, weights = dropless.route(logits, 3, **route_kw)
    args = (x, experts, weights, gate, up, down, 2)
    now = dropless.held_experts(*args, layer=layer)
    was = parent_held_experts(*args, layer=layer)
    assert now.dtype == was.dtype and bool((now == was).all())
    as_text = lambda fn, **kw: str(jax.make_jaxpr(
        lambda *a: fn(*a, layer=layer, **kw))(*args))
    # (the function's body, under a ``jax.jit`` of its own since PR 57)
    assert as_text(dropless._held_experts.__wrapped__, act="silu",
                   C=None) == as_text(parent_held_experts)


@pytest.mark.parametrize("layer", [None, 1])
def test_non_gated_experts_match_a_loop_over_experts(layer):
    """Experts 2..5 of 8; ``up_w`` goes in (out, in)."""
    x, logits, _, up, down = expert_operands(
        jnp.float32, layers=None if layer is None else 3)
    experts, weights = dropless.route(
        logits, 3, scoring_func="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.5, scale_normed=True,
        bias=jnp.linspace(-0.2, 0.2, 8))
    got = dropless.held_experts(x, experts, weights, None,
                                jnp.swapaxes(up, -1, -2), down, 2,
                                layer=layer, act="relu2")
    up_l, down_l = (up, down) if layer is None else (up[layer], down[layer])
    want = jnp.zeros_like(x)
    for e in range(4):
        w = jnp.where(experts == 2 + e, weights, 0.0).sum(-1)
        want += w[:, None] * (jnp.square(jnp.maximum(x @ up_l[e], 0.0))
                              @ down_l[e])
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(want).max()) > 0.1


@pytest.mark.parametrize("transposed, K, N", [(True, 256, 200),
                                              (False, 200, 384),
                                              (False, 256, 384)])
def test_the_pallas_grouped_product_matches_ragged_dot(transposed, K, N):
    """What a TPU runs in ``grouped_product``'s place
    (``ops/grouped_matmul.py``, interpreted here) at dims that are and are not multiples of 128, 200
    rows that are no multiple of a tile, an empty group, a group that
    crosses a tile's edge, rows in no group, and layer 1's groups of a
    merged stack of three layers."""
    key = jax.random.split(jax.random.PRNGKey(0), 2)
    rows = jax.random.normal(key[0], (200, K))
    w = jax.random.normal(key[1], (12, K, N)) * 0.1
    sizes = jnp.zeros((12,), jnp.int32).at[4:8].set(
        jnp.asarray([90, 0, 70, 15]))
    want = jax.lax.ragged_dot(rows, w, sizes)
    stored = jnp.swapaxes(w, 1, 2) if transposed else w
    got = dropless.grouped_product(rows, stored, sizes, transposed=transposed,
                                   interpret=True)
    assert got.shape == want.shape == (200, N)
    assert float(jnp.abs(got - want)[:175].max()) < 1e-4
    assert float(jnp.abs(dropless.grouped_product(
        rows, stored, sizes, transposed=transposed) - want).max()) == 0.0


@pytest.mark.parametrize("transposed", [False, True])
def test_the_kernel_sums_over_tiles_of_k_and_of_n(transposed):
    """``ops/grouped_matmul.py`` alone under tiles smaller than every dim
    (three tiles of K into the accumulator, two of N, 64 rows a tile): a
    group inside one tile, one over three, an empty one between, two that
    share a tile, rows in no group."""
    from deepspeed_tpu.ops.grouped_matmul import grouped_matmul
    key = jax.random.split(jax.random.PRNGKey(1), 2)
    rows = jax.random.normal(key[0], (256, 384))
    w = jax.random.normal(key[1], (6, 384, 256)) * 0.1
    sizes = jnp.asarray([0, 30, 140, 0, 20, 9], jnp.int32)
    want = jax.lax.ragged_dot(rows, w, sizes)
    got = grouped_matmul(rows, jnp.swapaxes(w, 1, 2) if transposed else w,
                         sizes, (64, 128, 128), transposed, interpret=True)
    assert got.shape == (256, 256)
    assert float(jnp.abs(got - want)[:199].max()) < 1e-4


def test_the_table_of_visits_is_every_group_tile_pair_in_order():
    """Against a loop over groups and tiles, for sizes with empty groups,
    groups that end on a tile's edge and a group that spans several tiles;
    the steps past the count are never run and only have to be in range."""
    from deepspeed_tpu.ops.grouped_matmul import visits
    rng = np.random.default_rng(0)
    for trial in range(20):
        G, tm, tiles_m = 9, 8, 12
        sizes = rng.integers(0, 30, G) * (rng.random(G) < 0.6)
        sizes[rng.integers(G)] = 16            # one that ends on an edge
        sizes = (sizes * (tiles_m * tm - 5) // max(sizes.sum(), 1)
                 if sizes.sum() > tiles_m * tm else sizes).astype(np.int32)
        group, tile, start, end, count = visits(jnp.asarray(sizes), tiles_m,
                                                tm)
        want, at = [], 0
        for g, n in enumerate(sizes):
            want += [(g, t) for t in range(at // tm, (at + n - 1) // tm + 1)
                     if n]
            assert (int(start[g]), int(end[g])) == (at, at + n)
            at += n
        assert int(count) == len(want) <= tiles_m + G - 1 == group.shape[0]
        assert list(zip(group[:len(want)].tolist(),
                        tile[:len(want)].tolist())) == want
        assert 0 <= int(tile.min()) and int(tile.max()) < tiles_m
        assert 0 <= int(group.min()) and int(group.max()) < G


def test_a_gated_stacked_layer_through_the_pallas_product(monkeypatch):
    """A gated expert layer as ``deepseek_v2`` and ``afmoe`` call it (the
    stack of three layers whole, ``layer`` 1, experts 2..5 of 8 held), all
    three products the interpreted Pallas call at widths that are multiples
    of 128: what ``ragged_dot`` gives, to rounding, with an idle expert and
    absent experts' pairs in no group."""
    x, logits, gate, up, down = expert_operands(jnp.float32, layers=3,
                                                N=40, D=128, F=256)
    experts, weights = dropless.route(logits.at[:, 3].set(-1e9), 3)
    args = (x, experts, weights, gate, up, down, 2)
    want = dropless.held_experts(*args, layer=1)
    product = dropless.grouped_product
    monkeypatch.setattr(dropless, "grouped_product",
                        lambda *a, **kw: product(*a, interpret=True, **kw))
    got = dropless.held_experts(*args, layer=1)
    assert float(jnp.abs(want).max()) > 0.1
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())


# (layers x count groups, D, F) of the three cells' expert layers, and the
# rows of a decode step and of a prompt (slots or tokens x picks a token)
CELL_WIDTHS = {"deepseek-v2": (120, 5120, 1536), "trinity": (128, 3072, 3072),
               "nemotron-3-nano": (224, 2688, 1856),
               # 12 layers x 64 held experts of width 512 (PR 54)
               "qwen3-next": (768, 2048, 512)}


@pytest.mark.parametrize("cell, rows, product, plan", [
    ("deepseek-v2", 768, "up", (128, 5120, 384)),
    ("deepseek-v2", 768, "down", (128, 1536, 1280)),
    ("deepseek-v2", 6144, "up", (128, 5120, 384)),
    ("deepseek-v2", 6144, "down", (128, 1536, 1280)),
    ("trinity", 384, "up", (128, 3072, 768)),
    ("trinity", 4096, "down", (128, 3072, 768)),
    ("trinity", 32768, "up", (128, 3072, 768)),
    # PR 42's, measured there: this cell's executables do not change
    ("nemotron-3-nano", 1536, "up", (128, 896, 1856)),
    ("nemotron-3-nano", 1536, "down", (128, 1856, 896)),
    ("nemotron-3-nano", 6144, "up", (128, 896, 1856)),
    ("nemotron-3-nano", 6144, "down", (128, 1856, 896)),
    # many small experts: a matrix is ONE tile (2 MiB) either way; a decode
    # step's 640 pairs (64 slots x 10) and a 4,096-token prompt's
    ("qwen3-next", 640, "up", (128, 2048, 512)),
    ("qwen3-next", 640, "down", (128, 512, 2048)),
    ("qwen3-next", 40960, "up", (128, 2048, 512)),
    ("qwen3-next", 40960, "down", (128, 512, 2048)),
])
def test_the_tile_plan_comes_from_the_shapes(monkeypatch, cell, rows, product,
                                             plan):
    """At the three cells' widths, a decode step's rows and a prompt's over
    the merged stack: the product takes the measured plan (``tile_plan``'s
    table) and says so; a tile divides its dim in multiples of 128 or spans
    it, and two copies of every tile with the accumulator fit the VMEM the
    module states."""
    import time
    monkeypatch.setattr(dropless, "_on_tpu", lambda: True)
    groups, D, F = CELL_WIDTHS[cell]
    K, N = (D, F) if product == "up" else (F, D)
    transposed = cell == "nemotron-3-nano" and product == "up"
    tm, tk, tn = dropless.tile_plan(K, N)
    assert (tm, tk, tn) == plan
    for tile, dim in ((tk, K), (tn, N)):
        assert tile == dim or (tile % 128 == 0 and dim % tile == 0)
    assert dropless.plan_vmem_bytes(plan) <= dropless._VMEM_BYTES < 16 << 20
    shapes = [((rows, K), jnp.bfloat16),
              ((groups, N, K) if transposed else (groups, K, N), jnp.bfloat16),
              ((groups,), jnp.int32)]
    t0 = time.monotonic()
    out = jax.eval_shape(
        lambda *a: dropless.grouped_product(*a, transposed=transposed),
        *[jax.ShapeDtypeStruct(*s) for s in shapes])
    assert out.shape == (rows, N) and out.dtype == jnp.bfloat16
    assert dropless.products_traced(t0, time.monotonic()) == {
        f"gmm {tm}x{tk}x{tn} of {rows}x{K}x{N}/{groups}": 1}


def test_a_plan_that_cannot_fit_is_refused_by_name():
    """Two dims that are no multiples of 128 go in whole; where that is
    more VMEM than a call may hold, the refusal names dims and tiles."""
    with pytest.raises(ValueError, match=r"\(2600, 3000\).*VMEM"):
        dropless.tile_plan(2600, 3000)


@pytest.mark.parametrize("F", [256, 200])
def test_on_a_tpu_every_product_is_the_pallas_call(monkeypatch, F):
    """With the backend a TPU all three products of a gated expert are the
    Pallas call and none is ``ragged_dot``, at a width that is a multiple
    of 128 and at one that is not: the backend chooses, not the widths; and
    the trace leaves each product's kernel, tiles and shapes behind for
    the ``compile.lower`` row."""
    import time
    from deepspeed_tpu.analysis.jaxpr_audit import iter_eqns
    monkeypatch.setattr(dropless, "_on_tpu", lambda: True)
    x, logits, gate, up, down = expert_operands(jnp.float32, D=128, F=F)
    experts, weights = dropless.route(logits, 3)
    t0 = time.monotonic()
    jaxpr = jax.make_jaxpr(lambda *a: dropless.held_experts(
        *a, 0))(x, experts, weights, gate, up, down)
    names = [e.primitive.name for e, _ in iter_eqns(jaxpr.jaxpr)]
    assert names.count("pallas_call") == 3
    assert names.count("ragged_dot_general") == 0
    traced = dropless.products_traced(t0, time.monotonic())
    assert sum(traced.values()) == 3 and all(
        what.startswith("gmm 128x") for what in traced)
    assert f"gmm 128x128x{F} of 72x128x{F}/4" in traced
    # a call with a narrow width (PR 57) has the same three, a slab of rows
    # wide: 1,536 pairs, 4 experts held of 64, twice the even share = 256
    monkeypatch.setattr(dropless, "_COMPACT_MIN_PAIRS", 1024)
    x, logits, gate, up, down = expert_operands(jnp.float32, D=128, F=F,
                                                N=512)
    experts, weights = dropless.route(logits, 3)
    t0 = time.monotonic()
    jaxpr = jax.make_jaxpr(lambda *a: dropless.held_experts(
        *a, 0, width=64))(x, experts, weights, gate, up, down)
    names = [e.primitive.name for e, _ in iter_eqns(jaxpr.jaxpr)]
    assert names.count("pallas_call") == 3 and names.count("while") == 1
    assert names.count("cond") == 0
    traced = dropless.products_traced(t0, time.monotonic())
    assert traced == {f"gmm 128x128x{F} of 256x128x{F}/4": 2,
                      f"gmm 128x{F}x128 of 256x{F}x128/4": 1}


def test_an_unknown_activation_is_refused_by_name():
    x, logits, _, up, down = expert_operands(jnp.float32)
    experts, weights = dropless.route(logits, 3)
    with pytest.raises(ValueError, match="swish7"):
        dropless.held_experts(x, experts, weights, None,
                              jnp.swapaxes(up, -1, -2), down, 0, act="swish7")


def test_the_shares_add_up_to_the_whole_layer(model_params):
    """One expert layer over the same normed tokens, held four ways: the four
    shares' routed parts, with the shared expert (which every chip computes
    alike) counted once, equal the UNCUT reference's whole layer."""
    m, params = model_params
    pm = params["moe"]
    h = jax.random.normal(jax.random.PRNGKey(7), (1, 24, 64))
    u = h[0] / jnp.sqrt((h[0] ** 2).mean(-1, keepdims=True) + 1e-5)
    whole, _ = reference.experts(ref_cfg(m), pm, 1, u)
    total, shared = 0.0, None
    for first in (0, 4, 8, 12):
        share = tiny(experts_held=(first, 4))
        cut = dict(pm, up_w=pm["up_w"][:, first:first + 4],
                   down_w=pm["down_w"][:, first:first + 4])
        out, counts, _ = share._moe(cut, h, 1)
        only_shared = (jnp.square(jnp.maximum(u @ pm["shared_up_w"][1], 0.0))
                       @ pm["shared_down_w"][1])
        total = total + (out[0] - h[0]) - only_shared
        shared = only_shared
        # and the reference, given the same share, leaves the same out
        mine, _ = reference.experts(
            ref_cfg(m, experts_held=[first, 4]), cut, 1, u)
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


def serve_and_compare(params, model=None):
    """Six requests through three slots: every slot is seated, freed and
    seated again by a second stream (no state may leak).  Returns the worst
    logit error seen at any step and the engine (drained)."""
    m = model or tiny()
    cfg = ref_cfg(m)
    ref = jax.jit(lambda p, t, pos: reference.logits_at(cfg, p, t, pos))
    eng = ds.init_inference(m, params=params, dtype=jnp.float32)
    srv = ServingEngine(engine=eng, config={
        "batch_slots": 3, "block_size": 8})
    uids = [srv.submit(Request(tokens=tokens(20 + i, n), max_new_tokens=new))
            for i, (n, new) in enumerate(zip(PROMPTS, NEW))]
    worst, seen = 0.0, 0
    while srv.step():
        if any(s is not None for s in srv._slots):
            err, n = live_logit_error(srv, params, ref)
            worst, seen = max(worst, err), seen + n
    assert seen > 20
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
    per_stream = c.count("M") * (c.state_bytes_per_layer
                                 + (c.conv_kernel - 1) * c.conv_dim * 4)
    assert st["recurrent_state_bytes"] == 3 * per_stream
    assert st["state_bytes_per_stream"] == per_stream
    assert srv.pool["k"].shape == (1, srv.num_blocks, 8, 32)   # 2 x 16 wide
    assert st["kv_pool_bytes"] == 2 * srv.pool["k"].nbytes
    assert (st["mamba_layers"], st["attention_layers"],
            st["expert_layers"]) == (3, 1, 2)
    # the new attributes of the spans
    rows = [r for r in srv._spans.rows() if r.t_start >= t0]
    pre = [r for r in rows if r.name == "serving.prefill"][-1].attrs
    assert pre["ssd_tokens"] == pre["scan_tokens"] == pre["prompt_len"]
    assert pre["ssd_chunks"] == -(-pre["prompt_len"] // c.chunk_size)
    assert pre["routed_pairs"] + pre["pairs_elsewhere"] == \
        4 * 2 * pre["prompt_len"]
    steps = [r.attrs for r in rows if r.name == "serving.step" and r.attrs]
    assert steps and max(a["seated_slots"] for a in steps) == 3
    for a in steps:
        assert a["seated_slots"] + a["free_slots"] == 3
        assert a["state_bytes"] == a["seated_slots"] * 3 * 2 \
            * c.state_bytes_per_layer
        # (a step that `live_logit_error` settled from outside books none)
        assert a.get("experts_touched", 0) + a.get("experts_idle", 32) == 32


def test_an_inactive_row_keeps_its_recurrent_rows(model_params):
    m, params = model_params
    pool = m.init_serving_state(2, 5, 8, dtype=jnp.float32)
    pool = dict(pool, ssm=pool["ssm"] + 1.0, conv=pool["conv"] + 2.0)
    tables = jnp.asarray([[1, 2], [0, 0]], jnp.int32)       # row 1: scratch
    _, new, routes = m.decode_step_paged(
        params, jnp.asarray([3, 4]), pool, tables,
        jnp.asarray([5, 0], jnp.int32), with_routes=True)
    assert routes.shape == (2, 2, 4)
    assert float(jnp.abs(new["ssm"][:, 1] - 1.0).max()) == 0.0
    assert float(jnp.abs(new["conv"][:, 1] - 2.0).max()) == 0.0
    assert float(jnp.abs(new["ssm"][:, 0] - 1.0).max()) > 0.0
    assert int(new["counters"][0] + new["counters"][1]) == 4 * 2   # 1 live


def test_the_seat_and_the_step_agree_on_the_states_layout(model_params):
    """A prompt through ``prefill_paged`` (its length no multiple of the
    chunk), then three ``decode_step_paged`` tokens: the first layer's rows of
    the slot are the token-by-token ``ssm_step_jnp`` recurrence from zero over
    the same operands.  Every head has a decay of its own and every group a
    ``B`` and a ``C`` of its own, so a leaf that the seat lays out otherwise
    than the step reads it (transposed, or its heads in other groups) stands
    orders above the rounding; and what the step writes is float32."""
    m, params = model_params
    c = m.config
    toks = tokens(11, 1, 14)
    prompt, slot = 11, 1
    pool = m.init_serving_state(3, 6, 8, dtype=jnp.float32)
    assert pool["ssm"].shape == (3, 3) + m2.step_layout(
        c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size, c.n_groups)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :prompt] = toks[0, :prompt]
    _, pool = m.prefill_paged(params, jnp.asarray(padded), pool,
                              jnp.asarray([1, 2], jnp.int32), slot, prompt)
    tables = jnp.asarray([[0, 0], [1, 2], [0, 0]], jnp.int32)
    for t in range(prompt, 14):
        step_toks = jnp.asarray([0, toks[0, t], 0], jnp.int32)
        _, pool = m.decode_step_paged(params, step_toks, pool, tables,
                                      jnp.asarray([0, t, 0], jnp.int32))
    # the first layer is a mixer: its input is the embedding
    p0 = jax.tree_util.tree_map(lambda a: a[0], params["mamba"])
    x, _, dt, B, C, _ = m._scan_inputs(p0, m._embed(params, jnp.asarray(toks)),
                                       None)
    A = m._A(p0)
    assert len(set(np.asarray(A).round(4))) == c.mamba_num_heads
    _, S = recurrence(x, dt, A, B, C, p0["D"],
                      jnp.zeros((1, c.mamba_num_heads, c.mamba_head_dim,
                                 c.ssm_state_size)))
    got = m2.from_step_layout(pool["ssm"][0, slot], c.mamba_num_heads)
    assert rel_err(got, S[0]) < 1e-5
    # the leaf read as if it lay in the published orientation does not pass
    assert rel_err(pool["ssm"][0, slot].reshape(S[0].shape), S[0]) > 1e-2
    assert low_bits_share(pool["ssm"][:, slot]) > 0.99
    # the other slots were never seated
    assert float(jnp.abs(pool["ssm"][:, 0]).max()) == 0.0
    assert float(jnp.abs(pool["ssm"][:, 2]).max()) == 0.0


# ---------------------------------------------------------- (e) sensitivity
def test_state_taken_at_the_buckets_end_fails(model_params, monkeypatch):
    """The pad after the prompt enters the recurrence: what this family is
    most likely to get wrong, and the check sees it."""
    _, params = model_params
    monkeypatch.setattr(m2, "mask_delta", lambda delta, t_real: delta)
    monkeypatch.setattr(m2, "conv_tail_at",
                        lambda padded, t_real, width: padded[:, -width:])
    worst, _, _ = serve_and_compare(params)
    assert worst > 10 * TOL


def test_a_seat_that_keeps_the_previous_rows_fails(model_params, monkeypatch):
    _, params = model_params
    sound = nemotron_h.NemotronH.prefill_paged

    def keeps_rows(self, params, toks, pool, blocks, slot, t_real):
        row, new = sound(self, params, toks, pool, blocks, slot, t_real)
        return row, dict(new, conv=pool["conv"], ssm=pool["ssm"])
    monkeypatch.setattr(nemotron_h.NemotronH, "prefill_paged", keeps_rows)
    worst, _, _ = serve_and_compare(params)
    assert worst > 10 * TOL


# what each fault must read at the least: a mechanism computed otherwise
# stands ten times over TOL; the two that move a number by a percent or by
# bfloat16's rounding (0.002 and 0.0002 here, the sound program 1e-6) stand
# thirty times over the sound program's own reading
FAULT_FLOORS = dict.fromkeys(control_nemotron.FAULTS, 10 * TOL)
FAULT_FLOORS.update(bias_in_weights=TOL, state_bf16=TOL / 10)


@pytest.mark.parametrize("fault", control_nemotron.FAULTS)
def test_each_planted_fault_fails_at_float32(model_params, fault):
    """``benchmark/control_nemotron.py``'s faults, each against the sound
    reference at float32, where nothing hides below the precision served."""
    _, params = model_params
    unplant = control_nemotron.plant(fault)
    try:
        worst, _, _ = serve_and_compare(params)
    finally:
        unplant()
    sound = serve_and_compare(params)[0]               # and it is out again
    assert sound < TOL / 30
    assert worst > max(FAULT_FLOORS[fault], 30 * sound), (fault, worst)
