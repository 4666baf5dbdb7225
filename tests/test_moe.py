"""MoE subsystem tests.

Parity model: reference ``tests/unit/test_moe.py`` (e2e training of
``SimpleMoEModel`` across configurations) plus direct gating-math unit tests
(the reference exercises gating indirectly; we pin the GShard formulas).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.moe import (MoE, Experts, TopKGate, top1gating, top2gating,
                               compute_capacity, split_moe_params)
from deepspeed_tpu.parallel.mesh import make_mesh

from simple_model import SimpleMoEModel, ExpertMLP, random_dataset, base_config


# ---------------------------------------------------------------- gating math
def test_compute_capacity():
    # reference _capacity: ceil(tokens/experts * cf) clamped to min_capacity
    assert compute_capacity(64, 4, 1.0, 0) == 16
    assert compute_capacity(64, 4, 1.25, 0) == 20
    assert compute_capacity(10, 4, 1.0, 4) == 4
    assert compute_capacity(10, 4, 1.0, 8) == 8


def test_top1_dispatch_and_aux():
    rng = jax.random.PRNGKey(0)
    S, E = 32, 4
    logits = jax.random.normal(rng, (S, E), jnp.float32) * 3.0
    l_aux, cw, dm, counts = top1gating(logits, capacity_factor=2.0,
                                       min_capacity=0, rng=rng, use_rts=False)
    C = compute_capacity(S, E, 2.0, 0)
    assert cw.shape == (S, E, C) and dm.shape == (S, E, C)
    gates = jax.nn.softmax(logits, axis=1)
    top = jnp.argmax(gates, axis=1)
    # every kept token's combine weight equals its top-1 gate probability
    per_token = cw.sum(axis=(1, 2))
    kept = dm.sum(axis=(1, 2)) > 0
    np.testing.assert_allclose(np.asarray(per_token[kept]),
                               np.asarray(gates[jnp.arange(S), top][kept]),
                               rtol=1e-6)
    # each capacity slot holds at most one token
    assert int(dm.astype(jnp.int32).sum(axis=0).max()) <= 1
    # counts = tokens routed per expert before capacity thinning
    assert int(counts.sum()) == S
    # aux loss: E * sum(me * ce) with ce from the pre-thinning mask
    me = gates.mean(axis=0)
    ce = jax.nn.one_hot(top, E).mean(axis=0)
    np.testing.assert_allclose(float(l_aux), float((me * ce).sum() * E), rtol=1e-6)


def test_top1_respects_capacity():
    # all tokens prefer expert 0 → only `capacity` survive
    S, E = 16, 4
    logits = jnp.zeros((S, E)).at[:, 0].set(10.0)
    l_aux, cw, dm, counts = top1gating(logits, capacity_factor=1.0,
                                       min_capacity=0, rng=jax.random.PRNGKey(1),
                                       use_rts=False)
    C = compute_capacity(S, E, 1.0, 0)
    assert int(dm.astype(jnp.int32).sum()) == C
    # sequence-priority (no RTS): the FIRST C tokens are kept
    kept = np.asarray(dm.sum(axis=(1, 2)) > 0)
    assert kept[:C].all() and not kept[C:].any()
    assert int(counts[0]) == S  # counts are pre-thinning


def test_top1_rts_keeps_capacity_random_subset():
    S, E = 16, 2
    logits = jnp.zeros((S, E)).at[:, 0].set(10.0)
    _, _, dm, _ = top1gating(logits, capacity_factor=1.0, min_capacity=0,
                             rng=jax.random.PRNGKey(2), use_rts=True)
    C = compute_capacity(S, E, 1.0, 0)
    assert int(dm.astype(jnp.int32).sum()) == C


def test_top1_no_drop_tokens():
    # drop_tokens=False → static worst-case capacity, nothing dropped
    S, E = 16, 4
    logits = jnp.zeros((S, E)).at[:, 0].set(10.0)
    _, _, dm, _ = top1gating(logits, capacity_factor=1.0, min_capacity=0,
                             rng=jax.random.PRNGKey(3), drop_tokens=False,
                             use_rts=False)
    assert dm.shape[2] == S
    assert int(dm.astype(jnp.int32).sum()) == S


@pytest.mark.parametrize("k", [
    # the top-1 variant (the heavier compile per the durations report)
    # rides the slow tier (conftest budget policy); k=2 keeps the
    # scatter==einsum property fast
    pytest.param(1, marks=pytest.mark.slow), 2])
def test_scatter_dispatch_matches_einsum(k):
    """The O(S·M) scatter dispatch computes EXACTLY what the GShard one-hot
    einsum computes — outputs and gradients — including capacity drops
    (VERDICT r2 #4: quantify/replace the einsum dispatch)."""
    dim, E, S = 8, 4, 32
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (S, dim), jnp.float32)
    outs, grads = {}, {}
    for impl in ("scatter", "einsum"):
        moe = MoE(dim, ExpertMLP(dim), num_experts=E, k=k,
                  capacity_factor=0.5, min_capacity=2, use_rts=False,
                  dispatch_impl=impl)   # tight capacity → real drops
        params = moe.init(jax.random.PRNGKey(2))

        def loss(p):
            out, l_aux, _, ovf = moe.apply(p, x, rng=rng,
                                           return_overflow=True)
            return jnp.sum(out ** 2) + l_aux, (out, ovf)

        (l, (out, ovf)), g = jax.value_and_grad(loss, has_aux=True)(params)
        outs[impl] = (np.asarray(out), float(l), int(ovf))
        grads[impl] = np.concatenate(
            [np.asarray(a).ravel() for a in jax.tree_util.tree_leaves(g)])
    np.testing.assert_allclose(outs["scatter"][0], outs["einsum"][0],
                               rtol=1e-5, atol=1e-6)
    assert outs["scatter"][1] == pytest.approx(outs["einsum"][1], rel=1e-6)
    assert outs["scatter"][2] == outs["einsum"][2]
    np.testing.assert_allclose(grads["scatter"], grads["einsum"],
                               rtol=1e-4, atol=1e-6)


def test_capacity_for_matches_gating():
    """TopKGate.capacity_for reports the SAME capacity apply() uses, for all
    three sizing modes — pairing it with tokens_overflowed must not produce
    phantom overflow."""
    from deepspeed_tpu.moe.sharded_moe import nodrop_capacity
    S = 32
    g1 = TopKGate(8, 4, k=1, capacity_factor=1.5, min_capacity=0)
    assert g1.capacity_for(S) == compute_capacity(S, 4, 1.5, 0)
    g2 = TopKGate(8, 4, k=2, capacity_factor=2.0, min_capacity=0)
    # top2gating doubles the factor (two slots per token)
    assert g2.capacity_for(S) == compute_capacity(S, 4, 4.0, 0)
    gn = TopKGate(8, 8, k=1, capacity_factor=1.0, min_capacity=0,
                  drop_tokens=False)
    # default no-drop capacity is the GUARANTEED worst case (= tokens)
    assert gn.capacity_for(S) == nodrop_capacity(S, 8, None, 0) == S
    gc = TopKGate(8, 8, k=1, capacity_factor=1.0, min_capacity=0,
                  drop_tokens=False, max_capacity=S // 2)
    assert gc.capacity_for(S) == nodrop_capacity(S, 8, S // 2, 0) == S // 2


def test_nodrop_default_never_drops():
    """drop_tokens=False default capacity guarantees zero drops even under
    total routing skew (the reference's no-drop contract)."""
    S, E, dim = 32, 8, 8
    moe = MoE(dim, ExpertMLP(dim), num_experts=E, k=1, min_capacity=0,
              drop_tokens=False, use_rts=False)
    params = moe.init(jax.random.PRNGKey(0))
    # force every token onto expert 0 — worst-case skew
    params["moe"]["gate"]["wg"] = jnp.zeros((dim, E)).at[:, 0].set(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (S, dim))) + 0.1
    _, _, _, ovf = moe.apply(params, x, rng=jax.random.PRNGKey(2),
                             return_overflow=True)
    assert moe.moe_layer.gate.capacity_for(S) == S
    assert int(ovf) == 0


def test_nodrop_capped_overflow_detected():
    """Opt-in max_capacity bounds memory; skewed routing past the cap drops
    tokens — and the overflow count says exactly how many."""
    from deepspeed_tpu.moe import tokens_overflowed
    S, E, dim = 32, 8, 8
    moe = MoE(dim, ExpertMLP(dim), num_experts=E, k=1, min_capacity=0,
              drop_tokens=False, use_rts=False, max_capacity=S // 2)
    params = moe.init(jax.random.PRNGKey(0))
    # force every token onto expert 0
    params["moe"]["gate"]["wg"] = jnp.zeros((dim, E)).at[:, 0].set(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (S, dim))) + 0.1
    out, _, counts, ovf = moe.apply(params, x, rng=jax.random.PRNGKey(2),
                                    return_overflow=True)
    cap = moe.moe_layer.gate.capacity_for(S)
    assert cap == S // 2
    assert int(ovf) == S - cap                 # exact drop count surfaced
    assert int(ovf) == int(tokens_overflowed(counts, cap))
    # balanced routing: no overflow
    params["moe"]["gate"]["wg"] = jax.random.normal(
        jax.random.PRNGKey(3), (dim, E)) * 0.02
    _, _, _, ovf0 = moe.apply(params, x, rng=jax.random.PRNGKey(2),
                              return_overflow=True)
    assert int(ovf0) <= int(ovf)


def test_top2_normalized_combine():
    rng = jax.random.PRNGKey(4)
    S, E = 32, 4
    logits = jax.random.normal(rng, (S, E), jnp.float32)
    l_aux, cw, dm, _ = top2gating(logits, capacity_factor=2.0, min_capacity=0,
                                  rng=rng)
    # capacity doubles for top-2 (reference passes 2*capacity_factor)
    assert cw.shape[2] == compute_capacity(S, E, 4.0, 0)
    # tokens with both experts kept have combine weights summing to 1
    per_token = np.asarray(cw.sum(axis=(1, 2)))
    slots = np.asarray(dm.astype(jnp.int32).sum(axis=(1, 2)))
    np.testing.assert_allclose(per_token[slots == 2], 1.0, rtol=1e-5)


# ------------------------------------------------------------------ MoE layer
def test_moe_layer_matches_naive_loop():
    """MOELayer einsum dispatch == per-token loop over selected experts."""
    dim, E = 8, 4
    moe = MoE(dim, ExpertMLP(dim), num_experts=E, k=1, capacity_factor=8.0,
              min_capacity=0, use_rts=False)
    rng = jax.random.PRNGKey(5)
    params = moe.init(rng)
    x = jax.random.normal(jax.random.PRNGKey(6), (16, dim), jnp.float32)
    out, l_aux, _ = moe.apply(params, x, rng=rng)

    # naive: route each token to argmax expert, weight by gate prob
    logits = x @ params["moe"]["gate"]["wg"]
    gates = jax.nn.softmax(logits, axis=1)
    top = np.asarray(jnp.argmax(gates, axis=1))
    expert = ExpertMLP(dim)
    expected = np.zeros_like(np.asarray(x))
    for s in range(x.shape[0]):
        e = top[s]
        p_e = jax.tree_util.tree_map(lambda a: a[e], params["moe"]["experts"])
        expected[s] = float(gates[s, e]) * np.asarray(expert.apply(p_e, x[s]))
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-4, atol=1e-5)


def test_moe_residual_mode():
    dim = 8
    moe = MoE(dim, ExpertMLP(dim), num_experts=2, use_residual=True,
              capacity_factor=4.0, min_capacity=0, use_rts=False)
    rng = jax.random.PRNGKey(7)
    params = moe.init(rng)
    assert "mlp" in params and "coefficient" in params
    x = jax.random.normal(rng, (8, dim), jnp.float32)
    out, l_aux, _ = moe.apply(params, x, rng=rng)
    assert out.shape == x.shape and np.isfinite(np.asarray(out)).all()


def test_experts_stacked_vmap():
    dim, E = 4, 3
    ex = Experts(ExpertMLP(dim), E)
    params = ex.init(jax.random.PRNGKey(0))
    assert params["w1"].shape == (E, dim, 4 * dim)
    x = jax.random.normal(jax.random.PRNGKey(1), (E, 5, dim))
    y = ex.apply(params, x)
    assert y.shape == (E, 5, dim)
    # expert 0 applied alone matches the stacked result
    p0 = jax.tree_util.tree_map(lambda a: a[0], params)
    np.testing.assert_allclose(np.asarray(ExpertMLP(dim).apply(p0, x[0])),
                               np.asarray(y[0]), rtol=1e-5)


def test_split_moe_params():
    model = SimpleMoEModel(dim=8, num_experts=2)
    params = model.init(jax.random.PRNGKey(0))
    non_moe, moe_p = split_moe_params(params)
    assert non_moe["proj_in"]["w"] is not None
    assert non_moe["moe"]["moe"]["experts"]["w1"] is None
    assert moe_p["moe"]["moe"]["experts"]["w1"] is not None
    assert moe_p["proj_in"]["w"] is None


# ------------------------------------------------------- expert parallelism
def test_moe_expert_parallel_matches_single(devices):
    """Same MoE forward on expert=4 mesh vs single device — identical output.

    This is the TPU analogue of the reference's EP-correctness tests: expert
    parallelism must be a pure layout change.
    """
    dim, E = 8, 4
    moe = MoE(dim, ExpertMLP(dim), num_experts=E, k=1, capacity_factor=4.0,
              min_capacity=0, use_rts=False)
    rng = jax.random.PRNGKey(8)
    params = moe.init(rng)
    x = jax.random.normal(jax.random.PRNGKey(9), (32, dim), jnp.float32)

    ref_out, ref_aux, _ = moe.apply(params, x, rng=rng)

    mesh = make_mesh({"data": 2, "expert": 4})
    with jax.set_mesh(mesh):
        specs = {"moe": moe.partition_specs(params)}["moe"]
        p_sh = jax.device_put(params, jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), specs,
            is_leaf=lambda v: isinstance(v, P)))
        x_sh = jax.device_put(x, NamedSharding(mesh, P(("data", "expert"))))

        @jax.jit
        def fwd(p, xx):
            out, aux, _ = moe.apply(p, xx, rng=rng)
            return out, aux

        out, aux = fwd(p_sh, x_sh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(float(aux), float(ref_aux), rtol=1e-5)


# ------------------------------------------------------------------------ e2e
@pytest.mark.parametrize("use_residual", [
    # residual-MoE e2e rides the slow tier (conftest budget policy);
    # residual-mode semantics keep test_moe_residual_mode fast
    False, pytest.param(True, marks=pytest.mark.slow)])
def test_moe_e2e_training(devices, use_residual):
    """Train SimpleMoEModel on a data×expert mesh; loss must decrease
    (reference ``test_moe.py`` pattern)."""
    model = SimpleMoEModel(dim=8, num_experts=4, use_residual=use_residual)
    mesh = make_mesh({"data": 2, "expert": 4})
    config = base_config(micro=4, over={})
    engine, _, _, _ = ds.initialize(config=config, model=model,
                                    training_data=random_dataset(n=256),
                                    mesh=mesh)
    losses = [float(engine.train_batch()) for _ in range(15)]
    assert np.isfinite(losses).all()
    assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses


def test_moe_e2e_matches_data_parallel_only(devices):
    """EP×DP training == pure-DP training on the same data (layout-purity
    oracle, the reference's strongest MoE test idea)."""
    data = random_dataset(n=128)
    losses = {}
    for name, axes in [("dp", {"data": 8}), ("ep", {"data": 2, "expert": 4})]:
        model = SimpleMoEModel(dim=8, num_experts=4)
        engine, _, _, _ = ds.initialize(config=base_config(micro=4),
                                        model=model, training_data=data,
                                        mesh=make_mesh(axes))
        losses[name] = [float(engine.train_batch()) for _ in range(5)]
    np.testing.assert_allclose(losses["dp"], losses["ep"], rtol=2e-4)


@pytest.mark.slow   # compile-heavy; fast tier stays inside the driver budget
                    # (conftest policy — moe e2e/dp-match twins stay fast)
def test_moe_with_zero_stages(devices):
    """MoE composes with ZeRO sharding (reference ``test_moe.py`` zero-stage
    parametrization)."""
    for stage in (0, 1, 2):
        model = SimpleMoEModel(dim=8, num_experts=2)
        cfg = base_config(micro=4, over={"zero_optimization": {"stage": stage}})
        engine, _, _, _ = ds.initialize(config=cfg, model=model,
                                        training_data=random_dataset(n=128),
                                        mesh=make_mesh({"data": 2, "fsdp": 2,
                                                        "expert": 2}))
        losses = [float(engine.train_batch()) for _ in range(8)]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0], (stage, losses)


# ---------------------------------------------------- engine MoE bookkeeping
@pytest.mark.slow   # compile-heavy; fast tier stays inside the driver budget (conftest)
def test_engine_metrics_carry_moe_aux_and_overflow(devices):
    """Training GPT-MoE through DeepSpeedEngine must surface the gate's aux
    loss and token-overflow count in train_batch metrics (reference: the
    engine's MoE state surfacing, ``engine.py:1639``) — without bypassing
    the engine."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt2_moe import GPT2MoE

    model = GPT2MoE(preset="gpt2-moe-tiny", num_experts=8, n_layer=2,
                    embd_pdrop=0.0, attn_pdrop=0.0, resid_pdrop=0.0,
                    remat=False, attention_impl="jnp")
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 1024, (32, 33)).astype(np.int32)
    config = {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 2,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "mesh": {"axes": {"data": 1, "expert": 8}},
    }
    engine, _, _, _ = ds.initialize(config=config, model=model,
                                    training_data=(toks,))
    engine.train_batch()
    m = engine._last_metrics
    assert "moe_aux_loss" in m and "moe_tokens_dropped" in m
    assert np.isfinite(float(m["moe_aux_loss"]))
    assert float(m["moe_aux_loss"]) > 0.0
    assert float(m["moe_tokens_dropped"]) >= 0.0


@pytest.mark.slow   # compile-heavy; fast tier stays inside the driver budget (conftest)
def test_gpt_moe_16e_ep8_converges(devices):
    """The graded 16-expert shape: GPT-MoE with num_experts=16 trains on an
    expert=8 mesh (EP groups of 2 experts per rank) and the loss drops —
    the reference handles arbitrary expert counts via EP groups
    (``utils/groups.py:107``)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt2_moe import GPT2MoE

    model = GPT2MoE(preset="gpt2-moe-tiny", num_experts=16, n_layer=2,
                    capacity_factor=2.0, embd_pdrop=0.0, attn_pdrop=0.0,
                    resid_pdrop=0.0, remat=False, attention_impl="jnp")
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 1024, (64, 33)).astype(np.int32)
    config = {
        "train_micro_batch_size_per_gpu": 16,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
        "mesh": {"axes": {"data": 1, "expert": 8}},
    }
    engine, _, _, _ = ds.initialize(config=config, model=model,
                                    training_data=(toks,))
    losses = [float(engine.train_batch()) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.3, losses
    assert all(np.isfinite(l) for l in losses)


@pytest.mark.slow   # compile-heavy 16e/ep8 build (conftest budget policy);
                    # dispatch math keeps scatter_dispatch_matches_einsum
                    # + the wire parity tests in the fast tier
def test_moe_16e_ep8_dispatch_matches_single(devices):
    """16-expert MoE layer on an expert=8 mesh computes the SAME output as
    unsharded — EP with experts-per-rank > 1 is a pure layout change."""
    dim, E = 8, 16
    moe = MoE(dim, ExpertMLP(dim), num_experts=E, k=1, capacity_factor=4.0,
              min_capacity=0, use_rts=False)
    rng = jax.random.PRNGKey(4)
    params = moe.init(rng)
    x = jax.random.normal(jax.random.PRNGKey(5), (64, dim), jnp.float32)
    ref_out, ref_aux, _ = moe.apply(params, x, rng=rng)

    mesh = make_mesh({"data": 1, "expert": 8})
    with jax.set_mesh(mesh):
        specs = moe.partition_specs(params)
        p_sh = jax.device_put(params, jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), specs,
            is_leaf=lambda v: isinstance(v, P)))
        x_sh = jax.device_put(x, NamedSharding(mesh, P(("data", "expert"))))

        @jax.jit
        def fwd(p, xx):
            out, aux, _ = moe.apply(p, xx, rng=rng)
            return out, aux

        out, aux = fwd(p_sh, x_sh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(float(aux), float(ref_aux), rtol=1e-5)


# =================================================== quantized expert wire
# int8 dispatch/combine all_to_all (runtime/comm/moe_wire.py, ISSUE 8 /
# docs/comms-compression.md `moe` route).  Oracle strategy mirrors the
# EP tests above: the wire must be a LAYOUT+PRECISION change only — same
# gate decisions, same aux loss, outputs within the block-scale bound.

from deepspeed_tpu.runtime.comm import moe_wire as mw  # noqa: E402


def _wire_setup(devices, k=1, dim=16, tokens=64, capacity_factor=4.0,
                num_experts=4, block_size=16, hierarchical=True,
                data_axis=2, seed=8):
    """Sharded MoE wire fixture: (moe, mesh, wire, p_sh, x_sh, rng).

    Callers build distinct function objects per variant — the process-global wire is
    read at TRACE time, so reusing one jitted callable across a policy
    flip would silently reuse the stale executable (exactly why the
    ENGINE keys its compile cache on the policy)."""
    moe = MoE(dim, ExpertMLP(dim), num_experts=num_experts, k=k,
              capacity_factor=capacity_factor, min_capacity=0, use_rts=False)
    rng = jax.random.PRNGKey(seed)
    params = moe.init(rng)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (tokens, dim),
                          jnp.float32)
    mesh = make_mesh({"data": data_axis, "expert": 8 // data_axis})
    wire = mw.MoEWire(mesh, bits=8, block_size=block_size,
                      hierarchical=hierarchical)
    specs = moe.partition_specs(params)
    p_sh = jax.device_put(params, jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda v: isinstance(v, P)))
    x_sh = jax.device_put(x, NamedSharding(mesh, P(("data", "expert"))))
    return moe, mesh, wire, p_sh, x_sh, rng


@pytest.mark.parametrize("k,hierarchical", [(1, True), (2, True), (1, False)])
def test_moe_wire_matches_fullwidth(devices, k, hierarchical):
    """Quantized dispatch/combine vs the full-width constraint path:
    outputs within a tolerance TIED TO THE BLOCK SCALE (two int8 hops,
    each bounded by scale/2 = amax/254 per element), gate decisions and
    aux loss untouched (top-1 AND top-2)."""
    moe, mesh, wire, p_sh, x_sh, rng = _wire_setup(
        devices, k=k, hierarchical=hierarchical)

    with jax.set_mesh(mesh):
        def full_fn(p, xx):
            out, aux, _ = moe.apply(p, xx, rng=rng)
            return out, aux

        def quant_fn(p, xx):
            out, aux, _ = moe.apply(p, xx, rng=rng)
            return out, aux

        mw.set_active(None)
        out_f, aux_f = jax.jit(full_fn)(p_sh, x_sh)
        try:
            mw.set_active(wire)
            out_q, aux_q = jax.jit(quant_fn)(p_sh, x_sh)
        finally:
            mw.set_active(None)

    assert wire.trace_log, "the quantized wire never traced"
    out_f, out_q = np.asarray(out_f), np.asarray(out_q)
    # block-scale bound: dispatch quantizes the activations (amax_in),
    # combine quantizes the expert outputs; k routes sum.  scale/2 per
    # element per hop, with slack 2 for the f32 accumulation order.
    amax_in = np.max(np.abs(np.asarray(x_sh)))
    amax_out = np.max(np.abs(out_f))
    bound = 2 * k * (amax_in + amax_out) / 254 + 1e-5
    err = np.max(np.abs(out_q - out_f))
    assert err <= bound, (err, bound)
    assert err > 0                      # it IS a lossy wire (int8 moved)
    np.testing.assert_allclose(float(aux_q), float(aux_f), rtol=1e-6)


def test_moe_wire_gradient_flows_ste(devices):
    """No silent zero grads through the int8 cast (the qwZ custom_vjp
    lesson): gradients w.r.t. the dispatched activations AND the expert
    weights must flow through both quantized exchanges and track the
    full-width gradients."""
    moe, mesh, wire, p_sh, x_sh, rng = _wire_setup(devices, k=1)

    with jax.set_mesh(mesh):
        def mk_loss():
            def loss_fn(p, xx):
                # proj on the input makes the dispatch payload depend on
                # differentiated params -> the dispatch BACKWARD (gather
                # direction) is exercised too
                h = xx @ p["proj"]
                out, aux, _ = moe.apply(p["moe"], h, rng=rng)
                return jnp.mean(jnp.square(out)) + 0.01 * aux
            return loss_fn

        proj = jnp.eye(x_sh.shape[-1], dtype=jnp.float32)
        args = ({"proj": proj, "moe": p_sh}, x_sh)
        mw.set_active(None)
        g_f = jax.jit(jax.grad(mk_loss()))(*args)
        try:
            mw.set_active(wire)
            g_q = jax.jit(jax.grad(mk_loss()))(*args)
        finally:
            mw.set_active(None)

    tags = [ev["tag"] for ev in wire.trace_log]
    assert "dispatch_bwd" in tags and "combine_bwd" in tags, tags
    for path in (("moe", "moe", "experts", "w1"),
                 ("moe", "moe", "experts", "w2"), ("proj",)):
        lf, lq = g_f, g_q
        for kpath in path:
            lf, lq = lf[kpath], lq[kpath]
        lf, lq = np.asarray(lf), np.asarray(lq)
        assert np.linalg.norm(lq) > 1e-6, path   # not silently zeroed
        rel = np.linalg.norm(lq - lf) / max(np.linalg.norm(lf), 1e-12)
        assert rel < 0.1, (path, rel)


def test_moe_wire_zero_token_expert(devices):
    """An expert that receives ZERO tokens must contribute exact zeros
    through the int8 wire (zero-scale blocks sum exactly — the
    disjointness invariant) and the step stays finite."""
    # 8 tokens onto 8 experts top-1: several experts get no token
    moe, mesh, wire, p_sh, x_sh, rng = _wire_setup(
        devices, k=1, tokens=8, num_experts=8, capacity_factor=8.0)

    with jax.set_mesh(mesh):
        def full_fn(p, xx):
            return moe.apply(p, xx, rng=rng)[0]

        def quant_fn(p, xx):
            return moe.apply(p, xx, rng=rng)[0]

        mw.set_active(None)
        out_f = jax.jit(full_fn)(p_sh, x_sh)
        try:
            mw.set_active(wire)
            out_q = jax.jit(quant_fn)(p_sh, x_sh)
        finally:
            mw.set_active(None)

    out_f, out_q = np.asarray(out_f), np.asarray(out_q)
    assert np.isfinite(out_q).all()
    amax = max(np.max(np.abs(out_f)), np.max(np.abs(np.asarray(x_sh))))
    assert np.max(np.abs(out_q - out_f)) <= 4 * amax / 254 + 1e-5


def test_moe_wire_capacity_overflow(devices):
    """Capacity-dropped routes (weight 0, OOB slot address) must vanish
    identically on the quantized wire — the drop mask is the gate's,
    never the quantizer's."""
    # tiny capacity forces drops: 64 tokens, 4 experts, cf such that
    # C < per-expert demand
    moe, mesh, wire, p_sh, x_sh, rng = _wire_setup(
        devices, k=1, tokens=64, num_experts=4, capacity_factor=0.5)

    with jax.set_mesh(mesh):
        def full_fn(p, xx):
            out, _, _, ovf = moe.moe_layer.apply(p["moe"], xx, rng=rng)
            return out, ovf

        def quant_fn(p, xx):
            out, _, _, ovf = moe.moe_layer.apply(p["moe"], xx, rng=rng)
            return out, ovf

        mw.set_active(None)
        out_f, ovf_f = jax.jit(full_fn)(p_sh, x_sh)
        try:
            mw.set_active(wire)
            out_q, ovf_q = jax.jit(quant_fn)(p_sh, x_sh)
        finally:
            mw.set_active(None)

    assert int(ovf_f) > 0, "fixture must actually overflow capacity"
    assert int(ovf_q) == int(ovf_f)
    out_f, out_q = np.asarray(out_f), np.asarray(out_q)
    amax = max(np.max(np.abs(out_f)), np.max(np.abs(np.asarray(x_sh))))
    assert np.max(np.abs(out_q - out_f)) <= 4 * amax / 254 + 1e-5


@pytest.mark.slow   # two engine builds x 8 steps (conftest budget policy);
                    # the wire numerics keep fast twins (moe_wire_matches_
                    # fullwidth, STE/zero-token/capacity) and the engine
                    # integration keeps the census test fast
def test_moe_wire_engine_loss_tracks_full(devices):
    """EP loss tracking, compressed vs full width, >=8 steps on a
    data×expert mesh through the ENGINE (the moe route of
    comms_compression) — plus the wire census: int8 on the all_to_all,
    replica groups > 1 (two-level phase).  The >=3x reduction acceptance
    runs at a payload-dominated scale in ``--audit-step moe``."""
    from deepspeed_tpu.analysis.jaxpr_audit import audit_engine
    from deepspeed_tpu.analysis.comms import wire_report

    data = random_dataset(n=256)
    mesh = make_mesh({"data": 2, "expert": 4})

    def build(comp):
        cfg = base_config(micro=4, over={})
        if comp:
            cfg["comms_compression"] = {
                "enabled": True, "routes": ["moe"],
                "moe": {"bits": 8, "block_size": 8}}
        model = SimpleMoEModel(dim=8, num_experts=4)
        e, _, _, _ = ds.initialize(config=cfg, model=model,
                                   training_data=data, mesh=mesh)
        return e

    e_full = build(False)
    ref = [float(e_full.train_batch()) for _ in range(8)]
    e_full.close()

    e = build(True)
    assert e._router.moe_active and e._moe_wire is not None
    got = [float(e.train_batch()) for _ in range(8)]
    rep = audit_engine(e)
    hlo = [c for c in rep.census if c.level == "hlo"]
    e.close()

    assert all(np.isfinite(got))
    assert got[-1] < got[0]                      # it still learns
    assert abs(got[-1] - ref[-1]) / max(abs(ref[-1]), 1e-6) < 0.1, (ref, got)
    # the wire truly moved int8, in a grouped (two-level) phase
    quant = [c for c in hlo if c.quantized]
    assert any(c.kind == "all_to_all" for c in quant), [c.kind for c in quant]
    assert any(c.groups > 1 for c in quant)
    wr = wire_report(hlo)
    assert wr["quantized_wire_bytes"] > 0


def test_moe_wire_census_counts_each_layer_site(devices):
    """Two same-shaped MoE layers in one model must EACH contribute
    their exchanges to the wire's census expectation (distinct per-layer
    sites — otherwise ``comms_budget()`` under-declares and the
    compressed step's own census violates it), while a RETRACE of the
    same layers (eval twin, warm re-specialization) must not inflate
    it."""
    dim, E = 16, 4
    mesh = make_mesh({"data": 2, "expert": 4})
    rng = jax.random.PRNGKey(11)
    ka, kb = jax.random.split(rng)
    mk = lambda: MoE(dim, ExpertMLP(dim), num_experts=E, k=1,
                     capacity_factor=4.0, min_capacity=0, use_rts=False)
    moe_a, moe_b = mk(), mk()
    params = {"a": moe_a.init(ka), "b": moe_b.init(kb)}
    specs = {"a": moe_a.partition_specs(params["a"]),
             "b": moe_b.partition_specs(params["b"])}
    p_sh = jax.device_put(params, jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda v: isinstance(v, P)))
    x = jax.random.normal(jax.random.PRNGKey(12), (64, dim), jnp.float32)
    x_sh = jax.device_put(x, NamedSharding(mesh, P(("data", "expert"))))

    def single_fn(p, xx):
        return moe_a.apply(p["a"], xx, rng=rng)[0]

    def stacked_fn(p, xx):
        h = moe_a.apply(p["a"], xx, rng=rng)[0]
        return moe_b.apply(p["b"], h, rng=rng)[0]

    def trace(fn, wire):
        mw.set_active(wire)
        try:
            with jax.set_mesh(mesh):
                jax.jit(fn)(p_sh, x_sh)
        finally:
            mw.set_active(None)
        return wire.expected_wire_bytes()

    w1 = mw.MoEWire(mesh, bits=8, block_size=16)
    one = trace(single_fn, w1)
    w2 = mw.MoEWire(mesh, bits=8, block_size=16)
    two = trace(stacked_fn, w2)
    assert one and set(two) == set(one)
    for kind, b in one.items():
        assert two[kind] == 2 * b, (kind, one, two)
    # a retrace of the SAME layers stays deduped
    assert trace(stacked_fn, w2) == two
    # a re-specialization at a SMALLER batch shape (eval twin) keeps the
    # largest variant per (tag, site) — it must not inflate the per-step
    # expectation by summing two programs
    x_small = jax.device_put(x[:32], NamedSharding(mesh,
                                                   P(("data", "expert"))))

    def small_fn(p, _):
        return stacked_fn(p, x_small)

    assert trace(small_fn, w2) == two


@pytest.mark.slow   # three engine builds (conftest budget policy); the
# key mechanism itself stays tier-1-covered by test_compile_cache.py and
# test_quantized_comm.py::test_compile_cache_key_covers_compression_policy
def test_compile_cache_key_covers_moe_policy(devices):
    """Flipping the moe route (or its knobs) must change the compile
    cache key: the wire is read at TRACE time, so a stale executable
    under a different policy would silently move full-width bytes."""
    mesh = make_mesh({"data": 2, "expert": 4})
    data = random_dataset(n=64)

    def build(moe_policy):
        cfg = base_config(micro=4, over={})
        if moe_policy is not None:
            cfg["comms_compression"] = {"enabled": True,
                                        "routes": ["moe"],
                                        "moe": moe_policy}
        e, _, _, _ = ds.initialize(config=cfg,
                                   model=SimpleMoEModel(dim=8,
                                                        num_experts=4),
                                   training_data=data, mesh=mesh)
        return e

    e_off = build(None)
    e_on = build({"bits": 8, "block_size": 8})
    e_blk = build({"bits": 8, "block_size": 4})
    keys = [e._cc_key_slice["comms_compression"]
            for e in (e_off, e_on, e_blk)]
    for e in (e_off, e_on, e_blk):
        e.close()
    assert keys[0] != keys[1] and keys[1] != keys[2], keys
    assert keys[1]["enabled"] and keys[1]["moe"] == {"bits": 8,
                                                     "block_size": 8}
    assert keys[2]["moe"]["block_size"] == 4


# --------------------------------------- the dropless route beside the old gate
# moe/dropless.py (PR 34) routes top-k with no capacity; TopKGate keeps its
# capacity path for gpt2_moe, refuses any other k, and the two agree on WHICH
# experts a token picks.
from deepspeed_tpu.moe import dropless  # noqa: E402


@pytest.mark.parametrize("k", [1, 2])
def test_dropless_route_picks_what_the_capacity_gate_picks(k):
    S, E, M = 24, 8, 16
    gate = TopKGate(M, E, k=k, capacity_factor=float(S), min_capacity=S,
                    use_rts=False)
    params = gate.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (S, M))
    _, combine, dispatch, counts = gate.apply(
        params, x, rng=jax.random.PRNGKey(4), train=False)
    # ample capacity: nothing dropped, k picks a token
    assert int(dispatch.sum()) == k * S     # (exp_counts counts first picks)
    logits = x @ params["wg"]
    experts, weights = dropless.route(logits, k, norm_topk_prob=(k == 2))
    picked = np.asarray(dispatch.any(axis=-1))            # (S, E)
    first = np.asarray(experts)[:, 0]
    assert picked[np.arange(S), first].all()
    np.testing.assert_array_equal(first, np.asarray(logits).argmax(1))
    if k == 1:
        # one pick: the same expert, weighted by its softmax score
        assert picked.sum() == S
        np.testing.assert_allclose(
            np.asarray(combine.sum(axis=-1))[np.arange(S), first],
            np.asarray(weights)[:, 0], rtol=1e-5)
    else:
        # the old gate SAMPLES its second expert (Gumbel); the dropless
        # route takes the second largest, and renormalises the two
        second = np.asarray(experts)[:, 1]
        masked = np.asarray(logits).copy()
        masked[np.arange(S), first] = -np.inf
        np.testing.assert_array_equal(second, masked.argmax(1))
        np.testing.assert_allclose(np.asarray(weights).sum(1), 1.0, rtol=1e-5)


def test_the_capacity_gate_still_refuses_other_k_and_still_drops():
    with pytest.raises(ValueError, match="top-1 and top-2"):
        TopKGate(16, 8, k=6)
    # 24 tokens to one expert at capacity 4: the old gate drops 20, the
    # dropless layer computes all 24
    logits = jnp.zeros((24, 8)).at[:, 3].set(9.0)
    _, _, dispatch, _ = top1gating(logits, 1.0, 4, use_rts=False)
    assert int(dispatch.sum()) == 4
    experts, weights = dropless.route(logits, 1)
    assert (np.asarray(experts) == 3).all()
    x = jax.random.normal(jax.random.PRNGKey(2), (24, 16))
    w = jax.random.normal(jax.random.PRNGKey(3), (8, 16, 16)) * 0.2
    out = dropless.held_experts(x, experts, weights, w, w,
                                jnp.swapaxes(w, 1, 2), 0)
    assert (np.abs(np.asarray(out)).max(axis=1) > 0).all()
    assert dropless.route_counters(experts, 0, 8).tolist() == [
        24, 0, 1, 7, 0, 0, 0]


# ----------------- many small experts (PR 54: Qwen3-Next, top-10 of 512)
@pytest.mark.parametrize("held", [(0, 64), (448, 64), (100, 8), (0, 512)],
                         ids=lambda h: f"experts_{h[0]}_to_{h[0] + h[1] - 1}")
def test_dropless_top10_of_512_renormalised_over_every_pick(held):
    """Softmax over 512 outputs, the ten largest, their weights over the sum
    of ALL ten whether the expert is held here or not; the held experts' part
    is the sum over the held picks under those weights, and the counters
    split the 10 pairs a token into held and elsewhere."""
    first, count = held
    N, E, k, D, F = 96, 512, 10, 16, 8
    rng = jax.random.split(jax.random.PRNGKey(54), 5)
    logits = jax.random.normal(rng[0], (N, E)) * 2
    experts, weights = dropless.route(logits, k, scoring_func="softmax",
                                      norm_topk_prob=True)
    p = np.asarray(jax.nn.softmax(logits, -1), np.float64)
    want = np.argsort(-p, axis=1, kind="stable")[:, :k]
    np.testing.assert_array_equal(np.sort(np.asarray(experts), 1),
                                  np.sort(want, 1))
    picked = np.take_along_axis(p, np.asarray(experts), 1)
    np.testing.assert_allclose(np.asarray(weights),
                               picked / picked.sum(1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(1), 1.0, rtol=1e-5)
    x = jax.random.normal(rng[1], (N, D))
    gate = jax.random.normal(rng[2], (count, D, F)) * 0.3
    up = jax.random.normal(rng[3], (count, D, F)) * 0.3
    down = jax.random.normal(rng[4], (count, F, D)) * 0.3
    out = dropless.held_experts(x, experts, weights, gate, up, down, first)
    ref = np.zeros((N, D))
    for n in range(N):
        for e, w in zip(np.asarray(experts)[n], np.asarray(weights)[n]):
            if first <= e < first + count:
                i = int(e) - first
                ref[n] += float(w) * np.asarray(
                    (jax.nn.silu(x[n] @ gate[i]) * (x[n] @ up[i])) @ down[i])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)
    here = int(((np.asarray(experts) >= first)
                & (np.asarray(experts) < first + count)).sum())
    n = dropless.route_counters(experts, first, count).tolist()
    assert (n[0], n[1]) == (here, k * N - here)
    assert n[2] + n[3] == count and n[4] == int(
        (~((np.asarray(experts) >= first)
           & (np.asarray(experts) < first + count)).any(1)).sum())


# ------------------------- a router wider than its experts (PR 50: LongCat)
# ids past the real experts are ZERO-COMPUTE (identity) experts: in no group
# of ``held_experts``, their part the token's own input times the weight.
@pytest.mark.parametrize("case", ["ids_past_the_real_fall_in_no_group",
                                  "the_identity_part",
                                  "top12_of_768_with_a_bias"])
def test_dropless_zero_compute_experts(case):
    rng = jax.random.split(jax.random.PRNGKey(11), 6)
    if case == "top12_of_768_with_a_bias":
        # 512 real and 256 identity outputs, logits of spread 2, a bias of
        # the configuration's width: the pick is top-12 of scores + bias,
        # the weight the plain score times the factor
        N, W, k = 300, 768, 12
        logits = jax.random.normal(rng[0], (N, W)) * 2
        bias = jax.random.normal(rng[1], (W,)) * 3e-4
        experts, weights = dropless.route(
            logits, k, routed_scaling_factor=6.0, bias=bias)
        scores = np.asarray(jax.nn.softmax(logits, -1), np.float64)
        want = np.argsort(-(scores + np.asarray(bias, np.float64)),
                          axis=1, kind="stable")[:, :k]
        np.testing.assert_array_equal(np.sort(np.asarray(experts), 1),
                                      np.sort(want, 1))
        np.testing.assert_allclose(
            np.asarray(weights),
            6.0 * np.take_along_axis(scores, np.asarray(experts), 1),
            rtol=1e-5)
        plain, _ = dropless.route(logits, k, routed_scaling_factor=6.0)
        moved = np.mean([set(a) != set(b) for a, b in
                         zip(np.asarray(experts), np.asarray(plain))])
        assert 0.08 < moved < 0.35, moved      # the file says 18 %
        share = float((np.asarray(experts) >= 512).mean())
        assert 0.28 < share < 0.39             # a third of the outputs
        assert int(dropless.zero_pairs(experts, 512)) == \
            int((np.asarray(experts) >= 512).sum())
        return
    N, D, F, first, count, real, k = 20, 16, 24, 2, 3, 8, 4
    x = jax.random.normal(rng[0], (N, D))
    w = jax.random.normal(rng[1], (count, D, F)) * 0.3
    down = jax.random.normal(rng[2], (count, F, D)) * 0.3
    weights = jax.random.uniform(rng[3], (N, k)) + 0.5
    # every token: one held expert (3), one absent (6), two identity (9, 11)
    experts = jnp.tile(jnp.asarray([3, 6, 9, 11]), (N, 1))
    if case == "ids_past_the_real_fall_in_no_group":
        out = dropless.held_experts(x, experts, weights, w, w, down, first)
        alone = dropless.held_experts(x, experts[:, :1], weights[:, :1], w,
                                      w, down, first)
        np.testing.assert_allclose(out, alone, rtol=1e-5, atol=1e-5)
        assert (np.abs(np.asarray(out)).max(axis=1) > 0).all()
        # the counters tell the absent real expert's pair from the
        # identity experts' (which ``route_counters`` counts as not held)
        n = dropless.route_counters(experts, first, count).tolist()
        z = int(dropless.zero_pairs(experts, real))
        assert (n[0], n[1] - z, z) == (N, N, 2 * N)
        # all four ids past the held ones: nothing, exactly
        none = dropless.held_experts(x, experts + 8, weights, w, w, down,
                                     first)
        assert float(jnp.abs(none).max()) == 0.0
    else:
        zero = dropless.zero_experts(x, experts, weights, real)
        assert zero.dtype == jnp.float32 and zero.shape == (N, D)
        np.testing.assert_allclose(
            zero, np.asarray(x) * np.asarray(weights)[:, 2:].sum(
                1, keepdims=True), rtol=1e-6)
        # bfloat16 tokens: the product is still made in float32
        z16 = dropless.zero_experts(x.astype(jnp.bfloat16), experts, weights,
                                    real)
        assert z16.dtype == jnp.float32
        assert float(jnp.abs(dropless.zero_experts(
            x, experts, weights, 12)).max()) == 0.0      # no id that high
        live = jnp.arange(N) < 5
        assert int(dropless.zero_pairs(experts, real, live)) == 10


# ------------- the held pairs compacted to a static width (PR 57)
def uncompacted_held_experts(x, experts, weights, gate_w, up_w, down_w, first,
                             layer=None, act="silu"):
    """``moe/dropless.py::held_experts`` as it stood before the narrow branch
    came (PR 56's tree), operation for operation: a row for EVERY pair."""
    N, k = experts.shape
    act = dropless.activation(act)
    count = up_w.shape[-3]
    local = experts.reshape(-1) - first
    held = (local >= 0) & (local < count)
    local = jnp.where(held, local, count)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.zeros((count,), jnp.int32).at[local].add(1, mode="drop")
    if layer is not None:
        n = up_w.shape[0] * count
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n,), jnp.int32), sizes, (layer * count,))
        gate_w, up_w, down_w = (
            None if w is None else w.reshape((n,) + w.shape[2:])
            for w in (gate_w, up_w, down_w))
    rows = x[order // k]
    dt = x.dtype
    if gate_w is None:
        h = act(dropless.grouped_product(rows, up_w.astype(dt), sizes,
                                         transposed=True))
    else:
        h = act(dropless.grouped_product(rows, gate_w.astype(dt), sizes)) \
            * dropless.grouped_product(rows, up_w.astype(dt), sizes)
    out = dropless.grouped_product(h, down_w.astype(dt), sizes)
    back = jnp.zeros((N * k,), jnp.int32).at[order].set(
        jnp.arange(N * k, dtype=jnp.int32))
    out = out[back].reshape(N, k, -1).astype(jnp.float32)
    w = jnp.where(held.reshape(N, k), weights, 0.0)[..., None]
    return jnp.where(w != 0, out * w, 0.0).sum(axis=1).astype(dt)


def _loops_and_branches(fn, *args):
    from deepspeed_tpu.analysis.jaxpr_audit import iter_eqns
    names = [e.primitive.name
             for e, _ in iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr)]
    return names.count("while"), names.count("cond")


# 96 tokens of 4 picks over a router of 32 outputs, experts 8..11 held: 384
# pairs, 48 of them held if the routing is even, the narrow width twice that
# in whole tiles of rows = 128; the cases plant the count of held pairs
_PLANTED = {"none_held": 0, "even_share": 48, "exactly_C": 128,
            "C_plus_1": 129, "every_pair_held": 384}       # 0, 1, 1, 2, 3 slabs


def _planted_experts(n_held, N, k, first, count, width, seed):
    """(N, k) ids of which exactly ``n_held`` pairs (spread over the tokens,
    a token's picks distinct where its held picks number ``count`` or
    fewer) fall to ``first .. first + count - 1``."""
    rng = np.random.RandomState(seed)
    absent = [e for e in range(width) if not first <= e < first + count]
    flat = rng.choice(absent, size=N * k)
    at = rng.permutation(N * k)[:n_held]
    flat[at] = first + rng.randint(0, count, size=n_held)
    return jnp.asarray(flat.reshape(N, k), jnp.int32)


@pytest.mark.parametrize("case", [
    *_PLANTED,
    "non_gated_transposed", "non_gated_two_slabs", "merged_stack",
    "merged_stack_three_slabs", "one_pick_a_token", "ids_past_the_real_experts",
    "bfloat16", "under_the_threshold", "no_width_given", "over_half_the_width"])
def test_held_pairs_compacted_to_a_static_width(case, monkeypatch):
    """``held_experts`` with a narrow width against the form that lays out a
    row for every pair: equal within float32 rounding of a ``k``-term sum
    whether the held pairs fill one slab of the width or several (a token's
    pairs are summed a tile of token-sorted rows at a time and slab after
    slab, not in pair order), ONE loop over the slabs and no ``cond``; no
    loop at all, the same operations to the letter and the same bits, where
    the call keeps its whole-width path; ``route_counters`` says by the same
    rule which calls took one pass."""
    monkeypatch.setattr(dropless, "_COMPACT_MIN_PAIRS", 256)
    N, k, D, F, first, count, width = 96, 4, 16, 24, 8, 4, 32
    dtype, layer, gated, tol = jnp.float32, None, True, 2e-6
    n_held = _PLANTED.get(case, 48)
    if case == "non_gated_two_slabs":
        n_held = 200
    if case == "merged_stack_three_slabs":
        n_held = 384
    if case == "one_pick_a_token":
        N, k, n_held = 512, 1, 70               # 512 pairs, C = 128
    if case == "under_the_threshold":
        N = 64                                  # 256 pairs: one path
    if case == "over_half_the_width":
        width = 12                              # C = 256 of 384 pairs
    experts = _planted_experts(n_held, N, k, first, count, width, seed=57)
    if case == "ids_past_the_real_experts":     # LongCat's pad fill: width
        experts = experts.at[N // 2:].set(width)
    if case == "bfloat16":
        dtype, tol = jnp.bfloat16, 1e-2
    gated = not case.startswith("non_gated")
    if case.startswith("merged_stack"):
        layer = jnp.int32(1)
    key = jax.random.split(jax.random.PRNGKey(57), 5)
    lead = () if layer is None else (3,)
    x = jax.random.normal(key[0], (N, D)).astype(dtype)
    weights = jax.random.uniform(key[1], (N, k)) + 0.5
    w = lambda kk, *shape: jax.random.normal(kk, lead + shape) * 0.3
    gate = w(key[2], count, D, F) if gated else None
    up = w(key[3], count, D, F) if gated else w(key[3], count, F, D)
    down = w(key[4], count, F, D)
    kw = dict(layer=layer, act="silu" if gated else "relu2")
    given = None if case == "no_width_given" else width
    now = lambda *a: dropless.held_experts(*a, first, width=given, **kw)
    was = lambda *a: uncompacted_held_experts(*a, first, **kw)
    args = (x, experts, weights, gate, up, down)
    got, ref = jax.jit(now)(*args), jax.jit(was)(*args)
    assert got.dtype == ref.dtype == dtype
    C = dropless.compact_width(N, k, count, given)
    held_here = int(((experts >= first) & (experts < first + count)).sum())
    calls = dropless.route_counters(experts, first, count,
                                    width=given).tolist()[5:]
    if case in ("under_the_threshold", "no_width_given",
                "over_half_the_width"):
        assert C is None and calls == [0, 0]
        assert _loops_and_branches(now, *args) == (0, 0)
        bare = lambda *a: dropless._held_experts.__wrapped__(
            *a, first, C=None, **kw)            # under its own ``jax.jit``
        assert str(jax.make_jaxpr(bare)(*args)) == str(
            jax.make_jaxpr(was)(*args))
        assert bool((got == ref).all())
        return
    assert C == 128 and _loops_and_branches(now, *args) == (1, 0)
    if case != "ids_past_the_real_experts":
        assert held_here == n_held
    assert calls == ([0, 1] if held_here > C else [1, 0])
    err = float(jnp.abs(got.astype(jnp.float32)
                        - ref.astype(jnp.float32)).max())
    assert err <= tol * max(1.0, float(jnp.abs(ref).max())), err
    assert (float(jnp.abs(ref).max()) > 0.1) == (held_here > 0)


def test_call_counters_follow_a_family_that_cuts_a_long_prompt(monkeypatch):
    """``route_counters(rows=...)``: one call of ``held_experts`` every
    ``rows`` tokens (LongCat's ``_MOE_CHUNK``), the last chunk's pad rows in
    no group; each chunk is judged by its own count (``calls_whole``: its
    held pairs took more than one slab of the narrow width)."""
    monkeypatch.setattr(dropless, "_COMPACT_MIN_PAIRS", 256)
    first, count, width, k = 8, 4, 32, 4
    crowded = _planted_experts(300, 96, k, first, count, width, seed=1)
    sparse = _planted_experts(20, 96, k, first, count, width, seed=2)
    tail = _planted_experts(10, 40, k, first, count, width, seed=3)
    experts = jnp.concatenate([crowded, sparse, tail])
    n = dropless.route_counters(experts, first, count, width=width, rows=96)
    assert n.tolist()[5:] == [2, 1] and int(n[0]) == 330
    whole = dropless.route_counters(experts, first, count, width=width)
    assert whole.tolist()[5:] == [0, 1]         # 330 of 928 pairs, C = 256


@pytest.mark.parametrize("case", ["three_rows_a_token", "random", "one_token",
                                  "no_row_is_a_pair"])
def test_the_narrow_way_back_sums_each_tokens_rows(case):
    """``_sum_by_token`` over three tiles of 128 rows against a loop: tokens
    whose rows straddle a tile's edge (three rows a token: rows 126..128 are
    token 42's), tokens with no row, rows that are no pair's (token ``N``,
    weight 0, holding NaN: never read), every row one token's."""
    C, D, N = 384, 16, 200
    rng = np.random.RandomState(57)
    out = rng.randn(C, D).astype(np.float32)
    w = (rng.rand(C) + 0.5).astype(np.float32)
    if case == "three_rows_a_token":
        token = np.arange(C) // 3
        n_real = 300
    elif case == "random":
        token, n_real = rng.randint(0, N, size=C), 333
    elif case == "one_token":
        token, n_real = np.full((C,), 7), 128     # k <= 128 rows a token
    else:
        token, n_real = rng.randint(0, N, size=C), 0
    token = rng.permutation(token[:n_real])
    token = np.concatenate([token, np.full((C - n_real,), N)]).astype(np.int32)
    w[n_real:] = 0.0
    out[n_real:] = np.nan
    want = np.zeros((N, D))
    for j in range(n_real):
        want[token[j]] += float(w[j]) * out[j].astype(np.float64)
    got = jax.jit(dropless._sum_by_token, static_argnums=3)(
        jnp.asarray(out), jnp.asarray(w), jnp.asarray(token), N)
    assert got.shape == (N, D) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("cell, config, picks, held, width", [
    ("serve_batch_deepseek_v2", "deepseek-v2", "num_experts_per_tok", 20, 160),
    ("serve_longmix_trinity", "trinity-large-preview", "num_experts_per_tok",
     32, 256),
    ("serve_longanswer_nemotron3", "nemotron-3-nano-30b-a3b",
     "num_experts_per_tok", 32, 128),
    ("serve_agent_longcat", "longcat-flash-chat", "moe_topk", 16, 768),
    ("serve_longctx_qwen3next", "qwen3-next-80b-a3b", "num_experts_per_tok",
     64, 512)])
def test_a_decode_step_of_a_served_cell_keeps_one_path(cell, config, picks,
                                                       held, width):
    """Every slot's token through an expert layer is a call under the
    threshold: no narrow width, so no ``cond`` in a decode step and both
    call counters 0; the cell's longest prompt chunk has one."""
    import json
    import os
    root = os.path.join(os.path.dirname(__file__), "..", "benchmark")
    with open(os.path.join(root, "traffic", cell + ".json")) as f:
        slots = json.load(f)["serving"]["batch_slots"]
    with open(os.path.join(root, "configs", config + ".json")) as f:
        cfg = json.load(f)
    k = cfg[picks]
    assert cfg["experts_held"][1] == held
    assert slots * k <= dropless._COMPACT_MIN_PAIRS
    assert dropless.compact_width(slots, k, held, width) is None
    C = dropless.compact_width(2048, k, held, width)
    assert C is not None and C % 128 == 0 and 2 * C <= 2048 * k
    assert C >= 2 * 2048 * k * held / width
