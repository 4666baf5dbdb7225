"""Model-family tests: BERT encoder + GPT-2 MoE.

Parity model: reference vendored-model numerics tests
(``tests/unit/modeling.py`` BERT, ``tests/unit/test_moe.py``) — tiny
presets trained a few steps, loss decreases, TP/EP specs resolve.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.models import build
from deepspeed_tpu.models.bert import Bert
from deepspeed_tpu.models.gpt2_moe import GPT2MoE
from deepspeed_tpu.parallel.mesh import make_mesh

from simple_model import base_config


def test_build_factory_knows_all_families():
    assert build("bert-tiny", dtype=jnp.float32).config.n_layer == 4
    assert build("gpt2-tiny").config.n_layer == 4
    assert build("gpt2-moe-tiny").config.num_experts == 4
    with pytest.raises(ValueError):
        build("nope-7b")


def _mlm_batch(rng, B=8, T=32, V=1024):
    ids = rng.randint(0, V, size=(B, T)).astype(np.int32)
    labels = np.full((B, T), -100, np.int32)
    mask_pos = rng.rand(B, T) < 0.15
    labels[mask_pos] = ids[mask_pos]
    attn = np.ones((B, T), np.int32)
    attn[:, T - 4:] = 0  # padding tail
    return {"input_ids": ids, "labels": labels, "attention_mask": attn}


def test_bert_forward_shapes_and_mask():
    model = Bert(preset="bert-tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = _mlm_batch(rng)
    hidden = model.apply(params, batch["input_ids"],
                         attention_mask=batch["attention_mask"])
    assert hidden.shape == (8, 32, 128)
    logits = model.mlm_logits(params, hidden)
    assert logits.shape == (8, 32, 1024)
    # masked positions cannot attend: changing a padded token's id must not
    # change unpadded outputs
    ids2 = batch["input_ids"].copy()
    ids2[:, -1] = (ids2[:, -1] + 1) % 1024
    h2 = model.apply(params, ids2, attention_mask=batch["attention_mask"])
    np.testing.assert_allclose(np.asarray(hidden[:, :28]),
                               np.asarray(h2[:, :28]), atol=1e-5)


@pytest.mark.slow   # compile-heavy; fast tier stays inside the driver budget (conftest)
def test_bert_mlm_training_loss_decreases(devices):
    model = Bert(preset="bert-tiny", dtype=jnp.float32)
    rng = np.random.RandomState(1)
    batches = [_mlm_batch(rng) for _ in range(12)]
    engine, _, _, _ = ds.initialize(
        config=base_config(micro=1, over={
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}),
        model=model, mesh=make_mesh({"data": 8}))
    losses = [float(engine.train_batch(iter([b]))) for b in batches]
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_bert_ignore_index_loss():
    model = Bert(preset="bert-tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    b = _mlm_batch(rng)
    # all labels ignored → loss well-defined (0 via safe denom)
    b_ignored = dict(b, labels=np.full_like(b["labels"], -100))
    loss = float(model.loss(params, b_ignored, jax.random.PRNGKey(0)))
    assert np.isfinite(loss)


def test_bert_num_params_matches_tree():
    model = Bert(preset="bert-tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    actual = sum(int(np.prod(np.shape(l) or (1,)))
                 for l in jax.tree_util.tree_leaves(params))
    assert model.num_params() == actual


def test_bert_tp_specs_cover_params():
    model = Bert(preset="bert-tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    specs = model.partition_specs(params)
    # same tree structure
    jax.tree_util.tree_map(lambda p, s: None, params, specs,
                           is_leaf=lambda x: isinstance(
                               x, jax.sharding.PartitionSpec))


def test_build_rotary_families():
    gj = build("gptj-tiny", dtype=jnp.float32)
    nx = build("gptneox-tiny", dtype=jnp.float32)
    assert gj.config.neox_style is False and nx.config.neox_style is True
    assert nx.config.dual_layernorm and nx.config.qkv_bias


def test_rotary_embedding_properties():
    from deepspeed_tpu.models.rotary import rotary_freqs, apply_rotary_pos_emb
    cos, sin = rotary_freqs(16, 64)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 4, 32), jnp.float32)
    for style in (True, False):
        out = apply_rotary_pos_emb(x, cos, sin, jnp.arange(8), style)
        assert out.shape == x.shape
        # rotation preserves the norm of the rotated feature block
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(out[..., :16]), axis=-1),
            np.linalg.norm(np.asarray(x[..., :16]), axis=-1), rtol=1e-5)
        # features beyond rotary_dim pass through untouched
        np.testing.assert_array_equal(np.asarray(out[..., 16:]),
                                      np.asarray(x[..., 16:]))
        # position 0 is the identity rotation
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(x[:, 0]), rtol=1e-6)


@pytest.mark.slow   # compile-heavy; fast tier stays inside the driver budget (conftest)
def test_gptj_trains(devices):
    model = build("gptj-tiny", dtype=jnp.float32)
    rng = np.random.RandomState(5)
    fixed = rng.randint(0, 1024, size=(8, 33)).astype(np.int32)
    engine, _, _, _ = ds.initialize(
        config=base_config(micro=1, over={
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}),
        model=model, mesh=make_mesh({"data": 8}))
    losses = [float(engine.train_batch(iter([fixed]))) for _ in range(10)]
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_gptneox_tp_specs_cover_params():
    model = build("gptneox-tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    specs = model.partition_specs(params)
    jax.tree_util.tree_map(lambda p, s: None, params, specs,
                           is_leaf=lambda x: isinstance(
                               x, jax.sharding.PartitionSpec))


def test_gpt2_moe_alternating_layers():
    model = GPT2MoE(preset="gpt2-moe-tiny", dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    kinds = ["moe" if "moe" in l else "ffn" for l in params["layers"]]
    assert kinds == ["ffn", "moe", "ffn", "moe"]


@pytest.mark.slow
def test_gpt2_moe_trains_and_uses_aux_loss(devices):
    model = GPT2MoE(preset="gpt2-moe-tiny", dtype=jnp.float32,
                    embd_pdrop=0.0, attn_pdrop=0.0, resid_pdrop=0.0)
    rng = np.random.RandomState(3)
    fixed = rng.randint(0, 1024, size=(8, 33)).astype(np.int32)
    engine, _, _, _ = ds.initialize(
        config=base_config(micro=2, over={
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}),
        model=model, mesh=make_mesh({"data": 2, "expert": 4}))
    # memorize one fixed batch — loss must drop monotonically-ish
    losses = [float(engine.train_batch(iter([fixed]))) for _ in range(12)]
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_gpt2_moe_aux_loss_contributes():
    m0 = GPT2MoE(preset="gpt2-moe-tiny", dtype=jnp.float32, aux_loss_coef=0.0)
    m1 = GPT2MoE(preset="gpt2-moe-tiny", dtype=jnp.float32, aux_loss_coef=1.0)
    params = m0.init(jax.random.PRNGKey(0))
    toks = np.random.RandomState(4).randint(0, 1024, size=(2, 17)).astype(np.int32)
    l0 = float(m0.loss(params, toks, jax.random.PRNGKey(1)))
    l1 = float(m1.loss(params, toks, jax.random.PRNGKey(1)))
    assert l1 > l0  # aux loss is strictly positive with random gating


def test_cifar_cnn_trains(devices):
    from deepspeed_tpu.models.cifar import CifarCNN
    model = CifarCNN(preset="cifar-cnn-tiny")
    rng = np.random.RandomState(9)
    images = rng.rand(64, 32, 32, 3).astype(np.float32)
    score = images[:, :8, :8].mean((1, 2, 3))
    labels = (np.argsort(np.argsort(score)) * 10 // len(score)).astype(np.int32)
    engine, _, _, _ = ds.initialize(
        config=base_config(micro=8, over={
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}),
        model=model, training_data=(images, labels),
        mesh=make_mesh({"data": 8}))
    losses = [float(engine.train_batch()) for _ in range(15)]
    assert np.mean(losses[-3:]) < np.mean(losses[:3])
    acc = float(model.accuracy(engine.state.params, images, labels))
    assert acc > 0.2  # well above chance after a few steps


@pytest.mark.slow
def test_gptj_flash_attention_matches_jnp():
    """Verdict #4: rotary models get the fast path — flash on pre-rotated
    q/k must reproduce the jnp attention logits, fwd AND grad."""
    import jax
    mj = build("gptj-tiny", dtype=jnp.float32, attention_impl="jnp")
    mf = build("gptj-tiny", dtype=jnp.float32, attention_impl="flash")
    params = mj.init(jax.random.PRNGKey(0))
    ids = np.random.RandomState(0).randint(0, 1024, (2, 32)).astype(np.int32)
    lj = np.asarray(mj.apply(params, jnp.asarray(ids)))
    lf = np.asarray(mf.apply(params, jnp.asarray(ids)))
    np.testing.assert_allclose(lf, lj, atol=2e-4, rtol=2e-4)

    batch = jnp.asarray(np.random.RandomState(1).randint(
        0, 1024, (2, 33)).astype(np.int32))
    gj = jax.grad(lambda p: mj.loss(p, batch, jax.random.PRNGKey(2)))(params)
    gf = jax.grad(lambda p: mf.loss(p, batch, jax.random.PRNGKey(2)))(params)
    for a, b in zip(jax.tree_util.tree_leaves(gj),
                    jax.tree_util.tree_leaves(gf)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=5e-4, rtol=5e-3)


@pytest.mark.slow
def test_gptneox_flash_trains(devices):
    """NeoX (partial-rotary, dual-LN) trains through the flash path."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel.mesh import make_mesh
    model = build("gptneox-tiny", dtype=jnp.float32, attention_impl="flash")
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 1024, size=(64, 33)).astype(np.int32)
    engine, _, _, _ = ds.initialize(
        config={"train_micro_batch_size_per_gpu": 4, "steps_per_print": 1000,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}},
        model=model, training_data=(tokens,), mesh=make_mesh({"data": 8}))
    losses = [float(engine.train_batch()) for _ in range(8)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_gptj_unrolled_matches_scanned():
    """unroll_layers parity for the rotary family: forward AND cache decode
    match the scanned path."""
    import jax
    ms = build("gptj-tiny", dtype=jnp.float32, attention_impl="jnp")
    mu = build("gptj-tiny", dtype=jnp.float32, attention_impl="jnp",
               unroll_layers=True)
    params = ms.init(jax.random.PRNGKey(0))
    ids = np.random.RandomState(0).randint(0, 1024, (2, 16)).astype(np.int32)
    np.testing.assert_allclose(
        np.asarray(ms.apply(params, jnp.asarray(ids))),
        np.asarray(mu.apply(params, jnp.asarray(ids))),
        atol=1e-5, rtol=1e-5)
    c1, c2 = ms.init_cache(2, 20), mu.init_cache(2, 20)
    l1, c1 = ms.apply_with_cache(params, jnp.asarray(ids), c1)
    l2, c2 = mu.apply_with_cache(params, jnp.asarray(ids), c2)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=1e-5, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(c1),
                    jax.tree_util.tree_leaves(c2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("fwd_len,t_real", [(1, 1), (8, 5), (6, 6)])
def test_gpt2_prefill_paged_matches_apply(fwd_len, t_real):
    """``GPT2.prefill_paged`` on one prompt against ``apply``: the logits at
    the prompt's last token, and the pool's rows against the K/V the cached
    forward computed (its cache is seq-major, ``(L, S, B, H, hd)``).  The
    forward is 1 token long (one row of K/V padded to the block), a whole
    bucket, and shorter than its bucket."""
    from deepspeed_tpu.inference import paged_kv as pk
    m = build("gpt2-tiny", dtype=jnp.float32, attention_impl="jnp")
    c = m.config
    params = m.init(jax.random.PRNGKey(0))
    bs, nb = 4, 2
    toks = np.random.RandomState(3).randint(
        0, c.vocab_size, (1, fwd_len)).astype(np.int32)
    pool = m.init_serving_state(1, 1 + nb, bs)
    blocks = jnp.asarray([2, 1], jnp.int32)
    row, pool = m.prefill_paged(params, jnp.asarray(toks), pool, blocks, 0,
                                t_real)
    want = m.apply(params, jnp.asarray(toks))
    np.testing.assert_allclose(np.asarray(row), np.asarray(want)[:, t_real - 1],
                               atol=1e-5, rtol=1e-5)
    _, cache = m.apply_with_cache(params, jnp.asarray(toks),
                                  m.init_cache(1, fwd_len))
    for layer in range(c.n_layer):
        rows = pk.gather_kv(pool, layer, blocks[None], jnp.float32, c.n_head)
        for name, got in zip(("k", "v"), rows):
            np.testing.assert_allclose(
                np.asarray(got)[0, :fwd_len],
                np.asarray(cache[name])[layer, :, 0], atol=1e-6)
            assert not np.asarray(got)[0, fwd_len:].any()   # the pad rows
