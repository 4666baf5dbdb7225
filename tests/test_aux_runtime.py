"""Auxiliary runtime features: curriculum, PLD, eigenvalue, MoQ, sparse tensor.

Parity model: reference ``tests/unit/test_curriculum_learning.py``,
``test_pld.py``, and the MoQ/eigenvalue configs.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler import CurriculumScheduler
from deepspeed_tpu.runtime.progressive_layer_drop import ProgressiveLayerDrop
from deepspeed_tpu.runtime.eigenvalue import Eigenvalue
from deepspeed_tpu.runtime.quantize import Quantizer
from deepspeed_tpu.runtime.sparse_tensor import SparseTensor, sparse_allreduce
from deepspeed_tpu.parallel.mesh import make_mesh

from simple_model import SimpleModel, random_dataset, base_config


# ------------------------------------------------------------- curriculum
def test_curriculum_fixed_linear():
    sched = CurriculumScheduler({
        "curriculum_type": "seqlen", "min_difficulty": 8,
        "max_difficulty": 64, "schedule_type": "fixed_linear",
        "schedule_config": {"total_curriculum_step": 100, "difficulty_step": 8}})
    assert sched.update_difficulty(0) == 8
    mid = sched.update_difficulty(50)
    assert 8 < mid < 64 and mid % 8 == 0
    assert sched.update_difficulty(100) == 64
    assert sched.update_difficulty(500) == 64


def test_curriculum_fixed_root():
    sched = CurriculumScheduler({
        "curriculum_type": "seqlen", "min_difficulty": 8,
        "max_difficulty": 64, "schedule_type": "fixed_root",
        "schedule_config": {"total_curriculum_step": 100, "difficulty_step": 8,
                            "root_degree": 2}})
    # sqrt schedule grows faster early than linear
    lin = CurriculumScheduler({
        "curriculum_type": "seqlen", "min_difficulty": 8,
        "max_difficulty": 64, "schedule_type": "fixed_linear",
        "schedule_config": {"total_curriculum_step": 100, "difficulty_step": 8}})
    assert sched.get_difficulty(25) >= lin.get_difficulty(25)


def test_curriculum_fixed_discrete():
    sched = CurriculumScheduler({
        "curriculum_type": "seqlen", "min_difficulty": 1,
        "max_difficulty": 3, "schedule_type": "fixed_discrete",
        "schedule_config": {"difficulty": [1, 2, 3], "max_step": [5, 10]}})
    assert sched.get_difficulty(3) == 1
    assert sched.get_difficulty(7) == 2
    assert sched.get_difficulty(11) == 3


@pytest.mark.slow   # compile-heavy; fast tier stays inside the driver budget (conftest)
def test_curriculum_engine_crops_batch(devices):
    """Engine crops token batches to the scheduled seqlen (the jitted step
    retraces per difficulty exactly as the reference recompiles)."""
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
    model = GPT2(GPT2Config(vocab_size=64, max_seq=32, n_embd=32, n_layer=1,
                            n_head=2, embd_pdrop=0, attn_pdrop=0,
                            resid_pdrop=0, attention_impl="jnp"),
                 dtype=jnp.float32)
    cfg = base_config(micro=2, over={
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "curriculum_learning": {
            "enabled": True, "curriculum_type": "seqlen",
            "min_difficulty": 8, "max_difficulty": 16,
            "schedule_type": "fixed_linear",
            "schedule_config": {"total_curriculum_step": 4,
                                "difficulty_step": 8}},
    })
    tokens = np.random.default_rng(0).integers(0, 64, (64, 17)).astype(np.int32)
    engine, _, _, _ = ds.initialize(config=cfg, model=model,
                                    training_data=(tokens,),
                                    mesh=make_mesh({"data": 8}))
    engine.train_batch()
    assert engine.curriculum_seqlen() == 8
    for _ in range(5):
        engine.train_batch()
    assert engine.curriculum_seqlen() == 16


# -------------------------------------------------------------------- PLD
def test_pld_theta_schedule():
    pld = ProgressiveLayerDrop(theta=0.5, gamma=0.01)
    assert pld.get_theta() == 1.0
    t10 = pld.update_state(10)
    t1000 = pld.update_state(1000)
    assert t10 > t1000 >= 0.5
    assert abs(t1000 - 0.5) < 1e-3
    assert pld.get_state()["progressive_layer_drop"] is True


def test_pld_engine_integration(devices):
    model = SimpleModel(dim=8)
    cfg = base_config(micro=4, over={
        "progressive_layer_drop": {"enabled": True, "theta": 0.5,
                                   "gamma": 0.1}})
    engine, _, _, _ = ds.initialize(config=cfg, model=model,
                                    training_data=random_dataset(n=64),
                                    mesh=make_mesh({"data": 8}))
    for _ in range(3):
        engine.train_batch()
    assert engine.progressive_layer_drop.get_theta() < 1.0


# -------------------------------------------------------------- eigenvalue
def test_eigenvalue_quadratic_exact():
    """For loss = ½ xᵀ A x the Hessian is A; power iteration must find its
    largest eigenvalue."""
    A = jnp.diag(jnp.asarray([4.0, 1.0, 0.5]))

    def loss(p):
        return 0.5 * p["x"] @ A @ p["x"]

    ev = Eigenvalue(max_iter=100, tol=1e-4, layer_name="x", layer_num=1)
    val = ev.compute_eigenvalue(loss, {"x": jnp.ones((3,))}, layerwise=False)
    np.testing.assert_allclose(val, 4.0, rtol=1e-2)


def test_eigenvalue_layerwise_stacked():
    """Stacked-block mode: per-layer eigenvalues of independent quadratics,
    post-processed to [0, 1] with the max at 1.0."""
    scales = jnp.asarray([1.0, 2.0, 8.0])

    def loss(p):
        # layer i: 0.5 * s_i * ||w_i||²  → Hessian eigenvalue s_i
        return 0.5 * jnp.sum(scales[:, None] * p["w"] ** 2)

    ev = Eigenvalue(max_iter=50, tol=1e-3, layer_name="w", layer_num=3)
    vals = ev.compute_eigenvalue(loss, {"w": jnp.ones((3, 4))}, layerwise=True)
    np.testing.assert_allclose(vals, [1.0 / 8.0, 2.0 / 8.0, 1.0], rtol=5e-2)


# -------------------------------------------------------------------- MoQ
def test_quantizer_bit_schedule():
    q = Quantizer(q_target_bits=8, q_start_bits=10, q_period=10, q_offset=0,
                  layer_num=0)
    x = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(16, 16)),
                          jnp.float32)}
    bits_seen = set()
    for _ in range(8):
        x = q.quantize(x)
        bits_seen.add(q.q_start_bits[0])
    assert min(bits_seen) == 8  # reached target
    assert q.q_start_bits[0] == 8


def test_quantizer_quantizes_values():
    q = Quantizer(q_target_bits=4, q_start_bits=4, q_period=1, q_offset=0)
    w = jnp.asarray(np.linspace(-1, 1, 64, dtype=np.float32).reshape(8, 8))
    out = q.quantize({"w": w})["w"]
    # 4-bit symmetric → at most 16 distinct levels
    assert len(np.unique(np.asarray(out))) <= 16
    # 1-D params untouched (reference quantizes only 2-D+)
    b = jnp.ones((8,))
    assert q.quantize({"b": b})["b"] is b


def test_quantizer_offset_warmup():
    q = Quantizer(q_target_bits=8, q_start_bits=16, q_period=10, q_offset=100)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(8, 8)), jnp.float32)
    out = q.quantize({"w": w})["w"]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(w))  # no-op yet


# ------------------------------------------------------------ sparse tensor
def test_sparse_tensor_roundtrip():
    dense = np.zeros((10, 4), np.float32)
    dense[2] = 1.0
    dense[7] = 3.0
    st = SparseTensor.from_dense(jnp.asarray(dense))
    np.testing.assert_allclose(np.asarray(st.to_dense()), dense)
    both = st.add(st)
    np.testing.assert_allclose(np.asarray(both.to_dense()), 2 * dense)


def test_sparse_allreduce(devices):
    mesh = make_mesh({"data": 8})
    dense_size = (16, 4)

    def per_rank(vals, idx):
        st = SparseTensor(idx, vals, dense_size)
        out = sparse_allreduce(st, "data")
        return out.to_dense()

    rng = np.random.default_rng(0)
    vals = rng.normal(size=(8, 2, 4)).astype(np.float32)
    idx = rng.integers(0, 16, (8, 2)).astype(np.int32)
    fn = jax.shard_map(per_rank, mesh=mesh,
                       in_specs=(P("data"), P("data")),
                       out_specs=P("data"), check_vma=False)
    with jax.set_mesh(mesh):
        out = np.asarray(fn(vals.reshape(16, 4), idx.reshape(16,)))
    # every rank's dense result equals the mean of all ranks' dense grads
    expected = np.zeros(dense_size, np.float32)
    for r in range(8):
        for j in range(2):
            expected[idx[r, j]] += vals[r, j] / 8
    np.testing.assert_allclose(out[:16], expected, rtol=1e-5, atol=1e-6)


def test_weight_quantization_class():
    import jax, jax.numpy as jnp, numpy as np
    from deepspeed_tpu.runtime.weight_quantizer import WeightQuantization
    params = {"w": jnp.asarray(np.random.RandomState(0).randn(64, 128),
                               jnp.float32),
              "b": jnp.zeros((128,), jnp.float32)}
    wq = WeightQuantization(mlp_extra_grouping=True)
    qp, stats = wq.model_quantize(params, groups=2)
    assert qp["w"]["q"].dtype == jnp.int8
    assert qp["b"].dtype == jnp.float32  # small 1-D stays fp
    deq = WeightQuantization.dequantize(qp, jnp.float32)
    err = np.abs(np.asarray(deq["w"]) - np.asarray(params["w"])).max()
    assert err < np.abs(np.asarray(params["w"])).max() / 50


def test_debug_name_maps():
    import jax.numpy as jnp
    from deepspeed_tpu.utils import debug
    params = {"layer": {"w": jnp.ones((2, 2))}}
    names = debug.build_param_names(params)
    key = next(iter(names))
    assert "layer" in key and "w" in key
    leaf = names[key]
    assert "shape=(2, 2)" in debug.debug_param2name_id_shape(leaf)
