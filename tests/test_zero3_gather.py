"""ZeRO-3's fetch as the compiled step shows it (``zero/partition.py``
``gather_layer`` / ``shard_stream``, called by ``models/gpt2.py``): the
collective census of the step's HLO (``analysis/comms.py``) on the CPU
mesh, where the partitioner left alone moves activations, and the
numbers a sharded run must share with an unsharded one."""

import itertools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.analysis.comms import _param_shaped, step_collectives
from deepspeed_tpu.analysis.jaxpr_audit import census_from_hlo_text
from deepspeed_tpu.models import build
from deepspeed_tpu.monitor import gauges
from deepspeed_tpu.parallel.mesh import dp_world_size, make_mesh
from deepspeed_tpu.runtime.zero import partition as zpart

D, T, L, V = 256, 128, 2, 512       # no two of them equal: shapes tell apart
BLOCK_MATRICES = {(D, 3 * D), (D, D), (D, 4 * D), (4 * D, D)}


def engine_for(devices, axes, stage, tmp_path, dtype=jnp.bfloat16, **model):
    n = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, devices=devices[:n])
    kw = dict(n_embd=D, n_head=4, n_layer=L, max_seq=T, vocab_size=V,
              remat=True, remat_policy="names:attn_out,mlp_fc",
              loss_chunk=4 * T, embd_pdrop=0.0, attn_pdrop=0.0, resid_pdrop=0.0)
    kw.update(model)
    config = {
        "train_micro_batch_size_per_gpu": 4 // dp_world_size(mesh),
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "gradient_clipping": 1.0,
        "bf16": {"enabled": dtype == jnp.bfloat16},
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-3, "weight_decay": 0.1}},
        "zero_optimization": {"stage": stage,
                              "stage3_param_persistence_threshold": 4 * D},
        "compile_cache": {"dir": str(tmp_path / "aot")},
    }
    engine, _, _, _ = ds.initialize(
        config=config, model=build("gpt2-125m", dtype=dtype, **kw),
        mesh=mesh, rng_seed=0)
    return engine


def token_batches():
    batch = np.random.default_rng(0).integers(
        0, V, size=(4, T + 1)).astype(np.int32)
    return itertools.repeat(batch)


def step_census(engine):
    engine.train_batch(token_batches())
    exe = gauges.latest_executable(engine._jit_train_step)
    return census_from_hlo_text(exe.as_text()), exe


def squeezed(dims):
    return tuple(d for d in dims if d != 1)


def test_zero3_step_gathers_weights_inside_the_scan(devices, tmp_path):
    """fsdp=4, ZeRO-3, one sequence a device: wide enough that the CPU's
    partitioner, left alone, all-reduces activations and logits."""
    engine = engine_for(devices, {"fsdp": 4}, 3, tmp_path)
    census, exe = step_census(engine)
    params = engine.state.master
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))

    # nothing whose payload carries batch x T beside a feature dim
    for e in census:
        for dims, _ in e.shapes:
            tokens = any(dims[i:i + 2] == (4, T) for i in range(len(dims)))
            assert not (tokens and int(np.prod(dims)) > 4 * T), (e.op, dims)
    # each block matrix is gathered in the forward AND the backward body
    loops = {}
    for e in census:
        for dims, _ in e.shapes:
            if e.kind == "all_gather" and e.loop is not None \
                    and squeezed(dims) in BLOCK_MATRICES:
                assert e.trips == L
                loops.setdefault(squeezed(dims), set()).add(e.loop)
    assert set(loops) == BLOCK_MATRICES, loops
    assert all(len(bodies) == 2 for bodies in loops.values()), loops

    # ZeRO-3's volume: the parameters gathered twice (forward, remat +
    # backward) and reduced once, vectors and scalars besides; the CPU
    # moves bf16 as f32
    report = engine.compile_report()["collectives"]["DeepSpeedEngine.train_step"]
    shapes = {np.shape(p) for p in jax.tree_util.tree_leaves(params)}
    assert report == step_collectives(census, shapes)
    assert report["all_gather_bytes"] <= 2.1 * 4 * n_params
    assert report["reduce_bytes"] <= 1.1 * 4 * n_params
    assert report["other_bytes"] == 0
    # what has no parameter's shape: token ids, and a weight gradient
    # the CPU reduces transposed
    transposed = {m[::-1] for m in BLOCK_MATRICES}
    odd = [(dtype, dims) for e in census
           for dtype, (dims, _) in zip(e.dtypes, e.shapes)
           if not _param_shaped(dims, shapes)]
    assert all(dtype == "s32" or squeezed(dims) in transposed
               for dtype, dims in odd), odd
    assert report["non_param_bytes"] == sum(
        e.trips * nbytes for e in census for dims, nbytes in e.shapes
        if not _param_shaped(dims, shapes))

    # the gathered weights are no residual of the forward scan: the
    # executable's temporaries stay under one layer's whole weights plus
    # what the remat policy keeps (an f32 stack of L gathered layers
    # would be L * 12 D^2 * 4 bytes more)
    ma = exe.memory_analysis()
    one_layer = 12 * D * D * 4
    kept = L * (T * 6 * D) * 4 + 3 * T * V * 4      # names policy; logits
    assert ma.temp_size_in_bytes <= 4 * one_layer + 4 * kept, ma
    engine.close()


@pytest.mark.parametrize("axes,stage", [({"fsdp": 4}, 1), ({"fsdp": 4}, 2),
                                        ({"data": 1}, 3)])
def test_fetch_is_the_identity_below_stage3_and_on_one_device(
        devices, tmp_path, monkeypatch, axes, stage):
    """Where no leaf is fsdp-sharded the constraints restate what is:
    the step's census equals that of a program without them."""
    engine = engine_for(devices, axes, stage, tmp_path / "a")
    with_fetch, _ = step_census(engine)
    engine.close()
    monkeypatch.setattr(zpart, "gather_layer", lambda params, specs: params)
    monkeypatch.setattr(zpart, "shard_stream", lambda x: x)
    engine = engine_for(devices, axes, stage, tmp_path / "b")
    without, _ = step_census(engine)
    engine.close()
    key = lambda c: sorted((e.kind, e.shapes, e.trips) for e in c)
    assert key(with_fetch) == key(without)
    if axes == {"data": 1}:
        assert with_fetch == []


def two_steps(devices, axes, stage, tmp_path, **model):
    engine = engine_for(devices, axes, stage, tmp_path, dtype=jnp.float32,
                        **model)
    data = token_batches()
    seen = []
    for _ in range(2):
        seen.append(float(engine.train_batch(data)))
        # Adam hides a gradient scaled wrongly; its norm does not
        seen.append(float(engine._last_metrics["grad_norm"]))
    params = jax.tree_util.tree_map(np.asarray, engine.state.params)
    exe = gauges.latest_executable(engine._jit_train_step)
    census = census_from_hlo_text(exe.as_text())
    engine.close()
    return seen, params, census


@pytest.mark.parametrize("axes,remat,unroll", [
    ({"fsdp": 4}, True, False), ({"fsdp": 4}, False, False),
    ({"fsdp": 4}, True, True), ({"fsdp": 4}, False, True),
    ({"fsdp": 2, "tensor": 2}, True, False)])
def test_zero3_matches_one_device_and_plain_data_parallel(
        devices, tmp_path, axes, remat, unroll):
    """Loss, gradient norm and updated parameters of two steps: ZeRO-3 over fsdp (and
    over fsdp x tensor, the tensor-parallel entry of a layer's spec kept)
    against one device and against ZeRO-0 on data=4."""
    model = dict(remat=remat, unroll_layers=unroll)
    ref_losses, ref, _ = two_steps(devices, {"data": 1}, 0,
                                   tmp_path / "one", **model)
    for name, ax, stage in (("dp", {"data": 4}, 0), ("z3", axes, 3)):
        losses, params, census = two_steps(devices, ax, stage,
                                           tmp_path / name, **model)
        np.testing.assert_allclose(losses, ref_losses, rtol=2e-4,
                                   err_msg=f"{name} {ax}")
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(ref)):
            # two AdamW steps move a weight by up to 2 lr = 2e-3, and
            # Adam turns the rounding of a near-zero gradient into a
            # visible step: a few elements in a million, so the mean too
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                       err_msg=f"{name} {ax}")
            assert np.abs(a - b).mean() < 2e-6, (name, ax)
    if "tensor" in axes:
        # the z3 step gathers a layer over fsdp alone: qkv_w arrives as a
        # device's tensor-parallel half, never whole
        gathered = {squeezed(dims) for e in census if e.kind == "all_gather"
                    and e.loop is not None for dims, _ in e.shapes}
        assert (D, 3 * D // 2) in gathered and (D, 3 * D) not in gathered, \
            gathered
