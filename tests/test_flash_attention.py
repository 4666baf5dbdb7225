"""Flash attention numerics vs pure-jnp oracle (interpret mode on CPU).

Parity model: reference ``tests/unit/test_cuda_forward/backward.py`` — kernel
output vs dense reference with atol sweeps.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.flash_attention import (
    flash_attention, flash_attention_with_lse, attention_reference)


def make_qkv(B=2, T=128, H=2, d=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, T, H, d)
    q = jax.random.normal(ks[0], shape, dtype)
    k = jax.random.normal(ks[1], shape, dtype)
    v = jax.random.normal(ks[2], shape, dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = make_qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_forward_uneven_blocks():
    # T not a multiple of the block size exercises the padded tail path
    q, k, v = make_qkv(T=96)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_backward_matches_reference(causal):
    q, k, v = make_qkv(B=1, T=64, H=2, d=16)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(
            flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(attention_reference(q, k, v, causal=causal)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name} mismatch")


def test_bf16_forward_close():
    q, k, v = make_qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = attention_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), atol=3e-2, rtol=3e-2)


def test_single_block():
    q, k, v = make_qkv(T=32)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_auto_blocks_heuristic():
    """v5e-measured policy: large SQUARE blocks (end-to-end MFU beats the
    tall-q microbench winner — see _auto_blocks NOTE); halved caps for
    wide heads (VMEM).  The 1024-block figure was measured at T = 4096.
    Since PR 37 a block no longer bounds what the causal mask skips: the
    kernels walk a resident block in 256-tiles (``test_tile_plan``), so
    these values amortise the grid step and nothing else.  256 was chosen
    on the chip for both head sizes (the three kernels alone, ms a layer,
    parent -> 128 / 256 / 512-tiles): hd 64, T 1024, 80 heads 1.468 ->
    1.113 / 1.035 / 1.134; hd 128, T 2048, 16 heads 0.919 -> 0.876 / 0.873
    / 0.896 (PERF.md §6, PR 37)."""
    from deepspeed_tpu.ops.transformer.flash_attention import _auto_blocks
    assert _auto_blocks(512, 64, None, None) == (512, 512)
    assert _auto_blocks(1024, 64, None, None) == (1024, 1024)
    assert _auto_blocks(4096, 64, None, None) == (1024, 1024)
    assert _auto_blocks(4096, 128, None, None) == (512, 512)
    # explicit overrides pass through
    assert _auto_blocks(4096, 64, 256, 128) == (256, 128)


def test_dma_slot_walk_unroll_bounded():
    """Dense layouts make num_k_blocks = T/block_k large (T=8k, block=128
    -> 64 slots); full unroll there emits the whole softmax body per slot
    and blows Mosaic compile time.  The walk fully unrolls only below the
    threshold and falls back to ring-depth unrolling above it (slot
    rotation still static per unrolled group)."""
    from deepspeed_tpu.ops.transformer.flash_attention import (
        _FULL_UNROLL_MAX_K_BLOCKS, _N_KV_BUF, _slot_walk_unroll)
    assert _slot_walk_unroll(1) is True
    assert _slot_walk_unroll(_FULL_UNROLL_MAX_K_BLOCKS) is True
    assert _slot_walk_unroll(_FULL_UNROLL_MAX_K_BLOCKS + 1) == _N_KV_BUF
    assert _slot_walk_unroll(64) == _N_KV_BUF
    # the bounded unroll must divide into the ring without aliasing a
    # live slot: ring depth itself is the safe group size
    assert _N_KV_BUF >= 2


# ------------------------------------------------------ the causal tile walk
def _plan_tiles(plan):
    """``(q_tile, k_tile, masked)`` of every tile a plan visits."""
    for r, (lo, hi, masked) in enumerate(plan):
        for i in range(lo, hi):
            yield r, i, False
        if masked is not None:
            yield r, masked, True


@pytest.mark.parametrize("block,tile,diagonal,counts", [
    (1024, 256, True, (10, 4, 16)),
    (1024, 256, False, (16, 0, 16)),
    (1024, 128, True, (36, 8, 64)),
    (1024, 512, True, (3, 2, 4)),
    (512, 256, True, (3, 2, 4)),
    (512, 256, False, (4, 0, 4)),
    (256, 256, True, (1, 1, 1)),
    (256, 256, False, (1, 0, 1)),
    (384, None, True, (1, 1, 1)),         # the trivial plan
])
def test_tile_plan(block, tile, diagonal, counts):
    """The plan as a pure function: every (q, k) position at or under the
    diagonal lies in exactly one visited tile, none above it in an unmasked
    one, and a masked tile is one the diagonal crosses.  On a diagonal
    block each row's tiles are contiguous and end in the masked one, which
    is what lets the kernels run a row as one product (``_strips``)."""
    from deepspeed_tpu.ops.transformer.flash_attention import (
        _tile_plan, _plan_counts, _strips)
    plan = _tile_plan(block, block, tile, diagonal)
    assert _plan_counts(plan) == counts
    edge = tile or block
    n = block // edge
    seen = np.zeros((n, n), int)
    for a, b, masked in _plan_tiles(plan):
        seen[a, b] += 1
        if diagonal:
            assert b <= a                     # nothing above the diagonal
            assert masked == (a == b)         # the mask where it crosses
        else:
            assert not masked or tile is None
    live = np.tril(np.ones((n, n), int)) if diagonal else np.ones((n, n), int)
    np.testing.assert_array_equal(seen, live)
    if diagonal and tile:
        for r, (rows, cols) in enumerate(_strips(plan, tile)):
            assert (rows.start, rows.size) == (r * tile, tile)
            assert (cols.start, cols.size) == (0, (r + 1) * tile)


@pytest.mark.parametrize("args,tile", [
    ((True, 1024, 1024, 64), 256),
    ((True, 512, 512, 128), 256),
    ((True, 96, 96, 64), 96),             # T below one sub-tile
    ((True, 384, 384, 64), None),         # not a multiple: the whole block
    ((True, 512, 256, 64), None),         # block_q != block_k
    ((False, 1024, 1024, 64), None),      # non-causal
    ((True, 1024, 1024, 64, False), None),    # LUT / banded / biased
])
def test_causal_tile_choice(args, tile):
    from deepspeed_tpu.ops.transformer.flash_attention import _causal_tile
    assert _causal_tile(*args) == tile


def _weighted(fn, w):
    return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w)


@pytest.mark.parametrize("d,T,blocks,dtype,causal", [
    (64, 256, None, jnp.float32, True),       # one tile: the block itself
    (64, 1024, None, jnp.float32, True),      # train_z1: one 1024-block
    (64, 1024, None, jnp.bfloat16, True),
    (64, 1536, (1024, 1024), jnp.float32, True),   # Tp > T, a block under
    (128, 1024, None, jnp.float32, True),
    (128, 2048, (512, 512), jnp.float32, True),    # train_z3_x4
    (128, 2048, (512, 512), jnp.bfloat16, True),
    (64, 1024, (512, 256), jnp.float32, True),     # block_q != block_k
    (64, 1024, (256, 512), jnp.float32, True),
    (64, 96, None, jnp.float32, True),        # T below one sub-tile
    (64, 384, None, jnp.float32, True),       # no multiple of the 256-tile
    (64, 512, None, jnp.float32, False),
    (64, 1024, None, jnp.float32, False),     # one block, no mask to skip
    (128, 1024, (512, 512), jnp.float32, False),   # several blocks, dQ summed
    (128, 1280, (512, 512), jnp.float32, False),   # and padded keys masked
    # heads of 256 (PR 54: Qwen3-Next's attention layers): blocks of 512
    (256, 1024, None, jnp.float32, True),
    (256, 1024, None, jnp.bfloat16, True),
    (256, 640, (512, 512), jnp.float32, True),     # Tp > T at hd 256
], ids=lambda v: getattr(v, "__name__", None) or str(v).replace(" ", ""))
def test_walk_forward_and_backward_match_reference(d, T, blocks, dtype,
                                                   causal):
    """Forward and gradients against the dense oracle at the shapes the
    tile walk engages at (and at those it must leave alone).  Every case is
    a dense call whose dQ accumulator, where it needs one, is inside its
    budget: the backward is the FUSED kernel (one block a head: dQ written
    a strip at a time; several: summed k block by k block)."""
    from deepspeed_tpu.ops.transformer.flash_attention import (
        reset_tile_census, tile_census)
    bq, bk = blocks or (None, None)
    reset_tile_census()
    q, k, v = make_qkv(B=1, T=T, H=2, d=d, dtype=dtype, seed=T + d)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)
    flash = lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                            block_q=bq, block_k=bk)
    f32 = lambda t: t.astype(jnp.float32)
    ref = lambda q, k, v: attention_reference(f32(q), f32(k), f32(v),
                                              causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(f32(flash(q, k, v))),
                               np.asarray(ref(q, k, v)), atol=tol, rtol=tol)
    gf = jax.grad(_weighted(flash, w), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(_weighted(ref, w), argnums=(0, 1, 2))(q, k, v)
    tol = 1e-4 if dtype == jnp.float32 else 6e-2
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(f32(a)), np.asarray(f32(b)),
                                   atol=tol, rtol=tol,
                                   err_msg=f"d{name} mismatch")
    census = tile_census()
    assert (census["bwd_fused"], census["bwd_split"]) == (1, 0)


@pytest.mark.parametrize("T,blocks,d", [
    (1024, None, 64), (1536, (1024, 1024), 64), (1024, (512, 256), 64),
    (1024, (512, 512), 128), (384, None, 64)],
    ids=["one_block", "padded_two_blocks", "bq_ne_bk", "hd128_two_blocks",
         "no_tile_multiple"])
def test_walk_with_lse_and_a_nonzero_dlse(T, blocks, d):
    """``flash_attention_with_lse`` shares the kernels: both outputs and
    the gradient of a loss that reads BOTH (so ``dlse`` is not zero; it
    enters the fused backward through ``delta``, as it entered the two)."""
    bq, bk = blocks or (None, None)
    q, k, v = make_qkv(B=1, T=T, H=2, d=d, seed=T)
    w = jax.random.normal(jax.random.PRNGKey(3), q.shape, jnp.float32)

    def ref_pair(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        return (attention_reference(q, k, v),
                jax.scipy.special.logsumexp(s, axis=-1))

    def loss(pair):
        def f(q, k, v):
            out, lse = pair(q, k, v)
            return jnp.sum(out * w) + jnp.sum(jnp.sin(lse))
        return f
    flash = lambda q, k, v: flash_attention_with_lse(q, k, v, block_q=bq,
                                                     block_k=bk)
    for a, b in zip(flash(q, k, v), ref_pair(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(ref_pair), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name} mismatch")


def _trivial_plan_case(name):
    """Output and q/k/v gradients of one call that runs the trivial plan
    (one tile, the whole block, masked as before PR 37)."""
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
        BSLongformerSparsityConfig, BigBirdSparsityConfig)
    from deepspeed_tpu.ops.transformer.flash_attention import (
        sparse_flash_attention)
    B, T, H, d = 1, 128, 2, 16
    q, k, v = make_qkv(B=B, T=T, H=H, d=d, seed=11)
    band = jnp.asarray(BSLongformerSparsityConfig(
        num_heads=H, block=16, num_sliding_window_blocks=3,
        global_block_indices=[0]).make_layout(T), jnp.int32)
    bird = jnp.asarray(BigBirdSparsityConfig(
        num_heads=H, block=16, num_random_blocks=1,
        num_sliding_window_blocks=3, num_global_blocks=1,
        attention="unidirectional").make_layout(T), jnp.int32)
    kp = jnp.where(jnp.arange(T)[None, :] < 100, 0.0, -1e9) * \
        jnp.ones((B, 1), jnp.float32)
    ab = jnp.where(jnp.arange(T)[:, None] - jnp.arange(T)[None, :] < 40,
                   0.0, -1e9).astype(jnp.float32)
    fn = {
        "non_causal": lambda q, k, v: flash_attention(
            q, k, v, causal=False, block_q=64, block_k=64),
        "causal_blocks": lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32),
        "lut": lambda q, k, v: sparse_flash_attention(q, k, v, bird),
        "banded": lambda q, k, v: sparse_flash_attention(q, k, v, band),
        "merged": lambda q, k, v: sparse_flash_attention(
            q, k, v, band, block_q_merge=2),
        "biased": lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64,
            key_padding_bias=kp, attn_bias=ab),
    }[name]
    grads = jax.grad(lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v))),
                     argnums=(0, 1, 2))(q, k, v)
    return (fn(q, k, v),) + grads


def _digest(arrays):
    import hashlib
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a, np.float32)).tobytes())
    return h.hexdigest()[:16]


# taken from the parent tree (commit 2e4dd84, interpreted on the CPU):
# `python -c "import test_flash_attention as t; ..."` under its package
PARENT_DIGESTS = {
    "non_causal": "722b0d20c3e93a04",
    "lut": "8948410434ff1185",
    "banded": "79f93339e7437f50",
    "merged": "7f202d850bcd2674",
    "biased": "1715fd65178ce166",
}


@pytest.mark.parametrize("name", sorted(PARENT_DIGESTS))
def test_trivial_plan_equals_the_parent_to_the_bit(name):
    """What gets no tile walk keeps its numerics: output and gradients of
    one call each equal, bit for bit, what the tree before PR 37 gave."""
    arrays = _trivial_plan_case(name)
    assert all(np.isfinite(np.asarray(a)).all() for a in arrays)
    assert _digest(arrays) == PARENT_DIGESTS[name]


# dense calls of several k blocks, so dQ is a sum over visits; taken from
# the tree before PR 41 (commit c34cda0), whose backward was two kernels
DENSE_PARENT_DIGESTS = {
    "non_causal": PARENT_DIGESTS["non_causal"],
    "causal_blocks": "085c7df16b0eefce",
}


@pytest.mark.parametrize("form", ["bwd_fused", "bwd_split"])
@pytest.mark.parametrize("name", sorted(DENSE_PARENT_DIGESTS))
def test_dense_backward_equals_the_parent_in_either_form(name, form,
                                                         monkeypatch):
    """The fused backward sums dQ k block by k block in ascending order, as
    the dQ kernel did, so where a diagonal block is one tile it equals the
    two kernels to the bit.  And a call whose accumulator is over the
    budget (shrunk here to one byte under what T 128 needs: 128 rows x 128
    lanes x 4 bytes) takes the two kernels, unchanged."""
    import importlib
    fa = importlib.import_module(
        "deepspeed_tpu.ops.transformer.flash_attention")
    if form == "bwd_split":
        monkeypatch.setattr(fa, "_FUSED_BWD_DQ_ACC_BYTES", 128 * 128 * 4 - 1)
    fa.reset_tile_census()
    arrays = _trivial_plan_case(name)
    census = fa.tile_census()
    other = {"bwd_fused": "bwd_split", "bwd_split": "bwd_fused"}[form]
    assert (census[form], census[other]) == (1, 0)
    assert _digest(arrays) == DENSE_PARENT_DIGESTS[name]


@pytest.mark.parametrize("dense,T,block,d,fused", [
    (True, 1024, 1024, 64, True),         # train_z1: one block, no accumulator
    (True, 2048, 512, 128, True),         # train_z3_x4: 1 MB
    (True, 4096, 1024, 64, True),         # 2 MB: a 64-wide row fills 128 lanes
    (True, 8192, 512, 128, True),         # 4 MB, the budget
    (True, 8192 + 512, 512, 128, False),  # over it: two kernels
    (True, 16384, 1024, 64, False),
    (False, 1024, 1024, 64, False),       # LUT, banded, merged, biased
    (False, 2048, 512, 128, False),
])
def test_fused_backward_choice(dense, T, block, d, fused):
    """One kernel or two is read off the call's own shapes: the dense plan
    and an fp32 dQ accumulator over the padded sequence inside its budget."""
    from deepspeed_tpu.ops.transformer.flash_attention import _fused_backward
    n = T // block
    assert _fused_backward(dense, n, n, block, d) is fused


# ------------------------------------------------- residuals under remat
# A rematerialised backward reruns whatever made a residual it was not
# handed.  ``residual_name`` puts the two residuals only the forward kernel
# can make under a name a ``names:`` policy can save (PERF.md §6, PR 33).
B_, T_, H_, D_HEAD = 4, 128, 2, 32


def _block(x, w_qkv, w_proj, residual_name, with_lse=False):
    """A transformer block's attention half and an ``mlp_fc``-named matmul,
    as ``models/gpt2.gpt2_block_forward`` lays them out."""
    from jax.ad_checkpoint import checkpoint_name
    from deepspeed_tpu.ops.transformer.flash_attention import (
        flash_attention_with_lse)
    from deepspeed_tpu.parallel.mesh import BATCH_AXES, per_device
    from jax.sharding import PartitionSpec as P
    q, k, v = (t.reshape(B_, T_, H_, D_HEAD)
               for t in jnp.split(x @ w_qkv, 3, axis=-1))
    spec = P(BATCH_AXES, None, "tensor", None)
    if with_lse:
        attn, lse = flash_attention_with_lse(
            q, k, v, block_q=64, block_k=64, residual_name=residual_name)
        attn = attn * jnp.tanh(lse).transpose(0, 2, 1)[..., None]
    else:
        attn = per_device(
            lambda q, k, v: flash_attention(q, k, v, block_q=64, block_k=64,
                                            residual_name=residual_name),
            (spec, spec, spec), spec)(q, k, v)
    h = checkpoint_name(attn.reshape(B_, T_, H_ * D_HEAD) @ w_proj, "mlp_fc")
    return jnp.sum(jnp.square(h))


def _block_args(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    d = H_ * D_HEAD
    return (jax.random.normal(ks[0], (B_, T_, d), jnp.float32),
            jax.random.normal(ks[1], (d, 3 * d), jnp.float32) * 0.1,
            jax.random.normal(ks[2], (d, d), jnp.float32) * 0.1)


def _remat_grad(policy, residual_name, with_lse=False):
    from deepspeed_tpu.models.gpt2 import resolve_remat_policy
    block = jax.checkpoint(
        lambda x, a, b: _block(x, a, b, residual_name, with_lse),
        policy=resolve_remat_policy(policy))
    return jax.grad(block, argnums=(0, 1, 2))


def _pallas_calls(fn, *args):
    from deepspeed_tpu.analysis.jaxpr_audit import iter_eqns
    return sum(eqn.primitive.name == "pallas_call"
               for eqn, _ in iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one_device", "per_device_x4"])
@pytest.mark.parametrize("policy,residual_name,calls", [
    ("names:attn_out,mlp_fc", "attn_out", 2),
    ("names:attn_out,mlp_fc", None, 3),
    (None, "attn_out", 3),
    ("dots", "attn_out", 3),
], ids=["named_and_saved", "unnamed", "remat_all", "dots"])
def test_remat_backward_kernel_calls(policy, residual_name, calls, sharded):
    """Forward and ONE backward: two kernels where the policy saves the
    named residuals; a third, the forward again, wherever it does not.
    (Three and four until PR 41: the backward was dK/dV and dQ, each
    computing the scores, the exp and ``dO·Vᵀ`` for itself; a dense call
    now gets all three gradients from one pass.)  The same with the kernel
    inside a four-device ``shard_map``, as a ZeRO-3 step runs it."""
    import contextlib
    from deepspeed_tpu.parallel import mesh as M
    ctx = contextlib.nullcontext()
    if sharded:
        ctx = jax.set_mesh(M.make_mesh({"data": 1, "fsdp": 4},
                                       devices=jax.devices()[:4]))
    with ctx:
        n = _pallas_calls(_remat_grad(policy, residual_name), *_block_args())
    assert n == calls


@pytest.mark.parametrize("with_lse", [False, True],
                         ids=["flash_attention", "flash_attention_with_lse"])
def test_saved_residuals_give_the_same_gradient_to_the_bit(with_lse):
    args = _block_args(seed=1)
    policy = "names:attn_out,mlp_fc"
    saved = jax.jit(_remat_grad(policy, "attn_out", with_lse))(*args)
    rerun = jax.jit(_remat_grad(policy, None, with_lse))(*args)
    assert _pallas_calls(_remat_grad(policy, "attn_out", with_lse), *args) \
        == _pallas_calls(_remat_grad(policy, None, with_lse), *args) - 1
    for a, b in zip(saved, rerun):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_train_step)/jvp(blocks)/while/body/closed_call/attention/"
     "pallas_call", "attention"),
    ("jit(_train_step)/transpose(jvp(blocks))/while/body/closed_call/"
     "checkpoint/rematted_computation/attention/shard_map/pallas_call",
     "attention"),
    ("jit(step)/jit(main)/ssm.scan/pallas_call", "ssm.scan"),
    ("jit(f)/jvp()/pallas_call", "pallas_call"),
])
def test_kernel_scope_of_an_op_name(op_name, scope):
    from deepspeed_tpu.analysis.jaxpr_audit import _kernel_scope
    assert _kernel_scope(op_name) == scope


def test_custom_call_census_counts_loop_trips():
    from deepspeed_tpu.analysis.jaxpr_audit import custom_calls_from_hlo_text
    call = ('  %k.{i} = bf16[8,128]{{1,0}} custom-call(%p), '
            'custom_call_target="tpu_custom_call", '
            'metadata={{op_name="jit(f)/{scope}/pallas_call"}}')
    text = "\n".join([
        "%body (p: bf16[8,128]) -> bf16[8,128] {",
        call.format(i=1, scope="while/body/attention"),
        call.format(i=2, scope="while/body/checkpoint/attention"),
        "}",
        "%cond (p: bf16[8,128]) -> pred[] {",
        "  %c = s32[] constant(5)",
        "}",
        "ENTRY %main (p: bf16[8,128]) -> bf16[8,128] {",
        "  %w = bf16[8,128]{1,0} while(%p), condition=%cond, body=%body",
        call.format(i=3, scope="head"),
        '  %o = f32[4]{0} custom-call(%p), custom_call_target="Sharding"',
        "}"])
    assert custom_calls_from_hlo_text(text) == {"attention": 10, "head": 1}


# ------------------------------------------------- the windowed forward
def _window_reference(q, k, v, window):
    """Masked dense oracle: key ``s`` seen by query ``t`` iff ``0 <= t - s
    < window``; query head ``h`` reads K/V head ``h // (H // Hkv)``."""
    T, d, G = q.shape[1], q.shape[-1], q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, G, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / np.sqrt(d)
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    s = jnp.where((ahead >= 0) & (ahead < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _window_qkv(T, H, Hkv, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    mk = lambda key, h: jax.random.normal(key, (1, T, h, d), jnp.float32)
    return mk(ks[0], H), mk(ks[1], Hkv), mk(ks[2], Hkv)


@pytest.mark.parametrize("T,window,block,H,Hkv,d", [
    (96, 128, None, 2, 2, 64),       # T below the window: the triangle
    (128, 128, 64, 2, 2, 64),        # T at the window
    (320, 128, 128, 6, 1, 128),      # above; a bucket of 64s, not of 128s
    (320, 200, 128, 2, 1, 64),       # the window no multiple of the block:
    #                                  two slots under the lower edge
    (300, 100, 128, 4, 2, 128),      # the window inside one block
    (448, 129, 64, 6, 1, 64),        # window - 1 a multiple of the block
    (1088, 600, 512, 2, 1, 128),     # the edge in two slots of 512
    (1088, 1024, 512, 6, 1, 64),     # the served ratio: 4 blocks a window
], ids=["below", "at", "bucket_of_64s", "edge_in_two_slots", "inside_a_block",
        "aligned_edge", "blocks_of_512_hd128", "blocks_of_512_grouped_hd64"])
def test_window_forward_matches_the_masked_reference(monkeypatch, T, window,
                                                     block, H, Hkv, d):
    """``flash_attention_window`` (interpreted) against a masked dense
    reference AND ``models/afmoe.banded_attention``, what runs off the
    chip; then the band's edge BY VALUE: a key moved far away moves the
    rows that see it, up to ``t - s = window - 1``, and leaves row ``t - s
    = window`` (and the row before the key) to the bit."""
    import importlib
    from deepspeed_tpu.models.afmoe import banded_attention
    fa = importlib.import_module(
        "deepspeed_tpu.ops.transformer.flash_attention")
    if block:
        monkeypatch.setattr(fa, "_WINDOW_BLOCK", block)
    q, k, v = _window_qkv(T, H, Hkv, d, seed=T + window)
    fn = lambda v: fa.flash_attention_window(q, k, v, window=window)
    out = fn(v)
    assert out.shape == q.shape
    ref = _window_reference(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    band = banded_attention(q, k, v, window=window, block=64)
    np.testing.assert_allclose(np.asarray(out.reshape(band.shape)),
                               np.asarray(band), atol=2e-5, rtol=2e-5)
    s = max(T - window - 3, 1)                # a key with rows past its band
    moved = np.abs(np.asarray(fn(v.at[:, s].add(100.0)) - out)).max(
        axis=(0, 2, 3))                       # (T,): how far each row moved
    last = min(s + window - 1, T - 1)
    assert (moved[s:last + 1] > 1e-3).all()   # t - s = 0 .. window - 1: seen
    assert moved[s - 1] == 0.0
    if s + window < T:
        assert moved[s + window] == 0.0       # t - s = window: not seen
        assert (moved[s + window:] == 0.0).all()


def test_window_forward_refuses_a_gradient_by_name():
    from deepspeed_tpu.ops.transformer.flash_attention import (
        flash_attention_window)
    q, k, v = _window_qkv(128, 2, 1, 64)
    loss = lambda q: flash_attention_window(q, k, v, window=64).sum()
    with pytest.raises(NotImplementedError,
                       match="flash_attention_window is forward-only"):
        jax.grad(loss)(q)


def test_window_call_leaves_the_dense_census_as_the_parent_had_it():
    """A dense causal call's ``tile_census()`` entry is the parent's (T
    1024, hd 64, 4 heads: one 1024-block of 10 tiles in 16 a head, 4
    masked; the fused backward), with a windowed call traced beside it."""
    import importlib
    fa = importlib.import_module(
        "deepspeed_tpu.ops.transformer.flash_attention")
    x = jax.ShapeDtypeStruct((2, 1024, 2, 64), jnp.bfloat16)
    fa.reset_tile_census()
    jax.eval_shape(jax.grad(lambda q, k, v: fa.flash_attention(
        q, k, v).astype(jnp.float32).sum()), x, x, x)
    dense = fa.tile_census()
    assert dense == {"visited": 2 * 4 * 10, "masked": 2 * 4 * 4,
                     "square": 2 * 4 * 16, "bwd_fused": 1, "bwd_split": 0}
    jax.eval_shape(lambda q, k, v: fa.flash_attention_window(
        q, k, v, window=256), x, x, x)
    assert fa.tile_census() == dense


# -------------------------------------------- the step the TPU compiles
@pytest.fixture(scope="module")
def v5e():
    """A described v5e:2x2 (no chip needed); built here and never at
    import, so every xdist worker collects the same tests."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("kernels", ["forward", "backward"])
@pytest.mark.parametrize("BH,T,d,bwd_calls", [
    (80, 1024, 64, 1), (16, 2048, 128, 1), (16, 4096, 64, 1),
    (4, 8192, 128, 1), (2, 16384, 128, 2)],
    ids=["train_z1", "train_z3_x4", "T4096_hd64", "T8192_at_the_budget",
         "T16384_over_it"])
def test_causal_kernels_compile_for_a_v5e(v5e, monkeypatch, BH, T, d,
                                          bwd_calls, kernels):
    """The kernels at both training cells' shapes (bf16, the blocks
    ``_auto_blocks`` gives), through Mosaic and XLA:TPU for a described
    v5e: one custom call each, forward and fused backward.  The tile
    walk's static slices, its lane concatenation under the mask, the
    stateless forward's writes of ``out`` and ``lse`` a strip at a time and
    the fused backward's of ``dq`` are what the interpreter cannot refuse
    and Mosaic can; so is the fused backward's VMEM (six operands and three
    outputs double-buffered, the dK and dV accumulators and, past one k
    block, dQ's over the whole sequence) against a v5e's scoped limit, up
    to the longest sequence whose accumulator is inside the budget.  Past
    it the backward compiles as the two kernels it was."""
    import importlib
    from jax.sharding import SingleDeviceSharding
    fa = importlib.import_module(
        "deepspeed_tpu.ops.transformer.flash_attention")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    x = jax.ShapeDtypeStruct((BH, T, d), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((BH, T), jnp.float32, sharding=one_chip)
    scale = 1.0 / np.sqrt(d)
    if kernels == "forward":
        fn = lambda q, k, v: fa._fwd(q, k, v, scale, True, None, None)
        args = (x, x, x)
    else:
        fn = lambda q, k, v, out, lse, do: fa._bwd(
            scale, True, None, None, (q, k, v, out, lse), do)
        args = (x, x, x, x, lse, x)
    calls = 1 if kernels == "forward" else bwd_calls
    fa.reset_tile_census()
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert text.count("tpu_custom_call") == calls
    # 256-tiles: T 1024 is one block of 10 tiles in 16; T 2048 is four
    # 512-blocks a side, the diagonal ones 3 tiles in 4 and the six under
    # them whole: 36 in 64
    block = 512 if d > 64 else 1024
    edge, n = T // block, block // 256    # blocks a side, tiles a block's side
    visited = edge * n * (n + 1) // 2 + edge * (edge - 1) // 2 * n * n
    assert fa.tile_census() == {
        "visited": calls * BH * visited,
        "masked": calls * BH * T // 256,
        "square": calls * BH * (T // 256) ** 2,
        "bwd_fused": int(kernels == "backward" and bwd_calls == 1),
        "bwd_split": int(kernels == "backward" and bwd_calls == 2)}


@pytest.mark.parametrize("T", [5120, 16384, 4160, 512])
def test_window_kernel_compiles_for_a_v5e(v5e, monkeypatch, T):
    """The windowed forward at Trinity's served shape (48 query heads over
    8 K/V heads of 128, a window of 4,096, bf16, blocks of 1,024: a 4 MB
    score tile beside the double-buffered operands) through Mosaic and
    XLA:TPU for a described v5e: ONE custom call, named as the model names
    it, and no repeated K/V (the index map reads K/V head ``h // 6``); a
    bucket that is no multiple of the block is padded, one under a block
    is one block."""
    import importlib
    from jax.sharding import SingleDeviceSharding
    fa = importlib.import_module(
        "deepspeed_tpu.ops.transformer.flash_attention")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    q = jax.ShapeDtypeStruct((1, T, 48, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, T, 8, 128), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(lambda q, k, v: fa.flash_attention_window(
        q, k, v, window=4096, name="prefill_band_attention")).trace(
            q, kv, kv).lower(lowering_platforms=("tpu",)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "%prefill_band_attention" in text
    assert "broadcast" not in text


N_LAYER = 3


@pytest.mark.parametrize("policy,axes,stage,per_layer", [
    ("names:attn_out,mlp_fc", {"data": 1}, 1, 2),
    ("names:mlp_fc", {"data": 1}, 1, 3),
    ("names:attn_out,mlp_fc", {"data": 1, "fsdp": 4}, 3, 2),
], ids=["z1_saved", "z1_not_saved", "z3_x4_saved"])
def test_compile_report_counts_the_flash_calls_of_a_step(
        v5e, monkeypatch, tmp_path, policy, axes, stage, per_layer):
    """``compile_report()["custom_calls"]`` and ``["flash_tiles"]`` of a
    tiny GPT-2 engine whose
    train step is acquired, through the engine's own wrapper, for the
    described TPU (the CPU interprets kernels and its executable holds no
    custom call): the Mosaic calls the compiler KEPT, times the layer
    scan's trips.  The recipe is the verify skill's: the state's shapes
    on a mesh of described devices, the engine's meshes swapped for it."""
    import importlib
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import deepspeed_tpu as ds
    import deepspeed_tpu.ops as ops
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
    from deepspeed_tpu.parallel import mesh as M
    fa = importlib.import_module(
        "deepspeed_tpu.ops.transformer.flash_attention")
    n_dev = int(np.prod(list(axes.values())))
    mesh = M.make_mesh(axes, devices=jax.devices()[:n_dev])
    # T 512: the shortest a 64-wide head's block is cut in tiles at
    model = GPT2(config=GPT2Config(
        vocab_size=256, max_seq=512, n_embd=128, n_layer=N_LAYER, n_head=2,
        embd_pdrop=0.0, attn_pdrop=0.0, resid_pdrop=0.0, remat=True,
        remat_policy=policy, attention_impl="auto", loss_chunk=128),
        dtype=jnp.bfloat16)
    engine, _, _, _ = ds.initialize(
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 10 ** 9, "bf16": {"enabled": True},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": stage},
                "compile_cache": {"dir": str(tmp_path)}},
        model=model, mesh=mesh, rng_seed=1)
    tpu_mesh = Mesh(np.array(v5e.devices[:n_dev]).reshape(mesh.devices.shape),
                    mesh.axis_names)

    def on_tpu(x):
        sh = getattr(x, "sharding", None)
        spec = sh.spec if isinstance(sh, NamedSharding) else P()
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(tpu_mesh, spec))
    state = jax.tree_util.tree_map(on_tpu, engine.state)
    batch = jax.ShapeDtypeStruct(
        (1, 2 * M.dp_world_size(mesh), 513), jnp.int32,
        sharding=NamedSharding(tpu_mesh, P(None, M.BATCH_AXES)))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(tpu_mesh, P()))
    engine.mesh = engine._router.mesh = tpu_mesh
    engine.mesh_ctx = M.MeshContext(tpu_mesh)
    monkeypatch.setattr(ops, "flash_attention_available", lambda: True)
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    with jax.set_mesh(tpu_mesh):
        engine._jit_train_step.executable(state, batch, rng)
    report = engine.compile_report()
    assert report["custom_calls"] == {
        "DeepSpeedEngine.train_step": {"attention": per_layer * N_LAYER}}
    # the forward and the ONE backward of the scanned layer, each counted
    # once: 2 sequences x 2 heads a device, a 512-block of 3 tiles in 4, 2
    # masked; and the backward's form, which no HLO shows on a CPU
    assert report["flash_tiles"] == {"DeepSpeedEngine.train_step": {
        "visited": 2 * 4 * 3, "masked": 2 * 4 * 2, "square": 2 * 4 * 4,
        "bwd_fused": 1, "bwd_split": 0}}
    assert not {"custom_calls", "flash_tiles"} & set(
        report["collectives"]["DeepSpeedEngine.train_step"])
    built = [row for row in engine._spans.rows("compile.build")
             if row.attrs.get("fn") == "DeepSpeedEngine.train_step"]
    assert built[-1].attrs["custom_calls"] == {
        "attention": per_layer * N_LAYER}
