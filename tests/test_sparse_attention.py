"""Block-sparse attention tests: layout math + kernel vs dense reference.

Parity model: reference ``tests/unit/test_sparse_attention.py`` (kernel vs
dense reference) and the SparsityConfig semantics.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
    DenseSparsityConfig, FixedSparsityConfig, VariableSparsityConfig,
    BigBirdSparsityConfig, BSLongformerSparsityConfig, build_sparsity_config)
from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import (
    SparseSelfAttention)
from deepspeed_tpu.ops.transformer.flash_attention import (
    sparse_flash_attention, sparse_attention_reference, attention_reference)


# ----------------------------------------------------------- layout semantics
def test_dense_layout_all_ones():
    cfg = DenseSparsityConfig(num_heads=2, block=16)
    layout = cfg.make_layout(64)
    assert layout.shape == (1, 4, 4)
    assert layout.sum() == 16


def test_fixed_layout_local_window():
    cfg = FixedSparsityConfig(num_heads=2, block=16, num_local_blocks=2,
                              num_global_blocks=1)
    layout = cfg.make_layout(128)  # 8 blocks
    # block 0 and 1 are in the same window → attend each other
    assert layout[0, 0, 1] == 1 and layout[0, 1, 0] == 1
    # global column (last of each window) reaches everyone
    assert layout[0, 6, 1] == 1  # col 1 = global of first window
    # non-global, non-local pair is blocked
    assert layout[0, 0, 2] == 0


def test_fixed_unidirectional_is_lower_triangular_local():
    cfg = FixedSparsityConfig(num_heads=1, block=16, num_local_blocks=4,
                              attention="unidirectional")
    layout = cfg.make_layout(128)
    assert np.all(np.triu(layout[0], 1) == 0)


def test_fixed_validation():
    with pytest.raises(ValueError):
        FixedSparsityConfig(num_heads=1, num_local_blocks=4, num_global_blocks=3)
    with pytest.raises(NotImplementedError):
        FixedSparsityConfig(num_heads=1, attention="sideways")
    with pytest.raises(ValueError):
        FixedSparsityConfig(num_heads=1, attention="unidirectional",
                            horizontal_global_attention=True)


def test_seq_not_divisible_raises():
    cfg = FixedSparsityConfig(num_heads=1, block=16)
    with pytest.raises(ValueError):
        cfg.make_layout(100)


def test_bigbird_layout():
    cfg = BigBirdSparsityConfig(num_heads=1, block=16, num_random_blocks=1,
                                num_sliding_window_blocks=3, num_global_blocks=1)
    layout = cfg.make_layout(256)  # 16 blocks
    n = layout.shape[1]
    for i in range(n):
        assert layout[0, i, i] == 1          # diagonal always in window
    assert np.all(layout[0, 0, :] == 1)      # global row
    assert np.all(layout[0, :, 0] == 1)      # global column
    # non-global rows: at most window(3) + global col(1) + random(1) entries
    assert layout[0, 1:].sum(axis=1).max() <= 5


def test_bslongformer_layout():
    cfg = BSLongformerSparsityConfig(num_heads=1, block=16,
                                     num_sliding_window_blocks=3,
                                     global_block_indices=[0, 5])
    layout = cfg.make_layout(256)
    assert np.all(layout[0, 5, :] == 1)
    assert np.all(layout[0, :, 5] == 1)
    assert layout[0, 2, 8] == 0  # outside window + not global


def test_different_layout_per_head():
    cfg = FixedSparsityConfig(num_heads=4, block=16, num_local_blocks=4,
                              num_global_blocks=1,
                              different_layout_per_head=True,
                              num_different_global_patterns=4)
    layout = cfg.make_layout(256)
    assert layout.shape[0] == 4
    assert not np.array_equal(layout[0], layout[1])


def test_build_from_json_section():
    cfg = build_sparsity_config({"mode": "bigbird", "block": 16,
                                 "num_random_blocks": 2}, num_heads=8)
    assert isinstance(cfg, BigBirdSparsityConfig)
    assert cfg.num_random_blocks == 2
    with pytest.raises(ValueError):
        build_sparsity_config({"mode": "diagonal"}, num_heads=8)


# ------------------------------------------------------------ kernel numerics
def make_qkv(B=1, T=128, H=2, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, T, H, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_sparse_kernel_matches_dense_reference(causal):
    q, k, v = make_qkv()
    cfg = FixedSparsityConfig(num_heads=2, block=32, num_local_blocks=2,
                              num_global_blocks=1)
    layout = jnp.asarray(cfg.make_layout(128), jnp.int32)
    out = sparse_flash_attention(q, k, v, layout, causal=causal)
    ref = sparse_attention_reference(q, k, v, layout, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_sparse_dense_layout_equals_flash():
    q, k, v = make_qkv()
    layout = jnp.ones((1, 4, 4), jnp.int32)  # block 32, fully dense
    out = sparse_flash_attention(q, k, v, layout, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_sparse_backward_matches_dense_reference():
    q, k, v = make_qkv(T=64)
    cfg = BigBirdSparsityConfig(num_heads=2, block=16, num_random_blocks=0,
                                num_sliding_window_blocks=3, num_global_blocks=1)
    layout = jnp.asarray(cfg.make_layout(64), jnp.int32)

    def loss_sparse(q, k, v):
        return jnp.sum(jnp.square(sparse_flash_attention(q, k, v, layout,
                                                         causal=False)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(sparse_attention_reference(q, k, v, layout,
                                                             causal=False)))

    gs = jax.grad(loss_sparse, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gs, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


def test_sparse_self_attention_module():
    q, k, v = make_qkv(T=128, H=4)
    cfg = FixedSparsityConfig(num_heads=4, block=32, num_local_blocks=2)
    attn = SparseSelfAttention(cfg)
    out = attn(q, k, v, causal=False)
    assert out.shape == q.shape
    assert 0.0 < attn.density(128) <= 1.0
    # layout cache reused
    assert attn.get_layout(128) is attn.get_layout(128)


# ------------------------------------------------ in-kernel masks (no fallback)
def test_masked_call_stays_on_kernel_path(monkeypatch):
    """A padded call must NOT route through the dense fallback — the masks
    enter the Pallas kernel as additive biases (reference softmax_kernels.cu
    masked attn_softmax)."""
    q, k, v = make_qkv(T=128, H=4)
    cfg = FixedSparsityConfig(num_heads=4, block=32, num_local_blocks=2)
    attn = SparseSelfAttention(cfg, key_padding_mask_mode="mul")
    called = []
    monkeypatch.setattr(
        SparseSelfAttention, "_masked_dense",
        lambda self, *a, **kw: called.append(1))
    kp = jnp.ones((1, 128), jnp.int32).at[:, 100:].set(0)
    out = attn(q, k, v, causal=False, key_padding_mask=kp)
    assert not called, "masked call fell back to the dense path"
    assert out.shape == q.shape


@pytest.mark.parametrize("kp_mode,am_mode", [("mul", "mul"), ("add", "add")])
def test_kernel_masks_match_dense_oracle(kp_mode, am_mode):
    """Kernel numerics with key-padding + attention masks == the dense
    oracle, in both 'add' and 'mul' mask modes."""
    B, T, H = 2, 128, 2
    q, k, v = make_qkv(B=B, T=T, H=H)
    cfg = FixedSparsityConfig(num_heads=H, block=32, num_local_blocks=2,
                              num_global_blocks=1)
    attn = SparseSelfAttention(cfg, key_padding_mask_mode=kp_mode,
                               attn_mask_mode=am_mode)
    layout = jnp.asarray(attn.get_layout(T))
    rng = np.random.default_rng(0)
    if kp_mode == "mul":
        kp = jnp.asarray(rng.integers(0, 2, (B, T)).astype(np.int32))
        am = jnp.asarray((rng.random((T, T)) > 0.1).astype(np.int32))
    else:
        kp = jnp.asarray(np.where(rng.integers(0, 2, (B, T)), 0.0,
                                  -1e9).astype(np.float32))
        am = jnp.asarray(np.where(rng.random((T, T)) > 0.1, 0.0,
                                  -1e9).astype(np.float32))
    out = attn(q, k, v, causal=False, key_padding_mask=kp, attn_mask=am)
    ref = attn._masked_dense(q, k, v, layout, False, None, kp, am)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.slow   # compile-heavy; fast tier stays inside the driver budget (conftest)
def test_kernel_masked_backward_matches_oracle():
    """Gradients through the masked kernel path match the dense oracle —
    BERT trains with real padding through the kernel."""
    B, T, H = 2, 64, 2
    q, k, v = make_qkv(B=B, T=T, H=H, d=16)
    cfg = FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=2,
                              num_global_blocks=1)
    attn = SparseSelfAttention(cfg, key_padding_mask_mode="mul")
    layout = jnp.asarray(attn.get_layout(T))
    kp = jnp.ones((B, T), jnp.int32).at[:, 48:].set(0)

    def loss_kernel(q, k, v):
        return jnp.sum(jnp.square(
            attn(q, k, v, causal=False, key_padding_mask=kp)))

    def loss_oracle(q, k, v):
        return jnp.sum(jnp.square(attn._masked_dense(
            q, k, v, layout, False, None, kp, None)))

    gs = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gs, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4, err_msg=f"d{name}")


def test_lut_compresses_grid():
    """The sparse grid's inner dimension is the max LIVE block count, not
    the full k-block count — skipped blocks are never visited (VERDICT r2:
    grid/LUT compression; reference make_lut, matmul.py:288)."""
    from deepspeed_tpu.ops.transformer.flash_attention import _layout_luts
    T, nq = 512, 16
    # pure sliding window (band of 3): every row has <= 3 live blocks
    r = np.arange(nq)
    layout = (np.abs(r[:, None] - r[None, :]) <= 1).astype(np.int32)[None]
    kmap, klen, qmap, qlen = _layout_luts(layout, T, 1, False, 32, 32)
    assert kmap.shape[2] <= 3       # window only
    assert kmap.shape[2] < nq       # genuinely compressed vs dense grid
    # causal pruning folds into the LUT too
    kmap_c, klen_c, _, _ = _layout_luts(layout, T, 1, True, 32, 32)
    assert int(np.asarray(klen_c).sum()) < int(np.asarray(klen).sum())
    # row 0 under causal: only block 0 is live
    assert int(np.asarray(klen_c)[0, 0]) == 1
    # with a global row the padded width grows, but short rows pad by
    # REPEATING their last live block (repeat == no new DMA in pallas)
    cfg_g = BSLongformerSparsityConfig(num_heads=1, block=32,
                                       num_sliding_window_blocks=3,
                                       global_block_indices=[0])
    kmap_g, klen_g, _, _ = _layout_luts(cfg_g.make_layout(T), T, 1,
                                        False, 32, 32)
    km, kl = np.asarray(kmap_g), np.asarray(klen_g)
    row = km[0, 2]                  # a windowed (non-global) row
    n = int(kl[0, 2])
    assert n < km.shape[1]
    assert (row[n:] == row[n - 1]).all()


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="wall-clock perf is only meaningful on TPU "
                           "(run directly: the suite conftest forces CPU)")
def test_sparse_beats_dense_flash_on_tpu():
    """The LUT grid's time scales with the LIVE block count: at T=16384 a
    window+global Longformer layout must clearly beat dense flash
    (the reference claims 6.3x at higher sparsity; no ledger cell
    measures this kernel).  Timed with in-graph iterations: the
    remote-attach dispatch jitter otherwise swamps single calls."""
    import time
    from jax import lax
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
    B, T, H, d = 1, 16384, 8, 64
    q, k, v = make_qkv(B=B, T=T, H=H, d=d)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    cfg = BSLongformerSparsityConfig(num_heads=1, block=512,
                                     num_sliding_window_blocks=3,
                                     global_block_indices=[0])
    layout = cfg.make_layout(T)

    N = 20

    def timed(fn):
        # optimization_barrier on the carried q: without it XLA proves the
        # input loop-invariant and hoists the kernel out of the loop
        # (timing one call as if it were N)
        def body(i, carry):
            acc, qq = carry
            qq = jax.lax.optimization_barrier(qq)
            return (acc + fn(qq, k, v).astype(jnp.float32).sum(), qq)
        g = jax.jit(lambda: lax.fori_loop(
            0, N, body, (jnp.float32(0.0), q))[0])
        float(g())                       # compile + warm
        t0 = time.time()
        float(g())
        return (time.time() - t0) / N

    t_s = timed(lambda q, k, v: sparse_flash_attention(
        q, k, v, layout, causal=True))
    t_d = timed(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=512, block_k=512))
    assert t_s < t_d * 0.75, (
        f"sparse {t_s*1e3:.2f}ms not clearly faster than dense "
        f"{t_d*1e3:.2f}ms at T={T}")


def test_flash_attention_with_padding_bias():
    """The dense flash kernel also accepts the additive biases."""
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
    B, T, H, d = 2, 128, 2, 16
    q, k, v = make_qkv(B=B, T=T, H=H, d=d)
    kp = jnp.where(jnp.arange(T)[None, :] < 100, 0.0, -1e9) * \
        jnp.ones((B, 1), jnp.float32)
    out = flash_attention(q, k, v, causal=True, key_padding_bias=kp)
    # oracle: causal + key mask
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / np.sqrt(d)
    s = s + kp[:, None, None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.slow   # compile-heavy; fast tier stays inside the driver budget (conftest)
def test_block_q_merge_exact():
    """block_q_merge=2 (two layout rows share one kernel row with
    per-half-row gating) must match the unmerged path — forward AND
    gradients.  The unmerged forward may take the banded static-map
    kernel (different slot visit order → last-ulp f32 differences), so
    forward compares to ~1 ulp; gradients run the SAME LUT backward
    kernels on both paths and must stay bit-exact."""
    from deepspeed_tpu.ops.transformer.flash_attention import (
        sparse_flash_attention)
    cfg = BSLongformerSparsityConfig(num_heads=2, block=16,
                                     num_sliding_window_blocks=3,
                                     global_block_indices=[0])
    T = 128
    layout = jnp.asarray(cfg.make_layout(T), jnp.int32)
    q, k, v = make_qkv(B=1, T=T, H=2, d=16, seed=3)

    ref = sparse_flash_attention(q, k, v, layout, causal=True)
    got = sparse_flash_attention(q, k, v, layout, causal=True,
                                 block_q_merge=2)
    np.testing.assert_allclose(np.asarray(ref, np.float32),
                               np.asarray(got, np.float32),
                               rtol=1e-4, atol=1e-6)

    def loss(fn):
        return jax.grad(lambda a: jnp.sum(
            fn(a, k, v).astype(jnp.float32) ** 2))
    g_ref = loss(lambda a, b, c: sparse_flash_attention(
        a, b, c, layout, causal=True))(q)
    g_got = loss(lambda a, b, c: sparse_flash_attention(
        a, b, c, layout, causal=True, block_q_merge=2))(q)
    np.testing.assert_allclose(np.asarray(g_ref, np.float32),
                               np.asarray(g_got, np.float32),
                               rtol=1e-4, atol=1e-6)


def test_block_q_merge_empty_row_outputs_zero():
    """A layout q-row with ZERO live blocks merged with a live sibling must
    output exact zeros (the unmerged path's compute-gated behavior), not
    the mean of the sibling's visited V rows."""
    from deepspeed_tpu.ops.transformer.flash_attention import (
        sparse_flash_attention)
    T, blk = 64, 16
    n = T // blk
    layout = np.zeros((1, n, n), np.int32)
    # row 0: EMPTY; rows 1..: diagonal only
    for i in range(1, n):
        layout[0, i, i] = 1
    layout = jnp.asarray(layout)
    q, k, v = make_qkv(B=1, T=T, H=2, d=16, seed=5)
    ref = sparse_flash_attention(q, k, v, layout, causal=True)
    got = sparse_flash_attention(q, k, v, layout, causal=True,
                                 block_q_merge=2)
    np.testing.assert_array_equal(np.asarray(ref, np.float32),
                                  np.asarray(got, np.float32))
    # row 0's tokens (first blk rows) must be exactly zero
    assert float(jnp.max(jnp.abs(got[:, :blk].astype(jnp.float32)))) == 0.0
