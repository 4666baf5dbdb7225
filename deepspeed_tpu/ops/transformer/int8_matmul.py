"""Weight-int8 matmul: dequantize on the fly so HBM streams int8.

Parity: the reference's int8 inference gemms
(``csrc/transformer/inference/csrc/pt_binding.cpp:1148`` ``qkv_gemm_int8`` /
``mlp_gemm_int8`` + ``dequantize.cu``) exist so int8-stored weights reach
the tensor cores without a full-width round trip through device memory.

TPU shape of the problem: batched decode is weight-streaming bound — each
token must read every weight byte out of HBM, so tok/s ≈ HBM_BW /
weight_bytes.  The trap is MATERIALIZING the bf16 convert of the whole
tree (the hoisted-dequant route): then the matmuls stream full-width.
Feeding the int8 leaf STRAIGHT into ``dot_general`` via an inline
``astype`` keeps the convert inside the dot's operand fusion — XLA
streams int8 bytes and converts in registers.  The hand-written Pallas
block kernel below needs 4·L+1 pallas_call launches per decoded token,
which cost more than the bytes they save (no ledger cell measures either
route: ROADMAP D4).

DEMOTED for decode: the per-layer kernel route lost to launch overhead,
and the launch-count problem is now fixed STRUCTURALLY — the fused
stacked-scan decode (``GPT2.apply_with_cache``) slices each
layer's int8 payload inside ONE ``lax.scan`` executable, so quantized
decode is a single launch per step with the int8 bytes still streaming
through the in-dot convert.  ``q_matmul`` never routes decode through
this kernel; ``use_pallas=True`` remains an opt-in experiment for
standalone large-M shapes only.  Scale applies on the (M, N) output
(per-tensor or per-output-channel), where XLA folds it into the
consumer.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _on_tpu():
    try:
        return jax.devices()[0].platform == "tpu"
    except Exception:                 # pragma: no cover - no backend
        return False


def _kernel_nt(x_ref, q_ref, o_ref):
    # q block: (K, bn) int8 → bf16 in VMEM; x: (M, K) bf16
    w = q_ref[...].astype(jnp.bfloat16)
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _kernel_t(x_ref, q_ref, o_ref):
    # q block: (bn, K) int8 (weight stored (N, K), used as x @ w.T)
    w = q_ref[...].astype(jnp.bfloat16)
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("w_transposed", "block_n"))
def _int8_mm_tpu(x, q, *, w_transposed, block_n):
    from jax.experimental import pallas as pl

    M, K = x.shape
    N = q.shape[0] if w_transposed else q.shape[1]
    grid = (pl.cdiv(N, block_n),)
    if w_transposed:
        q_spec = pl.BlockSpec((block_n, K), lambda i: (i, 0))
        kernel = _kernel_t
    else:
        q_spec = pl.BlockSpec((K, block_n), lambda i: (0, i))
        kernel = _kernel_nt
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((M, K), lambda i: (0, 0)), q_spec],
        out_specs=pl.BlockSpec((M, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
    )(x, q)


def int8_matmul(x, q, scale, *, w_transposed=False, block_n=512,
                out_dtype=None, use_pallas=False):
    """``x @ dequant(q)`` (or ``x @ dequant(q).T``) streaming int8 weights.

    ``x``: (..., K) floating; ``q``: int8 (K, N), or (N, K) when
    ``w_transposed``; ``scale``: per-tensor (size 1) or per-output-channel
    (size N, only with ``w_transposed`` — the quantizer's row groups).
    Returns (..., N) in ``out_dtype`` (default ``x.dtype``).
    """
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = q.shape[0] if w_transposed else q.shape[1]
    M = int(np.prod(lead)) if lead else 1
    x2 = x.reshape(M, K).astype(jnp.bfloat16)

    use_pallas = (use_pallas and _on_tpu() and M <= 64 and K % 128 == 0)
    if use_pallas:
        # pad rows to the bf16 sublane tile so tiny decode batches map
        # cleanly; cost is VMEM-only
        Mp = max(16, -(-M // 16) * 16)
        if Mp != M:
            x2 = jnp.pad(x2, ((0, Mp - M), (0, 0)))
        acc = _int8_mm_tpu(x2, q, w_transposed=w_transposed,
                           block_n=min(block_n, N))[:M]
    else:
        w = q.astype(jnp.bfloat16)
        acc = jax.lax.dot_general(
            x2, w, (((1,), (1 if w_transposed else 0,)), ((), ())),
            preferred_element_type=jnp.float32)

    scale = jnp.asarray(scale, jnp.float32).reshape(-1)
    if scale.size == 1:
        acc = acc * scale[0]
    elif w_transposed and scale.size == N:
        acc = acc * scale[None, :]
    else:
        raise ValueError(
            f"scale size {scale.size} does not map to per-tensor or "
            f"per-output-channel (N={N}, w_transposed={w_transposed})")
    return acc.astype(out_dtype).reshape(*lead, N)
