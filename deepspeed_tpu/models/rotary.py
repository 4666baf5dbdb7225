"""Rotary position embeddings.

Parity: reference ``csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu``
(the rotary kernel used by the GPT-J/GPT-NeoX inference paths).  On TPU the
rotation is two fused elementwise multiplies — XLA fuses them into the
surrounding QKV computation, so no custom kernel is needed.

Two layouts exist in the wild:

- ``neox_style=True`` (GPT-NeoX, LLaMA): rotate_half — the feature dim is
  split into two contiguous halves.
- ``neox_style=False`` (GPT-J): interleaved even/odd pairs.
"""

import numpy as np
import jax
import jax.numpy as jnp


def rotary_freqs(rotary_dim, max_seq, base=10000.0, dtype=jnp.float32,
                 inv_freq=None):
    """(max_seq, rotary_dim/2) angle table; ``inv_freq`` replaces the plain
    ``base^(-2i/d)`` (:func:`yarn_inv_freq`)."""
    inv = (1.0 / (base ** (np.arange(0, rotary_dim, 2) / rotary_dim))
           if inv_freq is None else np.asarray(inv_freq))
    t = np.arange(max_seq)
    ang = np.einsum("t,f->tf", t, inv)
    return jnp.asarray(np.cos(ang), dtype), jnp.asarray(np.sin(ang), dtype)


def yarn_inv_freq(rotary_dim, base, factor, original_max_position_embeddings,
                  beta_fast=32, beta_slow=1):
    """YaRN's per-pair inverse frequencies (arXiv:2309.00071, as HF
    ``DeepseekV2YarnRotaryEmbedding`` computes them): pair ``i`` blends
    ``base^(-2i/d)`` (kept, above the ``beta_fast`` correction dim: the fast
    pairs) and the same over ``factor`` (interpolated, below ``beta_slow``'s)
    by a linear ramp between the two correction dims at the ORIGINAL context
    length.  Nothing here depends on the served limit."""
    extra = base ** (-np.arange(0, rotary_dim, 2, dtype=np.float64)
                     / rotary_dim)
    inter = extra / factor

    def correction_dim(n_rot):
        return (rotary_dim * np.log(original_max_position_embeddings
                                    / (n_rot * 2 * np.pi))) / (2 * np.log(base))
    low = max(np.floor(correction_dim(beta_fast)), 0)
    high = min(np.ceil(correction_dim(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001                         # as published: no singularity
    ramp = np.clip((np.arange(rotary_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp                         # 1: the pair is extrapolated
    return inter * (1 - keep) + extra * keep


def yarn_mscale(factor, mscale):
    """YaRN's attention-magnitude factor ``0.1 * mscale * ln(factor) + 1``
    (1 for ``factor`` <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * np.log(factor) + 1.0


def apply_rotary_pos_emb(x, cos, sin, positions, neox_style=True):
    """Rotate the first ``2*cos.shape[-1]`` features of ``x``.

    x: (B, T, H, d); positions: (T,) or (B, T) absolute positions.
    """
    with jax.named_scope("rope"):
        r2 = cos.shape[-1]          # rotary_dim / 2
        rot, rest = x[..., :2 * r2], x[..., 2 * r2:]
        c = cos[positions][..., None, :].astype(x.dtype)   # (.., T, 1, r2)
        s = sin[positions][..., None, :].astype(x.dtype)
        if c.ndim == 3:             # positions was (T,): add batch axis
            c, s = c[None], s[None]
        if neox_style:
            x1, x2 = rot[..., :r2], rot[..., r2:]
            out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
        else:
            x1, x2 = rot[..., 0::2], rot[..., 1::2]
            o1 = x1 * c - x2 * s
            o2 = x2 * c + x1 * s
            out = jnp.stack([o1, o2], axis=-1).reshape(rot.shape)
        return jnp.concatenate([out, rest], axis=-1) if rest.shape[-1] else out
