"""Fused transformer training layer.

Parity: reference ``deepspeed/ops/transformer/transformer.py``
(``DeepSpeedTransformerConfig`` :39, ``DeepSpeedTransformerLayer`` :460) and
the CUDA kernel stack behind it (``csrc/transformer/ds_transformer_cuda.cpp``:
fused LN(+residual), QKV gemm, softmax(+mask), dropout with saved mask, GELU,
stochastic mode).

TPU re-design (SURVEY.md §2.4 / §8.2): the whole layer is ONE jitted function
— XLA fuses bias/gelu/dropout/residual into the matmuls, and the attention
core is the Pallas flash kernel — so the reference's hand-scheduled kernel
graph collapses into compiler output. The memory/recompute knobs become
`jax.checkpoint` (remat) regions instead of kernel variants:

  - ``normalize_invertible``  (drop LN inputs, recompute in bwd)  → remat of
    the whole layer body
  - ``attn_dropout_checkpoint`` (drop attn context, recompute)    → remat of
    the attention block
  - ``gelu_checkpoint``       (drop gelu output, recompute)       → remat of
    the MLP block
  - ``stochastic_mode``       (CUDA non-determinism for speed)    → no-op:
    XLA is deterministic at equal speed

Parameter names match the reference layer's state dict (``attn_qkvw`` …
``norm_b``) so weights round-trip 1:1 with HF-BERT conversion utilities.
"""

import math
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp


class DeepSpeedTransformerConfig:
    """Mirrors reference ``DeepSpeedTransformerConfig`` (:39) fields."""

    layer_id_counter = 0

    def __init__(self, batch_size=-1, hidden_size=-1, intermediate_size=-1,
                 heads=-1, attn_dropout_ratio=-1, hidden_dropout_ratio=-1,
                 num_hidden_layers=-1, initializer_range=0.02,
                 layer_norm_eps=1e-12, local_rank=-1, seed=-1, fp16=False,
                 pre_layer_norm=True, normalize_invertible=False,
                 gelu_checkpoint=False, adjust_init_range=True,
                 attn_dropout_checkpoint=False, stochastic_mode=False,
                 return_tuple=False, training=True, huggingface=False):
        self.batch_size = batch_size
        self.hidden_size = hidden_size
        self.intermediate_size = (intermediate_size if intermediate_size > 0
                                  else 4 * hidden_size)
        self.heads = heads
        self.attn_dropout_ratio = max(0.0, attn_dropout_ratio)
        self.hidden_dropout_ratio = max(0.0, hidden_dropout_ratio)
        self.num_hidden_layers = num_hidden_layers
        self.initializer_range = initializer_range
        self.layer_norm_eps = layer_norm_eps
        self.local_rank = local_rank
        self.seed = seed
        self.fp16 = fp16
        self.pre_layer_norm = pre_layer_norm
        self.normalize_invertible = normalize_invertible
        self.gelu_checkpoint = gelu_checkpoint
        self.adjust_init_range = adjust_init_range
        self.attn_dropout_checkpoint = attn_dropout_checkpoint
        self.stochastic_mode = stochastic_mode
        self.return_tuple = return_tuple
        self.training = training
        self.huggingface = huggingface

    @classmethod
    def from_dict(cls, d):
        return cls(**d)


def _layer_norm(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * w + b).astype(x.dtype)


def _dropout(x, rate, rng, training):
    if not training or rate <= 0.0 or rng is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


class DeepSpeedTransformerLayer:
    """One BERT-style encoder layer (functional: ``init`` / ``apply``)."""

    def __init__(self, config: DeepSpeedTransformerConfig, layer_id=None):
        self.config = config
        if layer_id is None:
            layer_id = DeepSpeedTransformerConfig.layer_id_counter
            DeepSpeedTransformerConfig.layer_id_counter += 1
        self.layer_id = layer_id

    # --------------------------------------------------------------- params
    def init(self, rng):
        cfg = self.config
        H, I = cfg.hidden_size, cfg.intermediate_size
        std = cfg.initializer_range
        # reference adjust_init_range: output-projection std /= sqrt(2*L)
        # (transformer.py:118-124 "num_layers is adjusted for the residual")
        out_std = std
        if cfg.adjust_init_range and cfg.num_hidden_layers > 0:
            out_std = std / math.sqrt(2.0 * cfg.num_hidden_layers)
        ks = jax.random.split(rng, 4)
        norm = lambda k, shape, s: jax.random.normal(k, shape, jnp.float32) * s
        return {
            "attn_qkvw": norm(ks[0], (H, 3 * H), std),
            "attn_qkvb": jnp.zeros((3 * H,), jnp.float32),
            "attn_ow": norm(ks[1], (H, H), out_std),
            "attn_ob": jnp.zeros((H,), jnp.float32),
            "attn_nw": jnp.ones((H,), jnp.float32),
            "attn_nb": jnp.zeros((H,), jnp.float32),
            "inter_w": norm(ks[2], (H, I), std),
            "inter_b": jnp.zeros((I,), jnp.float32),
            "output_w": norm(ks[3], (I, H), out_std),
            "output_b": jnp.zeros((H,), jnp.float32),
            "norm_w": jnp.ones((H,), jnp.float32),
            "norm_b": jnp.zeros((H,), jnp.float32),
        }

    # -------------------------------------------------------------- forward
    def _attention(self, params, x, mask, rng, training):
        cfg = self.config
        B, S, H = x.shape
        nh = cfg.heads
        hd = H // nh
        qkv = x @ params["attn_qkvw"].astype(x.dtype) \
            + params["attn_qkvb"].astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        # model layout (B, S, heads, head_dim) — what flash_attention expects
        shape = lambda t: t.reshape(B, S, nh, hd)
        q, k, v = shape(q), shape(k), shape(v)

        use_flash = (mask is None and cfg.attn_dropout_ratio == 0.0
                     and _flash_ok())
        if use_flash:
            from .flash_attention import flash_attention
            ctx = flash_attention(q, k, v, causal=False,
                                  sm_scale=1.0 / math.sqrt(hd))
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                                preferred_element_type=jnp.float32)
            scores = scores / math.sqrt(hd)
            if mask is not None:
                scores = scores + mask.astype(scores.dtype)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            if training and cfg.attn_dropout_ratio > 0.0 and rng is not None:
                probs = _dropout(probs, cfg.attn_dropout_ratio,
                                 jax.random.fold_in(rng, 1), training)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        ctx = ctx.reshape(B, S, H)
        out = ctx @ params["attn_ow"].astype(x.dtype) \
            + params["attn_ob"].astype(x.dtype)
        return _dropout(out, cfg.hidden_dropout_ratio,
                        jax.random.fold_in(rng, 2) if rng is not None else None,
                        training)

    def _mlp(self, params, x, rng, training):
        cfg = self.config
        inter = x @ params["inter_w"].astype(x.dtype) \
            + params["inter_b"].astype(x.dtype)
        inter = jax.nn.gelu(inter, approximate=False)
        out = inter @ params["output_w"].astype(x.dtype) \
            + params["output_b"].astype(x.dtype)
        return _dropout(out, cfg.hidden_dropout_ratio,
                        jax.random.fold_in(rng, 3) if rng is not None else None,
                        training)

    def apply(self, params, hidden_states, attention_mask=None, rng=None,
              training=None):
        """hidden_states: (B, S, H); attention_mask: additive (B,1,1,S) or
        (B,1,S,S) mask in the reference/HF convention."""
        cfg = self.config
        training = cfg.training if training is None else training
        eps = cfg.layer_norm_eps

        def attn_block(p, x):
            if cfg.pre_layer_norm:
                h = _layer_norm(x, p["attn_nw"], p["attn_nb"], eps)
                return x + self._attention(p, h, attention_mask, rng, training)
            a = self._attention(p, x, attention_mask, rng, training)
            return _layer_norm(x + a, p["attn_nw"], p["attn_nb"], eps)

        def mlp_block(p, x):
            if cfg.pre_layer_norm:
                h = _layer_norm(x, p["norm_w"], p["norm_b"], eps)
                return x + self._mlp(p, h, rng, training)
            m = self._mlp(p, x, rng, training)
            return _layer_norm(x + m, p["norm_w"], p["norm_b"], eps)

        if cfg.attn_dropout_checkpoint:
            attn_block = jax.checkpoint(attn_block)
        if cfg.gelu_checkpoint:
            mlp_block = jax.checkpoint(mlp_block)

        def body(p, x):
            return mlp_block(p, attn_block(p, x))

        if cfg.normalize_invertible:
            body = jax.checkpoint(body)

        out = body(params, hidden_states)
        return (out,) if cfg.return_tuple else out

    # torch-style alias
    def forward(self, params, hidden_states, attention_mask=None, rng=None,
                training=None):
        return self.apply(params, hidden_states, attention_mask, rng, training)

    # layer protocol used by PipelineModule/models
    def __call__(self, params, hidden_states, **kw):
        return self.apply(params, hidden_states, **kw)


def _flash_ok():
    """Pallas flash path: TPU backend (the kernel pads ragged seq/head
    shapes internally; see flash_attention._fwd)."""
    from ... import ops as _ops
    return _ops.flash_attention_available()
