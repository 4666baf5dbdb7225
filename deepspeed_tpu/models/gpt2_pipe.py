"""Pipelined GPT-2: the PP×DP configuration.

Role parity: the reference's Megatron-GPT2-over-PipelineModule setup
(reference `PipelineModule` wraps the transformer stack in
`LayerSpec`s).  The embedding runs as the pipeline
prologue, the final-LN + untied head as the epilogue, and the body is one
`LayerSpec` per transformer block over the `pipe` axis.
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .gpt2 import (GPT2Config, PRESETS, _layer_norm, _attention_jnp,
                   gpt2_block_forward)
from ..runtime.pipe.module import PipelineModule, LayerSpec
from ..utils.logging import logger


class GPT2Embedding:
    """Prologue: tokens (B, T) → hidden (B, T, D)."""

    def __init__(self, config: GPT2Config, dtype=jnp.bfloat16):
        self.c = config
        self.dtype = dtype

    def init(self, rng):
        c = self.c
        k1, k2 = jax.random.split(rng)
        return {"wte": jax.random.normal(k1, (c.vocab_size, c.n_embd),
                                         jnp.float32) * 0.02,
                "wpe": jax.random.normal(k2, (c.max_seq, c.n_embd),
                                         jnp.float32) * 0.01}

    def apply(self, params, tokens, rng=None):
        T = tokens.shape[1]
        return (params["wte"].astype(self.dtype)[tokens]
                + params["wpe"].astype(self.dtype)[jnp.arange(T)])

    def partition_specs(self):
        """Vocab-parallel embedding (Megatron ``VocabParallelEmbedding``):
        XLA turns the sharded-table gather into local lookup + collective."""
        return {"wte": P("tensor", None), "wpe": P()}


class GPT2Block:
    """One causal transformer block (layer protocol, (B,T,D) → (B,T,D))."""

    def __init__(self, config: GPT2Config, dtype=jnp.bfloat16):
        self.c = config
        self.dtype = dtype

    def init(self, rng):
        c = self.c
        D = c.n_embd
        k = jax.random.split(rng, 4)
        std, proj_std = 0.02, 0.02 / np.sqrt(2.0 * c.n_layer)
        n = lambda key, shape, s: jax.random.normal(key, shape, jnp.float32) * s
        return {
            "ln1_scale": jnp.ones((D,), jnp.float32),
            "ln1_bias": jnp.zeros((D,), jnp.float32),
            "qkv_w": n(k[0], (D, 3 * D), std),
            "qkv_b": jnp.zeros((3 * D,), jnp.float32),
            "proj_w": n(k[1], (D, D), proj_std),
            "proj_b": jnp.zeros((D,), jnp.float32),
            "ln2_scale": jnp.ones((D,), jnp.float32),
            "ln2_bias": jnp.zeros((D,), jnp.float32),
            "fc_w": n(k[2], (D, 4 * D), std),
            "fc_b": jnp.zeros((4 * D,), jnp.float32),
            "fc_proj_w": n(k[3], (4 * D, D), proj_std),
            "fc_proj_b": jnp.zeros((D,), jnp.float32),
        }

    def apply(self, params, x, rng=None):
        c = self.c
        T = x.shape[1]
        causal = jnp.tril(jnp.ones((T, T), bool))[None, None]
        # Layer protocol: rng=None is the engine's "deterministic" signal
        # (eval_batch) — dropout must not run there.
        deterministic = rng is None
        rng = rng if rng is not None else jax.random.PRNGKey(0)

        def attend(q, k, v, mask, r, det):
            return _attention_jnp(q, k, v, mask, c.attn_pdrop, r, det)

        return gpt2_block_forward(c, params, x, rng, deterministic, causal,
                                  attend)

    def partition_specs(self):
        """Megatron column→row sharding inside the block (PP×TP): attention
        and MLP each do one column-parallel then one row-parallel matmul, so
        the only tensor collective per sub-block is the output reduce."""
        return {
            "ln1_scale": P(), "ln1_bias": P(),
            "qkv_w": P(None, "tensor"), "qkv_b": P("tensor"),
            "proj_w": P("tensor", None), "proj_b": P(),
            "ln2_scale": P(), "ln2_bias": P(),
            "fc_w": P(None, "tensor"), "fc_b": P("tensor"),
            "fc_proj_w": P("tensor", None), "fc_proj_b": P(),
        }


class GPT2Head:
    """Epilogue: hidden → logits (untied head; PP keeps the embedding on
    stage 0 and the head on the last stage)."""

    def __init__(self, config: GPT2Config, dtype=jnp.bfloat16):
        self.c = config
        self.dtype = dtype

    def init(self, rng):
        c = self.c
        return {"lnf_scale": jnp.ones((c.n_embd,), jnp.float32),
                "lnf_bias": jnp.zeros((c.n_embd,), jnp.float32),
                "head_w": jax.random.normal(
                    rng, (c.n_embd, c.vocab_size), jnp.float32) * 0.02}

    def apply(self, params, x, rng=None):
        c = self.c
        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"],
                        c.layer_norm_eps)
        return jnp.einsum("btd,dv->btv", x, params["head_w"].astype(x.dtype),
                          preferred_element_type=jnp.float32)

    def partition_specs(self):
        """Row-parallel LM head: the CONTRACTING (n_embd) dim shards over
        'tensor', so logits are replicated after the reduce and the softmax
        sees a full vocab row.  (Megatron's vocab-parallel column head —
        ``P(None, 'tensor')`` — trips an XLA SPMD-partitioner CHECK
        (spmd_partitioner_util.cc:495) when partitioned inside the
        manual-'pipe' shard_map region, so the row layout is the TPU-safe
        choice here.)"""
        return {"lnf_scale": P(), "lnf_bias": P(),
                "head_w": P("tensor", None)}


def lm_loss(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    return -jnp.mean(ll)


def gpt2_pipeline(preset="gpt2-125m", num_stages=2, dtype=jnp.bfloat16,
                  partition_method="parameters", **overrides):
    """Build a PipelineModule for a GPT-2 preset.

    Feed it (tokens[:, :-1], tokens[:, 1:]) batches; the engine runs the
    1F1B schedule over the mesh `pipe` axis.
    """
    base = dict(PRESETS[preset])
    base.update(overrides)
    config = GPT2Config(**base)
    if config.embd_pdrop > 0.0:
        # per-layer dropout inside blocks works (rng threads through apply);
        # embedding dropout would live in the prologue, which has no rng —
        # zero it loudly rather than silently diverging from the DP model
        logger.warning("gpt2_pipeline: embd_pdrop is not applied in the "
                       "pipeline prologue; setting it to 0")
        config.embd_pdrop = 0.0
    specs = [LayerSpec(GPT2Block, config, dtype)
             for _ in range(config.n_layer)]
    return PipelineModule(
        layers=specs, num_stages=num_stages, loss_fn=lm_loss,
        partition_method=partition_method,
        prologue=GPT2Embedding(config, dtype),
        epilogue=GPT2Head(config, dtype))
