"""Plain reference for the GPT-2 family (GPT-2 Large, Cerebras-GPT): the
published block written straight down in ``jax.numpy`` and float32 — no
kernel, no cache, no batching tricks, no scan.  It is independent of
``deepspeed_tpu/models/gpt2.py`` and is what decides ``correct``.

The block (Radford et al. 2019; HF ``GPT2LMHeadModel``): learned absolute
positions, pre-LayerNorm, fused QKV, causal softmax attention scaled by
1/sqrt(head size), 4x MLP, tied output head.  One departure from the
Cerebras-GPT config, noted in its configuration file: the activation is the
tanh form of GELU for both models, because that is what the program runs.

On a TPU a float32 matmul runs in lower precision unless told otherwise, so
every entry point here runs under ``jax.default_matmul_precision("highest")``.
The parameter tree is the program's (``wte``, ``wpe``, stacked ``blocks``,
``lnf_*``), upcast to float32 leaf by leaf as each layer needs it.
"""

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32


def _layer_norm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale.astype(_F32) \
        + bias.astype(_F32)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def hidden_states(cfg, params, tokens):
    """(B, T) token ids -> (B, T, n_embd) after the final LayerNorm."""
    B, T = tokens.shape
    H = cfg["n_head"]
    D = cfg["n_embd"]
    eps = cfg["layer_norm_epsilon"]
    x = params["wte"].astype(_F32)[tokens] + params["wpe"].astype(_F32)[:T]
    causal = jnp.tril(jnp.ones((T, T), bool))
    blocks = params["blocks"]
    for i in range(cfg["n_layer"]):
        p = {k: v[i].astype(_F32) for k, v in blocks.items()}
        h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"], eps)
        q, k, v = jnp.split(h @ p["qkv_w"] + p["qkv_b"], 3, axis=-1)
        q, k, v = (t.reshape(B, T, H, D // H) for t in (q, k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D // H)
        s = jnp.where(causal, s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + a.reshape(B, T, D) @ p["proj_w"] + p["proj_b"]
        h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], eps)
        h = _gelu_tanh(h @ p["fc_w"] + p["fc_b"])
        x = x + h @ p["fc_proj_w"] + p["fc_proj_b"]
    return _layer_norm(x, params["lnf_scale"], params["lnf_bias"], eps)


def logits_at(cfg, params, tokens, positions):
    """Next-token logits (B, V) read at ``positions[b]`` of each row.  Rows
    may be padded on the right: under a causal mask what follows a position
    cannot reach it."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(cfg, params, tokens)
        rows = h[jnp.arange(h.shape[0]), positions]
        return rows @ params["wte"].astype(_F32).T


def loss(cfg, params, batch):
    """Mean next-token cross-entropy of ``batch`` (B, T + 1)."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(cfg, params, batch[:, :-1])
        logits = h @ params["wte"].astype(_F32).T
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, batch[:, 1:, None], axis=-1)
        return -picked.mean()
