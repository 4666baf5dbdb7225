"""Plain reference for the Ouro family (ByteDance Ouro 1.4B / 2.6B LoopLM):
the published forward written straight down in ``jax.numpy`` and float32 —
no kernel, no cache, no paging, no batching of rows.  It shares no code with
``deepspeed_tpu/`` and is what decides ``correct``.

The forward (HF ``OuroForCausalLM``; arXiv:2510.25741), for ``h`` (T, D),
``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w``, ``L = num_hidden_layers`` and
``R = total_ut_steps``::

    h = E[tokens]
    for r in 0..R-1:                    the SAME L layers' weights each time
      for l in 0..L-1:
        a = RMS(h; ln_in_l)
        q, k, v = rope(a Wq_l), rope(a Wk_l), a Wv_l
        h = h + RMS(softmax(q k^T / sqrt(head_dim) + causal) v Wo_l;
                    ln_attn_out_l)
        h = h + RMS(W_down(silu(W_gate m) * (W_up m)); ln_ff_out_l)
                                        m = RMS(h; ln_ff_l)
      h = RMS(h; lnf)                   the final norm closes every loop
      lambda_r = sigmoid(h . exit_w + exit_b)
    logits = h @ head^T                 after loop R - 1

``rope`` turns the pairs ``(x[i], x[i + head_dim/2])`` of every head by the
angle ``position * rope_theta^(-2i / head_dim)`` (rotate-half, the whole
head, no scaling); the angles are worked in float64 from the row's own
positions ``0..T-1``.  No biases; the query heads share
``num_key_value_heads`` K/V heads (16 of 16 as published).

Departures from the published description:

- ``early_exit_threshold`` is 1 (as published), so no token leaves early:
  every token runs all ``R`` loops and ``lambda_r`` enters no logit.  The
  gates are returned beside the hidden states so that they are tested.
- ``loss`` is next-token cross-entropy on the last loop's logits.  The
  paper trains on the expected loss over the exit distribution with an
  entropy term whose weight the config does not give.
- The sandwich norms (``ln_attn_out``, ``ln_ff_out``), the final norm
  closing every loop and the gate's form were typed without a network from
  the model's description; the configuration file lists them as assumed.
- The parameter tree is the program's (``wte``; ``blocks`` stacked in
  layer order; ``lnf``; ``exit_w``, ``exit_b``; ``head``), in which ``q_w``
  and ``k_w`` are stored (out, in) as published and the other matrices
  (in, out).  Leaves are
  upcast to float32 one layer at a time, so that on the chip the reference
  fits beside the bfloat16 weights.

On a TPU a float32 matmul runs in lower precision unless told otherwise, so
every entry point runs under ``jax.default_matmul_precision("highest")``.
Rows are computed one after another (``lax.map``); the loops are a scan and
the layers of a loop a loop over the layer index (the compiled reference
holds one layer's program, not 192).
"""

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rope_tables(cfg, T):
    """cos and sin, (T, 1, head_dim / 2) float32, of the angles at positions
    ``0..T-1``, worked in float64."""
    hd = cfg["head_dim"]
    inv = float(cfg["rope_theta"]) ** (-np.arange(0, hd, 2, dtype=np.float64)
                                       / hd)
    ang = np.arange(T, dtype=np.float64)[:, None, None] * inv
    return jnp.asarray(np.cos(ang), _F32), jnp.asarray(np.sin(ang), _F32)


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, p, h, cos, sin):
    T = h.shape[0]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    a = _rms(h, p["ln_in"], eps)
    q = _rope((a @ p["q_w"].T).reshape(T, H, hd), cos, sin)
    k = _rope((a @ p["k_w"].T).reshape(T, Hkv, hd), cos, sin)
    v = (a @ p["v_w"]).reshape(T, Hkv, hd)
    # query head h reads K/V head h // (H // Hkv)
    k, v = jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    h = h + _rms(o.reshape(T, H * hd) @ p["o_w"], p["ln_attn_out"], eps)
    m = _rms(h, p["ln_ff"], eps)
    return h + _rms((_silu(m @ p["gate_w"]) * (m @ p["up_w"])) @ p["down_w"],
                    p["ln_ff_out"], eps)


def hidden_states_row(cfg, params, tokens):
    """(T,) token ids -> ``(h (T, D) after the last loop's final norm, the
    gates (R, T))``.  Each layer's leaves are upcast as it is reached."""
    eps = cfg["rms_norm_eps"]
    cos, sin = _rope_tables(cfg, tokens.shape[0])
    lnf = params["lnf"].astype(_F32)
    h = params["wte"][tokens].astype(_F32)

    def layer(l, h):
        p = {k: w[l].astype(_F32) for k, w in params["blocks"].items()}
        return _layer(cfg, p, h, cos, sin)

    def loop(h, _):
        h = _rms(jax.lax.fori_loop(0, cfg["num_hidden_layers"], layer, h),
                 lnf, eps)
        return h, jax.nn.sigmoid(h @ params["exit_w"].astype(_F32)
                                 + params["exit_b"].astype(_F32))
    return jax.lax.scan(loop, h, None, length=cfg["total_ut_steps"])


def gates(cfg, params, tokens):
    """The exit gate's values after each loop, (R, B, T)."""
    with jax.default_matmul_precision("highest"):
        lam = jax.lax.map(lambda row: hidden_states_row(cfg, params, row)[1],
                          tokens)
        return jnp.swapaxes(lam, 0, 1)


def logits_at(cfg, params, tokens, positions):
    """Next-token logits (B, V) read at ``positions[b]`` of each row.  Rows
    may be padded on the right: attention is causal, so what follows a
    position cannot reach it."""
    with jax.default_matmul_precision("highest"):
        def one(row):
            toks, pos = row
            return hidden_states_row(cfg, params, toks)[0][pos]
        rows = jax.lax.map(one, (tokens, positions))
        return rows @ params["head"].astype(_F32).T


def loss(cfg, params, batch):
    """Mean next-token cross-entropy of ``batch`` (B, T + 1) on the last
    loop's logits."""
    with jax.default_matmul_precision("highest"):
        head = params["head"].astype(_F32)

        def one(row):
            h, _ = hidden_states_row(cfg, params, row[:-1])
            logp = jax.nn.log_softmax(h @ head.T, axis=-1)
            return -jnp.take_along_axis(logp, row[1:, None], axis=-1).mean()
        return jax.lax.map(one, batch).mean()
