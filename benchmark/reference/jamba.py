"""Plain reference for the Jamba family (AI21 Jamba / Jamba2 with
``num_experts`` 1): the published block written straight down in
``jax.numpy`` and float32 — no kernel, no cache, no paging, no batching of
rows, and the recurrence as a plain ``lax.scan`` over time (a run of Mamba
layers is a loop over the layer index, so the compiled program holds one
Mamba layer, not 26).  It shares no
code with ``deepspeed_tpu/`` and is what decides ``correct``.

The block (HF ``JambaForCausalLM``; Lieber et al. 2024; Gu & Dao 2023 for
the Mamba-1 mixer), for ``h`` (T, D) and ``RMS(x; w) = x / sqrt(mean(x^2) +
eps) * w``:

- every layer ``l``: ``h += Mixer_l(RMS(h; ln_in))``, then ``h +=
  W_down(silu(W_gate u) * (W_up u))`` with ``u = RMS(h; ln_ff)``; after the
  last layer ``RMS(h; lnf)`` and the tied head ``h @ E^T``.  No positions.
- ``l % attn_layer_period == attn_layer_offset``: causal attention, the
  query heads sharing ``num_key_value_heads`` K/V heads (query head ``h``
  reads K/V head ``h // group``), scores over ``sqrt(head size)``, no biases.
- every other layer, Mamba-1 with Jamba's inner norms: ``[x, z] = W_in u``;
  ``x = silu(conv(x))`` (causal, depthwise, width ``mamba_d_conv``, with
  bias); ``[dt, B, C] = W_x x``; each RMS-normed; ``delta = softplus(W_dt dt
  + b_dt)``; ``A = -exp(A_log)``; ``S_t = exp(delta_t A) S_{t-1} + (delta_t
  x_t) B_t``; ``y_t = S_t C_t + D x_t``; output ``W_out (y * silu(z))``.

Departures from the published orientation, none from the mathematics: the
parameter tree is the program's (``wte``; ``mamba`` / ``attn`` / ``mlp``
stacks in layer order; ``lnf``), in which ``A_log`` is stored ``(N, Di)`` and
the convolution's taps ``(K, Di)`` with tap ``K - 1`` on the current token,
where the published tensors are ``(Di, N)`` and ``(Di, 1, K)``.  Leaves are
upcast to float32 one layer at a time.

On a TPU a float32 matmul runs in lower precision unless told otherwise, so
every entry point runs under ``jax.default_matmul_precision("highest")``.
Rows are computed one after another (``lax.map``), so that the float32
activations of one row, not of the batch, sit beside the weights.
"""

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _layer(tree, i):
    return {k: v[i].astype(_F32) for k, v in tree.items()}


def _attention(cfg, p, u):
    T = u.shape[0]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    q = (u @ p["q_w"]).reshape(T, H, hd)
    # query head h reads K/V head h // (H // Hkv): repeat each K/V head
    k = jnp.repeat((u @ p["k_w"]).reshape(T, Hkv, hd), H // Hkv, axis=1)
    v = jnp.repeat((u @ p["v_w"]).reshape(T, Hkv, hd), H // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return a.reshape(T, H * hd) @ p["o_w"]


def _mamba(cfg, p, u):
    T = u.shape[0]
    N, K, R = (cfg["mamba_d_state"], cfg["mamba_d_conv"],
               cfg["mamba_dt_rank"])
    eps = cfg["rms_norm_eps"]
    xz = u @ p["in_w"]
    Di = xz.shape[-1] // 2
    x, z = xz[:, :Di], xz[:, Di:]
    padded = jnp.concatenate([jnp.zeros((K - 1, Di), _F32), x], axis=0)
    x = p["conv_b"] + sum(padded[k:k + T] * p["conv_w"][k] for k in range(K))
    x = _silu(x)
    dbc = x @ p["x_w"]
    dt = _rms(dbc[:, :R], p["dt_norm"], eps)
    B = _rms(dbc[:, R:R + N], p["b_norm"], eps)
    C = _rms(dbc[:, R + N:], p["c_norm"], eps)
    delta = jax.nn.softplus(dt @ p["dt_w"] + p["dt_b"])          # (T, Di)
    A = -jnp.exp(p["A_log"])                                      # (N, Di)

    def step(S, inp):
        x_t, d_t, b_t, c_t = inp
        S = jnp.exp(d_t[None, :] * A) * S + (d_t * x_t)[None, :] * b_t[:, None]
        return S, (S * c_t[:, None]).sum(0)

    _, y = jax.lax.scan(step, jnp.zeros((N, Di), _F32), (x, delta, B, C))
    y = y + p["D"] * x
    return (y * _silu(z)) @ p["out_w"]


def _mlp(cfg, f, h):
    u = _rms(h, f["ln_ff"], cfg["rms_norm_eps"])
    return h + (_silu(u @ f["gate_w"]) * (u @ f["up_w"])) @ f["down_w"]


def hidden_states_row(cfg, params, tokens):
    """(T,) token ids -> (T, D) after the final RMSNorm.  Layer after
    layer; a run of Mamba layers is a loop over the layer index (one copy
    of the layer's program, not 26: the compiled reference stays small),
    each layer's leaves upcast as it is reached."""
    eps = cfg["rms_norm_eps"]
    L, period, offset = (cfg["num_hidden_layers"], cfg["attn_layer_period"],
                         cfg["attn_layer_offset"])
    is_attn = [l % period == offset for l in range(L)]
    h = params["wte"].astype(_F32)[tokens]
    l = a = m = 0
    while l < L:
        if is_attn[l]:
            p = _layer(params["attn"], a)
            h = h + _attention(cfg, p, _rms(h, p["ln_in"], eps))
            h = _mlp(cfg, _layer(params["mlp"], l), h)
            l, a = l + 1, a + 1
            continue
        run = 1
        while l + run < L and not is_attn[l + run]:
            run += 1

        def mamba_layer(j, h, l=l, m=m):
            p = _layer(params["mamba"], m + j)
            h = h + _mamba(cfg, p, _rms(h, p["ln_in"], eps))
            return _mlp(cfg, _layer(params["mlp"], l + j), h)
        h = jax.lax.fori_loop(0, run, mamba_layer, h)
        l, m = l + run, m + run
    return _rms(h, params["lnf"].astype(_F32), eps)


def logits_at(cfg, params, tokens, positions):
    """Next-token logits (B, V) read at ``positions[b]`` of each row.  Rows
    may be padded on the right: attention is causal and a recurrence runs
    forward, so what follows a position cannot reach it."""
    with jax.default_matmul_precision("highest"):
        def one(row):
            toks, pos = row
            return hidden_states_row(cfg, params, toks)[pos]
        rows = jax.lax.map(one, (tokens, positions))
        return rows @ params["wte"].astype(_F32).T


def loss(cfg, params, batch):
    """Mean next-token cross-entropy of ``batch`` (B, T + 1)."""
    with jax.default_matmul_precision("highest"):
        wte = params["wte"].astype(_F32)

        def one(row):
            h = hidden_states_row(cfg, params, row[:-1])
            logp = jax.nn.log_softmax(h @ wte.T, axis=-1)
            return -jnp.take_along_axis(logp, row[1:, None], axis=-1).mean()
        return jax.lax.map(one, batch).mean()
