"""Plain reference for the ``phi4flash`` family (Phi-4-mini-flash-reasoning;
SambaY, arXiv:2507.06607, with differential attention, arXiv:2410.05258): the
published block written straight down in ``jax.numpy`` and float32 — no
kernel, no cache, no paging, no batching of rows, every layer at every
position, the recurrence a plain ``lax.scan`` over tokens, attention in blocks
of query rows so that an 8k row's scores fit.  It shares no code with
``deepspeed_tpu/`` and is what decides ``correct``.

For ``h`` (T, D), ``LN(x; w, b) = (x - mean) / sqrt(var + eps) * w + b`` and
layer ``l`` of ``L`` (32):

- every layer: ``h += Mix_l(LN(h; ln1))``, then ``[g | y] = fc1(LN(h; ln2))``
  (gate first, no bias), ``h += fc2(silu(g) * y)``; after the last layer
  ``LN(h; lnf) @ E^T`` (tied).  No positions anywhere.
- ``l`` even, ``l <= L/2``: Mamba-1.  ``[x | z] = W_in u``; ``x =
  silu(conv(x) + b)`` (causal, depthwise, width 4); ``[dt | B | C] = W_x x``
  (NO norm on any of them: Jamba has three); ``delta = softplus(W_dt dt +
  b_dt)``; ``A = -exp(A_log)``; ``S_t = exp(delta_t A) S_{t-1} + (delta_t x_t)
  B_t``; ``y_t = S_t C_t + D x_t``; output ``W_out (y * silu(z))``.  Layer
  ``L/2`` also hands ``m_t = y_t`` (before the gate) to the GMUs.
- ``l`` odd, ``l < L/2``: differential attention, position ``t`` seeing keys
  ``t - W + 1 .. t`` (``sliding_window`` keys counting its own).
- ``l = L/2 + 1``: differential attention over all keys ``<= t``; its ``k``
  and ``v`` are what the cross layers read.
- ``l`` even, ``l >= L/2 + 2``: Gated Memory Unit, ``W_2 (m_t * silu(W_1
  u_t))``.
- ``l`` odd, ``l >= L/2 + 3``: differential CROSS attention: ``q = W_q u + b``
  of its own, layer ``L/2 + 1``'s ``k`` and ``v``, causal.
- differential attention: query heads ``2j, 2j + 1`` are ``q1_j, q2_j``, K/V
  heads ``2g, 2g + 1`` are ``k1_g, k2_g, v1_g, v2_g``, ``g = j // (H / Hkv)``;
  ``o_j = (softmax(q1 k1^T / sqrt(hd)) - lambda softmax(q2 k2^T / sqrt(hd)))
  [v1_g | v2_g]``; ``o_j = RMS(o_j; sub_w, eps) (1 - lambda_init)``; ``lambda
  = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6
  exp(-0.3 l)``; the pairs' outputs concatenated go through ``W_o + b_o``.

Departures from the published orientation, none from the mathematics: the
parameter tree is the program's (``wte``; ``mamba`` / ``attn`` / ``gmu`` /
``cross`` / ``mlp`` stacks in layer order; ``lnf_w``, ``lnf_b``), in which
``A_log`` is stored ``(N, Di)`` and the convolution's taps ``(K, Di)`` with tap
``K - 1`` on the current token.  Leaves are upcast to float32 one layer at a
time; a run of like layers is a loop over the layer index, so the compiled
reference holds one copy of each kind.

On a TPU a float32 matmul runs in lower precision unless told otherwise, so
every entry point runs under ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
_QUERY_ROWS = 256       # query rows whose scores stand at once


def _ln(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _layer(tree, i):
    return {k: v[i].astype(_F32) for k, v in tree.items()}


def _sizes(cfg):
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return H, Hkv, cfg["hidden_size"] // H


def _differential(cfg, p, l, q, k, v, window):
    """``q`` (T, H, hd), ``k`` / ``v`` (T, Hkv, hd) -> (T, D): the pairs'
    two softmax maps, their difference over the shared values, the norm, the
    output projection.  ``l``: the layer's index (may be traced)."""
    H, Hkv, hd = _sizes(cfg)
    T = q.shape[0]
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, _F32))
    lam = (jnp.exp((p["lq1"] * p["lk1"]).sum())
           - jnp.exp((p["lq2"] * p["lk2"]).sum()) + lam0)
    per = H // Hkv                      # pairs that read one K/V pair
    q1, q2 = q[:, 0::2], q[:, 1::2]                             # (T, H/2, hd)
    k1 = jnp.repeat(k[:, 0::2], per, axis=1)
    k2 = jnp.repeat(k[:, 1::2], per, axis=1)
    vv = jnp.repeat(jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1),
                    per, axis=1)                                # (T, H/2, 2hd)
    bq = min(_QUERY_ROWS, T)
    nq = -(-T // bq)
    padq = lambda x: jnp.pad(x, ((0, nq * bq - T), (0, 0), (0, 0)))
    q1, q2 = padq(q1), padq(q2)
    s_pos = jnp.arange(T)[None, :]

    def block(i):
        t = i * bq + jnp.arange(bq)[:, None]
        seen = s_pos <= t
        if window is not None:
            seen &= t - s_pos < window
        cut = lambda x: jax.lax.dynamic_slice_in_dim(x, i * bq, bq, axis=0)

        def probs(qq, kk):
            s = jnp.einsum("qjd,kjd->jqk", cut(qq), kk) / np.sqrt(hd)
            return jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        a = probs(q1, k1) - lam * probs(q2, k2)
        return jnp.einsum("jqk,kje->qje", a, vv)
    o = jax.lax.map(block, jnp.arange(nq)).reshape(nq * bq, H // 2,
                                                   2 * hd)[:T]
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True)
                     + cfg["layer_norm_eps"]) * p["sub_w"] * (1.0 - lam0)
    return o.reshape(T, H * hd) @ p["o_w"] + p["o_b"]


def _attention(cfg, p, l, u, window):
    """A layer with K/V of its own.  Returns ``(out (T, D), k, v)``."""
    H, Hkv, hd = _sizes(cfg)
    T = u.shape[0]
    qkv = u @ p["qkv_w"] + p["qkv_b"]
    q = qkv[:, :H * hd].reshape(T, H, hd)
    k = qkv[:, H * hd:(H + Hkv) * hd].reshape(T, Hkv, hd)
    v = qkv[:, (H + Hkv) * hd:].reshape(T, Hkv, hd)
    return _differential(cfg, p, l, q, k, v, window), k, v


def _mamba(cfg, p, u):
    """Returns ``(out (T, D), y (T, Di))``, ``y`` before the gate."""
    T = u.shape[0]
    N, K, R = cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    xz = u @ p["in_w"]
    Di = xz.shape[-1] // 2
    x, z = xz[:, :Di], xz[:, Di:]
    padded = jnp.concatenate([jnp.zeros((K - 1, Di), _F32), x], axis=0)
    x = p["conv_b"] + sum(padded[k:k + T] * p["conv_w"][k] for k in range(K))
    x = _silu(x)
    dbc = x @ p["x_w"]
    dt, B, C = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    delta = jax.nn.softplus(dt @ p["dt_w"] + p["dt_b"])          # (T, Di)
    A = -jnp.exp(p["A_log"])                                      # (N, Di)

    def step(S, inp):
        x_t, d_t, b_t, c_t = inp
        S = jnp.exp(d_t[None, :] * A) * S + (d_t * x_t)[None, :] * b_t[:, None]
        return S, (S * c_t[:, None]).sum(0)

    _, y = jax.lax.scan(step, jnp.zeros((N, Di), _F32), (x, delta, B, C))
    y = y + p["D"] * x
    return (y * _silu(z)) @ p["out_w"], y


def _mlp(cfg, f, h):
    gy = _ln(h, f["ln_w"], f["ln_b"], cfg["layer_norm_eps"]) @ f["fc1_w"]
    F = gy.shape[-1] // 2
    return h + (_silu(gy[:, :F]) * gy[:, F:]) @ f["fc2_w"]


def hidden_states_row(cfg, params, tokens):
    """(T,) token ids -> (T, D) after the final LayerNorm."""
    cfg = with_assumed(cfg)
    eps = cfg["layer_norm_eps"]
    L = cfg["num_hidden_layers"]
    half = L // 2
    mlp = lambda l, h: _mlp(cfg, _layer(params["mlp"], l), h)
    norm = lambda p, h: _ln(h, p["ln_w"], p["ln_b"], eps)
    h = params["wte"][tokens].astype(_F32)

    def self_pair(i, h):
        p = _layer(params["mamba"], i)
        h = mlp(2 * i, h + _mamba(cfg, p, norm(p, h))[0])
        p = _layer(params["attn"], i)
        out, _, _ = _attention(cfg, p, 2 * i + 1, norm(p, h),
                               cfg["sliding_window"])
        return mlp(2 * i + 1, h + out)
    h = jax.lax.fori_loop(0, half // 2, self_pair, h)

    p = _layer(params["mamba"], half // 2)
    out, m = _mamba(cfg, p, norm(p, h))
    h = mlp(half, h + out)
    p = _layer(params["attn"], half // 2)
    out, k, v = _attention(cfg, p, half + 1, norm(p, h), None)
    h = mlp(half + 1, h + out)

    H, _, hd = _sizes(cfg)

    def cross_pair(j, h):
        l = half + 2 + 2 * j
        p = _layer(params["gmu"], j)
        h = mlp(l, h + (m * _silu(norm(p, h) @ p["in_w"])) @ p["out_w"])
        p = _layer(params["cross"], j)
        q = (norm(p, h) @ p["q_w"] + p["q_b"]).reshape(-1, H, hd)
        return mlp(l + 1, h + _differential(cfg, p, l + 1, q, k, v, None))
    h = jax.lax.fori_loop(0, half // 2 - 1, cross_pair, h)
    return _ln(h, params["lnf_w"].astype(_F32), params["lnf_b"].astype(_F32),
               eps)


def with_assumed(cfg):
    """The configuration with the sizes its ``assumed`` block states put
    beside the published keys (the published file gives no Mamba size)."""
    return {**{k: v["value"] for k, v in cfg.get("assumed", {}).items()
               if isinstance(v, dict) and "value" in v}, **cfg}


def logits_at(cfg, params, tokens, positions):
    """Next-token logits (B, V) read at ``positions[b]`` of each row, or (B,
    P, V) for ``positions`` (B, P): one pass a row, read P times.  Rows may
    be padded on the right: attention is causal and a recurrence runs
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
