"""Plain reference for the EvaByte family (EvaByte/EvaByte 6.5B, a byte-level
decoder with EVA attention): the forward written straight down in
``jax.numpy`` and float32 — no kernel, no cache, no paging, no fold kept from
one call to the next.  It shares no code with ``deepspeed_tpu/`` and is what
decides ``correct``.

The forward (EVA, arXiv:2302.04542, "Efficient Attention via Control
Variates", in the learned deterministic form of the public ``eva.py``), for
``h`` (T, D), ``RMS0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)``
(``norm_add_unit_offset``), a head of ``hd = hidden_size /
num_attention_heads``, ``s = hd^-1/2``, byte position ``t``, window ``W(t) =
t // window_size`` and chunk ``c = t // chunk_size``::

    x = RMS0(h; ln_in_l)
    q_t = rope_t(Wq x_t)    k_t = rope_t(Wk x_t)    v_t = Wv x_t
    for every chunk c of a whole window:
        k~_c  = mean_{m in c} k_m + mu_l              mu, phi: (H, hd)
        a_c,m = softmax_{m in c}(k_m . phi_l)
        b^_c  = sum_{m in c} a_c,m v_m
    E_t = {m : W(m) = W(t), m <= t}     S_t = {c : c's window < W(t)}
    Z_t = sum_{E_t} exp(s q_t.k_m) + sum_{S_t} exp(s q_t.k~_c)
    o_t = (sum_{E_t} exp(s q_t.k_m) v_m + sum_{S_t} exp(s q_t.k~_c) b^_c) / Z_t
    h = h + Wo o ;  h = h + W_down(silu(W_gate m) * (W_up m)),  m = RMS0(h; ln_ff_l)
    logits = RMS0(h; lnf) head^T viewed (num_pred_heads, V): head j reads byte
    t + 1 + j

``rope`` turns the pairs ``(x[i], x[i + hd/2])`` of every head by the angle
``position * rope_theta^(-2i / hd)`` (rotate-half, all ``hd`` dims, no
scaling); the angles are worked in float64.  No biases.

Departures from the public files, each typed without a network from the
paper and the configuration's keys (the configuration file lists them under
``assumed``):

- rotary positions are applied BEFORE a chunk's keys are pooled (a cache
  that keeps rotated keys can fold them later; pooling unrotated keys would
  need them kept twice);
- no scale inside ``a`` (``k . phi`` as it is);
- a window's own chunks are never read as summaries while it is the current
  window: ``S_t`` holds windows strictly before ``W(t)``;
- ``phi`` and ``mu`` are drawn normal(1) clipped to +-1 and ``Wk`` so that a
  key has unit scale: against such keys ``k . phi`` has a deviation near 8,
  a chunk's largest ``a`` is many times its smallest, and a mean-pooled value
  does not pass for ``b^`` (with ``phi`` at the matrices' 0.02 every ``a`` is
  1/16 and it would);
- the 8 heads' rows in ``head``: row ``j * V + v`` is head ``j``, byte ``v``;
- ``fp32_skip_add``, ``fp32_logits``, ``mixedp_attn`` read as: float32
  residual stream, float32 logits, float32 softmax over model-dtype products
  (here everything is float32);
- ``loss`` is next-byte cross-entropy on head 0 (the public model was
  trained on all 8 heads; their weights in the loss are not in the config);
- the parameter tree is the program's (``wte``; ``blocks`` stacked in layer
  order, ``q_w``, ``k_w`` and ``o_w`` stored (out, in), the other matrices
  (in, out); ``lnf``; ``head``).  Leaves are upcast to float32 one layer at
  a time, so that on the chip the reference fits beside the bfloat16 weights.

On a TPU a float32 matmul runs in lower precision unless told otherwise, so
every entry point runs under ``jax.default_matmul_precision(precision)``,
``"highest"`` unless the caller states another (``benchmark/
control_evabyte.py``'s witness states ``"bfloat16"``: what the program's
matmuls are).  A row is computed a layer at a time and, within a layer, a
WINDOW at a time (``lax.map``): the scores of one window against the
summaries before it and itself are the largest array held, so a row of
26,000 bytes fits the chip.  Rows are computed one after another.
"""

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32


def _rms0(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + w)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _rope_tables(cfg, T):
    """cos and sin, (T, 1, hd / 2) float32, of the angles at positions
    ``0..T-1``, worked in float64."""
    hd = head_dim(cfg)
    inv = float(cfg["rope_theta"]) ** (-np.arange(0, hd, 2, dtype=np.float64)
                                       / hd)
    ang = np.arange(T, dtype=np.float64)[:, None, None] * inv
    return jnp.asarray(np.cos(ang), _F32), jnp.asarray(np.sin(ang), _F32)


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def summarise(cfg, k, v, phi, mu):
    """The summaries of whole chunks: ``k``, ``v`` (n, H, hd) with ``n`` a
    multiple of ``chunk_size`` -> ``(k~, b^)``, (n / chunk_size, H, hd)
    each."""
    C = cfg["chunk_size"]
    kc = k.reshape((-1, C) + k.shape[1:])
    vc = v.reshape((-1, C) + v.shape[1:])
    a = jax.nn.softmax((kc * phi).sum(-1), axis=1)        # (chunks, C, H)
    return kc.mean(axis=1) + mu, (a[..., None] * vc).sum(axis=1)


def _layer(cfg, p, h, cos, sin):
    """One layer over ``h`` (n_win, W, D), the row cut into whole windows;
    ``cos``, ``sin`` (n_win, W, 1, hd / 2)."""
    n_win, W, D = h.shape
    H, hd = cfg["num_attention_heads"], head_dim(cfg)
    eps = cfg["rms_norm_eps"]
    per_window = W // cfg["chunk_size"]

    def qkv(xs):
        h_j, cos_j, sin_j = xs
        x = _rms0(h_j, p["ln_in"], eps)
        return (_rope((x @ p["q_w"].T).reshape(W, H, hd), cos_j, sin_j),
                _rope((x @ p["k_w"].T).reshape(W, H, hd), cos_j, sin_j),
                (x @ p["v_w"]).reshape(W, H, hd))
    q, k, v = jax.lax.map(qkv, (h, cos, sin))
    k_sum, v_sum = summarise(cfg, k.reshape(n_win * W, H, hd),
                             v.reshape(n_win * W, H, hd), p["phi"], p["mu"])
    chunk_window = jnp.arange(n_win * per_window) // per_window
    causal = jnp.tril(jnp.ones((W, W), bool))

    def attend(xs):
        j, q_j, k_j, v_j = xs
        s_own = jnp.where(causal, jnp.einsum("qhd,khd->hqk", q_j, k_j)
                          / np.sqrt(hd), -jnp.inf)
        s_sum = jnp.where(chunk_window < j,
                          jnp.einsum("qhd,chd->hqc", q_j, k_sum)
                          / np.sqrt(hd), -jnp.inf)
        w = jax.nn.softmax(jnp.concatenate([s_sum, s_own], -1), axis=-1)
        n = s_sum.shape[-1]
        return (jnp.einsum("hqc,chd->qhd", w[..., :n], v_sum)
                + jnp.einsum("hqk,khd->qhd", w[..., n:], v_j))
    o = jax.lax.map(attend, (jnp.arange(n_win), q, k, v))

    def rest(xs):
        h_j, o_j = xs
        h_j = h_j + o_j.reshape(W, H * hd) @ p["o_w"]
        m = _rms0(h_j, p["ln_ff"], eps)
        return h_j + (_silu(m @ p["gate_w"]) * (m @ p["up_w"])) @ p["down_w"]
    return jax.lax.map(rest, (h, o))


def hidden_states_row(cfg, params, tokens):
    """(T,) byte ids -> the hidden states (T, D) before the final norm.  The
    row is padded on the right to whole windows: attention is causal inside
    a window and a window's summaries are read by later windows alone, so
    what follows a position cannot reach it."""
    T = tokens.shape[0]
    W = cfg["window_size"]
    n_win = -(-T // W)
    tokens = jnp.pad(tokens, (0, n_win * W - T))
    cos, sin = _rope_tables(cfg, n_win * W)
    cos, sin = (x.reshape((n_win, W) + x.shape[1:]) for x in (cos, sin))
    h = params["wte"][tokens].astype(_F32).reshape(n_win, W, -1)

    def layer(l, h):
        p = {k: w[l].astype(_F32) for k, w in params["blocks"].items()}
        return _layer(cfg, p, h, cos, sin)
    h = jax.lax.fori_loop(0, cfg["num_hidden_layers"], layer, h)
    return h.reshape(n_win * W, -1)[:T]


def _logits(cfg, params, h, heads):
    head = params["head"][:heads * cfg["vocab_size"]].astype(_F32)
    return _rms0(h, params["lnf"].astype(_F32), cfg["rms_norm_eps"]) @ head.T


def all_logits(cfg, params, tokens):
    """(B, T) -> the logits of all ``num_pred_heads`` heads at every
    position, (B, T, num_pred_heads * V): head ``j``'s at ``[..., j * V:(j +
    1) * V]``."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda row: _logits(
            cfg, params, hidden_states_row(cfg, params, row),
            cfg["num_pred_heads"]), tokens)


def logits_at(cfg, params, tokens, positions, precision="highest"):
    """Next-byte logits (B, V), head 0's, read at ``positions[b]`` of each
    row.  Rows may be padded on the right."""
    with jax.default_matmul_precision(precision):
        def one(row):
            toks, pos = row
            return hidden_states_row(cfg, params, toks)[pos]
        return _logits(cfg, params, jax.lax.map(one, (tokens, positions)), 1)


def loss(cfg, params, batch):
    """Mean next-byte cross-entropy of ``batch`` (B, T + 1) on head 0."""
    with jax.default_matmul_precision("highest"):
        def one(row):
            h = hidden_states_row(cfg, params, row[:-1])
            logp = jax.nn.log_softmax(_logits(cfg, params, h, 1), axis=-1)
            return -jnp.take_along_axis(logp, row[1:, None], axis=-1).mean()
        return jax.lax.map(one, batch).mean()
