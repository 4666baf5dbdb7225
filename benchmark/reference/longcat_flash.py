"""Plain reference for the LongCat-Flash family (``longcat_flash``:
LongCat-Flash-Chat, -Thinking): the forward written straight down in
``jax.numpy`` and float32 from ISSUE 50's equations — no kernel, no cache, no
paging, no grouped product, no batching of rows, and the attention in the
EXPANDED form only, so that the program's absorbed decode is checked against
mathematics it does not share.  It shares no code with ``deepspeed_tpu/`` (not
the model, not ``models/mla.py``, not ``moe/``, not the rotary tables) and is
what decides ``correct``.

The forward (HF ``LongcatFlashForCausalLM``; arXiv:2509.01322), for ``h`` (T,
D) and ``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w``, ``H`` heads, ``n =
qk_nope_head_dim``, ``r = qk_rope_head_dim``, ``C = kv_lora_rank``, ``Rq =
q_lora_rank``, ``E`` real experts, ``Z = zero_expert_num`` identity experts,
``k = moe_topk``::

    h = E[tokens]
    for l in 0..L-1, with sub-layers s = 0, 1 (leaves at 2 l + s):
      MLA_s(a):
        c_q = RMS(a W_qa; q_norm) * sqrt(D / Rq)        (mla_scale_q_lora)
        [q_nope | q_pe] = c_q W_qb                      (H x (n | r))
        [c_kv | k_pe] = a W_kva      c_kv = RMS(c_kv; kv_norm) * sqrt(D / C)   (mla_scale_kv_lora; k_pe NOT scaled)
        q_pe, k_pe = rope(q_pe), rope(k_pe)             (theta, no scaling; k_pe one head for all H)
        for each head:  k = [c_kv W_UK[h] | k_pe]    v = c_kv W_UV[h]
            o[h] = softmax([q_nope[h] | q_pe[h]] k^T * (n + r)^-1/2 + causal) v
        MLA_s = concat_h(o) W_o
      h = h + MLA_0(RMS(h; ln_in[0]))
      u = RMS(h; ln_ff[0])
      m = MoE(u)                                        (held back)
      h = h + SwiGLU_dense_0(u)
      h = h + MLA_1(RMS(h; ln_in[1]))
      h = h + SwiGLU_dense_1(RMS(h; ln_ff[1])) + m      (the shortcut joins HERE)
    logits = RMS(h; lnf) head^T

    MoE(u):  s = softmax(u W_router) over all E + Z outputs, float32
             e_1..e_k = the k largest of x = s + router_bias   (ties: the lower id)
             w_i = s[e_i] * routed_scaling_factor              (the bias is NOT in w; no renormalisation)
             MoE(u) = sum_{i: e_i held} w_i SwiGLU^{e_i}(u)  +  u * sum_{i: e_i >= E} w_i

``rope`` turns the pairs ``(x[i], x[i + r/2])`` of the r rope dims by the angle
``position * theta^(-2i/r)``, worked in float64.

ONE CHIP'S SHARE.  ``cfg["experts_held"] = [first, count]`` (absent: all ``E``):
the routed sum runs over the held REAL experts only, by a plain loop over them,
each over every token with a 0/1 weight; what the absent experts would add is
left out, as the program leaves it out.  The identity experts' part is whole
in every share: it is the token's own input.  ``cfg["vocab_held"] = [first,
count]``: the embedding and the head are those rows.

Departures from the published description (each also under ``assumed`` in the
configuration's file):

- ``router_bias`` (HF ``e_score_correction_bias``) is a buffer whose values
  the config does not give; it is a leaf of the tree like any other here.
  The controller that keeps it in training is not run.
- The rope columns are taken in rotate-half order; ``kv_b_proj`` and
  ``q_b_proj`` are read as the program lays them out (``k_up_w`` (H, C, n),
  ``v_up_w`` (H, v, C), ``q_nope_w`` (H n, Rq), ``q_pe_w`` (H r, Rq)): the
  same elements.
- The parameter tree is the program's (``wte``, ``head``, ``lnf``; ``attn.*``
  and ``dense.*`` over the 2 L sub-layers; ``moe.*`` over the L layers).
  Leaves are upcast to float32 a sub-layer, and within an expert layer an
  expert, at a time, and attention walks head by head, so that on the chip the
  reference fits beside the bfloat16 weights.

The check's SCORES.  ``logits_and_scores_at`` returns what the pick is made
from, ``x = s + router_bias``: the runner's tie test scales a picked expert's
``x`` by ``1 + m`` and the others' by ``1 - m``; :func:`picks` ranks ``x``.
Given the program's picks (``forced``), it sends the compared token to them
in every layer: a token whose pick tied is then compared with a forward that
took the SAME experts, picks from the program and all else from here, and
each layer's ``x`` comes from a stream that took the layers above as the
program did.

On a TPU a float32 matmul runs in lower precision unless told otherwise, so
every entry point runs under ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _swiglu(x, gate, up, down):
    return (_silu(x @ gate) * (x @ up)) @ down


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _tables(cfg, T):
    r = cfg["qk_rope_head_dim"]
    f = float(cfg["rope_theta"]) ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    ang = np.arange(T, dtype=np.float64)[:, None] * f
    return jnp.asarray(np.cos(ang), _F32), jnp.asarray(np.sin(ang), _F32)


def _attention(cfg, p, a, cos, sin):
    """One MLA sub-layer's output before ``W_o``, (T, H v), head by head."""
    T, D = a.shape
    H, n = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    r, C, Rq = cfg["qk_rope_head_dim"], cfg["kv_lora_rank"], cfg["q_lora_rank"]
    eps = cfg["rms_norm_eps"]
    c_q = _rms(a @ p["q_a_w"], p["q_norm"], eps)
    if cfg.get("mla_scale_q_lora", False):
        c_q = c_q * (D / Rq) ** 0.5
    q_nope = (c_q @ p["q_nope_w"].T).reshape(T, H, n)
    kv = a @ p["kv_a_w"]
    c_kv = _rms(kv[:, :C], p["kv_norm"], eps)
    if cfg.get("mla_scale_kv_lora", False):
        c_kv = c_kv * (D / C) ** 0.5
    k_pe = _rope(kv[:, C:], cos, sin)                       # (T, r)
    q_pe = _rope((c_q @ p["q_pe_w"].T).reshape(T, H, r), cos[:, None],
                 sin[:, None])                              # (T, H, r)
    scale = (n + r) ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(xs):
        q_nope_h, q_pe_h, k_up, v_up = xs
        k = jnp.concatenate([c_kv @ k_up, k_pe], axis=-1)    # (T, n + r)
        qh = jnp.concatenate([q_nope_h, q_pe_h], axis=-1)
        s = jnp.where(causal, qh @ k.T * scale, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ (c_kv @ v_up.T)  # (T, v)

    o = jax.lax.map(head, (jnp.moveaxis(q_nope, 1, 0),
                           jnp.moveaxis(q_pe, 1, 0),
                           p["k_up_w"], p["v_up_w"]))        # (H, T, v)
    return jnp.moveaxis(o, 0, 1).reshape(T, -1)


def selection(cfg, pm, l, u):
    """``(s, x)`` (T, E + Z) each: the router's softmax scores of ``u`` in
    layer ``l`` and what the pick is made from, ``s + router_bias``."""
    s = jax.nn.softmax(u @ pm["router_w"][l].astype(_F32), -1)
    return s, s + pm["router_bias"][l].astype(_F32)


def picks(cfg, scores):
    """``scores`` (T, E + Z), anything monotone in ``s + router_bias`` -> (T,
    E + Z) bool: the ``moe_topk`` picked, by rank (how many score higher, or
    equal with a lower id), not by a sort."""
    ids = jnp.arange(scores.shape[1])

    def rank(row):
        ahead = (row[None, :] > row[:, None]) | (
            (row[None, :] == row[:, None]) & (ids[None, :] < ids[:, None]))
        return ahead.sum(-1)
    return jax.lax.map(rank, scores, batch_size=256) < cfg["moe_topk"]


def route(cfg, s, x):
    """The (T, E + Z) matrix of routing weights: ``w_i`` at each token's
    picked outputs, 0 elsewhere."""
    return jnp.where(picks(cfg, x), s, 0.0) * cfg["routed_scaling_factor"]


def moe(cfg, pm, l, u, with_scores=False, forced=None):
    """``MoE(u)`` of layer ``l`` of the stacked leaves ``pm`` over ``u`` (T,
    D): the held real experts' weighted part and the identity experts'; and,
    ``with_scores``, ``(s, x)`` they were routed by.  ``forced = (position,
    ids (k,))``: that one token goes to ``ids`` whatever ``x`` ranks first,
    weighed by its own ``s`` as any pick is (the check's second question: a
    program whose pick TIED with another, is everything else of it right?)."""
    Z = cfg["zero_expert_num"]
    E = pm["router_w"].shape[-1] - Z
    first, count = cfg.get("experts_held") or (0, E)
    assert count == pm["gate_w"].shape[1], (count, pm["gate_w"].shape)
    assert cfg.get("zero_expert_type", "identity") == "identity", cfg
    s, x = selection(cfg, pm, l, u)
    w = route(cfg, s, x)
    if forced is not None:
        pos, ids = forced
        w = w.at[pos].set(jnp.zeros_like(w[0]).at[ids].set(
            s[pos, ids] * cfg["routed_scaling_factor"]))

    def one(e, y):
        ex = lambda name: pm[name][l, e].astype(_F32)
        return y + w[:, first + e, None] * _swiglu(
            u, ex("gate_w"), ex("up_w"), ex("down_w"))
    y = jax.lax.fori_loop(0, count, one, jnp.zeros_like(u))
    y = y + u * w[:, E:].sum(-1, keepdims=True)
    return (y, s, x) if with_scores else y


def layer(cfg, params, l, h, cos, sin, watch=None, forced=None):
    """Layer ``l``'s double block over the stream ``h`` (T, D); with
    ``watch`` (a position), ``(h, x (E + Z,))``: what that token's pick was
    made from; with ``forced`` (k ids) besides, that token goes to them."""
    eps = cfg["rms_norm_eps"]
    sub = lambda tree, i: {k: w[i].astype(_F32) for k, w in tree.items()}
    a0, a1 = sub(params["attn"], 2 * l), sub(params["attn"], 2 * l + 1)
    h = h + _attention(cfg, a0, _rms(h, a0["ln_in"], eps), cos, sin) \
        @ a0["o_w"]
    u = _rms(h, a0["ln_ff"], eps)
    m, _, x = moe(cfg, params["moe"], l, u, with_scores=True,
                  forced=None if forced is None else (watch, forced))
    d = sub(params["dense"], 2 * l)
    h = h + _swiglu(u, d["gate_w"], d["up_w"], d["down_w"])
    h = h + _attention(cfg, a1, _rms(h, a1["ln_in"], eps), cos, sin) \
        @ a1["o_w"]
    d = sub(params["dense"], 2 * l + 1)
    h = h + _swiglu(_rms(h, a1["ln_ff"], eps), d["gate_w"], d["up_w"],
                    d["down_w"]) + m
    return h if watch is None else (h, x[watch])


def hidden_states_row(cfg, params, tokens, watch=None, forced=None):
    """(T,) token ids -> h (T, D) after the last layer (before ``lnf``);
    with ``watch`` (a position), ``(h, x (layers, E + Z))``; with ``forced``
    (layers, k) besides, the watched token's experts in every layer."""
    cos, sin = _tables(cfg, tokens.shape[0])
    first_row = (cfg.get("vocab_held") or (0, 0))[0]
    h = params["wte"][tokens - first_row].astype(_F32)
    L, W = params["moe"]["router_w"].shape[0], \
        params["moe"]["router_w"].shape[-1]

    def one(l, state):
        h, seen = state
        h, x = layer(cfg, params, l, h, cos, sin,
                     watch=0 if watch is None else watch,
                     forced=None if forced is None else forced[l])
        return h, jax.lax.dynamic_update_index_in_dim(seen, x, l, 0)
    h, seen = jax.lax.fori_loop(0, L, one, (h, jnp.zeros((L, W), _F32)))
    return h if watch is None else (h, seen)


def _logits(cfg, params, h):
    return _rms(h, params["lnf"].astype(_F32), cfg["rms_norm_eps"]) \
        @ params["head"].astype(_F32).T


def logits_at(cfg, params, tokens, positions):
    """Next-token logits (B, Vh) read at ``positions[b]`` of each row.  Rows
    may be padded on the right: attention is causal and an expert layer
    works a token at a time, so what follows a position cannot reach it."""
    with jax.default_matmul_precision("highest"):
        def one(row):
            toks, pos = row
            return hidden_states_row(cfg, params, toks)[pos]
        return _logits(cfg, params, jax.lax.map(one, (tokens, positions)))


def logits_and_scores_at(cfg, params, tokens, positions, forced=None):
    """``logits_at``'s logits and, beside them, what the token at
    ``positions[b]`` was routed by in every layer, ``x = s + router_bias``
    (B, layers, E + Z): what :func:`picks` chooses from.  ``forced`` (B,
    layers, k) ids: that token is sent to THESE experts in every layer (the
    tokens before it keep the reference's own picks), so that ``x`` of a
    layer is scored from a stream that took the forced picks above it."""
    with jax.default_matmul_precision("highest"):
        def one(row):
            toks, pos, *ids = row
            h, x = hidden_states_row(cfg, params, toks, watch=pos,
                                     forced=ids[0] if ids else None)
            return h[pos], x
        rows = (tokens, positions) + (() if forced is None else (forced,))
        h, x = jax.lax.map(one, rows)
        return _logits(cfg, params, h), x


def logits(cfg, params, tokens):
    """Every position's logits, (B, T, Vh)."""
    with jax.default_matmul_precision("highest"):
        return _logits(cfg, params, jax.lax.map(
            lambda row: hidden_states_row(cfg, params, row), tokens))


def loss(cfg, params, batch):
    """Mean next-token cross-entropy of ``batch`` (B, T + 1) over the held
    vocabulary rows."""
    with jax.default_matmul_precision("highest"):
        first_row = (cfg.get("vocab_held") or (0, 0))[0]

        def one(row):
            h = hidden_states_row(cfg, params, row[:-1])
            logp = jax.nn.log_softmax(_logits(cfg, params, h), axis=-1)
            return -jnp.take_along_axis(
                logp, (row[1:] - first_row)[:, None], axis=-1).mean()
        return jax.lax.map(one, batch).mean()
