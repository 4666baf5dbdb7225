"""Plain reference for the DeepSeek-V2 family (``deepseek_v2``: DeepSeek-V2,
DeepSeek-Coder-V2): the published forward written straight down in
``jax.numpy`` and float32 — no kernel, no cache, no paging, no grouped
product, no batching of rows, and the attention in the EXPANDED form only, so
that the program's absorbed decode is checked against mathematics it does not
share.  It shares no code with ``deepspeed_tpu/`` (not the model, not
``moe/``, not the rotary tables) and is what decides ``correct``.

The forward (HF ``DeepseekV2ForCausalLM``; arXiv:2405.04434), for ``h`` (T, D)
and ``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w``, ``H`` heads, ``n =
qk_nope_head_dim``, ``r = qk_rope_head_dim``, ``C = kv_lora_rank``::

    h = E[tokens]
    for l in 0..L-1:
      a = RMS(h; ln_in_l)
      c_q = RMS(a W_qa; q_norm)           [q_nope | q_pe] = c_q W_qb    (H x (n | r))
      [c_kv | k_pe] = a W_kva             c_kv = RMS(c_kv; kv_norm)     k_pe: one head for all H
      q_pe, k_pe = rope(q_pe), rope(k_pe)
      for each head:  k = [c_kv W_UK[h] | k_pe]    v = c_kv W_UV[h]
          o[h] = softmax([q_nope[h] | q_pe[h]] k^T * (n + r)^-1/2 * m^2 + causal) v
      h = h + concat_h(o) W_o
      u = RMS(h; ln_ff_l)
      l < first_k_dense_replace:   h = h + SwiGLU_dense(u)
      else:  p = softmax(u W_g) over all E experts, float32
             G_j = max of p over group j;  keep the topk_group groups of largest G, p = 0 elsewhere
             e_1..e_k = the k largest of what is left (ties: the lower id)
             w_i = p[e_i] * routed_scaling_factor     (or p[e_i] / sum_i p[e_i] with norm_topk_prob)
             h = h + sum_{i: e_i held} w_i SwiGLU^{e_i}(u) + SwiGLU_shared(u)
    logits = RMS(h; lnf) head^T

``rope`` turns the pairs ``(x[i], x[i + r/2])`` of the r rope dims by the
angle ``position * f_i``, ``f_i`` YaRN's blend of ``theta^(-2i/r)`` and the
same over ``factor`` (linear ramp between the correction dims of
``beta_fast`` and ``beta_slow`` at ``original_max_position_embeddings``),
worked in float64; cos and sin carry the factor ``mscale / mscale_all_dim``
(1 as published); ``m = 0.1 * mscale_all_dim * ln(factor) + 1``.

ONE CHIP'S SHARE.  ``cfg["experts_held"] = [first, count]`` (absent: all):
the routed sum runs over the held experts only, by a plain loop over them,
each over every token with a 0/1 weight; what the absent experts would add
is left out, as the program leaves it out.  ``cfg["vocab_held"] = [first,
count]``: the embedding and the head are those rows.

Departures from the published description:

- The balance losses (expert-, device- and communication-level) have
  weights the config does not give: ``loss`` is next-token cross-entropy
  over the held vocabulary rows.
- The rope columns of ``W_qb`` and ``W_kva`` are taken in rotate-half order
  (HF stores them interleaved and permutes q and k alike before the same
  rotation: the scores are equal); ``kv_b_proj`` is read as the program lays
  it out, ``k_up_w`` (H, C, n) and ``v_up_w`` (H, v, C), and ``q_b_proj`` as
  its two kinds of row, ``q_nope_w`` (H n, Rq) and ``q_pe_w`` (H r, Rq): the
  same elements.
- The parameter tree is the program's (``wte``, ``head``, ``lnf``;
  ``attn.*`` over all L layers; ``dense.*`` over the leading dense ones;
  ``moe.*`` over the others).  Leaves are upcast to float32 a layer, and
  within an expert layer an expert, at a time, and attention walks head by
  head (128 heads x 2,560^2 float32 scores are 3.4 GB a row), so that on the
  chip the reference fits beside the bfloat16 weights.

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


def _yarn(cfg):
    """``(f (r/2,) float64, m, table)``: the pair frequencies, the softmax
    factor's ``m`` and the factor on cos and sin."""
    r, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    plain = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    sc = cfg.get("rope_scaling")
    if sc is None:
        return plain, 1.0, 1.0
    assert sc["type"] == "yarn", sc
    factor, orig = float(sc["factor"]), sc["original_max_position_embeddings"]
    dim_of = lambda rot: r * np.log(orig / (rot * 2 * np.pi)) / (
        2 * np.log(theta))
    low = max(np.floor(dim_of(sc.get("beta_fast", 32))), 0)
    high = min(np.ceil(dim_of(sc.get("beta_slow", 1))), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    ms = lambda s: 1.0 if factor <= 1 else 0.1 * s * np.log(factor) + 1.0
    m = ms(sc.get("mscale_all_dim", 0))
    return plain / factor * ramp + plain * (1 - ramp), m, \
        ms(sc.get("mscale", 1)) / m


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(cfg, p, a, cos, sin, m):
    """The MLA mixer's output before ``W_o``, (T, H v), head by head."""
    T = a.shape[0]
    H, n = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    r, C = cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    c_q = _rms(a @ p["q_a_w"], p["q_norm"], eps)
    q_nope = (c_q @ p["q_nope_w"].T).reshape(T, H, n)
    kv = a @ p["kv_a_w"]
    c_kv = _rms(kv[:, :C], p["kv_norm"], eps)
    k_pe = _rope(kv[:, C:], cos, sin)                       # (T, r)
    q_pe = _rope((c_q @ p["q_pe_w"].T).reshape(T, H, r), cos[:, None],
                 sin[:, None])                              # (T, H, r)
    scale = (n + r) ** -0.5 * m * m
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


def route(cfg, scores):
    """``scores`` (T, E), the softmax over all experts -> the (T, E) matrix
    of routing weights: ``w_i`` at each token's picked experts, 0 elsewhere.
    Picks are by rank (how many experts score higher, or equal with a lower
    id), not by a sort."""
    T, E = scores.shape
    method = cfg.get("topk_method", "greedy")
    assert method in ("greedy", "group_limited_greedy"), method
    assert cfg.get("scoring_func", "softmax") == "softmax", cfg
    ids = jnp.arange(E)

    def rank(x):
        """How many entries of each row come before each entry."""
        ahead = (x[:, None, :] > x[:, :, None]) | (
            (x[:, None, :] == x[:, :, None])
            & (jnp.arange(x.shape[1])[None, None, :]
               < jnp.arange(x.shape[1])[None, :, None]))
        return ahead.sum(-1)
    left = scores
    if method == "group_limited_greedy":
        G = cfg["n_group"]
        best = scores.reshape(T, G, E // G).max(-1)
        kept = rank(best) < cfg["topk_group"]                # (T, G)
        left = jnp.where(kept[:, ids // (E // G)], scores, 0.0)
    picked = rank(left) < cfg["num_experts_per_tok"]
    w = jnp.where(picked, scores, 0.0)
    if cfg["num_experts_per_tok"] > 1 and cfg.get("norm_topk_prob", False):
        return w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg.get("routed_scaling_factor", 1.0)


def _experts(cfg, pm, l, u, with_scores=False):
    """Layer ``l`` of the stacked expert leaves ``pm`` over ``u`` (T, D):
    the held experts' weighted part and the shared experts; and,
    ``with_scores``, the router's scores (T, E) they were routed by."""
    E = pm["router_w"].shape[-1]
    first, count = cfg.get("experts_held") or (0, E)
    assert count == pm["gate_w"].shape[1], (count, pm["gate_w"].shape)
    scores = jax.nn.softmax(u @ pm["router_w"][l].astype(_F32), -1)
    w = route(cfg, scores)

    def one(e, y):
        ex = lambda name: pm[name][l, e].astype(_F32)
        return y + w[:, first + e, None] * _swiglu(
            u, ex("gate_w"), ex("up_w"), ex("down_w"))
    y = jax.lax.fori_loop(0, count, one, jnp.zeros_like(u))
    sh = lambda name: pm["shared_" + name][l].astype(_F32)
    y = y + _swiglu(u, sh("gate_w"), sh("up_w"), sh("down_w"))
    return (y, scores) if with_scores else y


def hidden_states_row(cfg, params, tokens, watch=None):
    """(T,) token ids -> h (T, D) after the last layer (before ``lnf``);
    with ``watch`` (a position), ``(h, scores (expert layers, E))``: the
    router's scores of that token in every expert layer."""
    eps = cfg["rms_norm_eps"]
    T = tokens.shape[0]
    f, m, table = _yarn(cfg)
    ang = np.arange(T, dtype=np.float64)[:, None] * f
    cos = jnp.asarray(np.cos(ang) * table, _F32)
    sin = jnp.asarray(np.sin(ang) * table, _F32)
    first_row = (cfg.get("vocab_held") or (0, 0))[0]
    h = params["wte"][tokens - first_row].astype(_F32)
    n_dense = params["dense"]["gate_w"].shape[0]
    n_moe = params["moe"]["router_w"].shape[0]

    def mixer(l, h):
        p = {k: w[l].astype(_F32) for k, w in params["attn"].items()}
        h = h + _attention(cfg, p, _rms(h, p["ln_in"], eps), cos, sin,
                           m) @ p["o_w"]
        return h, _rms(h, p["ln_ff"], eps)

    for l in range(n_dense):
        h, u = mixer(l, h)
        d = {k: w[l].astype(_F32) for k, w in params["dense"].items()}
        h = h + _swiglu(u, d["gate_w"], d["up_w"], d["down_w"])

    def expert_layer(i, state):
        h, seen = state
        h, u = mixer(n_dense + i, h)
        y, scores = _experts(cfg, params["moe"], i, u, with_scores=True)
        return h + y, jax.lax.dynamic_update_index_in_dim(
            seen, scores[0 if watch is None else watch], i, 0)
    seen = jnp.zeros((n_moe, params["moe"]["router_w"].shape[-1]), _F32)
    h, seen = jax.lax.fori_loop(0, n_moe, expert_layer, (h, seen))
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


def logits_and_scores_at(cfg, params, tokens, positions):
    """``logits_at``'s logits and, beside them, the router's scores of the
    token at ``positions[b]`` in every expert layer, (B, expert layers, E):
    what :func:`picks` chooses from.  A comparison with a computation in
    another precision needs them to tell a token whose scores TIED (either
    choice is the published forward, within that precision) from one that
    was routed wrong."""
    with jax.default_matmul_precision("highest"):
        def one(row):
            toks, pos = row
            h, scores = hidden_states_row(cfg, params, toks, watch=pos)
            return h[pos], scores
        h, scores = jax.lax.map(one, (tokens, positions))
        return _logits(cfg, params, h), scores


def picks(cfg, scores):
    """``scores`` (T, E) -> (T, E) bool: the experts :func:`route` picks."""
    return route(cfg, scores) > 0


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
