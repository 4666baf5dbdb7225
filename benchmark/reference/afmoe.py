"""Plain reference for the AFMoE family (``afmoe``: Arcee Trinity): the
published forward written straight down in ``jax.numpy`` and float32: no
kernel, no cache, no paging, no ring, no grouped product.  It shares no code
with ``deepspeed_tpu/`` (not the model, not ``moe/``, not the rotary tables)
and is what decides ``correct``.

The forward (HF ``AfmoeForCausalLM``), for ``h`` (T, D), ``RMS(x; w) = x /
sqrt(mean(x^2) + eps) * w``, ``H`` query heads over ``Hkv`` K/V heads of
``hd``, ``W = sliding_window``::

    h = E[tokens] * sqrt(D)                               (mup_enabled)
    for every layer l of type layer_types[l]:
      a = RMS(h; ln_in)
      q, k, v = a W_q, a W_k, a W_v
      q, k = RMS(q; q_norm), RMS(k; k_norm)               over each head's hd
      sliding_attention:  q, k = rope(q), rope(k)         full_attention: nothing
      o[t] = softmax over the visible s of q[t] k[s] / sqrt(hd), times v
             visible: s <= t, and on a sliding layer also t - s < W
             (query head i reads K/V head i // (H / Hkv))
      h = h + RMS((o * sigmoid(a W_gate)) W_o; ln_post_attn)
      u = RMS(h; ln_pre_mlp)
      l < num_dense_layers:  y = SwiGLU_dense(u)
      else:  s = sigmoid(u W_r)                            float32, all E experts
             e_1..e_k = the k largest of s + expert_bias   (ties: the lower id)
             w_i = s[e_i] / sum_j s[e_j] * route_scale     the bias is NOT in w
             y = SwiGLU_shared(u) + sum_{i: e_i held} w_i SwiGLU^{e_i}(u)
      h = h + RMS(y; ln_post_mlp)
    logits = RMS(h; lnf) head^T

``rope`` turns the pairs ``(x[i], x[i + hd/2])`` of all ``hd`` dims by the
angle ``position * theta^(-2i/hd)``, worked in float64.

ONE CHIP'S SHARE.  ``cfg["experts_held"] = [first, count]`` (absent: all): the
routed sum runs over the held experts only; what the absent experts would add
is left out, as the program leaves it out.  ``cfg["vocab_held"]`` likewise:
the embedding and the head are those rows.  ``cfg["layers_held"]`` (absent:
all) names the published layers this chip holds, in order; each keeps the
type ``layer_types`` gives it and is dense iff below the PUBLISHED
``num_dense_layers``.

Departures from the published description:

- ``load_balance_coeff`` and the update of ``expert_bias`` are the trainer's:
  ``loss`` is next-token cross-entropy over the held vocabulary rows.
- The parameter tree is the program's (``wte``, ``head``, ``lnf``; ``attn.*``
  over all held layers; ``dense.*`` over the dense ones; ``moe.*`` over the
  others).  Leaves are upcast to float32 a layer at a time; attention walks
  blocks of 256 queries, each over ALL the keys under its mask (no band is cut
  out: the mask alone says what is visible); a held expert runs over the
  tokens routed to it, gathered 512 at a time (a loop over every token with a
  0/1 weight would be 64 times the work at 32 of 256 experts and top-4), so
  that 8 rows of up to 17k tokens fit on the chip beside the bfloat16
  weights and run in tens of seconds.

The check's SCORES.  ``logits_and_scores_at`` returns, for the runner's tie
test, what the pick is made from, ``x = s + expert_bias``, as ``exp((x - 1) /
TIE_TEMPERATURE)``: the runner scales an expert's score by ``1 +- m`` to ask
whether two picks tied, and 256 sigmoids' top scores all lie within 0.02 of
1, where a relative margin says nothing; in this form ``m`` moves ``x`` by
``TIE_TEMPERATURE * ln(1 +- m)``, about ``0.03 m``.  :func:`picks` is
monotone in it.  ``router_scores_at`` gives the plain ``s`` and ``x``.

On a TPU a float32 matmul runs in lower precision unless told otherwise, so
every entry point runs under ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
TIE_TEMPERATURE = 0.03
_QUERY_BLOCK = 256
_EXPERT_CHUNK = 512


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


def layers_of(cfg):
    """``[(type, is_dense)]`` of the layers held, in order."""
    published = cfg.get("published", {})
    n_dense = published.get("num_dense_layers", cfg["num_dense_layers"])
    types = cfg["layer_types"]
    held = cfg.get("layers_held")
    ids = sorted(int(l) for l in held) if held is not None \
        else range(cfg["num_hidden_layers"])
    return [(types[l], l < n_dense) for l in ids]


def _attention(cfg, p, a, sliding, cos, sin):
    """The mixer's output before the gate, (T, H hd), a block of queries at a
    time over every key under the layer's mask."""
    T = a.shape[0]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    # q_w and k_w are stored (out, in), as published
    q = _rms((a @ p["q_w"].T).reshape(T, H, hd), p["q_norm"], eps)
    k = _rms((a @ p["k_w"].T).reshape(T, Hkv, hd), p["k_norm"], eps)
    v = (a @ p["v_w"]).reshape(T, Hkv, hd)
    if sliding:
        q = _rope(q, cos[:, None], sin[:, None])
        k = _rope(k, cos[:, None], sin[:, None])
    bq = min(_QUERY_BLOCK, T)
    nq = -(-T // bq)
    q = jnp.pad(q, ((0, nq * bq - T), (0, 0), (0, 0)))
    q = q.reshape(nq, bq, Hkv, H // Hkv, hd)
    s_pos = jnp.arange(T)[None, :]

    def block(xs):
        qb, i = xs
        t = i * bq + jnp.arange(bq)[:, None]
        visible = s_pos <= t
        if sliding:
            visible &= t - s_pos < cfg["sliding_window"]
        s = jnp.einsum("tkgd,skd->kgts", qb, k) / np.sqrt(hd)
        w = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        # a pad query (t >= T) sees every key: finite, and thrown away
        return jnp.einsum("kgts,skd->tkgd", w, v).reshape(bq, H * hd)
    return jax.lax.map(block, (q, jnp.arange(nq))).reshape(nq * bq, -1)[:T]


def selection(cfg, pm, l, u):
    """``(s, x)`` (T, E) each: the router's sigmoid scores of ``u`` in
    expert layer ``l`` and what the pick is made from, ``s + expert_bias``."""
    s = 1.0 / (1.0 + jnp.exp(-(u @ pm["router_w"][l].astype(_F32))))
    return s, s + pm["expert_bias"][l].astype(_F32)


def _rank(x):
    """How many entries of each row come before each entry (a larger value,
    or an equal one with a lower id); 1,024 rows' (E, E) comparisons at a
    time."""
    ids = jnp.arange(x.shape[1])

    def row(r):
        ahead = (r[None, :] > r[:, None]) | (
            (r[None, :] == r[:, None]) & (ids[None, :] < ids[:, None]))
        return ahead.sum(-1)
    return jax.lax.map(row, x, batch_size=1024)


def picks(cfg, scores):
    """``scores`` (T, E), anything monotone in ``s + expert_bias`` -> (T, E)
    bool: the ``num_experts_per_tok`` experts picked."""
    assert cfg.get("n_group", 1) == 1 and cfg["score_func"] == "sigmoid", cfg
    return _rank(scores) < cfg["num_experts_per_tok"]


def route(cfg, s, x):
    """The (T, E) matrix of routing weights: ``w_i`` at each token's picked
    experts, 0 elsewhere."""
    w = jnp.where(picks(cfg, x), s, 0.0)
    if cfg.get("route_norm", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg.get("route_scale", 1.0)


def _experts(cfg, pm, l, u):
    """Expert layer ``l`` of the stacked leaves ``pm`` over ``u`` (T, D): the
    held experts' weighted part and the shared expert; and what the tokens
    were routed by, ``(s, x)``."""
    T = u.shape[0]
    E = pm["router_w"].shape[-1]
    first, count = cfg.get("experts_held") or (0, E)
    assert count == pm["gate_w"].shape[1], (count, pm["gate_w"].shape)
    s, x = selection(cfg, pm, l, u)
    w = route(cfg, s, x)
    n = min(_EXPERT_CHUNK, T)
    rows = jnp.concatenate([u, jnp.zeros((n, u.shape[1]), _F32)])

    def one(e, y):
        we = w[:, first + e]
        ex = lambda name: pm[name][l, e].astype(_F32)
        gate, up, down = ex("gate_w"), ex("up_w"), ex("down_w")
        # this expert's tokens first, in order; T marks the end
        mine = jnp.nonzero(we > 0, size=T, fill_value=T)[0]
        mine = jnp.concatenate([mine, jnp.full((n,), T, mine.dtype)])

        def chunk(state):
            at, y = state
            ids = jax.lax.dynamic_slice_in_dim(mine, at, n)
            out = _swiglu(rows[ids], gate, up, down)
            scale = jnp.concatenate([we, jnp.zeros((1,), _F32)])[ids]
            return at + n, y.at[ids].add(out * scale[:, None], mode="drop")
        return jax.lax.while_loop(lambda st: mine[st[0]] < T, chunk,
                                  (0, y))[1]
    y = jax.lax.fori_loop(0, count, one, jnp.zeros_like(u))
    sh = lambda name: pm["shared_" + name][l].astype(_F32)
    return y + _swiglu(u, sh("gate_w"), sh("up_w"), sh("down_w")), (s, x)


def hidden_states_row(cfg, params, tokens, watch=None):
    """(T,) token ids -> h (T, D) after the last layer (before ``lnf``);
    with ``watch`` (a position), ``(h, s, x)``: the router's scores of that
    token in every expert layer, (expert layers, E) each."""
    eps = cfg["rms_norm_eps"]
    T = tokens.shape[0]
    hd = cfg["head_dim"]
    f = float(cfg["rope_theta"]) ** (
        -np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(T, dtype=np.float64)[:, None] * f
    cos, sin = jnp.asarray(np.cos(ang), _F32), jnp.asarray(np.sin(ang), _F32)
    first_row = (cfg.get("vocab_held") or (0, 0))[0]
    h = params["wte"][tokens - first_row].astype(_F32)
    if cfg.get("mup_enabled", False):
        h = h * np.sqrt(cfg["hidden_size"])
    seen = []
    n_dense = 0
    for l, (kind, dense) in enumerate(layers_of(cfg)):
        p = {k: w[l].astype(_F32) for k, w in params["attn"].items()}
        a = _rms(h, p["ln_in"], eps)
        o = _attention(cfg, p, a, kind == "sliding_attention", cos, sin)
        o = (o / (1.0 + jnp.exp(-(a @ p["gate_w"])))) @ p["o_w"]
        h = h + _rms(o, p["ln_post_attn"], eps)
        u = _rms(h, p["ln_pre_mlp"], eps)
        if dense:
            d = {k: w[n_dense].astype(_F32)
                 for k, w in params["dense"].items()}
            y = _swiglu(u, d["gate_w"], d["up_w"], d["down_w"])
            n_dense += 1
        else:
            y, sx = _experts(cfg, params["moe"], l - n_dense, u)
            if watch is not None:
                seen.append(jnp.stack([m[watch] for m in sx]))
        h = h + _rms(y, p["ln_post_mlp"], eps)
    if watch is None:
        return h
    seen = jnp.stack(seen)                                 # (layers, 2, E)
    return h, seen[:, 0], seen[:, 1]


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


def router_scores_at(cfg, params, tokens, positions):
    """``(logits (B, Vh), s, x (B, expert layers, E))``: the logits at
    ``positions[b]`` and that token's router scores in every expert layer,
    WITHOUT the bias (``s``: what the weights are made of) and WITH it
    (``x``: what the pick is made from)."""
    with jax.default_matmul_precision("highest"):
        def one(row):
            toks, pos = row
            h, s, x = hidden_states_row(cfg, params, toks, watch=pos)
            return h[pos], s, x
        h, s, x = jax.lax.map(one, (tokens, positions))
        return _logits(cfg, params, h), s, x


def logits_and_scores_at(cfg, params, tokens, positions):
    """``router_scores_at``'s logits and ``x`` in the form the runner's tie
    test scales (module docstring): (B, expert layers, E)."""
    logits, _, x = router_scores_at(cfg, params, tokens, positions)
    return logits, jnp.exp((x - 1.0) / TIE_TEMPERATURE)


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
