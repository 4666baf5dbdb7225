"""Plain reference for the Qwen3-Next family (``qwen3_next``:
Qwen3-Next-80B-A3B): the forward written straight down in ``jax.numpy`` and
float32: no kernel, no cache, no paging, no chunks, no grouped product.  It
shares no code with ``deepspeed_tpu/`` (not the model, not ``ops/``, not
``moe/``) and is what decides ``correct``.  Typed from ISSUE 54's equations,
not from the model file.

The forward, for ``h`` (T, D), ``RMS0(x; w) = x / sqrt(mean(x^2) + eps) * (1 +
w)`` (zero-centred: ``w`` is stored as ``gamma - 1``) and layer ``l`` full
attention when ``(l + 1) % full_attention_interval == 0``, else Gated
DeltaNet::

    h = E[tokens]
    h = h + Mixer_l(RMS0(h; ln1_l));  h = h + MoE_l(RMS0(h; ln2_l))
    logits = RMS0(h; lnf) head^T

    GatedDeltaNet(u), Hk key heads of dk, Hv value heads of dv, r = Hv / Hk:
       [q | k | v | z] = u W_qkvz     a key head at a time: (q dk, k dk, v r
                                      dv, z r dv) x Hk
       [b | a] = u W_ba               a key head at a time: (b r, a r) x Hk
       x[t] = silu(sum_j conv_w[j] x[t - (K-1) + j])  over [q | k | v], zeros
                                      before the first token, NO bias
       beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
       q = q / sqrt(sum q^2 + 1e-6) / sqrt(dk);  k = k / sqrt(sum k^2 + 1e-6)
       value head i reads key head i // r;  S_0 = 0 (dk, dv), a value head:
           S' = exp(g_t) S_{t-1};  d_t = beta_t (v_t - S'^T k_t)
           S_t = S' + k_t (x) d_t;  o_t = S_t^T q_t
       out = [o_t[i] / sqrt(mean(o_t[i]^2) + eps) * norm_w * silu(z_t[i])]_i
             W_out                    the norm over dv FIRST, then the gate;
                                      norm_w plain

    GatedAttention(u): [q | gate] = u W_q a head at a time (q hd, gate hd) x H
       k = u W_k, v = u W_v;  q = RMS0(q; q_norm), k = RMS0(k; k_norm) a head
       the first r = hd * partial_rotary_factor dims of q and k rotated
       (rotate-half WITHIN them, theta rope_theta), the rest left
       o[t] = softmax over s <= t of q[t] k[s] / sqrt(hd), times v
              (query head i reads K/V head i // (H / Hkv))
       out = (o * sigmoid(gate)) W_o

    MoE(u): p = softmax(u W_r)                     float32, all E experts
       e_1..e_k = the k largest of p (ties: the lower id)
       w_i = p[e_i] / sum_j p[e_j]                 over ALL k picks
       out = sum_{i: e_i held} w_i SwiGLU^{e_i}(u)
             + sigmoid(u . shared_gate) SwiGLU^{shared}(u)
       SwiGLU(u) = (silu(u W_gate) * (u W_up)) W_down

THE DELTA RULE IS A PLAIN ``lax.scan`` OVER TOKENS from a zero state, so the
program's chunked form (a triangular inverse a chunk) and its hand-off of
state from prefill to decode are compared with something that has neither.

ONE CHIP'S SHARE.  ``cfg["experts_held"] = [first, count]`` (absent: all): the
routed sum runs over the held experts only, a dense loop; what the absent
experts would add is left out, as the program leaves it out.
``cfg["vocab_held"]`` likewise: the embedding and the head are those rows.

Departures from the published description: the parameter tree is the
program's (``wte``, ``head``, ``lnf``; ``delta.*``, ``attn.*`` stacked per
kind, ``moe.*`` over every layer).  Leaves are upcast to float32 a layer at a
time; attention walks blocks of 256 queries, each over ALL the keys under the
causal mask; rows are worked one after the other: so that 8 rows of up to
12,000 tokens fit on the chip beside the bfloat16 weights.  The
multi-token-prediction head is not part of the served forward and is not
here.

The check's SCORES.  ``logits_and_scores_at`` returns the softmax scores
themselves (positive; a relative margin ``m`` of the runner's ladder spans
``ln((1 + m) / (1 - m))`` of a router LOGIT, 0.2 at 0.1: the tenth and the
eleventh of 512 logits of standard deviation about 2 lie about 0.03 apart).
:func:`picks` is monotone in them.

On a TPU a float32 matmul runs in lower precision unless told otherwise, so
every entry point runs under ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
_QUERY_BLOCK = 256
_L2_EPS = 1e-6


def _rms0(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + w)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _swiglu(u, gate, up, down):
    return (_silu(u @ gate) * (u @ up)) @ down


def _f32(tree, l):
    return {k: w[l].astype(_F32) for k, w in tree.items()}


def layer_types(cfg):
    n = cfg["full_attention_interval"]
    return ["full_attention" if (l + 1) % n == 0 else "linear_attention"
            for l in range(cfg["num_hidden_layers"])]


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token from a zero state: ``q``, ``k`` (T, H,
    dk); ``v`` (T, H, dv); ``g``, ``beta`` (T, H) -> ``o`` (T, H, dv)."""
    H, dk = q.shape[1:]
    dv = v.shape[-1]

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, None, None] * S
        said = (S * k_t[:, :, None]).sum(1)                   # S'^T k, (H, dv)
        d = b_t[:, None] * (v_t - said)
        S = S + k_t[:, :, None] * d[:, None, :]
        return S, (S * q_t[:, :, None]).sum(1)
    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), _F32),
                        (q, k, v, g, beta))
    return o


def gated_delta_net(cfg, p, u):
    """The Gated DeltaNet mixer's output for the normed stream ``u`` (T, D)."""
    T = u.shape[0]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    K, r = cfg["linear_conv_kernel_dim"], Hv // Hk
    qkvz = (u @ p["qkvz_w"]).reshape(T, Hk, 2 * dk + 2 * r * dv)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(T, Hv, dv)
    ba = (u @ p["ba_w"]).reshape(T, Hk, 2 * r)
    b, a = ba[..., :r].reshape(T, Hv), ba[..., r:].reshape(T, Hv)
    x = jnp.concatenate([q.reshape(T, -1), k.reshape(T, -1),
                         v.reshape(T, -1)], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), _F32), x])
    x = _silu(sum(padded[j:j + T] * p["conv_w"][j] for j in range(K)))
    q = x[:, :Hk * dk].reshape(T, Hk, dk)
    k = x[:, Hk * dk:2 * Hk * dk].reshape(T, Hk, dk)
    v = x[:, 2 * Hk * dk:].reshape(T, Hv, dv)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + _L2_EPS) / np.sqrt(dk)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + _L2_EPS)
    key_head = np.arange(Hv) // r
    beta = _sigmoid(b)
    g = -jnp.exp(p["A_log"]) * _softplus(a + p["dt_bias"])
    o = delta_rule(q[:, key_head], k[:, key_head], v, g, beta)
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + cfg["rms_norm_eps"])
    return (o * p["norm_w"] * _silu(z)).reshape(T, Hv * dv) @ p["out_w"]


def _rope(cfg, x):
    """The first ``head_dim * partial_rotary_factor`` dims of ``x`` (T, H,
    hd) rotated, rotate-half within them, position = row."""
    r = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    inv = 1.0 / (cfg["rope_theta"] ** (np.arange(0, r, 2) / r))
    ang = jnp.asarray(np.arange(x.shape[0])[:, None] * inv[None, :], _F32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def gated_attention(cfg, p, u):
    """The attention mixer's output for ``u`` (T, D), a block of queries at a
    time over every key under the causal mask."""
    T = u.shape[0]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    qg = (u @ p["q_w"]).reshape(T, H, 2, hd)
    q, gate = qg[:, :, 0], qg[:, :, 1].reshape(T, H * hd)
    k = (u @ p["k_w"]).reshape(T, Hkv, hd)
    v = (u @ p["v_w"]).reshape(T, Hkv, hd)
    q = _rope(cfg, _rms0(q, p["q_norm"], eps))
    k = _rope(cfg, _rms0(k, p["k_norm"], eps))
    bq = min(_QUERY_BLOCK, T)
    nq = -(-T // bq)
    q = jnp.pad(q, ((0, nq * bq - T), (0, 0), (0, 0)))
    q = q.reshape(nq, bq, Hkv, H // Hkv, hd)
    s_pos = jnp.arange(T)[None, :]

    def block(xs):
        qb, i = xs
        t = i * bq + jnp.arange(bq)[:, None]
        s = jnp.einsum("tkgd,skd->kgts", qb, k) / np.sqrt(hd)
        w = jax.nn.softmax(jnp.where(s_pos <= t, s, -jnp.inf), axis=-1)
        # a pad query (t >= T) sees every key: finite, and thrown away
        return jnp.einsum("kgts,skd->tkgd", w, v).reshape(bq, H * hd)
    o = jax.lax.map(block, (q, jnp.arange(nq))).reshape(nq * bq, -1)[:T]
    return (o * _sigmoid(gate)) @ p["o_w"]


def selection(cfg, pm, l, u):
    """(T, E): the router's softmax scores of ``u`` in layer ``l``."""
    return jax.nn.softmax(u @ pm["router_w"][l].astype(_F32), axis=-1)


def _rank(x):
    """How many entries of each row come before each entry (a larger value,
    or an equal one with a lower id); 256 rows' (E, E) comparisons at a
    time."""
    ids = jnp.arange(x.shape[1])

    def row(r):
        ahead = (r[None, :] > r[:, None]) | (
            (r[None, :] == r[:, None]) & (ids[None, :] < ids[:, None]))
        return ahead.sum(-1)
    return jax.lax.map(row, x, batch_size=256)


def picks(cfg, scores):
    """``scores`` (T, E), anything monotone in the router's softmax -> (T, E)
    bool: the ``num_experts_per_tok`` experts picked."""
    return _rank(scores) < cfg["num_experts_per_tok"]


def route(cfg, s):
    """The (T, E) matrix of routing weights: ``w_i`` at each token's picked
    experts, 0 elsewhere."""
    w = jnp.where(picks(cfg, s), s, 0.0)
    if cfg.get("norm_topk_prob", True):
        w = w / w.sum(-1, keepdims=True)
    return w


def experts(cfg, pm, l, u):
    """Expert layer ``l`` of the stacked leaves ``pm`` over ``u`` (T, D): the
    held experts' weighted part, a DENSE loop over them, and the gated shared
    expert; and the scores the tokens were routed by."""
    E = pm["router_w"].shape[-1]
    first, count = cfg.get("experts_held") or (0, E)
    assert count == pm["up_w"].shape[1], (count, pm["up_w"].shape)
    s = selection(cfg, pm, l, u)
    w = route(cfg, s)

    def one(e, y):
        out = _swiglu(u, pm["gate_w"][l, e].astype(_F32),
                      pm["up_w"][l, e].astype(_F32),
                      pm["down_w"][l, e].astype(_F32))
        we = jax.lax.dynamic_index_in_dim(w, first + e, axis=1)   # (T, 1)
        return y + we * out
    y = jax.lax.fori_loop(0, count, one, jnp.zeros_like(u))
    shared = _swiglu(u, pm["shared_gate_w"][l].astype(_F32),
                     pm["shared_up_w"][l].astype(_F32),
                     pm["shared_down_w"][l].astype(_F32))
    gate = _sigmoid(u @ pm["shared_gate"][l].astype(_F32))
    return y + gate[:, None] * shared, s


def hidden_states_row(cfg, params, tokens, watch=None):
    """(T,) token ids -> h (T, D) after the last layer (before ``lnf``);
    with ``watch`` (a position), ``(h, s)``: the router's scores of that
    token in every layer, (layers, E)."""
    eps = cfg["rms_norm_eps"]
    first_row = (cfg.get("vocab_held") or (0, 0))[0]
    h = params["wte"][tokens - first_row].astype(_F32)
    seen = []
    at = {"linear_attention": 0, "full_attention": 0}
    for l, kind in enumerate(layer_types(cfg)):
        i = at[kind]
        at[kind] += 1
        if kind == "linear_attention":
            p = _f32(params["delta"], i)
            h = h + gated_delta_net(cfg, p, _rms0(h, p["ln1"], eps))
        else:
            p = _f32(params["attn"], i)
            h = h + gated_attention(cfg, p, _rms0(h, p["ln1"], eps))
        u = _rms0(h, params["moe"]["ln2"][l].astype(_F32), eps)
        y, s = experts(cfg, params["moe"], l, u)
        h = h + y
        if watch is not None:
            seen.append(s[watch])
    return h if watch is None else (h, jnp.stack(seen))


def _logits(cfg, params, h):
    return _rms0(h, params["lnf"].astype(_F32), cfg["rms_norm_eps"]) \
        @ params["head"].astype(_F32).T


def logits_at(cfg, params, tokens, positions):
    """Next-token logits (B, Vh) read at ``positions[b]`` of each row.  Rows
    may be padded on the right: attention and the delta rule are causal and
    an expert layer works a token at a time, so what follows a position
    cannot reach it."""
    with jax.default_matmul_precision("highest"):
        def one(row):
            toks, pos = row
            return hidden_states_row(cfg, params, toks)[pos]
        return _logits(cfg, params, jax.lax.map(one, (tokens, positions)))


def router_scores_at(cfg, params, tokens, positions, precision="highest"):
    """``(logits (B, Vh), s (B, layers, E))``: the logits at ``positions[b]``
    and that token's router scores in every layer.  ``precision`` is the
    matmuls' (``control_qwen3next.py --witness`` alone asks for another)."""
    with jax.default_matmul_precision(precision):
        def one(row):
            toks, pos = row
            h, s = hidden_states_row(cfg, params, toks, watch=pos)
            return h[pos], s
        h, s = jax.lax.map(one, (tokens, positions))
        return _logits(cfg, params, h), s


def logits_and_scores_at(cfg, params, tokens, positions, precision="highest"):
    """``router_scores_at`` under the name the runner's tie test asks for
    (module docstring)."""
    return router_scores_at(cfg, params, tokens, positions, precision)


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
