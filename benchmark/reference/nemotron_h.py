"""Plain reference for the Nemotron-H family (``nemotron_h``: NVIDIA
Nemotron-3-Nano): the published forward written straight down in ``jax.numpy``
and float32: no kernel, no cache, no paging, no chunks, no grouped product.
It shares no code with ``deepspeed_tpu/`` (not the model, not ``ops/``, not
``moe/``) and is what decides ``correct``.

The forward (HF ``NemotronHForCausalLM``), for ``h`` (T, D), ``RMS(x; w) = x /
sqrt(mean(x^2) + eps) * w`` and layer ``l`` of kind
``hybrid_override_pattern[l]``::

    h = E[tokens]
    u = RMS(h; ln_l);  h = h + Mixer_l(u)        one norm, one residual a layer
    logits = RMS(h; lnf) head^T

    M  [z | xBC | dt] = u W_in                   Di | Di + 2 G N | H
       xBC[t] = silu(sum_k conv_w[k] xBC[t - (K-1) + k] + conv_b)   zeros
                                                  before the first token
       [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
       S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g(h)]
       y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]    g(h) = h // (H / G), S_0 = 0
       out = GroupRMS(y * silu(z); norm_w) W_out  mean square over each of the
                                                  G runs of Di / G channels
    *  q, k, v = u W_q, u W_k, u W_v
       o[t] = softmax over s <= t of q[t] k[s] / sqrt(hd), times v
              (query head i reads K/V head i // (H / Hkv)); NO position
       out = o W_o
    E  s = sigmoid(u W_r)                         float32, all E experts
       e_1..e_k = the k largest of s + e_score_correction_bias   (ties: the
                                                  lower id)
       w_i = s[e_i] / sum_j s[e_j] * routed_scaling_factor   (the bias is NOT
                                                  in w)
       out = Shared(u) + sum_{i: e_i held} w_i Expert^{e_i}(u)
       Expert(u) = relu(u W_up)^2 W_down          no gate matrix

THE RECURRENCE IS A PLAIN ``lax.scan`` OVER TOKENS from a zero state, so the
program's chunked form and its hand-off of state from prefill to decode are
compared with something that has neither.

ONE CHIP'S SHARE.  ``cfg["experts_held"] = [first, count]`` (absent: all): the
routed sum runs over the held experts only; what the absent experts would add
is left out, as the program leaves it out.  ``cfg["vocab_held"]`` likewise:
the embedding and the head are those rows.  ``cfg["hybrid_override_pattern"]``
is the pattern of the layers held.

Departures from the published description:

- The parameter tree is the program's (``wte``, ``head``, ``lnf``;
  ``mamba.*``, ``attn.*``, ``moe.*`` stacked per kind; ``moe.up_w`` (layers,
  Eh, F, D) is (out, in), as published).  Leaves are upcast to
  float32 a layer at a time; attention walks blocks of 256 queries, each over
  ALL the keys under the causal mask; a held expert runs over the tokens
  routed to it, gathered 512 at a time; rows are worked one after the other:
  so that 8 rows of up to 3,600 tokens fit on the chip beside the bfloat16
  weights.

The check's SCORES.  ``logits_and_scores_at`` returns, for the runner's tie
test, what the pick is made from, ``x = s + e_score_correction_bias``, as
``exp((x - 1) / TIE_TEMPERATURE)`` (``benchmark/reference/afmoe.py`` says
why: the top sigmoids all lie near 1, where a relative margin says nothing).
:func:`picks` is monotone in it.  The temperature is 0.1 where AFMoE's is
0.03: a margin ``m`` of the runner's ladder then spans ``0.1 ln((1 + m) / (1
- m))`` of ``x``, 0.02 at 0.1 and 0.11 at the ladder's last step of 0.5.  A
row here is moved by more than its own rounding: the convolution's four taps
and the fast heads of the state weigh the LAST tokens, and a token before
that took another expert in bfloat16 (one token in four does, in some layer)
moves this token's scores by up to 0.03 (my chip runs, PR 42: margins of 0.3
and 0.5 needed at 0.03).

On a TPU a float32 matmul runs in lower precision unless told otherwise, so
every entry point runs under ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
TIE_TEMPERATURE = 0.1
_QUERY_BLOCK = 256
_EXPERT_CHUNK = 512


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _relu2_mlp(x, up, down):
    return jnp.square(jnp.maximum(x @ up, 0.0)) @ down


def _f32(tree, l):
    return {k: w[l].astype(_F32) for k, w in tree.items()}


def mamba2(cfg, p, u):
    """The Mamba-2 mixer's output for the normed stream ``u`` (T, D), token
    by token from a zero state."""
    T = u.shape[0]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    Di = H * P
    z, xBC, dt = jnp.split(u @ p["in_w"], [Di, 2 * Di + 2 * G * N], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1]), _F32), xBC])
    xBC = _silu(sum(padded[k:k + T] * p["conv_w"][k] for k in range(K))
                + p["conv_b"])
    x, B, C = jnp.split(xBC, [Di, Di + G * N], axis=-1)
    x, B, C = x.reshape(T, H, P), B.reshape(T, G, N), C.reshape(T, G, N)
    dt = _softplus(dt + p["dt_bias"])                            # (T, H)
    A = -jnp.exp(p["A_log"])
    head_group = np.arange(H) // (H // G)

    def token(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[head_group][:, None, :]
        return S, (S * C_t[head_group][:, None, :]).sum(-1)
    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), _F32), (x, dt, B, C))
    y = (y + p["D"][:, None] * x).reshape(T, Di) * _silu(z)
    y = y.reshape(T, G, Di // G)
    y = y / jnp.sqrt((y * y).mean(-1, keepdims=True)
                     + cfg["layer_norm_epsilon"])
    return (y.reshape(T, Di) * p["norm_w"]) @ p["out_w"]


def attention(cfg, p, u):
    """The attention mixer's output for ``u`` (T, D), a block of queries at a
    time over every key under the causal mask.  No position enters."""
    T = u.shape[0]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = (u @ p["q_w"]).reshape(T, H, hd)
    k = (u @ p["k_w"]).reshape(T, Hkv, hd)
    v = (u @ p["v_w"]).reshape(T, Hkv, hd)
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
    return o @ p["o_w"]


def selection(cfg, pm, l, u):
    """``(s, x)`` (T, E) each: the router's sigmoid scores of ``u`` in expert
    layer ``l`` and what the pick is made from, ``s +
    e_score_correction_bias``."""
    s = 1.0 / (1.0 + jnp.exp(-(u @ pm["router_w"][l].astype(_F32))))
    return s, s + pm["e_score_correction_bias"][l].astype(_F32)


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
    """``scores`` (T, E), anything monotone in ``s + bias`` -> (T, E) bool:
    the ``num_experts_per_tok`` experts picked."""
    assert cfg.get("n_group", 1) == 1 and cfg.get("topk_group", 1) == 1, cfg
    return _rank(scores) < cfg["num_experts_per_tok"]


def route(cfg, s, x):
    """The (T, E) matrix of routing weights: ``w_i`` at each token's picked
    experts, 0 elsewhere."""
    w = jnp.where(picks(cfg, x), s, 0.0)
    if cfg.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg.get("routed_scaling_factor", 1.0)


def experts(cfg, pm, l, u):
    """Expert layer ``l`` of the stacked leaves ``pm`` over ``u`` (T, D): the
    held experts' weighted part and the shared expert; and what the tokens
    were routed by, ``(s, x)``."""
    T = u.shape[0]
    E = pm["router_w"].shape[-1]
    first, count = cfg.get("experts_held") or (0, E)
    assert count == pm["up_w"].shape[1], (count, pm["up_w"].shape)
    s, x = selection(cfg, pm, l, u)
    w = route(cfg, s, x)
    n = min(_EXPERT_CHUNK, T)
    rows = jnp.concatenate([u, jnp.zeros((n, u.shape[1]), _F32)])

    def one(e, y):
        we = w[:, first + e]
        up = pm["up_w"][l, e].astype(_F32).T
        down = pm["down_w"][l, e].astype(_F32)
        # this expert's tokens first, in order; T marks the end
        mine = jnp.nonzero(we > 0, size=T, fill_value=T)[0]
        mine = jnp.concatenate([mine, jnp.full((n,), T, mine.dtype)])

        def chunk(state):
            at, y = state
            ids = jax.lax.dynamic_slice_in_dim(mine, at, n)
            out = _relu2_mlp(rows[ids], up, down)
            scale = jnp.concatenate([we, jnp.zeros((1,), _F32)])[ids]
            return at + n, y.at[ids].add(out * scale[:, None], mode="drop")
        return jax.lax.while_loop(lambda st: mine[st[0]] < T, chunk,
                                  (0, y))[1]
    y = jax.lax.fori_loop(0, count, one, jnp.zeros_like(u))
    shared = _relu2_mlp(u, pm["shared_up_w"][l].astype(_F32),
                        pm["shared_down_w"][l].astype(_F32))
    return y + shared, (s, x)


def hidden_states_row(cfg, params, tokens, watch=None):
    """(T,) token ids -> h (T, D) after the last layer (before ``lnf``);
    with ``watch`` (a position), ``(h, s, x)``: the router's scores of that
    token in every expert layer, (expert layers, E) each."""
    eps = cfg["layer_norm_epsilon"]
    first_row = (cfg.get("vocab_held") or (0, 0))[0]
    h = params["wte"][tokens - first_row].astype(_F32)
    seen = []
    at = {"M": 0, "*": 0, "E": 0}
    for kind in cfg["hybrid_override_pattern"]:
        i = at[kind]
        at[kind] += 1
        if kind == "M":
            p = _f32(params["mamba"], i)
            h = h + mamba2(cfg, p, _rms(h, p["ln"], eps))
        elif kind == "*":
            p = _f32(params["attn"], i)
            h = h + attention(cfg, p, _rms(h, p["ln"], eps))
        elif kind == "E":
            u = _rms(h, params["moe"]["ln"][i].astype(_F32), eps)
            y, sx = experts(cfg, params["moe"], i, u)
            h = h + y
            if watch is not None:
                seen.append(jnp.stack([m[watch] for m in sx]))
        else:
            raise ValueError(f"layer kind {kind!r}: the reference computes "
                             "M, * and E")
    if watch is None:
        return h
    seen = jnp.stack(seen)                                 # (layers, 2, E)
    return h, seen[:, 0], seen[:, 1]


def _logits(cfg, params, h):
    return _rms(h, params["lnf"].astype(_F32), cfg["layer_norm_epsilon"]) \
        @ params["head"].astype(_F32).T


def logits_at(cfg, params, tokens, positions):
    """Next-token logits (B, Vh) read at ``positions[b]`` of each row.  Rows
    may be padded on the right: attention and the recurrence are causal and
    an expert layer works a token at a time, so what follows a position
    cannot reach it."""
    with jax.default_matmul_precision("highest"):
        def one(row):
            toks, pos = row
            return hidden_states_row(cfg, params, toks)[pos]
        return _logits(cfg, params, jax.lax.map(one, (tokens, positions)))


def router_scores_at(cfg, params, tokens, positions):
    """``(logits (B, Vh), s, x (B, expert layers, E))``: the logits at
    ``positions[b]`` and that token's router scores in every expert layer,
    WITHOUT the bias (``s``) and WITH it (``x``)."""
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
