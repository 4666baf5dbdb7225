"""Phi4Flash (SambaY): a decoder-hybrid-decoder.

No reference counterpart.  The architecture is SambaY (arXiv:2507.06607,
"Decoder-Hybrid-Decoder Architecture for Efficient Reasoning with Long
Generation") with differential attention (arXiv:2410.05258), as
``Phi-4-mini-flash-reasoning`` publishes it (``model_type`` ``phi4flash``).
For layer ``l`` of ``L`` (``L % 4 == 0``; 32 published)::

    h = h + Mix_l(LN(h; ln1))                 LN: weight and bias
    [g | y] = fc1(LN(h; ln2));  h = h + fc2(silu(g) * y)

and ``logits = LN(h; lnf) @ wte.T`` (tied).  No positional term anywhere.
``Mix_l`` is one of five, by the layer's place:

- **self-decoder**, ``l < L/2``: Mamba-1 on the even layers, differential
  attention over a WINDOW on the odd ones (position ``t`` sees keys ``t - W
  + 1 .. t``);
- ``l = L/2``: Mamba-1, whose scan output ``m_t = y_t`` (with the ``D x_t``
  term, BEFORE the output gate) is kept for the Gated Memory Units;
- ``l = L/2 + 1``: differential attention over ALL keys.  Its K and V are
  the model's one growing cache;
- **cross-decoder**, ``l >= L/2 + 2``: on the even layers a Gated Memory Unit,
  ``W_2(m_t * silu(W_1 u_t))`` with ``m_t`` layer ``L/2``'s at the SAME
  position; on the odd ones differential CROSS attention, a query
  projection of its own over layer ``L/2 + 1``'s K and V, causal.

The Mamba-1 mixer is ``ops/selective_scan.py``'s recurrence with no norm on
``dt``, ``B`` or ``C`` (``models/jamba.py`` has Jamba's three).

**Differential attention.**  Query heads ``2j, 2j + 1`` are ``q1_j, q2_j``,
K/V heads ``2g, 2g + 1`` are ``k1_g, k2_g, v1_g, v2_g``, pair ``j`` reads
``g = j // (H / Hkv)``: ``o_j = (softmax(q1 k1^T / sqrt(hd)) - lambda
softmax(q2 k2^T / sqrt(hd))) [v1 | v2]``, RMS-normed over its ``2 hd`` values
(``sub_w``), times ``1 - lambda_init``; ``lambda = exp(lq1 . lk1) - exp(lq2 .
lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``.

Every attention call here (the paged kernel's growing and ring walks, the
``jax.numpy`` prompt attention) sees it as GROUPED-QUERY attention
with heads of ``2 hd``: a K/V head PAIR is one head ``[k1 | k2]``, ``[v1 |
v2]`` (the projection's own column order, so nothing moves), and the
queries are ``[q1 | 0]`` and ``[0 | q2]`` (:func:`pad_queries`, already
scaled by ``1 / sqrt(hd)``: every call runs unscaled).  The zeros double the
score matmul's width and change no value; ``o1 - lambda o2`` follows the
call (:meth:`Phi4Flash._combine`).  At the published sizes that is 40 query
heads over 10 K/V heads of 128 lanes.

Parameter tree (stacked per kind, so a run of like layers is one loop)::

    wte (V, D)                 tied embedding / head
    mamba.* (L/4 + 1, ...)     the Mamba mixers, in layer order
    attn.*  (L/4 + 1, ...)     the window layers, then the full layer
    gmu.*   (L/4 - 1, ...)     the Gated Memory Units
    cross.* (L/4 - 1, ...)     the cross-attention layers
    mlp.*   (L, ...)           every layer's SwiGLU and its norm
    lnf_w, lnf_b (D,)

Layouts chosen for the TPU's 128 lanes as ``models/jamba.py``'s: ``A_log``
``(N, Di)``, ``conv_w`` ``(K, Di)``, the recurrent state ``(N, Di)`` float32.
The residual stream is float32; matmuls run in the model dtype.

Serving state (``init_serving_state``): THREE kinds in one pytree — the paged
``k`` / ``v`` pool over ONE layer (layer ``L/2 + 1``'s, read by ``L/4``
attention calls a step), the ring pool ``wk`` / ``wv`` over the window
layers, per slot ``conv`` and ``ssm`` (float32) over the Mamba layers — and
``counters``.  A prefill runs the self-decoder over the prompt and the
cross-decoder at the prompt's LAST position alone: the cross-decoder writes
no cache, so nothing later reads what it would have computed elsewhere.  The
same holds for the full layer past its K and V: the prompt's positions give
their keys and values, and the queries, the attention, ``W_o`` and the MLP
run at the last position alone (one query row over the prompt, no (T, T)
attention).
"""

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..ops import selective_scan as ss
from .gpt2 import GPT2, layer_slice as _take

# what a dispatch counts into ``counters``: the (layer, position) pairs the
# self-decoder with the full layer, and the cross-decoder, really ran (real
# tokens only; a prefill's cross-decoder runs one position)
COUNTERS = ("positions_self", "positions_cross")
_QUERY_BLOCK = 512      # query rows a step of the jax.numpy prompt attention


@dataclasses.dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    # the model class's defaults, absent from the published file
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160

    def __post_init__(self):
        L = self.num_hidden_layers
        assert L % 4 == 0 and L >= 8, \
            f"{L} layers: the pattern wants a multiple of 4, at least 8"
        assert self.mb_per_layer == 2, "a Mamba layer every second layer"
        assert self.hidden_act == "silu" and self.tie_word_embeddings \
            and not self.mlp_bias and not self.lm_head_bias, \
            "models/phi4flash.py computes the published block and no other"
        assert self.num_attention_heads % 2 == 0 \
            and self.num_key_value_heads % 2 == 0 \
            and self.num_attention_heads % self.num_key_value_heads == 0

    # ---- the names the serving layer and the analysis tools ask for
    @property
    def n_layer(self):
        return self.num_hidden_layers

    @property
    def n_head(self):
        return self.num_attention_heads

    @property
    def n_kv_head(self):
        return self.num_key_value_heads

    @property
    def n_embd(self):
        return self.hidden_size

    @property
    def head_dim(self):
        assert self.hidden_size % self.num_attention_heads == 0
        return self.hidden_size // self.num_attention_heads

    @property
    def max_seq(self):
        return self.max_position_embeddings

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    # ---- the layer pattern
    @property
    def memory_layer(self):
        """The Mamba layer whose scan output the GMUs read."""
        return self.num_hidden_layers // 2

    @property
    def full_layer(self):
        """The one full-attention layer, whose K/V are the cache."""
        return self.num_hidden_layers // 2 + 1

    @property
    def n_window_layer(self):
        return self.num_hidden_layers // 4

    @property
    def n_mamba_layer(self):
        return self.num_hidden_layers // 4 + 1

    @property
    def n_cross_layer(self):
        return self.num_hidden_layers // 4 - 1

    @property
    def kv_layers(self):
        """As ``GPT2Config.kv_layers``: ONE layer keeps a growing cache."""
        return 1

    @property
    def shared_kv_readers(self):
        """Attention calls a decode step makes over that one cache."""
        return 1 + self.n_cross_layer

    def kind(self, l):
        if l <= self.full_layer:
            return ("mamba" if l % 2 == 0 else
                    "full" if l == self.full_layer else "window")
        return "gmu" if l % 2 == 0 else "cross"


PRESETS = {
    # tests and CPU examples: all five kinds of layer, a window of 8 that a
    # 40-token stream slides several times
    "phi4flash-tiny": dict(
        vocab_size=128, hidden_size=64, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
        sliding_window=8, mamba_d_state=4, mamba_d_conv=4, mamba_expand=2,
        mamba_dt_rank=4, max_position_embeddings=256),
}


def _ln(x, w, b, eps):
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    xc = x - mu
    y = xc * jax.lax.rsqrt((xc * xc).mean(-1, keepdims=True) + eps)
    return y * w.astype(jnp.float32) + b.astype(jnp.float32)


def _mm(x, w):
    return x @ w.astype(x.dtype)


def lambda_init(l):
    """``0.8 - 0.6 exp(-0.3 l)`` for layer index ``l`` (a number or a traced
    scalar), float32."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, jnp.float32))


def pad_queries(q, hd):
    """``q`` (..., H, hd) -> (..., H, 2 hd), scaled by ``1 / sqrt(hd)``: an
    even head ``[q | 0]``, an odd one ``[0 | q]``, so that against a K/V head
    pair ``[k1 | k2]`` the even head scores with ``k1`` and the odd one with
    ``k2`` (module docstring)."""
    lead, H = q.shape[:-2], q.shape[-2]
    q = q * jnp.asarray(1.0 / np.sqrt(hd), q.dtype)
    q = q.reshape(lead + (H // 2, 2, hd))
    z = jnp.zeros_like(q[..., 0, :])
    out = jnp.stack([jnp.concatenate([q[..., 0, :], z], -1),
                     jnp.concatenate([z, q[..., 1, :]], -1)], axis=-2)
    return out.reshape(lead + (H, 2 * hd))


def grouped_attention(q, k, v, valid):
    """``models/jamba.py``'s, UNSCALED (the queries carry the scale): ``q``
    (B, T, H, hd) over ``k`` / ``v`` (B, S, Hkv, hd); ``valid`` broadcasts to
    (B, Hkv, G, T, S).  Returns (B, T, H * hd)."""
    B, T, H, hd = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, T, Hkv, H // Hkv, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32)
    s = jnp.where(valid, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgts,bskd->btkgd", p, v).reshape(B, T, H * hd)


def banded_attention(q, k, v, window=None, block=_QUERY_BLOCK):
    """``models/afmoe.py``'s ``banded_attention`` over UNSCALED scores: a
    prompt's causal grouped attention in blocks of query rows, so that no (T,
    T) score matrix stands; ``window``: key ``s`` is visible to query ``t``
    iff ``0 <= t - s < window``.  Returns (B, T, H * hd)."""
    B, T, H, hd = q.shape
    bq = min(block, T)
    nq = -(-T // bq)
    pad = lambda x, lo, hi: jnp.pad(x, ((0, 0), (lo, hi), (0, 0), (0, 0)))
    q, k, v = (pad(x, 0, nq * bq - T) for x in (q, k, v))
    back = 0 if window is None else -(-(window - 1) // bq) * bq
    banded = window is not None and back < (nq - 1) * bq
    if banded:
        k, v = pad(k, back, 0), pad(v, back, 0)
    span = back + bq if banded else nq * bq
    rows = jnp.arange(bq)[:, None]

    def one(i):
        t = i * bq + rows                                     # (bq, 1)
        s = jnp.arange(span)[None, :] + (i * bq - back if banded else 0)
        valid = (s >= 0) & (s <= t)
        if window is not None:
            valid &= t - s < window
        take = lambda x, at, n: jax.lax.dynamic_slice_in_dim(x, at, n, axis=1)
        at = i * bq if banded else 0
        return grouped_attention(take(q, i * bq, bq), take(k, at, span),
                                 take(v, at, span), valid)
    out = jax.lax.map(one, jnp.arange(nq))                    # (nq, B, bq, ·)
    return jnp.moveaxis(out, 0, 1).reshape(B, nq * bq, H * hd)[:, :T]


class Phi4Flash:
    """Decoder-hybrid-decoder LM (params: dict pytree with one stack per
    kind of layer)."""

    supports_paged_decode = True
    # a stream's state is more than the blocks of one growing table, twice
    # over: the serving layer refuses what assumes otherwise
    # (inference/serving.py)
    has_recurrent_state = True
    has_window_layers = True
    step_counters = COUNTERS

    def __init__(self, config: Optional[Phi4FlashConfig] = None,
                 preset: str = None, dtype=jnp.bfloat16, **overrides):
        if config is None:
            base = dict(PRESETS[preset or "phi4flash-tiny"])
            base.update(overrides)
            config = Phi4FlashConfig(**base)
        self.config = config
        self.dtype = dtype

    # ------------------------------------------------------------------ init
    def init(self, rng):
        """Matrices and biases normal(0.02), output projections scaled by
        1/sqrt(2L) as the GPT-2 family; the state-space constants as
        ``models/jamba.py``; the four ``lambda`` vectors normal(0, 0.1);
        LayerNorm and ``sub_w`` weights 1, LayerNorm biases 0."""
        c = self.config
        D, V, F, L = c.hidden_size, c.vocab_size, c.intermediate_size, \
            c.num_hidden_layers
        Lm, La, Lx = c.n_mamba_layer, c.n_window_layer + 1, c.n_cross_layer
        Di, N, K, R = c.d_inner, c.mamba_d_state, c.mamba_d_conv, \
            c.mamba_dt_rank
        hd = c.head_dim
        Q, KV = c.n_head * hd, c.n_kv_head * hd
        k = iter(jax.random.split(rng, 32))
        std, proj = 0.02, 0.02 / np.sqrt(2.0 * L)
        f32 = jnp.float32
        n = lambda shape, s=std: jax.random.normal(next(k), shape, f32) * s
        ln = lambda rows: {"ln_w": jnp.ones((rows, D), f32),
                           "ln_b": jnp.zeros((rows, D), f32)}
        diff = lambda rows: {
            "lq1": n((rows, hd), 0.1), "lk1": n((rows, hd), 0.1),
            "lq2": n((rows, hd), 0.1), "lk2": n((rows, hd), 0.1),
            "sub_w": jnp.ones((rows, 2 * hd), f32),
            "o_w": n((rows, Q, D), proj), "o_b": n((rows, D))}
        dt = jnp.exp(jax.random.uniform(next(k), (Lm, Di), f32)
                     * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
        lim = 1.0 / np.sqrt(K)
        return {
            "wte": n((V, D)),
            "mamba": {
                **ln(Lm),
                "in_w": n((Lm, D, 2 * Di)),
                "conv_w": jax.random.uniform(next(k), (Lm, K, Di), f32,
                                             -lim, lim),
                "conv_b": jnp.zeros((Lm, Di), f32),
                "x_w": n((Lm, Di, R + 2 * N)),
                "dt_w": n((Lm, R, Di)),
                "dt_b": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, N + 1, dtype=f32))[None, :, None],
                    (Lm, N, Di)),
                "D": jnp.ones((Lm, Di), f32),
                "out_w": n((Lm, Di, D), proj),
            },
            "attn": {**ln(La), "qkv_w": n((La, D, Q + 2 * KV)),
                     "qkv_b": n((La, Q + 2 * KV)), **diff(La)},
            "gmu": {**ln(Lx), "in_w": n((Lx, D, Di)),
                    "out_w": n((Lx, Di, D), proj)},
            "cross": {**ln(Lx), "q_w": n((Lx, D, Q)), "q_b": n((Lx, Q)),
                      **diff(Lx)},
            "mlp": {**ln(L), "fc1_w": n((L, D, 2 * F)),
                    "fc2_w": n((L, F, D), proj)},
            "lnf_w": jnp.ones((D,), f32), "lnf_b": jnp.zeros((D,), f32),
        }

    def num_params(self):
        c = self.config
        D, Di, N, K, R = c.hidden_size, c.d_inner, c.mamba_d_state, \
            c.mamba_d_conv, c.mamba_dt_rank
        hd = c.head_dim
        Q, KV = c.n_head * hd, c.n_kv_head * hd
        mamba = (D * 2 * Di + Di * K + Di + Di * (R + 2 * N) + R * Di + Di
                 + Di * N + Di + Di * D)
        diff = 4 * hd + 2 * hd + Q * D + D
        attn = D * (Q + 2 * KV) + Q + 2 * KV + diff
        cross = D * Q + Q + diff
        gmu = 2 * D * Di
        every = 3 * D * c.intermediate_size + 4 * D       # SwiGLU, two norms
        return (c.n_mamba_layer * mamba + (c.n_window_layer + 1) * attn
                + c.n_cross_layer * (gmu + cross)
                + c.num_hidden_layers * every + c.vocab_size * D + 2 * D)

    # ---------------------------------------------------------------- pieces
    def _norm(self, p, h):
        return _ln(h, p["ln_w"], p["ln_b"],
                   self.config.layer_norm_eps).astype(self.dtype)

    def _mlp(self, p, h):
        with jax.named_scope("mlp"):
            g, y = jnp.split(_mm(self._norm(p, h), p["fc1_w"]), 2, axis=-1)
            return h + _mm(jax.nn.silu(g) * y, p["fc2_w"]).astype(jnp.float32)

    def _scan_inputs(self, p, h, tail):
        """A Mamba mixer up to the recurrence, for ``h`` (B, T, D) and the
        convolution's incoming ``tail`` (B, K-1, Di) or None.  Returns ``(x,
        z, delta, B, C, padded)`` as ``Jamba._scan_inputs`` (whose three inner
        norms are absent here)."""
        with jax.named_scope("ssm.proj"):
            c = self.config
            N, R = c.mamba_d_state, c.mamba_dt_rank
            x, z = jnp.split(_mm(self._norm(p, h), p["in_w"]), 2, axis=-1)
            with jax.named_scope("ssm.conv"):
                x, padded = ss.causal_conv(x, p["conv_w"], p["conv_b"], tail)
                x = jax.nn.silu(x)
            dt, Bm, Cm = jnp.split(_mm(x, p["x_w"]), [R, R + N], axis=-1)
            delta = jax.nn.softplus(_mm(dt, p["dt_w"]).astype(jnp.float32)
                                    + p["dt_b"].astype(jnp.float32))
            return x, z, delta, Bm, Cm, padded

    @staticmethod
    def _A(p):
        return -jnp.exp(p["A_log"].astype(jnp.float32))

    def _scan_output(self, p, h, y, z, keep):
        """The mixer's gate, projection and residual.  ``y`` is gated when
        the scan was not asked to ``keep`` it: then it went in without ``z``
        and comes back as ``m``, gated here."""
        with jax.named_scope("ssm.proj"):
            out = y * jax.nn.silu(z) if keep else y
            return h + _mm(out, p["out_w"]).astype(jnp.float32), \
                (y if keep else None)

    def _mamba(self, p, h, tail=None, h0=None, t_real=None,
               scan_impl="auto", keep=False):
        """One Mamba mixer with its residual over ``h`` (B, T, D).  Returns
        ``(h, new tail (B, K-1, Di), state (B, N, Di) float32, m)``, tail and
        state taken after token ``t_real - 1`` (the last one when None); ``m``
        (B, T, Di) is the scan's output before the gate when ``keep``, else
        None."""
        c = self.config
        T = h.shape[1]
        x, z, delta, Bm, Cm, padded = self._scan_inputs(p, h, tail)
        if t_real is not None:
            delta = ss.mask_delta(delta, t_real)
        with jax.named_scope("ssm.scan"):
            y, S = ss.selective_scan(x, delta, self._A(p), Bm, Cm, p["D"],
                                     None if keep else z, h0=h0,
                                     impl=scan_impl)
        new_tail = ss.conv_tail_at(padded, T if t_real is None else t_real,
                                   c.mamba_d_conv - 1)
        h, m = self._scan_output(p, h, y, z, keep)
        return h, new_tail, S, m

    def _qkv(self, p, u, uq=None):
        """``u`` (B, T, D) normed -> the call's operands: queries (B, T, H,
        2 hd) padded and scaled, K and V (B, T, Hkv / 2, 2 hd).  ``uq`` (B,
        Tq, D): the rows of ``u`` whose queries are wanted when not all are
        (the weight's query columns meet them alone)."""
        c = self.config
        hd = c.head_dim
        Q, KV = c.n_head * hd, c.n_kv_head * hd
        w, b = p["qkv_w"], p["qkv_b"].astype(u.dtype)
        if uq is None:
            q, k, v = jnp.split(_mm(u, w) + b, [Q, Q + KV], axis=-1)
        else:
            q = _mm(uq, w[:, :Q]) + b[:Q]
            k, v = jnp.split(_mm(u, w[:, Q:]) + b[Q:], 2, axis=-1)
        heads = lambda x, n, width: x.reshape(x.shape[:-1] + (n, width))
        return (pad_queries(heads(q, c.n_head, hd), hd),
                heads(k, c.n_kv_head // 2, 2 * hd),
                heads(v, c.n_kv_head // 2, 2 * hd))

    def _cross_q(self, p, u):
        c = self.config
        q = _mm(u, p["q_w"]) + p["q_b"].astype(u.dtype)
        return pad_queries(q.reshape(u.shape[:-1] + (c.n_head, c.head_dim)),
                           c.head_dim)

    def _combine(self, p, out, l):
        """``out`` (..., H * 2 hd), the grouped call's result: the pair's
        ``o1 - lambda o2``, its RMSNorm, ``1 - lambda_init``, ``W_o + b_o``.
        ``l``: the layer's index (may be traced).  Returns (..., D) float32,
        no residual."""
        c = self.config
        hd = c.head_dim
        f32 = jnp.float32
        lam0 = lambda_init(l)
        dot = lambda a, b: jnp.sum(p[a].astype(f32) * p[b].astype(f32))
        lam = jnp.exp(dot("lq1", "lk1")) - jnp.exp(dot("lq2", "lk2")) + lam0
        o = out.astype(f32).reshape(out.shape[:-1] + (c.n_head // 2, 2,
                                                      2 * hd))
        o = o[..., 0, :] - lam * o[..., 1, :]
        o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True)
                              + c.layer_norm_eps)
        o = o * p["sub_w"].astype(f32) * (1.0 - lam0)
        o = o.reshape(out.shape[:-1] + (c.n_head * hd,)).astype(self.dtype)
        return (_mm(o, p["o_w"]) + p["o_b"].astype(o.dtype)).astype(f32)

    def _self_decoder(self, params, h, carry, mamba_fn, attn_fn, at=None):
        """Layers ``0 .. L/2 + 1``.  ``mamba_fn(p, h, i, keep, carry) -> (h,
        m, carry)`` runs Mamba mixer ``i`` with its residual;
        ``attn_fn(q, k, v, a, window, carry) -> (out, carry)`` runs attention
        layer ``a``'s call (``window`` None: the full layer) and gets the
        ``_combine`` and the residual here.  The L/4 (Mamba, window) pairs
        are ONE loop over the stacked weights, indexed in place.  Returns
        ``(h, m, carry)``.  ``at`` (a prefill's): the one position whose
        result is wanted.  The full layer's K and V are still those of every
        position, but its queries, its attention, ``W_o`` and its MLP run at
        ``at`` alone, and ``h`` and ``m`` come back one position long."""
        c = self.config
        mlp = lambda l, h: self._mlp(_take(params["mlp"], l), h)

        def attn(a, l, window, h, carry, row=None):
            with jax.named_scope("attention"):
                p = _take(params["attn"], a)
                u = self._norm(p, h)
                if row is None:
                    q, k, v = self._qkv(p, u)
                else:
                    q, k, v = self._qkv(p, u, row(u))
                    h = row(h)
                out, carry = attn_fn(q, k, v, a, window, carry)
                return h + self._combine(p, out, l), carry

        def pair(i, hc):
            h, carry = hc
            h, _, carry = mamba_fn(_take(params["mamba"], i), h, i, False,
                                   carry)
            h = mlp(2 * i, h)
            h, carry = attn(i, 2 * i + 1, c.sliding_window, h, carry)
            return mlp(2 * i + 1, h), carry
        h, carry = jax.lax.fori_loop(0, c.n_window_layer, pair, (h, carry))
        i = c.n_window_layer
        h, m, carry = mamba_fn(_take(params["mamba"], i), h, i, True, carry)
        h = mlp(c.memory_layer, h)
        row = None if at is None else (
            lambda x: jax.lax.dynamic_slice_in_dim(x, at, 1, axis=1))
        h, carry = attn(i, c.full_layer, None, h, carry, row)
        return mlp(c.full_layer, h), (m if row is None else row(m)), carry

    def _cross_decoder(self, params, h, m, cross_fn):
        """Layers ``L/2 + 2 .. L - 1`` over ``h`` with ``m`` at the same
        positions: the L/4 - 1 (GMU, cross attention) pairs as one loop.
        ``cross_fn(q) -> out`` attends the padded queries over the full
        layer's K/V."""
        c = self.config
        l0 = c.full_layer + 1
        mlp = lambda l, h: self._mlp(_take(params["mlp"], l), h)

        def pair(j, h):
            p = _take(params["gmu"], j)
            with jax.named_scope("gmu"):
                gate = jax.nn.silu(_mm(self._norm(p, h), p["in_w"]))
                h = h + _mm(m * gate, p["out_w"]).astype(jnp.float32)
            h = mlp(l0 + 2 * j, h)
            p = _take(params["cross"], j)
            with jax.named_scope("attention"):
                out = cross_fn(self._cross_q(p, self._norm(p, h)))
                h = h + self._combine(p, out, l0 + 2 * j + 1)
            return mlp(l0 + 2 * j + 1, h)
        return jax.lax.fori_loop(0, c.n_cross_layer, pair, h)

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["wte"][tokens].astype(jnp.float32)

    def _head(self, params, h):
        with jax.named_scope("lm_head"):
            c = self.config
            h = _ln(h, params["lnf_w"], params["lnf_b"],
                    c.layer_norm_eps).astype(self.dtype)
            return jnp.einsum("...d,vd->...v", h, params["wte"].astype(h.dtype),
                              preferred_element_type=jnp.float32)

    def _counts(self, n_self, n_cross):
        c = self.config
        return jnp.stack([jnp.asarray(n_self, jnp.int32)
                          * (c.full_layer + 1),
                          jnp.asarray(n_cross, jnp.int32)
                          * (c.num_hidden_layers - c.full_layer - 1)])

    # --------------------------------------------------------------- forward
    def apply(self, params, tokens, rng=None, deterministic=True,
              return_hidden=False, scan_impl="auto"):
        """``tokens`` (B, T) -> logits (B, T, V) float32: every layer at
        every position.  No dropout in the family.  Differentiable with
        ``scan_impl="jnp"`` (what ``loss`` passes: the kernel has no
        backward)."""
        def mamba_fn(p, h, i, keep, carry):
            h, _, _, m = self._mamba(p, h, scan_impl=scan_impl, keep=keep)
            return h, m, carry

        def attn_fn(q, k, v, a, window, carry):
            if window is None:
                return banded_attention(q, k, v), (k, v)
            return banded_attention(q, k, v, window=window), carry

        h, m, (k, v) = self._self_decoder(params, self._embed(params, tokens),
                                          (), mamba_fn, attn_fn)
        h = self._cross_decoder(params, h, m,
                                lambda q: banded_attention(q, k, v))
        if return_hidden:
            c = self.config
            return _ln(h, params["lnf_w"], params["lnf_b"], c.layer_norm_eps)
        return self._head(params, h)

    def loss(self, params, batch, rng=None):
        """Next-token LM loss; ``batch`` as ``GPT2.loss`` takes it."""
        tokens, labels = GPT2._split_batch(batch)
        logits = self.apply(params, tokens, scan_impl="jnp")
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.mean(lse - picked)

    # ---------------------------------------------------- contiguous decoding
    def init_cache(self, batch_size: int, max_len: Optional[int] = None,
                   dtype=None):
        """``InferenceEngine.generate``'s cache: dense K/V for the full layer
        (``k`` / ``v``) and, at the same length, for the window layers (``wk``
        / ``wv``: the window is a mask here, not a ring), the convolution
        tail and the recurrent state for the Mamba layers, and the write
        index."""
        c = self.config
        S = max_len or c.max_seq
        dtype = dtype or self.dtype
        kv = lambda n: jnp.zeros((n, batch_size, S, c.n_kv_head // 2,
                                  2 * c.head_dim), dtype)
        return {"k": kv(1), "v": kv(1), "wk": kv(c.n_window_layer),
                "wv": kv(c.n_window_layer),
                "conv": jnp.zeros((c.n_mamba_layer, batch_size,
                                   c.mamba_d_conv - 1, c.d_inner), dtype),
                "ssm": jnp.zeros((c.n_mamba_layer, batch_size,
                                  c.mamba_d_state, c.d_inner), jnp.float32),
                "index": jnp.zeros((), jnp.int32)}

    def apply_with_cache(self, params, tokens, cache):
        """Forward ``tokens`` (B, T) from ``cache['index']``; returns
        ``(logits (B, T, V), new_cache)``: prefill (T = prompt) and decode
        (T = 1) alike, every layer at every one of the T positions."""
        T = tokens.shape[1]
        index = cache["index"]
        S = cache["k"].shape[2]
        t = index + jnp.arange(T)[:, None]
        s = jnp.arange(S)[None, :]
        causal = s <= t

        def mamba_fn(p, h, i, keep, carry):
            conv, ssm = carry["conv"], carry["ssm"]
            h, tail, state, m = self._mamba(p, h, tail=conv[i], h0=ssm[i],
                                            scan_impl="jnp", keep=keep)
            return h, m, dict(carry, ssm=ssm.at[i].set(state),
                              conv=conv.at[i].set(tail.astype(conv.dtype)))

        def attn_fn(q, k, v, a, window, carry):
            kn, vn = ("k", "v") if window is None else ("wk", "wv")
            a = 0 if window is None else a
            put = lambda x, new: jax.lax.dynamic_update_slice(
                x, new[None].astype(x.dtype), (a, 0, index, 0, 0))
            ks, vs = put(carry[kn], k), put(carry[vn], v)
            valid = causal if window is None else causal & (t - s < window)
            return (grouped_attention(q, ks[a], vs[a], valid),
                    dict(carry, **{kn: ks, vn: vs}))

        carry = {n: cache[n] for n in ("k", "v", "wk", "wv", "conv", "ssm")}
        h, m, carry = self._self_decoder(params, self._embed(params, tokens),
                                         carry, mamba_fn, attn_fn)
        h = self._cross_decoder(
            params, h, m, lambda q: grouped_attention(
                q, carry["k"][0], carry["v"][0], causal))
        return self._head(params, h), dict(carry, index=index + T)

    # ------------------------------------------------------- paged serving
    def paged_attention_impl(self) -> str:
        """What the serving layer and the benchmark's checks ask: the decode
        step attends in place through the paged kernel, and no other way."""
        return "kernel"

    def ring_entries(self, block_size: int) -> int:
        """Entries of a slot's window-kind table: the ring."""
        from ..inference import paged_kv as pk
        return pk.ring_blocks(self.config.sliding_window, block_size)

    def init_serving_state(self, batch_slots, num_blocks, block_size,
                           kv_bits=16, quant_block=64, dtype=None,
                           window_num_blocks=None):
        """The one pytree the serving engine donates through its steps: the
        ``k`` / ``v`` pool over the full layer alone (``num_blocks``), the
        ``wk`` / ``wv`` pool over the window layers (``window_num_blocks``: by
        default a full ring for every slot), per slot the Mamba layers'
        ``conv`` tails and ``ssm`` states (float32), and ``counters``.  A pool
        row is the K/V head PAIRS' ``[k1 | k2]`` of ``2 hd`` lanes."""
        from ..inference import paged_kv as pk
        c = self.config
        if kv_bits != 16:
            raise ValueError(f"kv_bits = {kv_bits}: a window pool is 16-bit "
                             "(an int8 ring or shared cache: ROADMAP)")
        if window_num_blocks is None:
            window_num_blocks = 1 + batch_slots * self.ring_entries(block_size)
        dt = dtype or self.dtype
        heads, width = c.n_kv_head // 2, 2 * c.head_dim
        pool = pk.init_pool(1, num_blocks, block_size, c.n_head, width, dt,
                            n_kv_head=heads)
        pool.update(pk.init_window_pool(c.n_window_layer, window_num_blocks,
                                        block_size, heads, width, dt))
        return dict(
            pool,
            conv=jnp.zeros((c.n_mamba_layer, batch_slots, c.mamba_d_conv - 1,
                            c.d_inner), dt),
            ssm=jnp.zeros((c.n_mamba_layer, batch_slots, c.mamba_d_state,
                           c.d_inner), jnp.float32),
            counters=jnp.zeros((len(COUNTERS),), jnp.int32))

    @staticmethod
    def recurrent_state_bytes(pool) -> int:
        return int(pool["conv"].nbytes) + int(pool["ssm"].nbytes)

    def state_step_bytes(self) -> int:
        """What a decode step's state update moves for ONE live slot: every
        Mamba layer's state read and written."""
        c = self.config
        return c.n_mamba_layer * 2 * 4 * c.mamba_d_state * c.d_inner

    def prefill_attrs(self, prompt_len: int) -> dict:
        """For the prefill's span, beside what the dispatch counts itself
        (``positions_self``, ``positions_cross``): the (layer, position)
        pairs a full forward would have run and this prefill did not, the
        cross-decoder at every position but the last."""
        c = self.config
        return {"positions_skipped": (prompt_len - 1) * (
            c.num_hidden_layers - c.full_layer - 1)}

    def serving_stats(self, pool):
        """What ``ServingEngine.stats()`` reports beside its own;
        ``kv_bytes_per_token`` is what a token adds to the growing pool (one
        layer's K and V)."""
        c = self.config
        token = 2 * pool["k"].shape[-1] * pool["k"].dtype.itemsize
        return {"kv_bytes_per_token": token,
                "shared_kv_readers": c.shared_kv_readers,
                "window_layers": c.n_window_layer,
                "mamba_layers": c.n_mamba_layer,
                "sliding_window": c.sliding_window,
                "state_bytes_per_stream": self.recurrent_state_bytes(pool)
                // pool["ssm"].shape[1]}

    def prefill_paged(self, params, toks, pool, blocks, slot, t_real):
        """One prompt, padded to its bucket, into all three kinds of state:
        the full layer's K/V into the first entries of ``blocks``, a window
        layer's LAST blocks into the ring, ``blocks``' last
        :meth:`ring_entries` (``paged_kv.write_prefill_ring``), and slot
        ``slot``'s recurrent rows written WHOLE with the state after token
        ``t_real - 1``.  The self-decoder and the full layer run over the
        prompt; the cross-decoder runs at position ``t_real - 1`` ALONE, over
        the K/V just computed (module docstring), and so does everything of
        the full layer but its K and V: its one query row attends with
        :func:`grouped_attention`, so a prefill holds no (T, T) attention of
        the full layer at all.  ``toks``: (1, T); returns ``(logits (1, V) at
        token t_real - 1, pool)``."""
        from ..inference import paged_kv as pk
        T = toks.shape[1]
        bs = pool["k"].shape[2]
        ring = self.ring_entries(bs)
        table, ring_table = blocks[:-ring], blocks[-ring:]
        pad = ((0, table.shape[0] * bs - T), (0, 0), (0, 0))

        def mamba_fn(p, h, i, keep, pool):
            h, tail, state, m = self._mamba(p, h, t_real=t_real, keep=keep)
            with jax.named_scope("ssm.seat"):
                pool = dict(
                    pool,
                    conv=pool["conv"].at[i, slot].set(
                        tail[0].astype(pool["conv"].dtype)),
                    ssm=pool["ssm"].at[i, slot].set(state[0]))
            return h, m, pool

        def attn_fn(q, k, v, a, window, pool):
            with jax.named_scope("kv.seat"):
                kp, vp = jnp.pad(k[0], pad), jnp.pad(v[0], pad)
                if window is None:
                    pool = pk.write_prefill(pool, table, kp, vp, layer=0)
                else:
                    pool = pk.with_window(pool, pk.write_prefill_ring(
                        pk.window_view(pool), ring_table, kp, vp, a, t_real))
            if window is None:
                with jax.named_scope("attn.shared"):
                    # one query row; the cross-decoder below reads the same
                    # K/V the same way
                    return grouped_attention(q, k, v, seen), (pool, k, v)
            with jax.named_scope("attn.window"):
                return banded_attention(q, k, v, window=window), pool

        seen = (jnp.arange(T) < t_real)[None, None, None, None, :]
        h, m, (pool, k, v) = self._self_decoder(
            params, self._embed(params, toks), pool, mamba_fn, attn_fn,
            at=t_real - 1)
        with jax.named_scope("cross.last"):
            h = self._cross_decoder(
                params, h, m, lambda q: grouped_attention(q, k, v, seen))
        return self._head(params, h[:, 0]), dict(
            pool, counters=self._counts(t_real, 1))

    def decode_step_paged(self, params, toks, pool, block_tables, lengths):
        """One token for every slot: ``GPT2.decode_step_paged``'s contract
        (``toks`` (B,); ``lengths`` the tokens already cached, which is the
        token's position), over BOTH tables: ``block_tables``' first columns
        the full layer's growing table, its last :meth:`ring_entries` the
        window layers' ring.  The full layer writes the token's K/V into the
        growing pool and attends in place; the cross layers attend over the
        same blocks with queries of their own and write nothing.  A row whose
        table points at the scratch block is one the host holds inactive:
        its writes land in scratch and its recurrent rows stay as they are.
        Returns ``(logits (B, V) float32, pool)``."""
        from ..inference import paged_kv as pk
        from ..ops.transformer.paged_attention import paged_attention
        assert toks.ndim == 1, \
            "a recurrent state has no multi-token window to roll back"
        ring = self.ring_entries(pool["k"].shape[2])
        table, ring_table = block_tables[:, :-ring], block_tables[:, -ring:]
        active = table[:, 0] != pk.SCRATCH_BLOCK

        def attend(q, view, tables, layer, window):
            return paged_attention(
                q, view, tables, lengths, layer, window=window,
                scale_attn=False,
                name="paged_attention_" + ("window" if window else "shared"))

        def mamba_fn(p, h, i, keep, pool):
            tail, S = pool["conv"][i], pool["ssm"][i]
            x, z, delta, Bm, Cm, padded = self._scan_inputs(p, h, tail)
            with jax.named_scope("ssm.step"):
                y, S2 = ss.selective_step(
                    x[:, 0], delta[:, 0], self._A(p), Bm[:, 0], Cm[:, 0],
                    p["D"], None if keep else z[:, 0], S)
                S2 = jnp.where(active[:, None, None], S2, S)
                tail2 = jnp.where(active[:, None, None], padded[:, 1:], tail)
                pool = dict(pool, conv=pool["conv"].at[i].set(tail2),
                            ssm=pool["ssm"].at[i].set(S2))
            h, m = self._scan_output(p, h, y[:, None], z, keep)
            return h, m, pool

        def attn_fn(q, k, v, a, window, pool):
            if window is None:
                with jax.named_scope("kv.seat"):
                    pool = pk.write_tokens(pool, 0, table, lengths, k, v)
                with jax.named_scope("attn.shared"):
                    return attend(q, pool, table, 0, None), pool
            with jax.named_scope("kv.seat"):
                view = pk.write_tokens(pk.window_view(pool), a, ring_table,
                                       lengths, k, v, ring=True)
            with jax.named_scope("attn.window"):
                out = attend(q, view, ring_table, a, window)
            return out, pk.with_window(pool, view)

        h, m, pool = self._self_decoder(
            params, self._embed(params, toks)[:, None], pool, mamba_fn,
            attn_fn)

        def cross_fn(q):
            with jax.named_scope("attn.shared"):
                return attend(q, pool, table, 0, None)
        h = self._cross_decoder(params, h, m, cross_fn)
        n = active.sum()
        return self._head(params, h[:, 0]), dict(
            pool, counters=self._counts(n, n))
