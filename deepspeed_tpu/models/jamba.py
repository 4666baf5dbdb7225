"""Jamba: a hybrid decoder — Mamba-1 mixers beside a few attention layers.

No reference counterpart (the reference framework ships no state-space
model).  The block, per layer ``l`` (HF ``JambaForCausalLM`` with
``num_experts`` 1, so every MLP is dense)::

    h = h + Mixer_l(RMS(h; ln_in))        Mixer_l = attention if
    h = h + SwiGLU(RMS(h; ln_ff))         l % period == offset, else Mamba

No positional term anywhere; RMSNorm; tied output head.  Attention is
grouped-query (``num_key_value_heads`` K/V heads shared by the query heads;
1 = multi-query).  The Mamba mixer is Mamba-1 with Jamba's three inner
RMSNorms on ``dt``, ``B``, ``C`` (``ops/selective_scan.py`` has the
recurrence and its kernel).

``JambaConfig`` keeps the PUBLISHED key names, so a benchmark family file
passes a ``config.json``'s keys as overrides and translates nothing.

Parameter tree (stacked per kind, so a run of like layers is one loop)::

    wte (V, D)                         tied embedding / head
    mamba.*  (Lm, ...)                 the Lm Mamba mixers, in layer order
    attn.*   (La, ...)                 the La attention mixers
    mlp.*    (L, ...)                  every layer's SwiGLU and its norm
    lnf (D,)

Layouts chosen for the TPU's 128 lanes, where they differ from the published
orientation: ``A_log`` is ``(N, Di)`` and ``conv_w`` ``(K, Di)`` (the channel
dim minor), and the recurrent state is ``(N, Di)`` float32.

Serving state (``init_serving_state``): the paged ``{k, v}`` pool over the
ATTENTION layers only, plus per slot ``conv (Lm, slots, K-1, Di)`` in the
model dtype and ``ssm (Lm, slots, N, Di)`` float32 — a fixed size a stream,
written whole when a request is seated (``prefill_paged``) and advanced by
``decode_step_paged`` for the rows whose table does not point at the
scratch block.
"""

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..ops import selective_scan as ss
from .gpt2 import GPT2, layer_slice as _take


@dataclasses.dataclass
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    intermediate_size: int = 8192
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    # as GPT2Config.paged_attention_impl
    paged_attention_impl: str = "auto"

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

    @property
    def attn_layers(self):
        return tuple(l for l in range(self.num_hidden_layers)
                     if l % self.attn_layer_period == self.attn_layer_offset)

    @property
    def n_attn_layer(self):
        return len(self.attn_layers)

    @property
    def kv_layers(self):
        """As ``GPT2Config.kv_layers``: the attention layers alone."""
        return self.n_attn_layer

    @property
    def n_mamba_layer(self):
        return self.num_hidden_layers - self.n_attn_layer

    def segments(self):
        """The layer stack as ``("attn", l, l + 1, a)`` and ``("mamba", l0,
        l1, m0)`` pieces in order: an attention layer with its index among
        the attention layers, or a maximal run of Mamba layers with the
        first one's index among the Mamba layers."""
        out, a, m, l = [], 0, 0, 0
        attn = set(self.attn_layers)
        L = self.num_hidden_layers
        while l < L:
            if l in attn:
                out.append(("attn", l, l + 1, a))
                a, l = a + 1, l + 1
                continue
            l1 = l
            while l1 < L and l1 not in attn:
                l1 += 1
            out.append(("mamba", l, l1, m))
            m, l = m + (l1 - l), l1
        return out


PRESETS = {
    # tests and CPU examples; the benchmark's family file passes the
    # published keys of a real checkpoint as overrides
    "jamba-tiny": dict(vocab_size=512, hidden_size=128, num_hidden_layers=4,
                       num_attention_heads=4, num_key_value_heads=1,
                       intermediate_size=256, attn_layer_period=4,
                       attn_layer_offset=1, mamba_d_state=16, mamba_d_conv=4,
                       mamba_expand=2, mamba_dt_rank=8,
                       max_position_embeddings=256),
}


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _mm(x, w):
    return x @ w.astype(x.dtype)


def swiglu(p, u):
    """The SwiGLU MLP's three matmuls over the normed input ``u``: no norm
    and no residual (``models/ouro.py`` norms the output too)."""
    return _mm(jax.nn.silu(_mm(u, p["gate_w"])) * _mm(u, p["up_w"]),
               p["down_w"])


def grouped_attention(q, k, v, valid):
    """Masked attention of ``q`` (B, T, H, hd) over ``k``/``v`` (B, S, Hkv,
    hd), query head ``h`` reading K/V head ``h // (H // Hkv)``; ``valid``
    broadcasts to (B, Hkv, G, T, S).  fp32 softmax, input-dtype matmuls.
    Returns (B, T, H * hd)."""
    B, T, H, hd = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, T, Hkv, H // Hkv, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32)
    s = s * (1.0 / np.sqrt(hd))
    s = jnp.where(valid, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgts,bskd->btkgd", p, v).reshape(B, T, H * hd)


class Jamba:
    """Hybrid Mamba / attention decoder LM (params: dict pytree with one
    stack per kind of layer)."""

    supports_paged_decode = True
    # a stream's state is more than its K/V blocks: the serving layer
    # refuses what assumes otherwise (inference/serving.py)
    has_recurrent_state = True

    def __init__(self, config: Optional[JambaConfig] = None,
                 preset: str = None, dtype=jnp.bfloat16, **overrides):
        if config is None:
            base = dict(PRESETS[preset or "jamba-tiny"])
            base.update(overrides)
            config = JambaConfig(**base)
        assert config.attn_layers, "a Jamba needs at least one attention layer"
        assert config.n_head % config.n_kv_head == 0
        self.config = config
        self.dtype = dtype

    # ------------------------------------------------------------------ init
    def init(self, rng):
        """Matrices normal(0.02), output projections scaled by 1/sqrt(2L) as
        the GPT-2 family; the state-space constants as Mamba publishes them:
        ``A_log = log(1..N)`` per channel, ``D = 1``, the conv taps uniform
        in +-1/sqrt(K), ``dt_b`` the inverse softplus of ``dt`` log-uniform
        in [1e-3, 1e-1]; norm weights 1."""
        c = self.config
        D, V, F, L = c.hidden_size, c.vocab_size, c.intermediate_size, \
            c.num_hidden_layers
        Lm, La = c.n_mamba_layer, c.n_attn_layer
        Di, N, K, R = c.d_inner, c.mamba_d_state, c.mamba_d_conv, \
            c.mamba_dt_rank
        H, Hkv, hd = c.n_head, c.n_kv_head, c.head_dim
        k = jax.random.split(rng, 16)
        std, proj = 0.02, 0.02 / np.sqrt(2.0 * L)
        f32 = jnp.float32
        n = lambda key, shape, s=std: jax.random.normal(key, shape, f32) * s
        dt = jnp.exp(jax.random.uniform(k[12], (Lm, Di), f32)
                     * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
        lim = 1.0 / np.sqrt(K)
        return {
            "wte": n(k[0], (V, D)),
            "mamba": {
                "ln_in": jnp.ones((Lm, D), f32),
                "in_w": n(k[1], (Lm, D, 2 * Di)),
                "conv_w": jax.random.uniform(k[2], (Lm, K, Di), f32,
                                             -lim, lim),
                "conv_b": jnp.zeros((Lm, Di), f32),
                "x_w": n(k[3], (Lm, Di, R + 2 * N)),
                "dt_norm": jnp.ones((Lm, R), f32),
                "b_norm": jnp.ones((Lm, N), f32),
                "c_norm": jnp.ones((Lm, N), f32),
                "dt_w": n(k[4], (Lm, R, Di)),
                "dt_b": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, N + 1, dtype=f32))[None, :, None],
                    (Lm, N, Di)),
                "D": jnp.ones((Lm, Di), f32),
                "out_w": n(k[5], (Lm, Di, D), proj),
            },
            "attn": {
                "ln_in": jnp.ones((La, D), f32),
                "q_w": n(k[6], (La, D, H * hd)),
                "k_w": n(k[7], (La, D, Hkv * hd)),
                "v_w": n(k[8], (La, D, Hkv * hd)),
                "o_w": n(k[9], (La, H * hd, D), proj),
            },
            "mlp": {
                "ln_ff": jnp.ones((L, D), f32),
                "gate_w": n(k[10], (L, D, F)),
                "up_w": n(k[11], (L, D, F)),
                "down_w": n(k[13], (L, F, D), proj),
            },
            "lnf": jnp.ones((D,), f32),
        }

    def num_params(self):
        c = self.config
        D, Di, N, K, R = c.hidden_size, c.d_inner, c.mamba_d_state, \
            c.mamba_d_conv, c.mamba_dt_rank
        mamba = (D * 2 * Di + Di * K + Di + Di * (R + 2 * N) + R * Di + Di
                 + Di * N + Di + Di * D + R + 2 * N)
        attn = 2 * D * c.n_head * c.head_dim + 2 * D * c.n_kv_head * c.head_dim
        mlp = 3 * D * c.intermediate_size
        return (c.n_mamba_layer * (mamba + mlp + 2 * D)
                + c.n_attn_layer * (attn + mlp + 2 * D)
                + c.vocab_size * D + D)

    # ---------------------------------------------------------------- pieces
    def _mlp(self, p, h):
        with jax.named_scope("mlp"):
            c = self.config
            u = _rms(h, p["ln_ff"], c.rms_norm_eps)
            return h + swiglu(p, u)

    def _qkv(self, p, h):
        with jax.named_scope("attention"):
            c = self.config
            u = _rms(h, p["ln_in"], c.rms_norm_eps)
            lead = u.shape[:-1]
            return (_mm(u, p["q_w"]).reshape(lead + (c.n_head, c.head_dim)),
                    _mm(u, p["k_w"]).reshape(lead + (c.n_kv_head, c.head_dim)),
                    _mm(u, p["v_w"]).reshape(lead + (c.n_kv_head, c.head_dim)))

    def _scan_inputs(self, p, h, tail):
        """A Mamba mixer up to the recurrence, for ``h`` (B, T, D) and the
        convolution's incoming ``tail`` (B, K-1, Di) or None.  Returns ``(x,
        z, delta, B, C, padded)``: the scan's operands, and the
        convolution's input with its tail in front (the next tail is cut
        out of it)."""
        with jax.named_scope("ssm.proj"):
            c = self.config
            N, R = c.mamba_d_state, c.mamba_dt_rank
            u = _rms(h, p["ln_in"], c.rms_norm_eps)
            x, z = jnp.split(_mm(u, p["in_w"]), 2, axis=-1)
            with jax.named_scope("ssm.conv"):
                x, padded = ss.causal_conv(x, p["conv_w"], p["conv_b"], tail)
                x = jax.nn.silu(x)
            dt, Bm, Cm = jnp.split(_mm(x, p["x_w"]), [R, R + N], axis=-1)
            dt = _rms(dt, p["dt_norm"], c.rms_norm_eps)
            Bm = _rms(Bm.astype(jnp.float32), p["b_norm"], c.rms_norm_eps)
            Cm = _rms(Cm.astype(jnp.float32), p["c_norm"], c.rms_norm_eps)
            delta = jax.nn.softplus(_mm(dt, p["dt_w"]).astype(jnp.float32)
                                    + p["dt_b"].astype(jnp.float32))
            return x, z, delta, Bm, Cm, padded

    @staticmethod
    def _A(p):
        return -jnp.exp(p["A_log"].astype(jnp.float32))

    def _mamba(self, p, h, tail=None, h0=None, t_real=None,
               scan_impl="auto"):
        """One Mamba mixer with its residual over ``h`` (B, T, D).  Returns
        ``(h, new tail (B, K-1, Di), state (B, N, Di) float32)``, both taken
        after token ``t_real - 1`` (the last one when None)."""
        c = self.config
        T = h.shape[1]
        x, z, delta, Bm, Cm, padded = self._scan_inputs(p, h, tail)
        if t_real is not None:
            delta = ss.mask_delta(delta, t_real)
        with jax.named_scope("ssm.scan"):
            y, S = ss.selective_scan(x, delta, self._A(p), Bm, Cm, p["D"], z,
                                     h0=h0, impl=scan_impl)
        new_tail = ss.conv_tail_at(padded, T if t_real is None else t_real,
                                   c.mamba_d_conv - 1)
        with jax.named_scope("ssm.proj"):
            return h + _mm(y, p["out_w"]), new_tail, S

    def _layers(self, params, h, carry, mamba_fn, attn_fn, sliced=False):
        """``h`` through every layer.  ``mamba_fn(p, h, m, carry)`` and
        ``attn_fn(p, h, a, carry)`` run a mixer with its residual and return
        ``(h, carry)``; the MLP follows here.  A run of Mamba layers is ONE
        loop over the stacked weights — indexed in place (serving: a slice
        of a stack would copy it every call), or, ``sliced``, scanned over
        a slice (training: gradients then flow into the slice, not into a
        scatter per layer)."""
        for kind, l0, l1, i0 in self.config.segments():
            if kind == "attn":
                h, carry = attn_fn(_take(params["attn"], i0), h, i0, carry)
                h = self._mlp(_take(params["mlp"], l0), h)
            elif sliced:
                def body(hc, xs):
                    pm, pf, m = xs
                    hh, cc = mamba_fn(pm, hc[0], m, hc[1])
                    return (self._mlp(pf, hh), cc), None
                n = l1 - l0
                cut = lambda t, a: jax.tree_util.tree_map(
                    lambda x: x[a:a + n], t)
                (h, carry), _ = jax.lax.scan(
                    body, (h, carry),
                    (cut(params["mamba"], i0), cut(params["mlp"], l0),
                     jnp.arange(i0, i0 + n)))
            else:
                def body(j, hc, l0=l0, i0=i0):
                    hh, cc = mamba_fn(_take(params["mamba"], i0 + j), hc[0],
                                      i0 + j, hc[1])
                    return self._mlp(_take(params["mlp"], l0 + j), hh), cc
                h, carry = jax.lax.fori_loop(0, l1 - l0, body, (h, carry))
        return h, carry

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["wte"].astype(self.dtype)[tokens]

    def _head(self, params, h):
        with jax.named_scope("lm_head"):
            h = _rms(h, params["lnf"], self.config.rms_norm_eps)
            return jnp.einsum("...d,vd->...v", h, params["wte"].astype(h.dtype),
                              preferred_element_type=jnp.float32)

    # --------------------------------------------------------------- forward
    def apply(self, params, tokens, rng=None, deterministic=True,
              return_hidden=False, scan_impl="auto"):
        """``tokens`` (B, T) -> logits (B, T, V) float32.  No dropout in
        the family.  Differentiable with ``scan_impl="jnp"`` (what ``loss``
        passes: the kernel has no backward)."""
        T = tokens.shape[1]
        causal = jnp.tril(jnp.ones((T, T), bool))
        h = self._embed(params, tokens)

        def mamba_fn(p, h, m, carry):
            return self._mamba(p, h, scan_impl=scan_impl)[0], carry

        def attn_fn(p, h, a, carry):
            q, k, v = self._qkv(p, h)
            with jax.named_scope("attention"):
                return h + _mm(grouped_attention(q, k, v, causal),
                               p["o_w"]), carry

        h, _ = self._layers(params, h, (), mamba_fn, attn_fn, sliced=True)
        if return_hidden:
            return _rms(h, params["lnf"], self.config.rms_norm_eps)
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
        """``InferenceEngine.generate``'s cache: dense K/V for the
        attention layers, the convolution tail and the recurrent state for
        the Mamba layers, and the write index."""
        c = self.config
        S = max_len or c.max_seq
        dtype = dtype or self.dtype
        kv = (c.n_attn_layer, batch_size, S, c.n_kv_head, c.head_dim)
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
                "conv": jnp.zeros((c.n_mamba_layer, batch_size,
                                   c.mamba_d_conv - 1, c.d_inner), dtype),
                "ssm": jnp.zeros((c.n_mamba_layer, batch_size,
                                  c.mamba_d_state, c.d_inner), jnp.float32),
                "index": jnp.zeros((), jnp.int32)}

    def apply_with_cache(self, params, tokens, cache):
        """Forward ``tokens`` (B, T) from ``cache['index']``; returns
        ``(logits (B, T, V), new_cache)``: prefill (T = prompt) and decode
        (T = 1) alike."""
        B, T = tokens.shape
        index = cache["index"]
        S = cache["k"].shape[2]
        valid = (jnp.arange(S)[None, :] <= index + jnp.arange(T)[:, None])
        h = self._embed(params, tokens)

        def mamba_fn(p, h, m, carry):
            k, v, conv, ssm = carry
            h, tail, state = self._mamba(p, h, tail=conv[m], h0=ssm[m],
                                         scan_impl="jnp")
            return h, (k, v, conv.at[m].set(tail.astype(conv.dtype)),
                       ssm.at[m].set(state))

        def attn_fn(p, h, a, carry):
            k, v, conv, ssm = carry
            q, kn, vn = self._qkv(p, h)
            k = jax.lax.dynamic_update_slice(
                k, kn[None].astype(k.dtype), (a, 0, index, 0, 0))
            v = jax.lax.dynamic_update_slice(
                v, vn[None].astype(v.dtype), (a, 0, index, 0, 0))
            with jax.named_scope("attention"):
                out = grouped_attention(q, k[a], v[a], valid)
                return h + _mm(out, p["o_w"]), (k, v, conv, ssm)

        h, (k, v, conv, ssm) = self._layers(
            params, h, (cache["k"], cache["v"], cache["conv"], cache["ssm"]),
            mamba_fn, attn_fn)
        return self._head(params, h), {"k": k, "v": v, "conv": conv,
                                       "ssm": ssm, "index": index + T}

    # ------------------------------------------------------- paged serving
    def paged_attention_impl(self) -> str:
        impl = self.config.paged_attention_impl
        if impl == "auto":
            impl = "kernel"
        assert impl in ("kernel", "gather"), impl
        return impl

    def init_serving_state(self, batch_slots, num_blocks, block_size,
                           kv_bits=16, quant_block=64, dtype=None):
        """The one pytree the serving engine donates through its steps: the
        paged ``{k, v}`` pool over the attention layers, and per slot the
        Mamba layers' convolution tails and recurrent states."""
        from ..inference import paged_kv as pk
        c = self.config
        dtype = dtype or self.dtype
        pool = pk.init_pool(c.n_attn_layer, num_blocks, block_size, c.n_head,
                            c.head_dim, dtype, kv_bits=kv_bits,
                            quant_block=quant_block, n_kv_head=c.n_kv_head)
        pool["conv"] = jnp.zeros((c.n_mamba_layer, batch_slots,
                                  c.mamba_d_conv - 1, c.d_inner), dtype)
        pool["ssm"] = jnp.zeros((c.n_mamba_layer, batch_slots,
                                 c.mamba_d_state, c.d_inner), jnp.float32)
        return pool

    @staticmethod
    def recurrent_state_bytes(pool) -> int:
        return int(pool["conv"].nbytes) + int(pool["ssm"].nbytes)

    def prefill_paged(self, params, toks, pool, blocks, slot, t_real):
        """One prompt, padded to its bucket, into the pool: the attention
        layers' K/V into ``blocks``, and slot ``slot``'s recurrent rows
        written WHOLE with the state after token ``t_real - 1`` (the pad
        after it must not enter a recurrence).  ``toks``: (1, T); returns
        ``(logits (1, V) at token t_real - 1, pool)``."""
        from ..inference import paged_kv as pk
        c = self.config
        T = toks.shape[1]
        bucket = blocks.shape[0] * pool["k"].shape[2]
        causal = jnp.tril(jnp.ones((T, T), bool))
        h = self._embed(params, toks)

        def mamba_fn(p, h, m, carry):
            pool, ks, vs = carry
            h, tail, state = self._mamba(p, h, t_real=t_real)
            with jax.named_scope("ssm.seat"):
                pool = dict(
                    pool,
                    conv=pool["conv"].at[m, slot].set(
                        tail[0].astype(pool["conv"].dtype)),
                    ssm=pool["ssm"].at[m, slot].set(state[0]))
            return h, (pool, ks, vs)

        def attn_fn(p, h, a, carry):
            pool, ks, vs = carry
            q, k, v = self._qkv(p, h)
            with jax.named_scope("attention"):
                out = grouped_attention(q, k, v, causal)
                return (h + _mm(out, p["o_w"]),
                        (pool, ks + (k[0],), vs + (v[0],)))

        h, (pool, ks, vs) = self._layers(params, h, (pool, (), ()),
                                         mamba_fn, attn_fn)
        with jax.named_scope("kv.seat"):
            k, v = jnp.stack(ks), jnp.stack(vs)        # (La, T, Hkv, hd)
            if T < bucket:   # a bucket rounded past max_seq (GPT2 likewise)
                pad = ((0, 0), (0, bucket - T), (0, 0), (0, 0))
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            pool = pk.write_prefill(pool, blocks, k, v)
        row = jax.lax.dynamic_slice_in_dim(h[0], t_real - 1, 1, axis=0)
        return self._head(params, row), pool

    def decode_step_paged(self, params, toks, pool, block_tables, lengths):
        """One token for every slot: ``GPT2.decode_step_paged``'s contract
        (``toks`` (B,), ``lengths`` the tokens already cached).  A row whose
        table points at the scratch block is one the host holds inactive:
        its K/V write lands in scratch and its recurrent rows stay as they
        are.  Returns ``(logits (B, V) float32, pool)``."""
        from ..inference import paged_kv as pk
        from ..ops.transformer.paged_attention import paged_attention
        c = self.config
        assert toks.ndim == 1, \
            "a recurrent state has no multi-token window to roll back"
        impl = self.paged_attention_impl()
        active = block_tables[:, 0] != pk.SCRATCH_BLOCK
        h = self._embed(params, toks)[:, None]                  # (B, 1, D)

        def mamba_fn(p, h, m, pool):
            tail, S = pool["conv"][m], pool["ssm"][m]
            x, z, delta, Bm, Cm, padded = self._scan_inputs(p, h, tail)
            with jax.named_scope("ssm.step"):
                y, S2 = ss.selective_step(x[:, 0], delta[:, 0], self._A(p),
                                          Bm[:, 0], Cm[:, 0], p["D"],
                                          z[:, 0], S)
                S2 = jnp.where(active[:, None, None], S2, S)
                tail2 = jnp.where(active[:, None, None], padded[:, 1:], tail)
                pool = dict(pool, conv=pool["conv"].at[m].set(tail2),
                            ssm=pool["ssm"].at[m].set(S2))
            with jax.named_scope("ssm.proj"):
                return h + _mm(y[:, None], p["out_w"]), pool

        def attn_fn(p, h, a, pool):
            q, k, v = self._qkv(p, h)                  # (B, 1, H | Hkv, hd)
            with jax.named_scope("kv.seat"):
                pool = pk.write_tokens(pool, a, block_tables, lengths, k, v)
            with jax.named_scope("attention"):
                if impl == "kernel":
                    out = paged_attention(q, pool, block_tables, lengths, a)
                else:
                    keys, vals = pk.gather_kv(pool, a, block_tables,
                                              self.dtype, c.n_kv_head)
                    valid = (jnp.arange(keys.shape[1])[None, :]
                             <= lengths[:, None])[:, None, None, None, :]
                    out = grouped_attention(q, keys, vals, valid)
                return h + _mm(out, p["o_w"]), pool

        h, pool = self._layers(params, h, pool, mamba_fn, attn_fn)
        return self._head(params, h[:, 0]), pool
