"""Ouro: a looped decoder — the same stack of layers run several times.

No reference counterpart (the reference framework ships no looped model).
The forward (HF ``OuroForCausalLM`` / arXiv:2510.25741), with ``L`` layers
and ``R = total_ut_steps`` loops over ONE set of weights::

    h = E[tokens]
    for r in 0..R-1:
      for l in 0..L-1:
        q, k, v = rope(a Wq), rope(a Wk), a Wv        a = RMS(h; ln_in)
        h = h + RMS(attention(q, K[r,l], V[r,l]) Wo; ln_attn_out)
        h = h + RMS(SwiGLU(RMS(h; ln_ff)); ln_ff_out)
      h = RMS(h; lnf)                  the final norm closes every loop
      lambda_r = sigmoid(h . exit_w + exit_b)         the exit gate
    logits = h @ head^T

Sandwich norms (a norm on each sub-layer's output too), rotate-half rotary
positions over the whole head, no biases, untied head.  The residual stream
``h`` is carried in float32 whatever the model dtype; the matmuls, the K/V
and the weights are in the model dtype.  Through 2 x ``R * L`` = 384
additions of unit-size sub-layer outputs a bfloat16 stream of size about 10
rounds each sum to 8 bits: most of what the served logits then differ by
from the float32 reference (a tenth of a decode step's 16 x 2048 values is
nothing beside the weights it streams).  Loop ``r`` of layer
``l`` keeps ITS OWN keys and values: a cache, dense or paged, has ``R * L``
layer-applications, indexed ``r * L + l`` (``OuroConfig.kv_layers``).

With the published ``early_exit_threshold`` 1 no token leaves early: every
token runs all ``R`` loops and the gate's values enter no logit; any other
threshold is refused (rows of one batch at different depths have no path
here).  ``loss`` is next-token cross-entropy on the last loop's logits; the
paper's expected loss over the exit distribution has an entropy weight the
config does not give.

``OuroConfig`` keeps the PUBLISHED key names, as ``JambaConfig`` does.

Parameter tree::

    wte (V, D)                       embedding
    blocks.* (L, ...)                the L layers, stacked: one scan a loop
                                     (q_w, k_w (L, out, in) as published;
                                     the other matrices (L, in, out))
    lnf (D,)   exit_w (D,)  exit_b ()
    head (V, D)                      untied output head
"""

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from .gpt2 import GPT2, layer_slice as _take
from .jamba import _mm, _rms, grouped_attention, swiglu
from .rotary import apply_rotary_pos_emb, rotary_freqs


@dataclasses.dataclass
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 65536
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    # as GPT2Config.paged_attention_impl
    paged_attention_impl: str = "auto"

    # ---- the names the serving layer and the analysis tools ask for
    @property
    def n_layer(self):
        return self.num_hidden_layers

    @property
    def kv_layers(self):
        """Layer-applications that keep K/V for a token: every loop of
        every layer its own."""
        return self.total_ut_steps * self.num_hidden_layers

    @property
    def loop_steps(self):
        """Times the stack of layers runs (what the serving layer's spans
        and ``stats()`` report beside ``kv_layers``)."""
        return self.total_ut_steps

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
    def max_seq(self):
        return self.max_position_embeddings


PRESETS = {
    # tests and CPU examples; the benchmark's family file passes the
    # published keys of a real checkpoint as overrides
    "ouro-tiny": dict(vocab_size=512, hidden_size=128, num_hidden_layers=3,
                      num_attention_heads=4, num_key_value_heads=4,
                      head_dim=32, intermediate_size=256, total_ut_steps=4,
                      max_position_embeddings=256),
}


def _mmt(x, w):
    """``x @ w^T`` for a matrix stored (out, in).  ``q_w`` and ``k_w`` are:
    stored (in, out), the TPU compiler re-lays both stacks WHOLE on every
    decode step (the 16-row products that feed the rotation want the input
    dim minor): 0.8 GB of copies a step at the published size, found by
    compiling the step for a v5e."""
    return jnp.einsum("...d,nd->...n", x, w.astype(x.dtype))


class Ouro:
    """Looped decoder LM (params: dict pytree, the layers stacked)."""

    supports_paged_decode = True

    def __init__(self, config: Optional[OuroConfig] = None,
                 preset: str = None, dtype=jnp.bfloat16, **overrides):
        if config is None:
            base = dict(PRESETS[preset or "ouro-tiny"])
            base.update(overrides)
            config = OuroConfig(**base)
        if config.early_exit_threshold != 1:
            raise ValueError(
                f"early_exit_threshold = {config.early_exit_threshold!r}: "
                "below 1 tokens leave the loop at different depths, which "
                "models/ouro.py does not run; only the published 1 is")
        if config.rope_scaling is not None:
            raise ValueError(
                f"rope_scaling = {config.rope_scaling!r}: models/ouro.py "
                "computes plain rotary positions; only null is run")
        assert config.n_head % config.n_kv_head == 0
        assert config.total_ut_steps >= 1
        self.config = config
        self.dtype = dtype
        # (max_seq, head_dim / 2) float32 cos and sin, for the served limit
        self._rope = rotary_freqs(config.head_dim, config.max_seq,
                                  base=config.rope_theta)

    # ------------------------------------------------------------------ init
    def init(self, rng):
        """Matrices normal(0.02); the output projections (``o_w``,
        ``down_w``) scaled by 1/sqrt(2L) as the other families (L, the
        layers that HAVE weights: the sandwich norm takes a sub-layer's
        scale out of the forward again, so the choice between L and R * L
        moves no logit); norm weights 1; the exit gate zero-mean."""
        c = self.config
        D, V, F, L = (c.hidden_size, c.vocab_size, c.intermediate_size,
                      c.num_hidden_layers)
        H, Hkv, hd = c.n_head, c.n_kv_head, c.head_dim
        k = jax.random.split(rng, 10)
        std, proj = 0.02, 0.02 / np.sqrt(2.0 * L)
        f32 = jnp.float32
        n = lambda key, shape, s=std: jax.random.normal(key, shape, f32) * s
        ones = lambda: jnp.ones((L, D), f32)
        return {
            "wte": n(k[0], (V, D)),
            "blocks": {
                "ln_in": ones(),
                "q_w": n(k[1], (L, H * hd, D)),
                "k_w": n(k[2], (L, Hkv * hd, D)),
                "v_w": n(k[3], (L, D, Hkv * hd)),
                "o_w": n(k[4], (L, H * hd, D), proj),
                "ln_attn_out": ones(),
                "ln_ff": ones(),
                "gate_w": n(k[5], (L, D, F)),
                "up_w": n(k[6], (L, D, F)),
                "down_w": n(k[7], (L, F, D), proj),
                "ln_ff_out": ones(),
            },
            "lnf": jnp.ones((D,), f32),
            "exit_w": n(k[8], (D,)),
            "exit_b": jnp.zeros((), f32),
            "head": n(k[9], (V, D)),
        }

    def num_params(self):
        c = self.config
        D = c.hidden_size
        attn = 2 * D * c.n_head * c.head_dim + 2 * D * c.n_kv_head * c.head_dim
        layer = attn + 3 * D * c.intermediate_size + 4 * D
        return (c.num_hidden_layers * layer + 2 * c.vocab_size * D
                + D + D + 1)

    # ---------------------------------------------------------------- pieces
    def _layer(self, p, h, positions, attend):
        """One layer over the float32 residual stream ``h`` (B, T, D) at
        ``positions`` ((T,) or (B, T)).  ``attend(q, k, v)``, the rotated
        queries and keys and the values, (B, T, H | Hkv, hd) in the model
        dtype, returns ``((B, T, H * hd), carry)``."""
        c = self.config
        eps = c.rms_norm_eps
        f32 = jnp.float32
        with jax.named_scope("attention"):
            u = _rms(h, p["ln_in"], eps).astype(self.dtype)
            lead = u.shape[:-1]
            cos, sin = self._rope
            q = apply_rotary_pos_emb(
                _mmt(u, p["q_w"]).reshape(lead + (c.n_head, c.head_dim)),
                cos, sin, positions)
            k = apply_rotary_pos_emb(
                _mmt(u, p["k_w"]).reshape(lead + (c.n_kv_head, c.head_dim)),
                cos, sin, positions)
            v = _mm(u, p["v_w"]).reshape(lead + (c.n_kv_head, c.head_dim))
            out, carry = attend(q, k, v)
            h = h + _rms(_mm(out, p["o_w"]).astype(f32), p["ln_attn_out"],
                         eps)
        with jax.named_scope("mlp"):
            u = _rms(h, p["ln_ff"], eps).astype(self.dtype)
            h = h + _rms(swiglu(p, u).astype(f32), p["ln_ff_out"], eps)
        return h, carry

    def _loops(self, params, h, carry, positions, attn_fn, sliced=False,
               gates=False):
        """``h`` through ``R`` loops of the ``L`` layers.  ``attn_fn(q, k,
        v, i, carry)`` attends for layer-application ``i = r * L + l`` and
        returns ``(out, carry)``.  One loop is ONE scan over the stacked
        weights — indexed in place (serving), or, ``sliced``, scanned over
        (training: gradients flow into the stack, and every loop's add up).
        Returns ``(h after the last loop's final norm, carry, the gate's
        (R, B, T) values or None)``."""
        c = self.config
        L = c.num_hidden_layers
        blocks = params["blocks"]

        def one_loop(hc, r):
            def layer(p, l, hc):
                return self._layer(
                    p, hc[0], positions,
                    lambda q, k, v: attn_fn(q, k, v, r * L + l, hc[1]))
            with jax.named_scope("ut.loop"):
                if sliced:
                    hc, _ = jax.lax.scan(
                        lambda hc, xs: (layer(xs[0], xs[1], hc), None),
                        hc, (blocks, jnp.arange(L)))
                else:
                    hc = jax.lax.fori_loop(
                        0, L, lambda l, hc: layer(_take(blocks, l), l, hc),
                        hc)
            h = _rms(hc[0], params["lnf"], c.rms_norm_eps)
            return (h, hc[1]), (self._exit_gate(params, h) if gates else None)

        (h, carry), lam = jax.lax.scan(one_loop, (h, carry),
                                       jnp.arange(c.total_ut_steps))
        return h, carry, lam

    @staticmethod
    def _exit_gate(params, h):
        return jax.nn.sigmoid(
            h.astype(jnp.float32) @ params["exit_w"].astype(jnp.float32)
            + params["exit_b"].astype(jnp.float32))

    @staticmethod
    def _embed(params, tokens):
        with jax.named_scope("embed"):
            return params["wte"][tokens].astype(jnp.float32)

    def _head(self, params, h):
        with jax.named_scope("lm_head"):
            return jnp.einsum("...d,vd->...v", h.astype(self.dtype),
                              params["head"].astype(self.dtype),
                              preferred_element_type=jnp.float32)

    # --------------------------------------------------------------- forward
    def apply(self, params, tokens, rng=None, deterministic=True,
              return_hidden=False, return_gates=False):
        """``tokens`` (B, T) -> logits (B, T, V) float32 (no dropout in the
        family); with ``return_gates`` also the exit gate's values after
        each loop, (R, B, T)."""
        T = tokens.shape[1]
        causal = jnp.tril(jnp.ones((T, T), bool))
        h = self._embed(params, tokens)
        h, _, lam = self._loops(
            params, h, (), jnp.arange(T),
            lambda q, k, v, i, carry: (grouped_attention(q, k, v, causal),
                                       carry),
            sliced=True, gates=return_gates)
        out = h if return_hidden else self._head(params, h)
        return (out, lam) if return_gates else out

    def loss(self, params, batch, rng=None):
        """Next-token LM loss on the last loop's logits; ``batch`` as
        ``GPT2.loss`` takes it."""
        tokens, labels = GPT2._split_batch(batch)
        logits = self.apply(params, tokens)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.mean(lse - picked)

    # ---------------------------------------------------- contiguous decoding
    def init_cache(self, batch_size: int, max_len: Optional[int] = None,
                   dtype=None):
        """``InferenceEngine.generate``'s cache: dense K/V for every
        layer-application, and the write index."""
        c = self.config
        kv = (c.kv_layers, batch_size, max_len or c.max_seq, c.n_kv_head,
              c.head_dim)
        dtype = dtype or self.dtype
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
                "index": jnp.zeros((), jnp.int32)}

    def apply_with_cache(self, params, tokens, cache):
        """Forward ``tokens`` (B, T) from ``cache['index']``; returns
        ``(logits (B, T, V), new_cache)``: prefill and decode alike."""
        T = tokens.shape[1]
        index = cache["index"]
        S = cache["k"].shape[2]
        valid = jnp.arange(S)[None, :] <= index + jnp.arange(T)[:, None]
        h = self._embed(params, tokens)

        def attn_fn(q, kn, vn, i, kv):
            k = jax.lax.dynamic_update_slice(
                kv[0], kn[None].astype(kv[0].dtype), (i, 0, index, 0, 0))
            v = jax.lax.dynamic_update_slice(
                kv[1], vn[None].astype(kv[1].dtype), (i, 0, index, 0, 0))
            return grouped_attention(q, k[i], v[i], valid), (k, v)

        h, (k, v), _ = self._loops(params, h, (cache["k"], cache["v"]),
                                   index + jnp.arange(T), attn_fn)
        return self._head(params, h), {"k": k, "v": v, "index": index + T}

    # ------------------------------------------------------- paged serving
    def paged_attention_impl(self) -> str:
        impl = self.config.paged_attention_impl
        if impl == "auto":
            impl = "kernel"
        assert impl in ("kernel", "gather"), impl
        return impl

    def init_serving_state(self, batch_slots, num_blocks, block_size,
                           kv_bits=16, quant_block=64, dtype=None):
        """The pytree the serving engine donates through its steps: the
        paged ``{k, v}`` pool over the ``R * L`` layer-applications
        (``batch_slots`` sizes nothing here)."""
        from ..inference import paged_kv as pk
        c = self.config
        return pk.init_pool(c.kv_layers, num_blocks, block_size, c.n_head,
                            c.head_dim, dtype or self.dtype, kv_bits=kv_bits,
                            quant_block=quant_block, n_kv_head=c.n_kv_head)

    def prefill_paged(self, params, toks, pool, blocks, slot, t_real):
        """One prompt, padded to its bucket, into the pool at positions
        ``0..T-1``: each layer-application writes its K/V into ``blocks``
        as it goes (stacked first, a long prompt's would stand whole beside
        the pool).  ``toks``: (1, T); ``slot`` unused (no state a slot);
        returns ``(logits (1, V) at token t_real - 1, pool)``."""
        from ..inference import paged_kv as pk
        T = toks.shape[1]
        bucket = blocks.shape[0] * pool["k"].shape[2]
        causal = jnp.tril(jnp.ones((T, T), bool))
        # a bucket rounded past max_seq (GPT2 likewise): pad rows sit
        # beyond the slot's length, masked and then overwritten
        pad = ((0, bucket - T), (0, 0), (0, 0))
        h = self._embed(params, toks)

        def attn_fn(q, k, v, i, pool):
            with jax.named_scope("kv.seat"):
                pool = pk.write_prefill(pool, blocks, jnp.pad(k[0], pad),
                                        jnp.pad(v[0], pad), layer=i)
            return grouped_attention(q, k, v, causal), pool

        h, pool, _ = self._loops(params, h, pool, jnp.arange(T), attn_fn)
        row = jax.lax.dynamic_slice_in_dim(h[0], t_real - 1, 1, axis=0)
        return self._head(params, row), pool

    def decode_step_paged(self, params, toks, pool, block_tables, lengths):
        """One decode window for every slot: ``GPT2.decode_step_paged``'s
        contract (``toks`` (B,) or a (B, W) window; ``lengths`` the tokens
        already cached, which is the first window token's position).  Each
        of the ``R * L`` layer-applications writes the window's K/V into
        its own layer of the pool and attends over it.  Returns ``(logits
        (B, V) or (B, W, V) float32, pool)``."""
        from ..inference import paged_kv as pk
        from ..ops.transformer.paged_attention import paged_attention
        c = self.config
        squeeze = toks.ndim == 1
        if squeeze:
            toks = toks[:, None]
        W = toks.shape[1]
        impl = self.paged_attention_impl()
        window = jnp.arange(W, dtype=lengths.dtype)
        positions = jnp.minimum(lengths[:, None] + window[None, :],
                                c.max_seq - 1)
        h = self._embed(params, toks)                           # (B, W, D)

        def attn_fn(q, k, v, i, pool):
            with jax.named_scope("kv.seat"):
                pool = pk.write_tokens(pool, i, block_tables, lengths, k, v)
            if impl == "kernel":
                return paged_attention(q, pool, block_tables, lengths, i), pool
            keys, vals = pk.gather_kv(pool, i, block_tables, self.dtype,
                                      c.n_kv_head)
            valid = (jnp.arange(keys.shape[1])[None, None, :]
                     <= lengths[:, None, None] + window[None, :, None])
            return grouped_attention(q, keys, vals,
                                     valid[:, None, None]), pool

        h, pool, _ = self._loops(params, h, pool, positions, attn_fn)
        return self._head(params, h[:, 0] if squeeze else h), pool
