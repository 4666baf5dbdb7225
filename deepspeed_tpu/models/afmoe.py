"""AFMoE (Arcee Trinity): window and global attention layers mixed, a gated
grouped-query attention, routed experts beside a shared one.

No reference counterpart (the reference framework ships neither).  The layer
(HF ``AfmoeForCausalLM``, ``model_type`` ``afmoe``), for layer ``l`` with ``H``
query heads over ``Hkv`` K/V heads of ``hd`` and ``RMS(x; w)``::

    h0 = E[tokens] * sqrt(D)                          (mup_enabled)
    a = RMS(h; ln_in)
    q, k, v = a W_q, a W_k, a W_v                     (no bias)
    q, k = RMS(q; q_norm), RMS(k; k_norm)             (over each head's hd)
    sliding layer:  q, k = rope(q), rope(k)           (all hd dims, rotate-half)
    full layer:     no positions at all
    o = softmax(q k^T / sqrt(hd) + mask) v            (grouped: H / Hkv a head)
        sliding: key s visible to query t iff 0 <= t - s < sliding_window
        full:    iff s <= t
    h = h + RMS((o * sigmoid(a W_gate)) W_o; ln_post_attn)
    u = RMS(h; ln_pre_mlp)
    l <  num_dense_layers:  y = SwiGLU_dense(u)
    otherwise:  s = sigmoid(u W_r)  (float32, all E experts)
                e_1..e_k = top-k of s + expert_bias   (the bias picks only)
                w_i = s[e_i] / sum_j s[e_j] * route_scale
                y = SwiGLU_shared(u) + sum_i w_i SwiGLU^{e_i}(u)
    h = h + RMS(y; ln_post_mlp)
    logits = RMS(h; lnf) head^T

TWO KINDS OF K/V LIFETIME.  A full layer needs every position of a stream, a
sliding layer the last ``sliding_window`` and no more.  The serving state
holds two pools (``inference/paged_kv.py``, "window pool"): ``k`` / ``v`` over
the full layers with tables that grow, ``wk`` / ``wv`` over the sliding layers
with tables used as RINGS.  ``block_tables`` (and a prefill's ``blocks``)
carry both: the growing table first, the ring's ``ring_entries`` last.  A
prompt's attention runs in blocks (no ``T x T`` scores): a band on the sliding
layers, the causal triangle on the full ones (on a TPU both through the flash
forward, ``_attend_prompt``).

ONE CHIP'S SHARE, as ``models/deepseek_v2.py``: ``experts_held`` /
``vocab_held`` say which routed experts and vocabulary rows this chip holds;
the router keeps its ``num_experts`` outputs, the absent experts' part is
left out, everything else is whole.

The residual stream and the router are float32; ``loss`` is next-token
cross-entropy (``load_balance_coeff`` and the bias update are the trainer's).
What the family's config can say and this file does not compute is refused by
name: ``rope_scaling``, grouped routing (``n_group`` > 1), another
``score_func`` or activation.

Parameter tree (each kind of layer stacked)::

    wte (Vh, D)   head (Vh, D)   lnf (D,)
    attn.* (L, ...)   ln_in, ln_post_attn, ln_pre_mlp, ln_post_mlp (D,),
                      q_w (H hd, D), k_w (Hkv hd, D): (out, in) as published
                      (stored (in, out) the TPU compiler transposed both,
                      whole, on every decode step: their products feed a
                      norm over each head); gate_w (D, H hd), v_w (D, Hkv
                      hd), q_norm, k_norm (hd,), o_w (H hd, D)
    dense.* (Ld, ...) gate_w, up_w (D, F), down_w (F, D)
    moe.* (Lm, ...)   router_w (D, E), expert_bias (E,), gate_w, up_w
                      (Eh, D, Fm), down_w (Eh, Fm, D), shared_gate_w,
                      shared_up_w (D, Fs), shared_down_w (Fs, D)
"""

import dataclasses
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..moe import dropless
from .gpt2 import GPT2, layer_slice as _take
from .jamba import _mm, _rms, grouped_attention, swiglu
from .rotary import apply_rotary_pos_emb, rotary_freqs

SLIDING, FULL = "sliding_attention", "full_attention"
_QUERY_BLOCK = 256    # query rows whose scores stand at once in a prompt
_CHUNK_TOKENS = 4096  # what works a token at a time runs over so many of a
#                       prompt's tokens at once (`Afmoe._over_tokens`)


@dataclasses.dataclass
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_hidden_layers: int = 60
    num_dense_layers: int = 6
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_experts: int = 256
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.448
    n_group: int = 1
    topk_group: int = 1
    sliding_window: int = 4096
    global_attn_every_n_layers: int = 4
    layer_types: Optional[Tuple[str, ...]] = None   # None: every n-th full
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 262144
    mup_enabled: bool = True
    paged_attention_impl: str = "auto"    # auto | kernel | gather
    # ---- one chip's share (module docstring); None: the whole model
    experts_held: Optional[Tuple[int, int]] = None     # (first id, count)
    vocab_held: Optional[Tuple[int, int]] = None       # (first id, count)

    # ---- the names the serving layer and the analysis tools ask for
    @property
    def n_layer(self):
        return self.num_hidden_layers

    @property
    def kv_layers(self):
        return self.num_hidden_layers

    @property
    def types(self):
        """Each layer's attention type."""
        if self.layer_types is not None:
            return tuple(self.layer_types)
        n = self.global_attn_every_n_layers
        return tuple(FULL if (l + 1) % n == 0 else SLIDING
                     for l in range(self.num_hidden_layers))

    @property
    def n_dense_layer(self):
        return min(self.num_dense_layers, self.num_hidden_layers)

    @property
    def n_moe_layer(self):
        return self.num_hidden_layers - self.n_dense_layer

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

    @property
    def held(self):
        """``(first, count)`` of the routed experts held here."""
        return tuple(self.experts_held or (0, self.num_experts))

    @property
    def vocab_rows(self):
        """``(first, count)`` of the vocabulary's rows held here."""
        return tuple(self.vocab_held or (0, self.vocab_size))


PRESETS = {
    # tests and CPU examples: a window of 8 that a 40-token stream slides
    # several times, one full layer in four, one dense layer, 16 experts
    "afmoe-tiny": dict(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=16, num_experts_per_tok=4, sliding_window=8,
        max_position_embeddings=256),
}


def banded_attention(q, k, v, window=None, block=_QUERY_BLOCK):
    """Causal grouped attention of a prompt in BLOCKS of query rows, so that
    no (T, T) score matrix stands: ``q`` (B, T, H, hd) over ``k`` / ``v`` (B,
    T, Hkv, hd); ``window``: key ``s`` is visible to query ``t`` iff ``0 <= t
    - s < window`` (None: iff ``s <= t``).  A block of queries meets the
    keys of its band (the whole blocks behind it that the window reaches
    into, and its own), or every key where the band would cover them all.
    Returns (B, T, H * hd)."""
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


class Afmoe:
    """AFMoE decoder LM (params: dict pytree, each kind of layer stacked)."""

    supports_paged_decode = True
    # a stream's K/V is two kinds of block: the serving layer keeps an
    # allocator and a table for each (inference/serving.py)
    has_window_layers = True
    step_counters = dropless.COUNTERS

    def __init__(self, config: Optional[AfmoeConfig] = None,
                 preset: str = None, dtype=jnp.bfloat16, **overrides):
        if config is None:
            base = dict(PRESETS[preset or "afmoe-tiny"])
            base.update(overrides)
            config = AfmoeConfig(**base)
        c = config
        dropless.check_route("greedy", c.score_func)
        refused = {"rope_scaling": (c.rope_scaling, None),
                   "n_group": (c.n_group, 1), "topk_group": (c.topk_group, 1),
                   "hidden_act": (c.hidden_act, "silu")}
        for key, (got, want) in refused.items():
            if got != want:
                raise ValueError(f"{key} = {got!r}: models/afmoe.py computes "
                                 f"{want!r} and has no switch")
        types = c.types
        if len(types) != c.num_hidden_layers or set(types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types = {types!r}: one of {SLIDING!r} / "
                             f"{FULL!r} for each of the {c.num_hidden_layers} "
                             "layers")
        assert c.n_head % c.n_kv_head == 0, (c.n_head, c.n_kv_head)
        first, count = c.held
        assert 0 <= first and first + count <= c.num_experts, c.held
        self.config = c
        self.dtype = dtype
        self._rope = rotary_freqs(c.head_dim, c.max_seq, base=c.rope_theta)
        # layer -> its index among the layers of its kind
        self.window_layers = [l for l, t in enumerate(types) if t == SLIDING]
        self.global_layers = [l for l, t in enumerate(types) if t == FULL]

    # ------------------------------------------------------------------ init
    def init(self, rng):
        """Matrices normal(0.02); the output projections (``o_w`` and every
        ``down_w``) scaled by 1/sqrt(2L); norm weights 1; the router
        normal(2 / sqrt(D)) as ``models/deepseek_v2.py``; ``expert_bias``
        normal(0.01): a balancer's bias is small beside a score and large
        beside the gap between two neighbouring top scores."""
        c = self.config
        D, L, hd = c.hidden_size, c.num_hidden_layers, c.head_dim
        Hq, Hk = c.n_head * hd, c.n_kv_head * hd
        Ld, Lm = c.n_dense_layer, c.n_moe_layer
        F, Fm = c.intermediate_size, c.moe_intermediate_size
        Fs, Eh, Vh = Fm * c.num_shared_experts, c.held[1], c.vocab_rows[1]
        k = iter(jax.random.split(rng, 20))
        std, proj = 0.02, 0.02 / np.sqrt(2.0 * L)
        f32 = jnp.float32
        nrm = lambda shape, s=std: jax.random.normal(next(k), shape, f32) * s
        ones = lambda *shape: jnp.ones(shape, f32)
        return {
            "wte": nrm((Vh, D)),
            "attn": {
                "ln_in": ones(L, D), "ln_post_attn": ones(L, D),
                "ln_pre_mlp": ones(L, D), "ln_post_mlp": ones(L, D),
                "q_w": nrm((L, Hq, D)), "k_w": nrm((L, Hk, D)),
                "v_w": nrm((L, D, Hk)), "gate_w": nrm((L, D, Hq)),
                "q_norm": ones(L, hd), "k_norm": ones(L, hd),
                "o_w": nrm((L, Hq, D), proj),
            },
            "dense": {"gate_w": nrm((Ld, D, F)), "up_w": nrm((Ld, D, F)),
                      "down_w": nrm((Ld, F, D), proj)},
            "moe": {
                "router_w": nrm((Lm, D, c.num_experts), 2.0 / np.sqrt(D)),
                "expert_bias": nrm((Lm, c.num_experts), 0.01),
                "gate_w": nrm((Lm, Eh, D, Fm)), "up_w": nrm((Lm, Eh, D, Fm)),
                "down_w": nrm((Lm, Eh, Fm, D), proj),
                "shared_gate_w": nrm((Lm, D, Fs)),
                "shared_up_w": nrm((Lm, D, Fs)),
                "shared_down_w": nrm((Lm, Fs, D), proj),
            },
            "lnf": ones(D),
            "head": nrm((Vh, D)),
        }

    def num_params(self):
        c = self.config
        D, hd = c.hidden_size, c.head_dim
        attn = 3 * D * c.n_head * hd + 2 * D * c.n_kv_head * hd + 2 * hd
        expert = 3 * D * c.moe_intermediate_size
        moe = (D * c.num_experts + c.num_experts
               + (c.held[1] + c.num_shared_experts) * expert)
        return (c.num_hidden_layers * (attn + 4 * D)
                + c.n_dense_layer * 3 * D * c.intermediate_size
                + c.n_moe_layer * moe + 2 * c.vocab_rows[1] * D + D)

    # ---------------------------------------------------------------- pieces
    def _qkv(self, p, h, positions, sliding):
        """The stream ``h`` (B, T, D) -> ``(q (B, T, H, hd), k, v (B, T, Hkv,
        hd))``: q and k normed a head and, on a sliding layer, rotated."""
        with jax.named_scope("attention"):
            c = self.config
            a = _rms(h, p["ln_in"], c.rms_norm_eps).astype(self.dtype)
            heads = lambda x: x.reshape(a.shape[:-1] + (-1, c.head_dim))
            by_rows = lambda w: jnp.einsum("...d,od->...o", a, w.astype(a.dtype))
            q = _rms(heads(by_rows(p["q_w"])), p["q_norm"], c.rms_norm_eps)
            k = _rms(heads(by_rows(p["k_w"])), p["k_norm"], c.rms_norm_eps)
            if sliding:
                cos, sin = self._rope
                q = apply_rotary_pos_emb(q, cos, sin, positions)
                k = apply_rotary_pos_emb(k, cos, sin, positions)
            return q, k, heads(_mm(a, p["v_w"]))

    def _moe(self, pm, u, layer, live=None):
        """Expert layer ``layer`` (of the stacked ``pm``) over ``u`` (B, T,
        D), the normed stream in the model dtype: ``(output, counters (7,),
        experts (B T, k))``; ``live`` (B, T) bool leaves pad rows and empty
        slots out of the counts."""
        c = self.config
        x = u.reshape(-1, u.shape[-1])
        with jax.named_scope("moe.route"):
            # float32, as published: the top scores of 256 sigmoids lie
            # within bfloat16's rounding of each other
            logits = jnp.dot(x.astype(jnp.float32),
                             pm["router_w"][layer].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            experts, weights = dropless.route(
                logits, c.num_experts_per_tok, scoring_func=c.score_func,
                bias=pm["expert_bias"][layer], norm_topk_prob=c.route_norm,
                routed_scaling_factor=c.route_scale, scale_normed=True)
            counts = dropless.route_counters(
                experts, *c.held, width=c.num_experts,
                live=None if live is None else live.reshape(-1))
        with jax.named_scope("moe.experts"):
            routed = dropless.held_experts(
                x, experts, weights, pm["gate_w"], pm["up_w"], pm["down_w"],
                c.held[0], layer=layer, width=c.num_experts)
        with jax.named_scope("moe.shared"):
            shared = swiglu({"gate_w": pm["shared_gate_w"][layer],
                             "up_w": pm["shared_up_w"][layer],
                             "down_w": pm["shared_down_w"][layer]}, x)
        return (routed + shared).reshape(u.shape), counts, experts

    def _after_attention(self, params, p, h, out, live, l):
        """Layer ``l`` from its attention's output ``out`` (B, T, H hd) on:
        the output gate (from the layer's normed input, worked out again
        here), ``o_proj``, the residual, the MLP or the expert layer, the
        residual.  ``(h, counters (7,), experts (B T, k) or None)``."""
        c = self.config
        eps, f32, Ld = c.rms_norm_eps, jnp.float32, c.n_dense_layer
        with jax.named_scope("attn.gate"):
            a = _rms(h, p["ln_in"], eps).astype(self.dtype)
            out = out * jax.nn.sigmoid(_mm(a, p["gate_w"]))
        with jax.named_scope("attention"):
            h = h + _rms(_mm(out, p["o_w"]).astype(f32), p["ln_post_attn"],
                         eps)
        u = _rms(h, p["ln_pre_mlp"], eps).astype(self.dtype)
        counts = jnp.zeros((len(dropless.COUNTERS),), jnp.int32)
        experts = None
        if l < Ld:
            with jax.named_scope("mlp"):
                y = swiglu(_take(params["dense"], l), u)
        else:
            y, counts, experts = self._moe(params["moe"], u, l - Ld,
                                           live=live)
        return h + _rms(y.astype(f32), p["ln_post_mlp"], eps), counts, experts

    @staticmethod
    def _over_tokens(fn, *xs):
        """``fn(*xs)`` over arrays (B, T, ...), a long prompt in equal chunks
        of at most ``_CHUNK_TOKENS`` tokens one after the other: what works a
        token at a time (projections, norms, the MLP, an expert layer, which
        lays out ``num_experts_per_tok`` rows a token, held or not) stands 3.3
        GB of transients at 16k tokens taken whole.  ``fn`` returns arrays
        (B, T, ...) and, LAST, one that is summed over the chunks.  Pad rows
        are zeros (not live)."""
        B, T = xs[0].shape[:2]
        n = -(-T // _CHUNK_TOKENS)
        if n == 1:
            return fn(*xs)
        size = -(-T // n)
        split = lambda x: jnp.moveaxis(jnp.pad(
            x, ((0, 0), (0, n * size - T)) + ((0, 0),) * (x.ndim - 2)
        ).reshape((B, n, size) + x.shape[2:]), 1, 0)
        *ys, total = jax.lax.map(lambda c: fn(*c), tuple(map(split, xs)))
        merge = lambda y: jnp.moveaxis(y, 0, 1).reshape(
            (B, n * size) + y.shape[3:])[:, :T]
        return (*map(merge, ys), total.sum(0))

    def _layers(self, params, h, carry, positions, attn_fn, live=None,
                with_routes=False):
        """The float32 stream ``h`` (B, T, D) through every layer, unrolled
        (a layer's kind of attention and of MLP is static).  ``positions``
        (B, T); ``attn_fn(q, k, v, l, carry)`` attends for layer ``l`` and
        returns ``((B, T, H hd), carry)``.  Returns ``(h, carry, counters
        (7,) summed over the expert layers, routes (expert layers, B T, k)
        or None)``; ``with_routes`` is for a stream that is not cut into
        chunks (a decode step)."""
        c = self.config
        types = c.types
        B, T = h.shape[:2]
        live = jnp.broadcast_to(jnp.ones((), bool) if live is None else live,
                                (B, T))
        zero = jnp.zeros((len(dropless.COUNTERS),), jnp.int32)
        counts, routes = zero, []
        for l in range(c.num_hidden_layers):
            p = _take(params["attn"], l)
            q, k, v, _ = self._over_tokens(
                lambda hc, pos: (*self._qkv(p, hc, pos, types[l] == SLIDING),
                                 zero), h, positions)
            out, carry = attn_fn(q, k, v, l, carry)
            if with_routes:
                h, n, experts = self._after_attention(params, p, h, out,
                                                      live, l)
                routes += [] if experts is None else [experts]
            else:
                h, n = self._over_tokens(
                    lambda hc, oc, lc: self._after_attention(
                        params, p, hc, oc, lc, l)[:2], h, out, live)
            counts = counts + n
        return h, carry, counts, (jnp.stack(routes) if routes else None)

    def _attend_prompt(self, q, k, v, l):
        """A prompt's attention for layer ``l``, in blocks.  On a TPU both
        kinds go through the flash forward: a sliding layer the windowed
        entry, which walks the band alone (``prefill_band_attention`` in a
        capture; a prompt no longer than the window is the causal triangle,
        walked the same way), a full layer the dense causal call, K/V
        repeated to the query heads.  Elsewhere ``banded_attention``."""
        c = self.config
        from ..ops import flash_attention_available
        if c.types[l] == SLIDING:
            with jax.named_scope("attn.window"):
                if not flash_attention_available():
                    return banded_attention(q, k, v, window=c.sliding_window)
                from ..ops.transformer.flash_attention import (
                    flash_attention_window)
                # the kernel's OWN name, and no scope around it: what is
                # fused around the call stays ``attn.window`` work
                return flash_attention_window(
                    q, k, v, window=c.sliding_window,
                    name="prefill_band_attention").reshape(q.shape[:2] + (-1,))
        with jax.named_scope("attn.global"):
            if not flash_attention_available():
                return banded_attention(q, k, v)
            from ..ops.transformer.flash_attention import flash_attention
            # the kernel takes as many K/V heads as query heads: K and V
            # are repeated to ONE K/V head's group of query heads at a time,
            # not to all of them at once
            B, T, H, hd = q.shape
            G = H // c.n_kv_head
            group = lambda x: jnp.moveaxis(
                x.reshape(B, T, c.n_kv_head, -1, hd), 2, 0)

            def one(qkv):
                qg, kg, vg = qkv
                # the scope names the Mosaic call in a device trace
                with jax.named_scope("prefill_flash_attention"):
                    return flash_attention(
                        qg, jnp.repeat(kg, G, axis=2),
                        jnp.repeat(vg, G, axis=2), causal=True)
            out = jax.lax.map(one, (group(q), group(k), group(v)))
            return jnp.moveaxis(out, 0, 2).reshape(B, T, H * hd)

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            h = params["wte"][tokens - self.config.vocab_rows[0]].astype(
                jnp.float32)
            c = self.config
            return h * np.sqrt(c.hidden_size) if c.mup_enabled else h

    def _head(self, params, h):
        with jax.named_scope("lm_head"):
            h = _rms(h, params["lnf"], self.config.rms_norm_eps)
            return jnp.einsum("...d,vd->...v", h.astype(self.dtype),
                              params["head"].astype(self.dtype),
                              preferred_element_type=jnp.float32)

    # --------------------------------------------------------------- forward
    def apply(self, params, tokens, rng=None, deterministic=True,
              return_hidden=False):
        """``tokens`` (B, T) -> logits (B, T, Vh) float32 (no dropout in the
        family)."""
        h, _, _, _ = self._layers(
            params, self._embed(params, tokens), (),
            jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape),
            lambda q, k, v, l, carry: (self._attend_prompt(q, k, v, l),
                                       carry))
        if return_hidden:
            return _rms(h, params["lnf"], self.config.rms_norm_eps)
        return self._head(params, h)

    def loss(self, params, batch, rng=None):
        """Next-token LM loss over the held vocabulary rows; ``batch`` as
        ``GPT2.loss`` takes it."""
        tokens, labels = GPT2._split_batch(batch)
        logits = self.apply(params, tokens)
        lse = jax.nn.logsumexp(logits, axis=-1)
        labels = labels.astype(jnp.int32) - self.config.vocab_rows[0]
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)
        return jnp.mean(lse - picked[..., 0])

    # ---------------------------------------------------- contiguous decoding
    def init_cache(self, batch_size: int, max_len: Optional[int] = None,
                   dtype=None):
        """``InferenceEngine.generate``'s cache: dense K and V a layer (a
        sliding layer's too: the window is a mask here), and the write
        index."""
        c = self.config
        shape = (c.num_hidden_layers, batch_size, max_len or c.max_seq,
                 c.n_kv_head, c.head_dim)
        return {"k": jnp.zeros(shape, dtype or self.dtype),
                "v": jnp.zeros(shape, dtype or self.dtype),
                "index": jnp.zeros((), jnp.int32)}

    def apply_with_cache(self, params, tokens, cache):
        """Forward ``tokens`` (B, T) from ``cache['index']``; returns
        ``(logits (B, T, Vh), new_cache)``."""
        c = self.config
        T = tokens.shape[1]
        index = cache["index"]
        S = cache["k"].shape[2]
        t = index + jnp.arange(T)[:, None]
        s = jnp.arange(S)[None, :]
        causal = s <= t
        masks = {FULL: causal, SLIDING: causal & (t - s < c.sliding_window)}

        def attn_fn(q, k, v, l, kv):
            ck = jax.lax.dynamic_update_slice(
                kv[0], k[None].astype(kv[0].dtype), (l, 0, index, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                kv[1], v[None].astype(kv[1].dtype), (l, 0, index, 0, 0))
            return grouped_attention(q, ck[l].astype(self.dtype),
                                     cv[l].astype(self.dtype),
                                     masks[c.types[l]]), (ck, cv)

        h, (ck, cv), _, _ = self._layers(
            params, self._embed(params, tokens), (cache["k"], cache["v"]),
            jnp.broadcast_to(index + jnp.arange(T), tokens.shape), attn_fn)
        return self._head(params, h), {"k": ck, "v": cv, "index": index + T}

    # ------------------------------------------------------- paged serving
    def paged_attention_impl(self) -> str:
        impl = self.config.paged_attention_impl
        if impl == "auto":
            impl = "kernel"
        assert impl in ("kernel", "gather"), impl
        return impl

    def ring_entries(self, block_size: int) -> int:
        """Entries of a slot's window-kind table: the ring."""
        from ..inference import paged_kv as pk
        return pk.ring_blocks(self.config.sliding_window, block_size)

    def init_serving_state(self, batch_slots, num_blocks, block_size,
                           kv_bits=16, quant_block=64, dtype=None,
                           window_num_blocks=None):
        """The pytree the serving engine donates through its steps: the
        ``k`` / ``v`` pool over the full layers (``num_blocks``), the ``wk``
        / ``wv`` pool over the sliding layers (``window_num_blocks``: by
        default a full ring for every slot), and ``counters``."""
        from ..inference import paged_kv as pk
        c = self.config
        if kv_bits != 16:
            raise ValueError(f"kv_bits = {kv_bits}: a window pool is 16-bit "
                             "(an int8 ring: ROADMAP)")
        if not self.global_layers or not self.window_layers:
            raise ValueError("models/afmoe.py serves a model with layers of "
                             f"both kinds, not {c.types!r}")
        if window_num_blocks is None:
            window_num_blocks = 1 + batch_slots * self.ring_entries(block_size)
        dt = dtype or self.dtype
        pool = pk.init_pool(len(self.global_layers), num_blocks, block_size,
                            c.n_head, c.head_dim, dt, n_kv_head=c.n_kv_head)
        pool.update(pk.init_window_pool(
            len(self.window_layers), window_num_blocks, block_size,
            c.n_kv_head, c.head_dim, dt))
        return dict(pool, counters=jnp.zeros((len(self.step_counters),),
                                             jnp.int32))

    def serving_stats(self, pool):
        """What ``ServingEngine.stats()`` reports; ``kv_bytes_per_token`` is
        a token's cost while every layer holds it (the engine's own figure
        divides both pools' bytes by the global kind's tokens)."""
        c = self.config
        token = 2 * pool["k"].shape[-1] * pool["k"].dtype.itemsize
        return {"kv_bytes_per_token": token * c.num_hidden_layers,
                "experts_held": c.held[1], "experts_total": c.num_experts,
                "global_layers": len(self.global_layers),
                "window_layers": len(self.window_layers),
                "sliding_window": c.sliding_window,
                "kv_bytes_per_token_layer": token}

    def prefill_paged(self, params, toks, pool, blocks, slot, t_real):
        """One prompt, padded to its bucket, into both pools: a full layer
        writes positions ``0..T-1`` into the first entries of ``blocks``, a
        sliding layer its LAST blocks into the ring, ``blocks``' last
        :meth:`ring_entries` (``paged_kv.write_prefill_ring``).  ``toks``:
        (1, T); ``slot`` unused; the pad after token ``t_real - 1`` is routed
        like any token and left out of the counters.  Returns ``(logits (1,
        Vh) at token t_real - 1, pool)``."""
        from ..inference import paged_kv as pk
        T = toks.shape[1]
        bs = pool["k"].shape[2]
        ring = self.ring_entries(bs)
        table, ring_table = blocks[:-ring], blocks[-ring:]
        pad = ((0, table.shape[0] * bs - T), (0, 0), (0, 0))

        def attn_fn(q, k, v, l, pool):
            with jax.named_scope("kv.seat"):
                kp, vp = jnp.pad(k[0], pad), jnp.pad(v[0], pad)
                if l in self.global_layers:
                    pool = pk.write_prefill(pool, table, kp, vp,
                                            layer=self.global_layers.index(l))
                else:
                    pool = pk.with_window(pool, pk.write_prefill_ring(
                        pk.window_view(pool), ring_table, kp, vp,
                        self.window_layers.index(l), t_real))
            return self._attend_prompt(q, k, v, l), pool

        h, pool, counts, _ = self._layers(
            params, self._embed(params, toks), pool, jnp.arange(T)[None],
            attn_fn, live=(jnp.arange(T) < t_real)[None])
        row = jax.lax.dynamic_slice_in_dim(h[0], t_real - 1, 1, axis=0)
        return self._head(params, row), dict(pool, counters=counts)

    def decode_step_paged(self, params, toks, pool, block_tables, lengths,
                          with_routes=False):
        """One token for every slot: ``GPT2.decode_step_paged``'s contract
        (``toks`` (B,); ``lengths`` the tokens already cached, which is the
        token's position), over BOTH tables: ``block_tables``' first columns
        the full layers' growing table, its last :meth:`ring_entries` the
        sliding layers' ring.  Each layer writes the token's K/V into its
        pool and attends in place.  Returns ``(logits (B, Vh) float32,
        pool)`` and, ``with_routes``, the experts each slot's token was
        routed to, (expert layers, B, k)."""
        from ..inference import paged_kv as pk
        from ..ops.transformer.paged_attention import paged_attention
        c = self.config
        assert toks.ndim == 1, "a window layer attends one token a slot"
        impl = self.paged_attention_impl()
        ring = self.ring_entries(pool["k"].shape[2])
        table, ring_table = block_tables[:, :-ring], block_tables[:, -ring:]
        positions = jnp.minimum(lengths, c.max_seq - 1)[:, None]

        def attend(q, view, tables, l, window):
            if impl == "kernel":
                return paged_attention(
                    q, view, tables, lengths, l, window=window,
                    name="paged_attention_" + ("window" if window
                                               else "global"))
            if window is None:
                keys, vals = pk.gather_kv(view, l, tables, self.dtype,
                                          c.n_kv_head)
                pos = jnp.arange(keys.shape[1])[None, :]
            else:
                keys, vals, pos = pk.gather_ring(view, l, tables, lengths,
                                                 self.dtype, c.n_kv_head)
            valid = (pos >= 0) & (pos <= lengths[:, None])
            if window is not None:
                valid &= pos > lengths[:, None] - window
            return grouped_attention(q, keys, vals,
                                     valid[:, None, None, None, :])

        def attn_fn(q, k, v, l, pool):
            if l in self.global_layers:
                i = self.global_layers.index(l)
                with jax.named_scope("kv.seat"):
                    pool = pk.write_tokens(pool, i, table, lengths, k, v)
                with jax.named_scope("attn.global"):
                    return attend(q, pool, table, i, None), pool
            i = self.window_layers.index(l)
            with jax.named_scope("kv.seat"):
                view = pk.write_tokens(pk.window_view(pool), i, ring_table,
                                       lengths, k, v, ring=True)
            with jax.named_scope("attn.window"):
                out = attend(q, view, ring_table, i, c.sliding_window)
            return out, pk.with_window(pool, view)

        h, pool, counts, routes = self._layers(
            params, self._embed(params, toks)[:, None], pool, positions,
            attn_fn, live=(block_tables[:, 0] != pk.SCRATCH_BLOCK)[:, None],
            with_routes=with_routes)
        out = self._head(params, h[:, 0]), dict(pool, counters=counts)
        return out + (routes,) if with_routes else out
