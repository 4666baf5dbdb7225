"""LongCat-Flash: a shortcut-connected double block (two latent-attention
sub-layers, two dense FFNs and ONE expert layer that joins the stream late)
and a router wider than its experts (zero-compute identity experts).

No reference counterpart (the reference framework ships neither).  The block
(HF ``LongcatFlashForCausalLM``; arXiv:2509.01322), for layer ``l`` and its
sub-layers ``s = 0, 1``, with ``E`` real experts, ``Z`` identity experts and
``k = moe_topk``::

    h = h + MLA_0(RMS(h; ln_in[0]))
    u = RMS(h; ln_ff[0])
    m = MoE(u)                                   (held back: joins at the END of the layer)
    h = h + SwiGLU_dense_0(u)
    h = h + MLA_1(RMS(h; ln_in[1]))
    h = h + SwiGLU_dense_1(RMS(h; ln_ff[1])) + m

    MoE(u):  scores = softmax(u W_router), E + Z wide, float32
             picks  = top-k of (scores + router_bias);   w_i = scores[e_i] * routed_scaling_factor
             MoE(u) = sum_{e_i < E} w_i SwiGLU^{e_i}(u)  +  u * sum_{e_i >= E} w_i

The shortcut is what lets a deployment hide the experts' exchange behind the
first dense FFN and the second attention; on one chip it is an order of
additions, and that order is the model.  ``MLA_s`` is ``models/mla.py``'s, the
ONE copy (DeepSeek-V2 calls it too), with LongCat's two scales on the normed
latents (``mla_scale_q_lora``: ``sqrt(D / q_lora_rank)``; ``mla_scale_kv_lora``:
``sqrt(D / kv_lora_rank)``; ``k_pe`` is not scaled), plain rope and the softmax
scale ``(n + r)^-1/2``.  A token caches TWO latent rows a layer (one a
sub-layer), at the pool's layer index ``2 l + s``: ``kv_layers = 2 x
num_layers``.  Prompts run the attention EXPANDED and one decoded token
ABSORBED through the latent kernel, as ``models/deepseek_v2.py``.

ONE CHIP'S SHARE: ``experts_held = (first, count)`` of the REAL experts and
``vocab_held``, as ``DeepseekV2Config``.  The router keeps its ``E + Z``
outputs and every token its ``k`` picks; the held experts' part is computed
(``moe/dropless.held_experts``: an id past the held ones, an identity id
among them, falls in no group), the absent real experts' part is LEFT OUT,
and the identity experts' part is computed HERE for every token, whole
(``moe/dropless.zero_experts``): it is the token's own and needs no exchange,
like a shared expert.

``LongcatFlashConfig`` keeps the PUBLISHED key names.  ``router_bias_std`` is
not one: ``e_score_correction_bias`` is a buffer a load balancer keeps (a PID
controller, in training) and the config gives no values, so ``init`` draws it
normal at that width (0: no bias).  The residual stream is float32 (the
router reads it).  ``loss`` is next-token cross-entropy.

Parameter tree (sub-layers stacked ``2 l + s``, expert layers ``l``)::

    wte (Vh, D)      head (Vh, D)      lnf (D,)
    attn.* (2L, ...)   ln_in, q_a_w (D, Rq), q_norm, q_nope_w (H n, Rq),
                       q_pe_w (H r, Rq), kv_a_w (D, C + r), kv_norm, k_up_w
                       (H, C, n), v_up_w (H, v, C), o_w (H v, D), ln_ff
                       (laid out as models/deepseek_v2.py's, for its reasons)
    dense.* (2L, ...)  gate_w, up_w (D, F), down_w (F, D)
    moe.* (L, ...)     router_w (D, E + Z), router_bias (E + Z,), gate_w,
                       up_w (Eh, D, Fm), down_w (Eh, Fm, D)
"""

import dataclasses
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..moe import dropless
from . import mla
from .gpt2 import GPT2, layer_slice as _take
from .jamba import _mm, _rms, swiglu
from .rotary import rotary_freqs

_MOE_CHUNK = 2048     # tokens whose pairs the expert layer gathers at once

@dataclasses.dataclass
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    max_position_embeddings: int = 131072
    attention_method: str = "MLA"
    attention_bias: bool = False
    # ---- not published: the width ``init`` draws the selection bias at
    router_bias_std: float = 0.0
    # ---- one chip's share (module docstring); None: the whole model
    experts_held: Optional[Tuple[int, int]] = None     # (first id, count)
    vocab_held: Optional[Tuple[int, int]] = None       # (first id, count)

    # ---- the names the serving layer and the analysis tools ask for
    @property
    def n_layer(self):
        return self.num_layers

    @property
    def kv_layers(self):
        """Latent rows a token keeps: one a sub-layer, two a layer."""
        return 2 * self.num_layers

    @property
    def n_head(self):
        return self.num_attention_heads

    @property
    def n_kv_head(self):
        """Cached heads a token: the latent row is one, for every head."""
        return 1

    @property
    def head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_embd(self):
        return self.hidden_size

    @property
    def max_seq(self):
        return self.max_position_embeddings

    @property
    def router_width(self):
        """The router's outputs: the real experts, then the identity ones."""
        return self.n_routed_experts + self.zero_expert_num

    @property
    def held(self):
        """``(first, count)`` of the real experts held here."""
        return tuple(self.experts_held or (0, self.n_routed_experts))

    @property
    def vocab_rows(self):
        return tuple(self.vocab_held or (0, self.vocab_size))


PRESETS = {
    # tests and CPU examples, at widths that keep the ratios: 2 double
    # layers, 4 heads, q and kv ranks, a rope slice, a router of 16 real and
    # 8 identity experts, top-6; the benchmark's family file passes a real
    # checkpoint's published keys
    "longcat-flash-tiny": dict(
        vocab_size=512, hidden_size=64, ffn_hidden_size=160,
        expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
        zero_expert_num=8, moe_topk=6, max_position_embeddings=256,
        rope_theta=10000.0, router_bias_std=0.01),
}


class LongcatFlash:
    """LongCat-Flash decoder LM (params: dict pytree, sub-layers and expert
    layers stacked)."""

    supports_paged_decode = True
    # what each expert layer counts, summed over the layers of one dispatch
    # and carried in the serving state's ``counters`` leaf:
    # ``dropless.COUNTERS`` with the pairs that fell to an identity expert
    # told apart from those that fell to another chip (the three kinds of
    # pair sum to ``moe_topk`` x live tokens x layers)
    step_counters = ("routed_pairs", "pairs_elsewhere", "zero_pairs",
                     "experts_touched", "experts_idle", "tokens_unrouted",
                     "calls_compacted", "calls_whole")

    def __init__(self, config: Optional[LongcatFlashConfig] = None,
                 preset: str = None, dtype=jnp.bfloat16, **overrides):
        if config is None:
            base = dict(PRESETS[preset or "longcat-flash-tiny"])
            base.update(overrides)
            config = LongcatFlashConfig(**base)
        c = config
        for key, want in (("zero_expert_type", "identity"),
                          ("attention_method", "MLA"),
                          ("attention_bias", False)):
            if getattr(c, key) != want:
                raise ValueError(f"{key} = {getattr(c, key)!r}: "
                                 f"models/longcat_flash.py computes {want!r}")
        first, count = c.held
        assert 0 <= first and first + count <= c.n_routed_experts, c.held
        self.config = c
        self.dtype = dtype
        D = c.hidden_size
        self._mla = mla.LatentAttention(
            n_head=c.n_head, kv_lora_rank=c.kv_lora_rank, eps=c.rms_norm_eps,
            rope=rotary_freqs(c.qk_rope_head_dim, c.max_seq,
                              base=c.rope_theta),
            q_scale=(D / c.q_lora_rank) ** 0.5 if c.mla_scale_q_lora else 1.0,
            kv_scale=(D / c.kv_lora_rank) ** 0.5 if c.mla_scale_kv_lora
            else 1.0)
        self._sm_scale = float(c.head_dim ** -0.5)

    # ------------------------------------------------------------------ init
    def init(self, rng):
        """As ``DeepseekV2.init`` and for its reasons: matrices normal(0.02);
        the output projections (``o_w`` and every ``down_w``) scaled by
        1/sqrt(2 x sub-layers); norm weights 1; the router normal(2 /
        sqrt(D)), so that its logits have a spread near 2 at any width; the
        selection bias normal(``router_bias_std``)."""
        c = self.config
        D, L, H = c.hidden_size, c.num_layers, c.n_head
        n, r, v = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        C, Rq = c.kv_lora_rank, c.q_lora_rank
        F, Fm = c.ffn_hidden_size, c.expert_ffn_hidden_size
        Eh, Vh, W = c.held[1], c.vocab_rows[1], c.router_width
        S = 2 * L
        k = iter(jax.random.split(rng, 20))
        std, proj = 0.02, 0.02 / np.sqrt(2.0 * S)
        f32 = jnp.float32
        nrm = lambda shape, s=std: jax.random.normal(next(k), shape, f32) * s
        ones = lambda *shape: jnp.ones(shape, f32)
        return {
            "wte": nrm((Vh, D)),
            "attn": {
                "ln_in": ones(S, D),
                "q_a_w": nrm((S, D, Rq)), "q_norm": ones(S, Rq),
                "q_nope_w": nrm((S, H * n, Rq)),
                "q_pe_w": nrm((S, H * r, Rq)),
                "kv_a_w": nrm((S, D, C + r)), "kv_norm": ones(S, C),
                "k_up_w": nrm((S, H, C, n)), "v_up_w": nrm((S, H, v, C)),
                "o_w": nrm((S, H * v, D), proj),
                "ln_ff": ones(S, D),
            },
            "dense": {"gate_w": nrm((S, D, F)), "up_w": nrm((S, D, F)),
                      "down_w": nrm((S, F, D), proj)},
            "moe": {
                "router_w": nrm((L, D, W), 2.0 / np.sqrt(D)),
                "router_bias": nrm((L, W), c.router_bias_std),
                "gate_w": nrm((L, Eh, D, Fm)), "up_w": nrm((L, Eh, D, Fm)),
                "down_w": nrm((L, Eh, Fm, D), proj),
            },
            "lnf": ones(D),
            "head": nrm((Vh, D)),
        }

    def num_params(self):
        c = self.config
        D, H = c.hidden_size, c.n_head
        n, r, v = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        C, Rq = c.kv_lora_rank, c.q_lora_rank
        sub = (D * Rq + Rq * H * (n + r) + D * (C + r) + C * H * (n + v)
               + H * v * D + Rq + C + 3 * D * c.ffn_hidden_size + 2 * D)
        moe = ((D + 1) * c.router_width
               + c.held[1] * 3 * D * c.expert_ffn_hidden_size)
        return (c.num_layers * (2 * sub + moe) + 2 * c.vocab_rows[1] * D + D)

    # ---------------------------------------------------------------- pieces
    def _moe(self, pm, u, layer=None, live=None):
        """The expert layer's output for ``u`` (B, T, D), the normed stream
        in FLOAT32 (the router scores it as it is; the experts read it in
        the model dtype): the held real experts' part and the identity
        experts'.  ``pm``: one layer's leaves, or (``layer`` given) every
        layer's, stacked.  Returns ``(output (B, T, D) float32, counters
        (8,) in ``step_counters``' order, experts (B T, k))``; ``live`` (B,
        T) bool leaves pad rows and empty slots out of the counts."""
        c = self.config
        x32 = u.reshape(-1, u.shape[-1]).astype(jnp.float32)
        x = x32.astype(self.dtype)
        at = (lambda w: w[layer]) if layer is not None else (lambda w: w)
        live = None if live is None else live.reshape(-1)
        with jax.named_scope("moe.route"):
            # float32, as published: a token's twelfth and thirteenth scores
            # of 768 lie within bfloat16's rounding of each other
            logits = jnp.dot(x32, at(pm["router_w"]).astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            experts, weights = dropless.route(
                logits, c.moe_topk, scoring_func="softmax",
                routed_scaling_factor=c.routed_scaling_factor,
                bias=at(pm["router_bias"]))
            held, elsewhere, touched, idle, unrouted, *calls = \
                dropless.route_counters(
                    experts, *c.held, live=live, width=c.router_width,
                    rows=self._moe_chunks(x.shape[0])[1])
            zero = dropless.zero_pairs(experts, c.n_routed_experts, live)
            counts = jnp.stack([held, elsewhere - zero, zero, touched, idle,
                                unrouted, *calls])
        with jax.named_scope("moe.experts"):
            routed = self._held_experts(pm, x, experts, weights, layer)
        with jax.named_scope("moe.zero"):
            out = routed.astype(jnp.float32) + dropless.zero_experts(
                x32, experts, weights, c.n_routed_experts)
        return out.reshape(u.shape), counts, experts

    @staticmethod
    def _moe_chunks(N):
        """``(chunks, tokens a chunk)`` of :meth:`_held_experts` over ``N``
        tokens."""
        n = -(-N // _MOE_CHUNK)
        return n, -(-N // n)

    def _held_experts(self, pm, x, experts, weights, layer):
        """``dropless.held_experts`` over ``x`` (N, D), a long prompt in
        equal chunks of at most ``_MOE_CHUNK`` tokens (the last one filled
        up, where the length does not divide, with rows routed to no held
        expert).  It gathers a row for
        EVERY pair, held or not (N k rows of D in, as many out, and their
        weighted sum in float32): at top-12 a 4,096-token prompt's 49,152
        pairs are 2.4 GB of transients, of which this chip's experts hold 2
        %, and the engine's preflight prices the largest bucket the served
        positions allow (5,120 tokens: 3.3 GB beside 13.4 GB resident).  A
        chunk re-reads the matrices of the experts it touches (1.2 GB a
        layer here: 1.5 ms), so a prompt of up to 2,048 tokens goes in
        whole."""
        c = self.config
        N = x.shape[0]
        held = lambda xs: dropless.held_experts(
            *xs, pm["gate_w"], pm["up_w"], pm["down_w"], c.held[0],
            layer=layer, width=c.router_width)
        n, size = self._moe_chunks(N)
        if n == 1:
            return held((x, experts, weights))
        cut = lambda a, fill=0: jnp.pad(
            a, ((0, n * size - N),) + ((0, 0),) * (a.ndim - 1),
            constant_values=fill).reshape((n, size) + a.shape[1:])
        return jax.lax.map(held, (cut(x), cut(experts, c.router_width),
                                  cut(weights))).reshape(-1, x.shape[1])[:N]

    def _layers(self, params, h, carry, positions, attn_fn, live=None,
                with_routes=False):
        """The float32 stream ``h`` (B, T, D) through every layer.
        ``attn_fn(p, q_nope, q_pe, c_kv, k_pe, i, carry)`` attends for
        sub-layer ``i = 2 l + s`` and returns ``((B, T, H v), carry)``.  ONE
        loop over the stacked weights, indexed in place.  Returns ``(h,
        carry, counters (8,) summed over the layers, routes)``: ``routes``
        (layers, B T, k), the experts every token was routed to, where
        ``with_routes`` asks for them, else None."""
        c = self.config
        eps = c.rms_norm_eps
        f32 = jnp.float32
        pa, pd, pm = params["attn"], params["dense"], params["moe"]

        def layer(l, state):
            h, carry, counts, routes = state
            for s in (0, 1):
                i = 2 * l + s
                p = _take(pa, i)
                with jax.named_scope("attention"):
                    a = _rms(h, p["ln_in"], eps).astype(self.dtype)
                    out, carry = attn_fn(
                        p, *self._mla.project(p, a, positions), i, carry)
                    h = h + _mm(out, p["o_w"]).astype(f32)
                    u = _rms(h, p["ln_ff"], eps)
                if s == 0:
                    m, n, experts = self._moe(pm, u, layer=l, live=live)
                with jax.named_scope("mlp"):
                    h = h + swiglu(_take(pd, i),
                                   u.astype(self.dtype)).astype(f32)
                if s == 1:          # the shortcut: held back until here
                    h = h + m
            if routes is not None:
                routes = jax.lax.dynamic_update_index_in_dim(
                    routes, experts, l, 0)
            return h, carry, counts + n, routes

        routes = jnp.zeros((c.num_layers, h.shape[0] * h.shape[1],
                            c.moe_topk), jnp.int32)
        return jax.lax.fori_loop(
            0, c.num_layers, layer,
            (h, carry, jnp.zeros((len(self.step_counters),), jnp.int32),
             routes if with_routes else None))

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["wte"][tokens - self.config.vocab_rows[0]].astype(
                jnp.float32)

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
        family), the attention expanded."""
        T = tokens.shape[1]
        causal = jnp.tril(jnp.ones((T, T), bool))
        h, _, _, _ = self._layers(
            params, self._embed(params, tokens), (), jnp.arange(T),
            lambda p, qn, qp, ckv, kpe, i, carry: (
                self._mla.attend_expanded(p, qn, qp, ckv, kpe, causal,
                                          self._sm_scale), carry))
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
        """``InferenceEngine.generate``'s cache: a dense latent row a token
        and a SUB-layer (``[c_kv | k_pe]``, no padding), and the write
        index."""
        c = self.config
        return {"latent": jnp.zeros(
                    (c.kv_layers, batch_size, max_len or c.max_seq,
                     c.kv_lora_rank + c.qk_rope_head_dim),
                    dtype or self.dtype),
                "index": jnp.zeros((), jnp.int32)}

    def apply_with_cache(self, params, tokens, cache):
        """Forward ``tokens`` (B, T) from ``cache['index']``; returns
        ``(logits (B, T, Vh), new_cache)``.  A prompt (T > 1) attends
        expanded over the cached rows, one token absorbed."""
        T = tokens.shape[1]
        index = cache["index"]

        def attn_fn(p, qn, qp, ckv, kpe, i, lat):
            return self._mla.attend_cached(p, qn, qp, ckv, kpe, lat, i, index,
                                           self._sm_scale, self.dtype)

        h, lat, _, _ = self._layers(params, self._embed(params, tokens),
                                    cache["latent"], index + jnp.arange(T),
                                    attn_fn)
        return self._head(params, h), {"latent": lat, "index": index + T}

    # ------------------------------------------------------- paged serving
    def paged_attention_impl(self) -> str:
        """``"kernel"``, always: the latent Pallas kernel.  The tests set
        ``"gather"`` on an instance for their ``jax.numpy`` oracle."""
        return "kernel"

    def init_serving_state(self, batch_slots, num_blocks, block_size,
                           kv_bits=16, quant_block=64, dtype=None):
        """The pytree the serving engine donates through its steps: the
        LATENT pool over ``2 x num_layers`` sub-layers (two rows a token a
        layer) and ``counters`` (``step_counters``' order)."""
        from ..inference import paged_kv as pk
        c = self.config
        if kv_bits != 16:
            raise ValueError(f"kv_bits = {kv_bits}: the latent pool is "
                             "16-bit (an int8 latent pool: ROADMAP)")
        pool = pk.init_latent_pool(c.kv_layers, num_blocks, block_size,
                                   c.kv_lora_rank, c.qk_rope_head_dim,
                                   dtype or self.dtype)
        return dict(pool, counters=jnp.zeros((len(self.step_counters),),
                                             jnp.int32))

    def serving_stats(self, pool):
        """What ``ServingEngine.stats()`` reports beside
        ``kv_bytes_per_token``."""
        from ..inference import paged_kv as pk
        c = self.config
        return {"experts_held": c.held[1],
                "experts_total": c.n_routed_experts,
                "zero_experts": c.zero_expert_num,
                "latent_row_bytes": pk.latent_row_bytes(pool),
                "latent_rows_per_token": c.kv_layers}

    def prefill_paged(self, params, toks, pool, blocks, slot, t_real):
        """One prompt, padded to its bucket, into the pool at positions
        ``0..T-1``, the attention EXPANDED; each sub-layer writes its rows
        into ``blocks`` as it goes.  ``toks``: (1, T); ``slot`` unused; the
        pad after token ``t_real - 1`` is routed like any token and left out
        of the counters.  Returns ``(logits (1, Vh) at token t_real - 1,
        pool)``."""
        T = toks.shape[1]
        causal = jnp.tril(jnp.ones((T, T), bool))

        def attn_fn(p, qn, qp, ckv, kpe, i, pool):
            return self._mla.attend_prefill(p, qn, qp, ckv, kpe, pool, blocks,
                                            i, causal, self._sm_scale)

        h, pool, counts, _ = self._layers(
            params, self._embed(params, toks), pool, jnp.arange(T), attn_fn,
            live=(jnp.arange(T) < t_real)[None])
        row = jax.lax.dynamic_slice_in_dim(h[0], t_real - 1, 1, axis=0)
        return self._head(params, row), dict(pool, counters=counts)

    def decode_step_paged(self, params, toks, pool, block_tables, lengths,
                          with_routes=False):
        """One token for every slot, the attention ABSORBED:
        ``DeepseekV2.decode_step_paged``'s contract.  Returns ``(logits (B,
        Vh) float32, pool)`` and, ``with_routes``, the experts each slot's
        token was routed to, (layers, B, k): the same step's."""
        from ..inference import paged_kv as pk
        assert toks.ndim == 1, "the latent kernel attends one token a slot"
        impl = self.paged_attention_impl()
        positions = jnp.minimum(lengths, self.config.max_seq - 1)[:, None]

        def attn_fn(p, qn, qp, ckv, kpe, i, pool):
            return self._mla.attend_decode(
                p, qn, qp, ckv, kpe, pool, block_tables, lengths, i,
                self._sm_scale, impl, self.dtype)

        h, pool, counts, routes = self._layers(
            params, self._embed(params, toks)[:, None], pool, positions,
            attn_fn, live=(block_tables[:, 0] != pk.SCRATCH_BLOCK)[:, None],
            with_routes=with_routes)
        out = self._head(params, h[:, 0]), dict(pool, counters=counts)
        return out + (routes,) if with_routes else out
