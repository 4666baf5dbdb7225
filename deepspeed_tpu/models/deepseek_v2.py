"""DeepSeek-V2: latent attention (MLA) and routed experts beside shared ones.

No reference counterpart (the reference framework ships neither).  The block
(HF ``DeepseekV2ForCausalLM``, arXiv:2405.04434), per layer ``l``, with ``H``
heads, ``n = qk_nope_head_dim``, ``r = qk_rope_head_dim``, ``C =
kv_lora_rank``::

    a = RMS(h; ln_in)
    c_q = RMS(a W_qa; q_norm)             [q_nope | q_pe] = c_q W_qb    (H x (n | r))
    [c_kv | k_pe] = a W_kva               c_kv = RMS(c_kv; kv_norm)     k_pe: ONE head
    q_pe, k_pe = rope(q_pe), rope(k_pe)   (the r rope dims only, YaRN frequencies)
    k_nope[h] = c_kv W_UK[h]^T            v[h] = c_kv W_UV[h]
    s = (q_nope . k_nope + q_pe . k_pe) * (n + r)^-1/2 * m^2            m: YaRN's mscale
    h = h + concat_h(softmax(s + causal) v) W_o
    u = RMS(h; ln_ff)
    l <  first_k_dense_replace:  h = h + SwiGLU_dense(u)
    otherwise:  h = h + sum_i w_i SwiGLU^{e_i}(u) + SwiGLU_shared(u)    (e_i, w_i): moe/dropless.route

A token caches, a layer, ``c_kv`` (after its norm) and ``k_pe`` (after
rope): ``C + r`` values, one row for all ``H`` heads.  TWO forms of the same
attention, and no switch between them: a prompt (``apply``, ``prefill_paged``,
a multi-token ``apply_with_cache``) runs EXPANDED, keys and values rebuilt
from ``c_kv`` at widths ``n + r`` and ``v_head_dim``, blocked over heads so
that no (H, T, T) score tensor stands whole; one decoded token runs
ABSORBED, ``q_lat[h] = q_nope[h] W_UK[h]`` meeting the cached rows directly
(``ops/transformer/paged_latent_attention.py``) and ``W_UV[h]`` applied to the
(C,)-wide result.  The attention's arithmetic (the projection, both forms,
the seat into a dense cache or the paged pool) is ``models/mla.py``'s
``LatentAttention``, the ONE copy, which ``models/longcat_flash.py`` calls
too; what is this family's is an argument to it: YaRN's rope table, the
softmax scale with ``m^2`` (``_sm_scale``), and no scale on the normed
latents.

ONE CHIP'S SHARE of an expert-parallel deployment: ``experts_held = (first,
count)`` says which routed experts this chip holds.  The router keeps its
``n_routed_experts`` outputs and every token its ``num_experts_per_tok``
picks; the held experts' part is computed (``moe/dropless.held_experts``), the
absent experts' part is LEFT OUT (other chips add it; nothing here stands in
for them), the shared experts and everything else are whole.  ``vocab_held =
(first, count)`` likewise: the embedding and the head hold those rows, ids
are looked up at ``id - first``, logits and loss are over the slice.  With
both absent the model is the whole one.

``DeepseekV2Config`` keeps the PUBLISHED key names, as ``JambaConfig``.  The
residual stream is float32 (as ``models/ouro.py``): the router reads it, and
its top-k is decided by small differences.  ``loss`` is next-token
cross-entropy; the published balance losses have weights the config does not
give.

Parameter tree (each kind of layer stacked)::

    wte (Vh, D)      head (Vh, D)      lnf (D,)
    attn.* (L, ...)  ln_in, q_a_w (D, Rq), q_norm, q_nope_w (H n, Rq),
                     q_pe_w (H r, Rq), kv_a_w (D, C + r), kv_norm,
                     k_up_w (H, C, n), v_up_w (H, v, C), o_w (H v, D), ln_ff
                     (q_nope_w | q_pe_w are the published q_b_proj's rows by
                     head, k_up_w | v_up_w the published kv_b_proj's: the
                     same elements, the two kinds of column apart and the
                     dim a DECODE step contracts minor.  Laid out otherwise,
                     the TPU compiler re-lays them whole on every step, 110
                     MB a layer: found by compiling the step for a v5e.  The
                     rope columns are stored in rotate-half order)
    dense.* (Ld, ...)   gate_w, up_w (D, F), down_w (F, D)
    moe.* (Lm, ...)     router_w (D, E), gate_w, up_w (Eh, D, Fm), down_w
                        (Eh, Fm, D), shared_gate_w, shared_up_w (D, Fs),
                        shared_down_w (Fs, D)
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
from .rotary import rotary_freqs, yarn_inv_freq, yarn_mscale


@dataclasses.dataclass
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 60
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 160
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_group: int = 8
    topk_group: int = 3
    topk_method: str = "group_limited_greedy"
    scoring_func: str = "softmax"
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 16.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 163840
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
    def n_dense_layer(self):
        return min(self.first_k_dense_replace, self.num_hidden_layers)

    @property
    def n_moe_layer(self):
        return self.num_hidden_layers - self.n_dense_layer

    @property
    def n_head(self):
        return self.num_attention_heads

    @property
    def n_kv_head(self):
        """Cached heads a token: the latent row is one, for every head."""
        return 1

    @property
    def head_dim(self):
        """The width queries meet keys at (``n + r``); values are
        ``v_head_dim`` wide, the cached row ``kv_lora_rank + r``."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_embd(self):
        return self.hidden_size

    @property
    def max_seq(self):
        return self.max_position_embeddings

    @property
    def held(self):
        """``(first, count)`` of the routed experts held here."""
        return tuple(self.experts_held or (0, self.n_routed_experts))

    @property
    def vocab_rows(self):
        """``(first, count)`` of the vocabulary's rows held here."""
        return tuple(self.vocab_held or (0, self.vocab_size))


PRESETS = {
    # tests and CPU examples, at widths that keep the ratios (8 groups of 2,
    # top-3 groups, top-6, 2 shared, a rope slice, q and kv ranks); the
    # benchmark's family file passes a real checkpoint's published keys
    "deepseek-v2-tiny": dict(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=16, n_shared_experts=2,
        num_experts_per_tok=6, n_group=8, topk_group=3,
        max_position_embeddings=256,
        rope_scaling=dict(type="yarn", factor=40, beta_fast=32, beta_slow=1,
                          mscale=0.707, mscale_all_dim=0.707,
                          original_max_position_embeddings=64)),
}


class DeepseekV2:
    """DeepSeek-V2 decoder LM (params: dict pytree, each kind of layer
    stacked)."""

    supports_paged_decode = True
    # what each expert layer counts, summed over the layers of one dispatch
    # and carried in the serving state's ``counters`` leaf
    step_counters = dropless.COUNTERS

    def __init__(self, config: Optional[DeepseekV2Config] = None,
                 preset: str = None, dtype=jnp.bfloat16, **overrides):
        if config is None:
            base = dict(PRESETS[preset or "deepseek-v2-tiny"])
            base.update(overrides)
            config = DeepseekV2Config(**base)
        c = config
        dropless.check_route(c.topk_method, c.scoring_func)
        if c.scoring_func != "softmax":
            # moe/dropless.py scores by sigmoid too (models/afmoe.py); this
            # family's reference and its cell know softmax alone
            raise ValueError(f"scoring_func = {c.scoring_func!r}: "
                             "models/deepseek_v2.py computes 'softmax'")
        scaling = c.rope_scaling
        if scaling is not None and scaling.get("type") != "yarn":
            raise ValueError(f"rope_scaling.type = {scaling.get('type')!r}: "
                             "models/deepseek_v2.py computes 'yarn' or none")
        if c.moe_layer_freq != 1 or c.q_lora_rank is None:
            raise ValueError(
                f"moe_layer_freq = {c.moe_layer_freq!r}, q_lora_rank = "
                f"{c.q_lora_rank!r}: models/deepseek_v2.py runs an expert "
                "layer after every leading dense one and a low-rank query")
        assert c.num_key_value_heads == c.num_attention_heads, \
            "MLA has as many key heads as query heads (built from one row)"
        first, count = c.held
        assert 0 <= first and first + count <= c.n_routed_experts, c.held
        self.config = c
        self.dtype = dtype
        r = c.qk_rope_head_dim
        inv, m, table = None, 1.0, 1.0
        if scaling is not None:
            inv = yarn_inv_freq(
                r, c.rope_theta, scaling["factor"],
                scaling["original_max_position_embeddings"],
                scaling.get("beta_fast", 32), scaling.get("beta_slow", 1))
            m = yarn_mscale(scaling["factor"],
                            scaling.get("mscale_all_dim", 0))
            table = yarn_mscale(scaling["factor"],
                                scaling.get("mscale", 1)) / m
        cos, sin = rotary_freqs(r, c.max_seq, base=c.rope_theta, inv_freq=inv)
        # the attention itself is models/mla.py's, the one copy; YaRN's
        # table and the softmax scale (with m squared) are this family's
        self._mla = mla.LatentAttention(
            n_head=c.n_head, kv_lora_rank=c.kv_lora_rank, eps=c.rms_norm_eps,
            rope=(cos * table, sin * table))
        self._sm_scale = float(c.head_dim ** -0.5 * m * m)

    # ------------------------------------------------------------------ init
    def init(self, rng):
        """Matrices normal(0.02); the output projections (``o_w`` and every
        ``down_w``, the routed experts' like the others) scaled by
        1/sqrt(2L) as the other families; norm weights 1; the router
        normal(2 / sqrt(D)), so that its logits have a standard deviation
        near 2 at ANY width (at 0.02 a tiny model's are within rounding of
        uniform and its top-k is noise)."""
        c = self.config
        D, L, H = c.hidden_size, c.num_hidden_layers, c.n_head
        n, r, v = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        C, Rq = c.kv_lora_rank, c.q_lora_rank
        Ld, Lm = c.n_dense_layer, c.n_moe_layer
        F, Fm = c.intermediate_size, c.moe_intermediate_size
        Fs, Eh, Vh = Fm * c.n_shared_experts, c.held[1], c.vocab_rows[1]
        k = iter(jax.random.split(rng, 20))
        std, proj = 0.02, 0.02 / np.sqrt(2.0 * L)
        f32 = jnp.float32
        nrm = lambda shape, s=std: jax.random.normal(next(k), shape, f32) * s
        ones = lambda *shape: jnp.ones(shape, f32)
        return {
            "wte": nrm((Vh, D)),
            "attn": {
                "ln_in": ones(L, D),
                "q_a_w": nrm((L, D, Rq)), "q_norm": ones(L, Rq),
                "q_nope_w": nrm((L, H * n, Rq)),
                "q_pe_w": nrm((L, H * r, Rq)),
                "kv_a_w": nrm((L, D, C + r)), "kv_norm": ones(L, C),
                "k_up_w": nrm((L, H, C, n)), "v_up_w": nrm((L, H, v, C)),
                "o_w": nrm((L, H * v, D), proj),
                "ln_ff": ones(L, D),
            },
            "dense": {"gate_w": nrm((Ld, D, F)), "up_w": nrm((Ld, D, F)),
                      "down_w": nrm((Ld, F, D), proj)},
            "moe": {
                "router_w": nrm((Lm, D, c.n_routed_experts),
                                2.0 / np.sqrt(D)),
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
        D, H = c.hidden_size, c.n_head
        n, r, v = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        C, Rq = c.kv_lora_rank, c.q_lora_rank
        mla = (D * Rq + Rq * H * (n + r) + D * (C + r) + C * H * (n + v)
               + H * v * D + Rq + C)
        expert = 3 * D * c.moe_intermediate_size
        moe = (D * c.n_routed_experts + (c.held[1] + c.n_shared_experts)
               * expert)
        return (c.num_hidden_layers * (mla + 2 * D)
                + c.n_dense_layer * 3 * D * c.intermediate_size
                + c.n_moe_layer * moe + 2 * c.vocab_rows[1] * D + D)

    # ---------------------------------------------------------------- pieces
    def _moe(self, pm, u, layer=None, live=None):
        """The expert layer's output for ``u`` (B, T, D), the normed stream
        in the model dtype: the held experts' part and the shared experts.
        ``pm``: one layer's leaves, or (``layer`` given) every layer's,
        stacked.  Returns ``(output (B, T, D), counters (7,), experts (B T,
        k))``; ``live`` (B, T) bool leaves pad rows and empty slots out of
        the counts."""
        c = self.config
        x = u.reshape(-1, u.shape[-1])
        at = (lambda w: w[layer]) if layer is not None else (lambda w: w)
        with jax.named_scope("moe.route"):
            # float32, as published: a token's sixth and seventh scores can
            # lie within bfloat16's rounding of each other
            logits = jnp.dot(x.astype(jnp.float32),
                             at(pm["router_w"]).astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            experts, weights = dropless.route(
                logits, c.num_experts_per_tok, topk_method=c.topk_method,
                n_group=c.n_group, topk_group=c.topk_group,
                scoring_func=c.scoring_func,
                norm_topk_prob=c.norm_topk_prob,
                routed_scaling_factor=c.routed_scaling_factor)
            counts = dropless.route_counters(
                experts, *c.held, width=c.n_routed_experts,
                live=None if live is None else live.reshape(-1))
        with jax.named_scope("moe.experts"):
            routed = dropless.held_experts(
                x, experts, weights, pm["gate_w"], pm["up_w"], pm["down_w"],
                c.held[0], layer=layer, width=c.n_routed_experts)
        with jax.named_scope("moe.shared"):
            shared = swiglu({"gate_w": at(pm["shared_gate_w"]),
                             "up_w": at(pm["shared_up_w"]),
                             "down_w": at(pm["shared_down_w"])}, x)
        return (routed + shared).reshape(u.shape), counts, experts

    def _layers(self, params, h, carry, positions, attn_fn, live=None,
                sliced=False, with_routes=False):
        """The float32 stream ``h`` (B, T, D) through every layer.
        ``attn_fn(p, q_nope, q_pe, c_kv, k_pe, l, carry)`` attends for layer
        ``l`` and returns ``((B, T, H v), carry)``.  The expert layers are
        ONE loop over the stacked weights, indexed in place (serving: a
        slice of a stack would copy it every call), or, ``sliced``, scanned
        over (training: gradients flow into the slices).  Returns ``(h,
        carry, counters (7,) summed over the expert layers, routes)``:
        ``routes`` (expert layers, B T, k), the experts every token was
        routed to, where ``with_routes`` asks for them, else None."""
        c = self.config
        eps = c.rms_norm_eps
        f32 = jnp.float32
        pa = params["attn"]

        def attention(p, h, l, carry):
            with jax.named_scope("attention"):
                a = _rms(h, p["ln_in"], eps).astype(self.dtype)
                out, carry = attn_fn(p, *self._mla.project(p, a, positions), l,
                                     carry)
                h = h + _mm(out, p["o_w"]).astype(f32)
                return h, _rms(h, p["ln_ff"], eps).astype(self.dtype), carry

        for l in range(c.n_dense_layer):
            h, u, carry = attention(_take(pa, l), h, l, carry)
            with jax.named_scope("mlp"):
                h = h + swiglu(_take(params["dense"], l), u).astype(f32)

        Ld, pm = c.n_dense_layer, params["moe"]

        def moe_layer(p, pm_l, i, layer, state):
            h, carry, counts, routes = state
            h, u, carry = attention(p, h, Ld + i, carry)
            out, n, experts = self._moe(pm_l, u, layer=layer, live=live)
            if routes is not None:
                routes = jax.lax.dynamic_update_index_in_dim(
                    routes, experts, i, 0)
            return h + out.astype(f32), carry, counts + n, routes

        routes = jnp.zeros((c.n_moe_layer, h.shape[0] * h.shape[1],
                            c.num_experts_per_tok), jnp.int32)
        state = (h, carry, jnp.zeros((len(dropless.COUNTERS),), jnp.int32),
                 routes if with_routes else None)
        if sliced:
            rest = jax.tree_util.tree_map(lambda w: w[Ld:], pa)
            state, _ = jax.lax.scan(
                lambda s, xs: (moe_layer(xs[0], xs[1], xs[2], None, s), None),
                state, (rest, pm, jnp.arange(c.n_moe_layer)))
        else:
            state = jax.lax.fori_loop(
                0, c.n_moe_layer,
                lambda i, s: moe_layer(_take(pa, Ld + i), pm, i, i, s), state)
        return state

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
            lambda p, qn, qp, ckv, kpe, l, carry: (
                self._mla.attend_expanded(p, qn, qp, ckv, kpe, causal,
                                          self._sm_scale), carry),
            sliced=True)
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
        and a layer (``[c_kv | k_pe]``, no padding), and the write index."""
        c = self.config
        return {"latent": jnp.zeros(
                    (c.num_hidden_layers, batch_size, max_len or c.max_seq,
                     c.kv_lora_rank + c.qk_rope_head_dim),
                    dtype or self.dtype),
                "index": jnp.zeros((), jnp.int32)}

    def apply_with_cache(self, params, tokens, cache):
        """Forward ``tokens`` (B, T) from ``cache['index']``; returns
        ``(logits (B, T, Vh), new_cache)``.  A prompt (T > 1) attends
        expanded over the cached rows, one token absorbed."""
        T = tokens.shape[1]
        index = cache["index"]

        def attn_fn(p, qn, qp, ckv, kpe, l, lat):
            return self._mla.attend_cached(p, qn, qp, ckv, kpe, lat, l, index,
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
        LATENT pool (``paged_kv.init_latent_pool``: one row a token and a
        layer) and ``counters``, what the expert layers of the last dispatch
        counted (``step_counters``' order), which the engine reads back with
        the step's tokens."""
        from ..inference import paged_kv as pk
        c = self.config
        if kv_bits != 16:
            raise ValueError(f"kv_bits = {kv_bits}: the latent pool is "
                             "16-bit (an int8 latent pool: ROADMAP)")
        pool = pk.init_latent_pool(c.num_hidden_layers, num_blocks,
                                   block_size, c.kv_lora_rank,
                                   c.qk_rope_head_dim, dtype or self.dtype)
        return dict(pool, counters=jnp.zeros((len(self.step_counters),),
                                             jnp.int32))

    def serving_stats(self, pool):
        """What ``ServingEngine.stats()`` reports beside
        ``kv_bytes_per_token``."""
        from ..inference import paged_kv as pk
        return {"experts_held": self.config.held[1],
                "experts_total": self.config.n_routed_experts,
                "latent_row_bytes": pk.latent_row_bytes(pool)}

    def prefill_paged(self, params, toks, pool, blocks, slot, t_real):
        """One prompt, padded to its bucket, into the pool at positions
        ``0..T-1``, the attention EXPANDED; each layer writes its rows into
        ``blocks`` as it goes.  ``toks``: (1, T); ``slot`` unused; the pad
        after token ``t_real - 1`` is routed like any token (nothing is
        dropped) and left out of the counters.  Returns ``(logits (1, Vh) at
        token t_real - 1, pool)``."""
        T = toks.shape[1]
        causal = jnp.tril(jnp.ones((T, T), bool))

        def attn_fn(p, qn, qp, ckv, kpe, l, pool):
            return self._mla.attend_prefill(p, qn, qp, ckv, kpe, pool, blocks,
                                            l, causal, self._sm_scale)

        h, pool, counts, _ = self._layers(
            params, self._embed(params, toks), pool, jnp.arange(T), attn_fn,
            live=(jnp.arange(T) < t_real)[None])
        row = jax.lax.dynamic_slice_in_dim(h[0], t_real - 1, 1, axis=0)
        return self._head(params, row), dict(pool, counters=counts)

    def decode_step_paged(self, params, toks, pool, block_tables, lengths,
                          with_routes=False):
        """One token for every slot, the attention ABSORBED:
        ``GPT2.decode_step_paged``'s contract (``toks`` (B,); ``lengths`` the
        tokens already cached, which is the token's position).  Each layer
        writes the token's row into the pool and the queries meet the
        cached rows in place.  A row whose table points at the scratch block
        is one the host holds inactive: it is left out of the counters.
        Returns ``(logits (B, Vh) float32, pool)`` and, ``with_routes``, the
        experts each slot's token was routed to, (expert layers, B, k): the
        same step's, so that a comparison with another precision can tell a
        token whose scores tied from one that was computed wrong."""
        from ..inference import paged_kv as pk
        c = self.config
        assert toks.ndim == 1, "the latent kernel attends one token a slot"
        impl = self.paged_attention_impl()
        positions = jnp.minimum(lengths, c.max_seq - 1)[:, None]

        def attn_fn(p, qn, qp, ckv, kpe, l, pool):
            return self._mla.attend_decode(
                p, qn, qp, ckv, kpe, pool, block_tables, lengths, l,
                self._sm_scale, impl, self.dtype)

        h, pool, counts, routes = self._layers(
            params, self._embed(params, toks)[:, None], pool, positions,
            attn_fn, live=(block_tables[:, 0] != pk.SCRATCH_BLOCK)[:, None],
            with_routes=with_routes)
        out = self._head(params, h[:, 0]), dict(pool, counters=counts)
        return out + (routes,) if with_routes else out
