"""Nemotron-H (NVIDIA Nemotron-3-Nano): Mamba-2 mixers, a few attention
layers and expert layers, ONE of them a layer.

No reference counterpart (the reference framework ships none of the three).
The block (HF ``NemotronHForCausalLM``, ``model_type`` ``nemotron_h``), for
layer ``l`` of kind ``hybrid_override_pattern[l]``::

    h = h + Mixer_l(RMS(h; ln_l))             one norm, one residual a layer
    logits = RMS(h; norm_f) head^T            head untied, embedding unscaled

- ``M``, Mamba-2 (``H`` heads of ``P``, ``G`` groups, state ``N``; ``Di = H
  P``)::

      [z | xBC | dt] = u W_in                 widths Di | Di + 2 G N | H
      xBC = silu(conv4(xBC) + conv_b)         causal, depthwise
      [x | B | C] = xBC                       Di | G N | G N
      dt = softplus(dt + dt_bias)             float32, one a head
      S_t[h] = exp(dt_t A_h) S_{t-1}[h] + dt_t x_t[h] (x) B_t[h // (H / G)]
      y_t[h] = S_t[h] C_t[h // (H / G)] + D_h x_t[h]      A = -exp(A_log)
      out = GroupRMS(y * silu(z); norm_w) W_out           groups of Di / G

  (``ops/mamba2.py`` has the recurrence in its chunked and its one-token form,
  and the kernels).
- ``*``, attention: bias-free ``q``, ``k``, ``v``, ``o``; causal softmax at
  ``1 / sqrt(head_dim)``; grouped-query; NO position of any kind.
- ``E``, experts: ``s = sigmoid(u W_r)`` in float32 over all ``E``; the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``; weights
  the picked ``s`` WITHOUT the bias over their sum, times
  ``routed_scaling_factor``; an expert is NON-GATED, ``down(relu(up(u))^2)``;
  a shared expert of the same form and another width is added for every token.

``NemotronHConfig`` keeps the PUBLISHED key names.  What the family's config
can say and this file does not compute is refused by name: a dense MLP layer
(``-``), grouped routing, another activation.

ONE CHIP'S SHARE, as ``models/afmoe.py``: ``experts_held`` / ``vocab_held``
say which routed experts and vocabulary rows this chip holds; the router
keeps its ``n_routed_experts`` outputs, the absent experts' part is left out,
everything else is whole.

Parameter tree (each kind of layer stacked, in layer order)::

    wte (Vh, D)   head (Vh, D)   lnf (D,)
    mamba.* (Lm, ...)  ln (D,), in_w (D, 2 Di + 2 G N + H), conv_w (K, Dc),
                       conv_b (Dc,), dt_bias, A_log, D (H,), norm_w (Di,),
                       out_w (Di, D)                  Dc = Di + 2 G N
    attn.*  (La, ...)  ln (D,), q_w (D, Hq hd), k_w, v_w (D, Hkv hd),
                       o_w (Hq hd, D)
    moe.*   (Le, ...)  ln (D,), router_w (D, E), e_score_correction_bias (E,),
                       up_w (Eh, F, D), down_w (Eh, F, D), shared_up_w (D, Fs),
                       shared_down_w (Fs, D)

``up_w`` is stored (out, in), as the published ``up_proj.weight``: 1,856 is
14.5 lane tiles, so the chip keeps a ``(D, 1856)`` stack with ``D`` minor
however it is declared, and a grouped product that wants the columns minor
re-lays all 2.2 GB of the stack in every layer of every step
(``moe/dropless.py::grouped_product`` reads it as it lies).

Serving state (``init_serving_state``; docs/serving.md#recurrent-state): the
paged ``{k, v}`` pool over the ATTENTION layers only, and per slot ``conv (Lm,
slots, K - 1, Dc)`` in the model dtype and ``ssm (Lm, slots, G, N, (H / G) P)``
float32: 2,097,152 + 36,864 bytes a Mamba layer a stream at the published
widths.  ``ssm`` lies as the one-token update reads it
(``ops/mamba2.py::step_layout``: a group, then ``N`` on the sublanes, then the
group's heads and channels on the lanes), not as the scan hands a state back.
A prefill writes a slot's rows whole, laying the prompt's final ``(H, P, N)``
state out once (``mamba2.to_step_layout``); a decode step advances the live
rows in place (``mamba2_state_update``).  The contiguous cache
(``init_cache``) keeps ``(Lm, B, H, P, N)``: the scan starts from it.
"""

import dataclasses
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..moe import dropless
from ..ops import mamba2 as m2
from .afmoe import banded_attention
from .gpt2 import GPT2, layer_slice as _take
from .jamba import _mm, _rms, grouped_attention

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
_STACK = {MAMBA: "mamba", EXPERTS: "moe", ATTENTION: "attn"}


@dataclasses.dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = \
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2                 # published; d_inner is heads x head size
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    num_experts_per_tok: int = 6
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    mlp_hidden_act: str = "relu2"
    mamba_hidden_act: str = "silu"
    layer_norm_epsilon: float = 1e-5
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    max_position_embeddings: int = 262144
    paged_attention_impl: str = "auto"    # auto | kernel | gather
    # ---- one chip's share (module docstring); None: the whole model
    experts_held: Optional[Tuple[int, int]] = None     # (first id, count)
    vocab_held: Optional[Tuple[int, int]] = None       # (first id, count)

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
    def max_seq(self):
        return self.max_position_embeddings

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def kinds(self):
        return tuple(self.hybrid_override_pattern)

    def count(self, kind):
        return self.kinds.count(kind)

    @property
    def kv_layers(self):
        """As ``GPT2Config.kv_layers``: the attention layers alone."""
        return self.count(ATTENTION)

    @property
    def held(self):
        """``(first, count)`` of the routed experts held here."""
        return tuple(self.experts_held or (0, self.n_routed_experts))

    @property
    def vocab_rows(self):
        """``(first, count)`` of the vocabulary's rows held here."""
        return tuple(self.vocab_held or (0, self.vocab_size))

    @property
    def state_bytes_per_layer(self):
        """One stream's recurrent state in one Mamba layer, float32."""
        return 4 * self.d_inner * self.ssm_state_size


PRESETS = {
    # tests and CPU examples: all three kinds, 4 heads of 8 in 2 groups over
    # a state of 16, chunks of 8 that a 40-token stream crosses several times
    "nemotron-h-tiny": dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=6,
        hybrid_override_pattern="MEM*EM", num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
        mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=8,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
        n_routed_experts=16, num_experts_per_tok=4,
        max_position_embeddings=256),
}


def causal_prompt_attention(q, k, v):
    """A prompt's causal grouped attention with no (T, T) score matrix: ``q``
    (B, T, H, hd) over ``k`` / ``v`` (B, T, Hkv, hd) -> (B, T, H * hd).  The
    flash forward kernel on a TPU, as ``models/afmoe.py``'s global layers run
    it (K and V repeated to ONE K/V head's group of query heads at a time),
    ``afmoe.banded_attention`` elsewhere."""
    from ..ops import flash_attention_available
    if not flash_attention_available():
        return banded_attention(q, k, v)
    from ..ops.transformer.flash_attention import flash_attention
    B, T, H, hd = q.shape
    n_kv = k.shape[2]
    G = H // n_kv
    group = lambda x: jnp.moveaxis(x.reshape(B, T, n_kv, -1, hd), 2, 0)

    def one(qkv):
        qg, kg, vg = qkv
        # the scope names the Mosaic call in a device trace
        with jax.named_scope("prefill_flash_attention"):
            return flash_attention(qg, jnp.repeat(kg, G, axis=2),
                                   jnp.repeat(vg, G, axis=2), causal=True)
    out = jax.lax.map(one, (group(q), group(k), group(v)))
    return jnp.moveaxis(out, 0, 2).reshape(B, T, H * hd)


class NemotronH:
    """Nemotron-H decoder LM (params: dict pytree, each kind of layer
    stacked)."""

    supports_paged_decode = True
    # a stream's state is more than its K/V blocks: the serving layer
    # refuses what assumes otherwise (inference/serving.py)
    has_recurrent_state = True
    step_counters = dropless.COUNTERS

    def __init__(self, config: Optional[NemotronHConfig] = None,
                 preset: str = None, dtype=jnp.bfloat16, **overrides):
        if config is None:
            base = dict(PRESETS[preset or "nemotron-h-tiny"])
            base.update(overrides)
            config = NemotronHConfig(**base)
        c = config
        dropless.check_route("greedy", "sigmoid")
        dropless.activation(c.mlp_hidden_act)
        refused = {"n_group": (c.n_group, 1), "topk_group": (c.topk_group, 1),
                   "mamba_hidden_act": (c.mamba_hidden_act, "silu"),
                   "n_shared_experts": (c.n_shared_experts, 1)}
        for key, (got, want) in refused.items():
            if got != want:
                raise ValueError(f"{key} = {got!r}: models/nemotron_h.py "
                                 f"computes {want!r} and has no switch")
        if len(c.kinds) != c.num_hidden_layers or \
                set(c.kinds) - set(_STACK):
            raise ValueError(
                f"hybrid_override_pattern = {c.hybrid_override_pattern!r}: "
                f"one of {tuple(_STACK)} for each of the "
                f"{c.num_hidden_layers} layers (a dense MLP layer, '-', is "
                "not computed here)")
        assert c.n_head % c.n_kv_head == 0, (c.n_head, c.n_kv_head)
        assert c.mamba_num_heads % c.n_groups == 0, (c.mamba_num_heads,
                                                     c.n_groups)
        first, count = c.held
        assert 0 <= first and first + count <= c.n_routed_experts, c.held
        self.config = c
        self.dtype = dtype
        # layer -> (kind, its index among the layers of its kind)
        seen = {k: 0 for k in _STACK}
        self.layers = []
        for kind in c.kinds:
            self.layers.append((kind, seen[kind]))
            seen[kind] += 1

    # ------------------------------------------------------------------ init
    def init(self, rng):
        """Matrices normal(0.02); the output projections (``out_w``, ``o_w``,
        every ``down_w``) scaled by 1/sqrt(L), one residual a layer; norm
        weights 1; the router normal(2 / sqrt(D)) as ``models/deepseek_v2.py``;
        ``e_score_correction_bias`` normal(0.01) as ``models/afmoe.py``; the
        state-space constants as Mamba-2 publishes them: ``A_log = log(uniform
        1..16)`` a head, ``D`` = 1, ``dt_bias`` the inverse softplus of ``dt``
        log-uniform in [time_step_min, time_step_max] floored at
        time_step_floor, the conv taps uniform in +-1/sqrt(K)."""
        c = self.config
        D, L = c.hidden_size, c.num_hidden_layers
        Lm, La, Le = c.count(MAMBA), c.count(ATTENTION), c.count(EXPERTS)
        H, Di, Dc, K = c.mamba_num_heads, c.d_inner, c.conv_dim, c.conv_kernel
        Hq, Hk = c.n_head * c.head_dim, c.n_kv_head * c.head_dim
        F, Fs = c.moe_intermediate_size, c.moe_shared_expert_intermediate_size
        E, Eh, Vh = c.n_routed_experts, c.held[1], c.vocab_rows[1]
        k = iter(jax.random.split(rng, 24))
        std, proj = 0.02, 0.02 / np.sqrt(float(L))
        f32 = jnp.float32
        nrm = lambda shape, s=std: jax.random.normal(next(k), shape, f32) * s
        uni = lambda shape, lo, hi: jax.random.uniform(next(k), shape, f32,
                                                       lo, hi)
        ones = lambda *shape: jnp.ones(shape, f32)
        dt = jnp.maximum(jnp.exp(uni((Lm, H), np.log(c.time_step_min),
                                     np.log(c.time_step_max))),
                         c.time_step_floor)
        lim = 1.0 / np.sqrt(K)
        return {
            "wte": nrm((Vh, D)),
            "mamba": {
                "ln": ones(Lm, D),
                "in_w": nrm((Lm, D, 2 * Di + 2 * c.n_groups
                             * c.ssm_state_size + H)),
                "conv_w": uni((Lm, K, Dc), -lim, lim),
                "conv_b": jnp.zeros((Lm, Dc), f32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(uni((Lm, H), 1.0, 16.0)),
                "D": ones(Lm, H),
                "norm_w": ones(Lm, Di),
                "out_w": nrm((Lm, Di, D), proj),
            },
            "attn": {
                "ln": ones(La, D),
                "q_w": nrm((La, D, Hq)), "k_w": nrm((La, D, Hk)),
                "v_w": nrm((La, D, Hk)), "o_w": nrm((La, Hq, D), proj),
            },
            "moe": {
                "ln": ones(Le, D),
                "router_w": nrm((Le, D, E), 2.0 / np.sqrt(D)),
                "e_score_correction_bias": nrm((Le, E), 0.01),
                "up_w": nrm((Le, Eh, F, D)),
                "down_w": nrm((Le, Eh, F, D), proj),
                "shared_up_w": nrm((Le, D, Fs)),
                "shared_down_w": nrm((Le, Fs, D), proj),
            },
            "lnf": ones(D),
            "head": nrm((Vh, D)),
        }

    def num_params(self):
        """The closed form of :meth:`init`'s shapes: 38,744,896 a Mamba-2
        layer, 23,399,040 an attention layer and 1,297,468,160 a whole expert
        layer at the published widths."""
        c = self.config
        D, Di, Dc, H = c.hidden_size, c.d_inner, c.conv_dim, c.mamba_num_heads
        mamba = (D * (Di + Dc + H) + c.conv_kernel * Dc + Dc + 3 * H + Di
                 + Di * D + D)
        attn = 2 * D * c.head_dim * (c.n_head + c.n_kv_head) + D
        E = c.n_routed_experts
        moe = (D * E + E + 2 * D * (c.held[1] * c.moe_intermediate_size
                                    + c.moe_shared_expert_intermediate_size)
               + D)
        return (c.count(MAMBA) * mamba + c.count(ATTENTION) * attn
                + c.count(EXPERTS) * moe + 2 * c.vocab_rows[1] * D + D)

    # ---------------------------------------------------------------- pieces
    def _norm(self, p, h):
        return _rms(h, p["ln"], self.config.layer_norm_epsilon).astype(
            self.dtype)

    def _scan_inputs(self, p, h, tail):
        """A Mamba-2 mixer up to the recurrence, for the stream ``h`` (B, T,
        D) and the convolution's incoming ``tail`` (B, K - 1, Dc) or None.
        Returns ``(x (B, T, H, P), z (B, T, Di), dt (B, T, H) float32, B, C
        (B, T, G, N), padded)``: the recurrence's operands, and the
        convolution's input with its tail in front."""
        with jax.named_scope("ssm.proj"):
            c = self.config
            Di, G, N = c.d_inner, c.n_groups, c.ssm_state_size
            z, xBC, dt = jnp.split(_mm(self._norm(p, h), p["in_w"]),
                                   [Di, Di + c.conv_dim], axis=-1)
            with jax.named_scope("ssm.conv"):
                xBC, padded = m2.causal_conv(xBC, p["conv_w"], p["conv_b"], tail)
                xBC = jax.nn.silu(xBC)
            x, Bm, Cm = jnp.split(xBC, [Di, Di + G * N], axis=-1)
            lead = x.shape[:-1]
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + p["dt_bias"].astype(jnp.float32))
            return (x.reshape(lead + (c.mamba_num_heads, c.mamba_head_dim)), z,
                    dt, Bm.reshape(lead + (G, N)), Cm.reshape(lead + (G, N)),
                    padded)

    def _scan_output(self, p, y, z):
        """From the recurrence's ``y`` (..., H, P) on: the grouped gated norm
        and ``out_proj``."""
        with jax.named_scope("ssm.proj"):
            c = self.config
            y = m2.gated_group_norm(y.reshape(z.shape), z, p["norm_w"],
                                    c.n_groups, c.layer_norm_epsilon)
            return _mm(y, p["out_w"]).astype(jnp.float32)

    @staticmethod
    def _A(p):
        return -jnp.exp(p["A_log"].astype(jnp.float32))

    def _mamba(self, p, h, tail=None, h0=None, t_real=None, impl="auto"):
        """One Mamba-2 layer over ``h`` (B, T, D).  Returns ``(h, new tail (B,
        K - 1, Dc), state (B, H, P, N) float32)``, both taken after token
        ``t_real - 1`` (the last one when None)."""
        c = self.config
        T = h.shape[1]
        x, z, dt, Bm, Cm, padded = self._scan_inputs(p, h, tail)
        if t_real is not None:
            dt = m2.mask_delta(dt, t_real)
        with jax.named_scope("ssm.scan"):
            y, S = m2.ssd_scan(x, dt, self._A(p), Bm, Cm, p["D"], h0=h0,
                               chunk=c.chunk_size, impl=impl)
        new_tail = m2.conv_tail_at(padded, T if t_real is None else t_real,
                                   c.conv_kernel - 1)
        return h + self._scan_output(p, y, z), new_tail, S

    def _qkv(self, p, h):
        c = self.config
        u = self._norm(p, h)
        heads = lambda x: x.reshape(u.shape[:-1] + (-1, c.head_dim))
        return (heads(_mm(u, p["q_w"])), heads(_mm(u, p["k_w"])),
                heads(_mm(u, p["v_w"])))

    def _moe(self, pm, h, layer, live=None):
        """Expert layer ``layer`` (of the stacked ``pm``) over the stream ``h``
        (B, T, D): ``(h, counters (7,), experts (B T, k))``; ``live`` (B, T)
        bool leaves pad rows and empty slots out of the counts."""
        c = self.config
        eps = c.layer_norm_epsilon
        u = _rms(h, pm["ln"][layer], eps)
        x = u.astype(self.dtype).reshape(-1, u.shape[-1])
        act = dropless.activation(c.mlp_hidden_act)
        with jax.named_scope("moe.route"):
            # float32, as published: the top scores of 128 sigmoids lie
            # within bfloat16's rounding of each other
            logits = jnp.dot(u.astype(jnp.float32).reshape(x.shape),
                             pm["router_w"][layer].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            experts, weights = dropless.route(
                logits, c.num_experts_per_tok, scoring_func="sigmoid",
                bias=pm["e_score_correction_bias"][layer],
                norm_topk_prob=c.norm_topk_prob,
                routed_scaling_factor=c.routed_scaling_factor,
                scale_normed=True)
            counts = dropless.route_counters(
                experts, *c.held, width=c.n_routed_experts,
                live=None if live is None else live.reshape(-1))
        with jax.named_scope("moe.experts"):
            routed = dropless.held_experts(
                x, experts, weights, None, pm["up_w"], pm["down_w"],
                c.held[0], layer=layer, act=c.mlp_hidden_act,
                width=c.n_routed_experts)
        with jax.named_scope("moe.shared"):
            shared = _mm(act(_mm(x, pm["shared_up_w"][layer])),
                         pm["shared_down_w"][layer])
        y = (routed + shared).reshape(h.shape).astype(jnp.float32)
        return h + y, counts, experts

    def _layers(self, params, h, carry, mamba_fn, attn_fn, live=None):
        """The float32 stream ``h`` (B, T, D) through every layer, unrolled (a
        layer's kind is static).  ``mamba_fn(p, h, m, carry)`` and
        ``attn_fn(p, h, a, carry)`` run a mixer with its residual and return
        ``(h, carry)``.  Returns ``(h, carry, counters (7,) summed over the
        expert layers, routes (expert layers, B T, k))``."""
        counts = jnp.zeros((len(dropless.COUNTERS),), jnp.int32)
        routes = []
        for kind, i in self.layers:
            if kind == EXPERTS:
                h, n, experts = self._moe(params["moe"], h, i, live=live)
                counts = counts + n
                routes.append(experts)
            else:
                fn = mamba_fn if kind == MAMBA else attn_fn
                h, carry = fn(_take(params[_STACK[kind]], i), h, i, carry)
        return h, carry, counts, (jnp.stack(routes) if routes else None)

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["wte"][tokens - self.config.vocab_rows[0]].astype(
                jnp.float32)

    def _head(self, params, h):
        with jax.named_scope("lm_head"):
            h = _rms(h, params["lnf"], self.config.layer_norm_epsilon)
            return jnp.einsum("...d,vd->...v", h.astype(self.dtype),
                              params["head"].astype(self.dtype),
                              preferred_element_type=jnp.float32)

    # --------------------------------------------------------------- forward
    def apply(self, params, tokens, rng=None, deterministic=True,
              return_hidden=False, scan_impl="auto"):
        """``tokens`` (B, T) -> logits (B, T, Vh) float32 (no dropout in the
        family).  Differentiable with ``scan_impl="jnp"`` (what ``loss``
        passes: the kernels have no backward)."""
        def mamba_fn(p, h, m, carry):
            return self._mamba(p, h, impl=scan_impl)[0], carry

        def attn_fn(p, h, a, carry):
            with jax.named_scope("attention"):
                q, k, v = self._qkv(p, h)
                out = causal_prompt_attention(q, k, v)
                return h + _mm(out, p["o_w"]).astype(jnp.float32), carry

        h, _, _, _ = self._layers(params, self._embed(params, tokens), (),
                                  mamba_fn, attn_fn)
        if return_hidden:
            return _rms(h, params["lnf"], self.config.layer_norm_epsilon)
        return self._head(params, h)

    def loss(self, params, batch, rng=None):
        """Next-token LM loss over the held vocabulary rows; ``batch`` as
        ``GPT2.loss`` takes it."""
        tokens, labels = GPT2._split_batch(batch)
        logits = self.apply(params, tokens, scan_impl="jnp")
        lse = jax.nn.logsumexp(logits, axis=-1)
        labels = labels.astype(jnp.int32) - self.config.vocab_rows[0]
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)
        return jnp.mean(lse - picked[..., 0])

    # ---------------------------------------------------- contiguous decoding
    def _recurrent_rows(self, rows, dtype, state):
        """Per row and Mamba layer the convolution's tail and the recurrent
        state, ``state`` one row's shape of it: ``(H, P, N)`` as the scan
        hands it back (``init_cache``), or the one-token update's own
        (``init_serving_state``)."""
        c = self.config
        Lm = c.count(MAMBA)
        return {"conv": jnp.zeros((Lm, rows, c.conv_kernel - 1, c.conv_dim),
                                  dtype),
                "ssm": jnp.zeros((Lm, rows) + tuple(state), jnp.float32)}

    @property
    def _state_dims(self):
        c = self.config
        return (c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size)

    def init_cache(self, batch_size: int, max_len: Optional[int] = None,
                   dtype=None):
        """``InferenceEngine.generate``'s cache: dense K/V for the attention
        layers, the convolution tail and the recurrent state for the Mamba
        layers, and the write index."""
        c = self.config
        dtype = dtype or self.dtype
        kv = (c.count(ATTENTION), batch_size, max_len or c.max_seq,
              c.n_kv_head, c.head_dim)
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
                **self._recurrent_rows(batch_size, dtype, self._state_dims),
                "index": jnp.zeros((), jnp.int32)}

    def apply_with_cache(self, params, tokens, cache):
        """Forward ``tokens`` (B, T) from ``cache['index']``; returns
        ``(logits (B, T, Vh), new_cache)``: prefill (T = prompt) and decode
        (T = 1) alike."""
        T = tokens.shape[1]
        index = cache["index"]
        S = cache["k"].shape[2]
        valid = (jnp.arange(S)[None, :] <= index + jnp.arange(T)[:, None])

        def mamba_fn(p, h, m, carry):
            k, v, conv, ssm = carry
            h, tail, state = self._mamba(p, h, tail=conv[m], h0=ssm[m],
                                         impl="jnp")
            return h, (k, v, conv.at[m].set(tail.astype(conv.dtype)),
                       ssm.at[m].set(state))

        def attn_fn(p, h, a, carry):
            with jax.named_scope("attention"):
                k, v, conv, ssm = carry
                q, kn, vn = self._qkv(p, h)
                k = jax.lax.dynamic_update_slice(
                    k, kn[None].astype(k.dtype), (a, 0, index, 0, 0))
                v = jax.lax.dynamic_update_slice(
                    v, vn[None].astype(v.dtype), (a, 0, index, 0, 0))
                out = grouped_attention(q, k[a], v[a], valid)
                return h + _mm(out, p["o_w"]).astype(jnp.float32), (k, v, conv,
                                                                    ssm)

        h, (k, v, conv, ssm), _, _ = self._layers(
            params, self._embed(params, tokens),
            (cache["k"], cache["v"], cache["conv"], cache["ssm"]),
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
        paged ``{k, v}`` pool over the attention layers, per slot the Mamba
        layers' convolution tails and recurrent states, and ``counters``.  The
        states lie in the one-token update's layout, ``ssm (Lm, slots, G, N,
        (H / G) P)`` float32 (module docstring): index it by ``[layer]`` and
        ``[:, slot]``, read a row back with ``mamba2.from_step_layout``."""
        from ..inference import paged_kv as pk
        c = self.config
        dtype = dtype or self.dtype
        pool = pk.init_pool(c.count(ATTENTION), num_blocks, block_size,
                            c.n_head, c.head_dim, dtype, kv_bits=kv_bits,
                            quant_block=quant_block, n_kv_head=c.n_kv_head)
        state = m2.step_layout(*self._state_dims, c.n_groups)
        return dict(pool, **self._recurrent_rows(batch_slots, dtype, state),
                    counters=jnp.zeros((len(self.step_counters),), jnp.int32))

    @staticmethod
    def recurrent_state_bytes(pool) -> int:
        return int(pool["conv"].nbytes) + int(pool["ssm"].nbytes)

    def state_step_bytes(self) -> int:
        """What a decode step's state update must move for ONE live slot:
        every Mamba layer's state read and written."""
        c = self.config
        return c.count(MAMBA) * 2 * c.state_bytes_per_layer

    def prefill_attrs(self, prompt_len: int) -> dict:
        """What the chunked scan walks for a prompt, for the prefill's span."""
        return {"ssd_tokens": prompt_len,
                "ssd_chunks": -(-prompt_len // self.config.chunk_size)}

    def serving_stats(self, pool):
        """What ``ServingEngine.stats()`` reports beside its own."""
        c = self.config
        return {"experts_held": c.held[1], "experts_total": c.n_routed_experts,
                "mamba_layers": c.count(MAMBA),
                "attention_layers": c.count(ATTENTION),
                "expert_layers": c.count(EXPERTS),
                "state_bytes_per_stream": self.recurrent_state_bytes(pool)
                // pool["ssm"].shape[1]}

    def prefill_paged(self, params, toks, pool, blocks, slot, t_real):
        """One prompt, padded to its bucket, into the pool: the attention
        layers' K/V into ``blocks``, and slot ``slot``'s recurrent rows written
        WHOLE with the state after token ``t_real - 1`` (the pad after it must
        not enter a recurrence; it is routed like any token and left out of
        the counters), the scan's ``(H, P, N)`` state re-laid once into the
        update's layout (``ssm.seat``; 2 MB a layer).  ``toks``: (1, T);
        returns ``(logits (1, Vh) at token t_real - 1, pool)``."""
        from ..inference import paged_kv as pk
        T = toks.shape[1]
        bucket = blocks.shape[0] * pool["k"].shape[2]

        def mamba_fn(p, h, m, carry):
            pool, ks, vs = carry
            h, tail, state = self._mamba(p, h, t_real=t_real)
            with jax.named_scope("ssm.seat"):
                pool = dict(
                    pool,
                    conv=pool["conv"].at[m, slot].set(
                        tail[0].astype(pool["conv"].dtype)),
                    ssm=pool["ssm"].at[m, slot].set(
                        m2.to_step_layout(state[0], self.config.n_groups)))
            return h, (pool, ks, vs)

        def attn_fn(p, h, a, carry):
            with jax.named_scope("attention"):
                pool, ks, vs = carry
                q, k, v = self._qkv(p, h)
                out = causal_prompt_attention(q, k, v)
                return (h + _mm(out, p["o_w"]).astype(jnp.float32),
                        (pool, ks + (k[0],), vs + (v[0],)))

        h, (pool, ks, vs), counts, _ = self._layers(
            params, self._embed(params, toks), (pool, (), ()), mamba_fn,
            attn_fn, live=(jnp.arange(T) < t_real)[None])
        with jax.named_scope("kv.seat"):
            k, v = jnp.stack(ks), jnp.stack(vs)        # (La, T, Hkv, hd)
            if T < bucket:   # a bucket rounded past max_seq (GPT2 likewise)
                pad = ((0, 0), (0, bucket - T), (0, 0), (0, 0))
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            pool = pk.write_prefill(pool, blocks, k, v)
        row = jax.lax.dynamic_slice_in_dim(h[0], t_real - 1, 1, axis=0)
        return self._head(params, row), dict(pool, counters=counts)

    def decode_step_paged(self, params, toks, pool, block_tables, lengths,
                          with_routes=False):
        """One token for every slot: ``GPT2.decode_step_paged``'s contract
        (``toks`` (B,), ``lengths`` the tokens already cached).  A row whose
        table points at the scratch block is one the host holds inactive: its
        K/V write lands in scratch and its recurrent rows stay as they are.
        Returns ``(logits (B, Vh) float32, pool)`` and, ``with_routes``, the
        experts each slot's token was routed to, (expert layers, B, k)."""
        from ..inference import paged_kv as pk
        from ..ops.transformer.paged_attention import paged_attention
        c = self.config
        assert toks.ndim == 1, \
            "a recurrent state has no multi-token window to roll back"
        impl = self.paged_attention_impl()
        active = block_tables[:, 0] != pk.SCRATCH_BLOCK

        def mamba_fn(p, h, m, pool):
            tail = pool["conv"][m]
            x, z, dt, Bm, Cm, padded = self._scan_inputs(p, h, tail)
            with jax.named_scope("ssm.step"):
                y, ssm = m2.ssm_step(pool["ssm"], m, x[:, 0], dt[:, 0],
                                     self._A(p), Bm[:, 0], Cm[:, 0], p["D"],
                                     active=active)
                tail = jnp.where(active[:, None, None], padded[:, 1:], tail)
                pool = dict(pool, conv=pool["conv"].at[m].set(tail), ssm=ssm)
            return h + self._scan_output(p, y[:, None], z), pool

        def attn_fn(p, h, a, pool):
            with jax.named_scope("attention"):
                q, k, v = self._qkv(p, h)                  # (B, 1, H | Hkv, hd)
                with jax.named_scope("kv.seat"):
                    pool = pk.write_tokens(pool, a, block_tables, lengths, k,
                                           v)
                if impl == "kernel":
                    out = paged_attention(q, pool, block_tables, lengths, a)
                else:
                    keys, vals = pk.gather_kv(pool, a, block_tables, self.dtype,
                                              c.n_kv_head)
                    valid = (jnp.arange(keys.shape[1])[None, :]
                             <= lengths[:, None])[:, None, None, None, :]
                    out = grouped_attention(q, keys, vals, valid)
                return h + _mm(out, p["o_w"]).astype(jnp.float32), pool

        h, pool, counts, routes = self._layers(
            params, self._embed(params, toks)[:, None], pool, mamba_fn,
            attn_fn, live=active[:, None])
        out = self._head(params, h[:, 0]), dict(pool, counters=counts)
        return out + (routes,) if with_routes else out
