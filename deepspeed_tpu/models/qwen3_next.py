"""Qwen3-Next (``model_type`` ``qwen3_next``: Qwen3-Next-80B-A3B): Gated
DeltaNet layers and gated attention layers, EVERY layer followed by an expert
layer with a gated shared expert.

No reference counterpart.  The block (HF ``Qwen3NextForCausalLM``), layer ``l``
full attention when ``(l + 1) % full_attention_interval == 0``, else Gated
DeltaNet::

    RMS0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)       zero-centred
    h = h + Mixer_l(RMS0(h; ln1_l))
    h = h + MoE_l(RMS0(h; ln2_l))
    logits = RMS0(h; lnf) head^T                            head untied

- Gated DeltaNet (``Hk`` key heads of ``dk``, ``Hv`` value heads of ``dv``,
  ``r = Hv / Hk``)::

      [q | k | v | z] = u W_qkvz        a KEY head at a time: (q dk, k dk,
                                        v r dv, z r dv) x Hk
      [b | a] = u W_ba                  a key head at a time: (b r, a r) x Hk
      [q | k | v] = silu(conv4([q | k | v]))      causal, depthwise, no bias
      beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)     float32
      q = l2norm(q) / sqrt(dk),  k = l2norm(k)    a head; key head j serves
                                                  value heads r j .. r j + r - 1
      S_t = exp(g_t) S_{t-1} + k_t (x) beta_t (v_t - (exp(g_t) S_{t-1})^T k_t)
      o_t = S_t^T q_t                   ``ops/gated_delta.py``: both forms
      out = [RMS(o_t[h]; norm_w) silu(z_t[h])]_h W_out    the norm over dv, a
                                        head, weight plain; the norm FIRST

- Gated attention: ``[q | gate] = u W_q`` a head at a time ``(q hd, gate hd) x
  H``; ``q``, ``k`` normed a head by ``RMS0``; rotary on the first
  ``partial_rotary_factor`` of a head's dims (rotate-half within them);
  grouped-query causal softmax at ``1 / sqrt(hd)``; ``out = (o sigmoid(gate))
  W_o``.
- Experts: ``p = softmax(u W_r)`` in float32 over all ``E``; the
  ``num_experts_per_tok`` largest, renormalised over the picks
  (``norm_topk_prob``); SwiGLU experts; a shared SwiGLU expert behind
  ``sigmoid(u . w_sg)``.

``Qwen3NextConfig`` keeps the PUBLISHED key names.  What the family's config
can say and this file does not compute is refused by name: a dense MLP layer
(``mlp_only_layers``, ``decoder_sparse_step`` other than 1), another
activation, a multi-token-prediction head (not part of the served forward).

ONE CHIP'S SHARE, as ``models/nemotron_h.py``: ``experts_held`` /
``vocab_held``; the router keeps its ``num_experts`` outputs and every token
its picks and their renormalisation over ALL of them, the absent experts'
part is left out, everything else is whole.

Parameter tree (each kind of mixer stacked in layer order; ``moe`` over every
layer; every ``ln*``, ``lnf``, ``q_norm``, ``k_norm`` stores ``w - 1``)::

    wte (Vh, D)   head (Vh, D)   lnf (D,)
    delta.* (Ld, ...)  ln1 (D,), qkvz_w (D, 2 Hk dk + 2 Hv dv), ba_w (D, 2 Hv),
                       conv_w (K, Dc), dt_bias, A_log (Hv,), norm_w (dv,),
                       out_w (Hv dv, D)            Dc = 2 Hk dk + Hv dv
    attn.*  (La, ...)  ln1 (D,), q_w (D, 2 H hd), k_w, v_w (D, Hkv hd),
                       q_norm, k_norm (hd,), o_w (H hd, D)
    moe.*   (L, ...)   ln2 (D,), router_w (D, E), gate_w, up_w (Eh, D, F),
                       down_w (Eh, F, D), shared_gate_w, shared_up_w (D, Fs),
                       shared_down_w (Fs, D), shared_gate (D,)

Serving state (``init_serving_state``; docs/serving.md#recurrent-state): the
paged ``{k, v}`` pool over the ATTENTION layers only, and per slot ``conv (Ld,
slots, K - 1, Dc)`` in the model dtype and ``delta (Ld, slots, Hv, dk, dv)``
float32: 2,097,152 + 49,152 bytes a DeltaNet layer a stream at the published
widths.  A prefill writes a slot's rows whole with the state after token
``t_real - 1``; a decode step advances the live rows in place
(``gated_delta_state_update``).
"""

import dataclasses
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..moe import dropless
from ..ops import gated_delta as gd
from ..ops.selective_scan import causal_conv, conv_tail_at
from .afmoe import Afmoe, _CHUNK_TOKENS
from .gpt2 import GPT2, layer_slice as _take
from .jamba import _mm, _rms, grouped_attention
from .nemotron_h import causal_prompt_attention
from .rotary import apply_rotary_pos_emb, rotary_freqs

DELTA, ATTENTION = "linear_attention", "full_attention"
_L2_EPS = 1e-6


@dataclasses.dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    intermediate_size: int = 5120     # published; no layer here is a dense MLP
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    chunk_size: int = 64              # the chunked delta rule's, not published
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
    def layer_types(self):
        return tuple(ATTENTION if (l + 1) % self.full_attention_interval == 0
                     else DELTA for l in range(self.num_hidden_layers))

    def count(self, kind):
        return self.layer_types.count(kind)

    @property
    def kv_layers(self):
        """As ``GPT2Config.kv_layers``: the attention layers alone."""
        return self.count(ATTENTION)

    @property
    def rotary_dim(self):
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self):
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self):
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self):
        return 2 * self.key_dim + self.value_dim

    @property
    def held(self):
        """``(first, count)`` of the routed experts held here."""
        return tuple(self.experts_held or (0, self.num_experts))

    @property
    def vocab_rows(self):
        """``(first, count)`` of the vocabulary's rows held here."""
        return tuple(self.vocab_held or (0, self.vocab_size))

    @property
    def state_bytes_per_layer(self):
        """One stream's delta-rule state in one DeltaNet layer, float32."""
        return 4 * self.linear_num_value_heads * self.linear_key_head_dim \
            * self.linear_value_head_dim


PRESETS = {
    # tests and CPU examples, every ratio kept: 2 value heads a key head,
    # rotary on a quarter of the head, a period of 4 (two of them), 4 query
    # heads a K/V head, chunks of 16 that a 40-token stream crosses twice
    "qwen3-next-tiny": dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=1, head_dim=16,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8, num_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, intermediate_size=160,
        chunk_size=16, max_position_embeddings=256),
}


def _rms0(x, w, eps):
    """The zero-centred RMS norm: ``w`` is stored as ``gamma - 1``."""
    return _rms(x, 1.0 + w.astype(jnp.float32), eps)


def _l2norm(x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def gated_head_norm(o, z, w, eps):
    """``RMS(o; w) * silu(z)`` over the last dim, a head at a time: the norm
    FIRST, then the gate (Mamba-2 gates first); ``w`` plain.  float32 inside;
    returns ``z.dtype``."""
    f32 = jnp.float32
    o = o.astype(f32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return (o * w.astype(f32) * jax.nn.silu(z.astype(f32))).astype(z.dtype)


def shared_expert_gate(x, w):
    """``sigmoid(x . w)``: ``x`` (N, D), ``w`` (D,) -> (N,) float32."""
    return jax.nn.sigmoid(jnp.einsum("nd,d->n", x, w.astype(x.dtype),
                                     preferred_element_type=jnp.float32))


class Qwen3Next:
    """Qwen3-Next decoder LM (params: dict pytree, each kind of mixer stacked,
    the expert layers stacked over every layer)."""

    supports_paged_decode = True
    # a stream's state is more than its K/V blocks: the serving layer
    # refuses what assumes otherwise (inference/serving.py)
    has_recurrent_state = True
    step_counters = dropless.COUNTERS

    def __init__(self, config: Optional[Qwen3NextConfig] = None,
                 preset: str = None, dtype=jnp.bfloat16, **overrides):
        if config is None:
            base = dict(PRESETS[preset or "qwen3-next-tiny"])
            base.update(overrides)
            config = Qwen3NextConfig(**base)
        c = config
        dropless.check_route("greedy", "softmax")
        dropless.activation(c.hidden_act)
        refused = {"decoder_sparse_step": (c.decoder_sparse_step, 1),
                   "mlp_only_layers": (tuple(c.mlp_only_layers), ()),
                   "hidden_act": (c.hidden_act, "silu")}
        for key, (got, want) in refused.items():
            if got != want:
                raise ValueError(f"{key} = {got!r}: models/qwen3_next.py "
                                 f"computes {want!r} and has no switch")
        assert c.n_head % c.n_kv_head == 0, (c.n_head, c.n_kv_head)
        assert c.linear_num_value_heads % c.linear_num_key_heads == 0, c
        assert c.rotary_dim % 2 == 0 and 0 < c.rotary_dim <= c.head_dim, c
        first, count = c.held
        assert 0 <= first and first + count <= c.num_experts, c.held
        self.config = c
        self.dtype = dtype
        self._rope = rotary_freqs(c.rotary_dim, c.max_seq, base=c.rope_theta)
        # layer -> (kind, its index among the layers of its kind)
        seen = {DELTA: 0, ATTENTION: 0}
        self.layers = []
        for kind in c.layer_types:
            self.layers.append((kind, seen[kind]))
            seen[kind] += 1

    # ------------------------------------------------------------------ init
    def init(self, rng):
        """Matrices normal(0.02); the output projections (``out_w``, ``o_w``,
        every ``down_w``) scaled by 1/sqrt(2 L), two residuals a layer; the
        router normal(2 / sqrt(D)) as ``models/nemotron_h.py``; the
        zero-centred norms' ``w`` normal(0.1) (so that ``1 + w`` is not 1); the
        gated norm's weight 1; ``A_log = log(uniform 0..16)`` and ``dt_bias``
        the inverse softplus of ``exp(uniform(log 0.001, log 0.1))`` a value
        head, as the published initialiser; the conv taps uniform in
        +-1/sqrt(K)."""
        c = self.config
        D, L = c.hidden_size, c.num_hidden_layers
        Ld, La = c.count(DELTA), c.count(ATTENTION)
        Hv, dv, K = (c.linear_num_value_heads, c.linear_value_head_dim,
                     c.linear_conv_kernel_dim)
        Hq, Hk = c.n_head * c.head_dim, c.n_kv_head * c.head_dim
        F, Fs = c.moe_intermediate_size, c.shared_expert_intermediate_size
        E, Eh, Vh = c.num_experts, c.held[1], c.vocab_rows[1]
        k = iter(jax.random.split(rng, 32))
        std, proj = 0.02, 0.02 / np.sqrt(2.0 * L)
        f32 = jnp.float32
        nrm = lambda shape, s=std: jax.random.normal(next(k), shape, f32) * s
        uni = lambda shape, lo, hi: jax.random.uniform(next(k), shape, f32,
                                                       lo, hi)
        dt = jnp.exp(uni((Ld, Hv), np.log(1e-3), np.log(1e-1)))
        lim = 1.0 / np.sqrt(K)
        return {
            "wte": nrm((Vh, D)),
            "delta": {
                "ln1": nrm((Ld, D), 0.1),
                "qkvz_w": nrm((Ld, D, 2 * c.key_dim + 2 * c.value_dim)),
                "ba_w": nrm((Ld, D, 2 * Hv)),
                "conv_w": uni((Ld, K, c.conv_dim), -lim, lim),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(uni((Ld, Hv), 1e-4, 16.0)),
                "norm_w": jnp.ones((Ld, dv), f32),
                "out_w": nrm((Ld, c.value_dim, D), proj),
            },
            "attn": {
                "ln1": nrm((La, D), 0.1),
                "q_w": nrm((La, D, 2 * Hq)), "k_w": nrm((La, D, Hk)),
                "v_w": nrm((La, D, Hk)),
                "q_norm": nrm((La, c.head_dim), 0.1),
                "k_norm": nrm((La, c.head_dim), 0.1),
                "o_w": nrm((La, Hq, D), proj),
            },
            "moe": {
                "ln2": nrm((L, D), 0.1),
                "router_w": nrm((L, D, E), 2.0 / np.sqrt(D)),
                "gate_w": nrm((L, Eh, D, F)), "up_w": nrm((L, Eh, D, F)),
                "down_w": nrm((L, Eh, F, D), proj),
                "shared_gate_w": nrm((L, D, Fs)),
                "shared_up_w": nrm((L, D, Fs)),
                "shared_down_w": nrm((L, Fs, D), proj),
                "shared_gate": nrm((L, D)),
            },
            "lnf": nrm((D,), 0.1),
            "head": nrm((Vh, D)),
        }

    def num_params(self):
        """The closed form of :meth:`init`'s shapes: 33,718,464 a DeltaNet
        mixer, 27,263,488 an attention mixer, 4,200,448 a layer outside its
        mixer and its routed experts, 3,145,728 an expert at the published
        widths."""
        c = self.config
        D, Hv = c.hidden_size, c.linear_num_value_heads
        delta = (D * (2 * c.key_dim + 2 * c.value_dim + 2 * Hv)
                 + c.linear_conv_kernel_dim * c.conv_dim + 2 * Hv
                 + c.linear_value_head_dim + c.value_dim * D)
        attn = D * c.head_dim * (3 * c.n_head + 2 * c.n_kv_head) \
            + 2 * c.head_dim
        layer = (D * c.num_experts + 3 * D * (
            c.held[1] * c.moe_intermediate_size
            + c.shared_expert_intermediate_size) + D + 2 * D)
        return (c.count(DELTA) * delta + c.count(ATTENTION) * attn
                + c.num_hidden_layers * layer + 2 * c.vocab_rows[1] * D + D)

    # ---------------------------------------------------------------- pieces
    def _norm(self, w, h):
        return _rms0(h, w, self.config.rms_norm_eps).astype(self.dtype)

    def _delta_inputs(self, p, h, tail):
        """A DeltaNet mixer up to the recurrence, for the stream ``h`` (B, T,
        D) and the convolution's incoming ``tail`` (B, K - 1, Dc) or None.
        Returns ``(q, k (B, T, Hv, dk), v (B, T, Hv, dv), z (B, T, Hv, dv),
        g, beta (B, T, Hv) float32, padded)``: the recurrence's operands, the
        gate, and the convolution's input with its tail in front."""
        with jax.named_scope("gdn.project"):
            c = self.config
            f32 = jnp.float32
            Hk, Hv = c.linear_num_key_heads, c.linear_num_value_heads
            dk, dv, r = c.linear_key_head_dim, c.linear_value_head_dim, Hv // Hk
            u = self._norm(p["ln1"], h)
            lead = u.shape[:-1]
            qkvz = _mm(u, p["qkvz_w"]).reshape(lead + (Hk, 2 * dk + 2 * r * dv))
            q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
            b, a = jnp.split(_mm(u, p["ba_w"]).reshape(lead + (Hk, 2 * r)),
                             [r], axis=-1)
            flat = lambda x: x.reshape(lead + (-1,))
            mixed, padded = causal_conv(
                jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1),
                p["conv_w"], jnp.zeros((c.conv_dim,), f32), tail)
            q, k, v = jnp.split(jax.nn.silu(mixed),
                                [c.key_dim, 2 * c.key_dim], axis=-1)
            heads = lambda x, d: jnp.repeat(
                x.reshape(lead + (Hk, d)), r, axis=-2).astype(self.dtype)
            q = heads(_l2norm(q.reshape(lead + (Hk, dk))) / np.sqrt(dk), dk)
            k = heads(_l2norm(k.reshape(lead + (Hk, dk))), dk)
            beta = jax.nn.sigmoid(flat(b).astype(f32))
            g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
                flat(a).astype(f32) + p["dt_bias"].astype(f32))
            return (q, k, v.reshape(lead + (Hv, dv)),
                    z.reshape(lead + (Hv, dv)), g, beta, padded)

    def _delta_output(self, p, o, z):
        """From the recurrence's ``o`` (..., Hv, dv) on: the gated norm a
        head and ``out_proj``."""
        with jax.named_scope("gdn.gate_norm"):
            y = gated_head_norm(o, z, p["norm_w"], self.config.rms_norm_eps)
            return _mm(y.reshape(y.shape[:-2] + (-1,)),
                       p["out_w"]).astype(jnp.float32)

    def _delta(self, p, h, tail=None, S0=None, t_real=None):
        """One DeltaNet mixer over ``h`` (B, T, D).  Returns ``(h, new tail
        (B, K - 1, Dc), state (B, Hv, dk, dv) float32)``, both taken after
        token ``t_real - 1`` (the last one when None).  A long prompt goes in
        equal segments of at most ``afmoe._CHUNK_TOKENS`` tokens one after the
        other, the tail and the state handed on: the projections' 8,192 and
        12,288 channels and the chunks' operands stand 4 GB of transients at
        16k tokens taken whole."""
        c = self.config
        B, T = h.shape[:2]
        K = c.linear_conv_kernel_dim
        if tail is None:
            tail = jnp.zeros((B, K - 1, c.conv_dim), self.dtype)
        if S0 is None:
            S0 = jnp.zeros((B, c.linear_num_value_heads, c.linear_key_head_dim,
                            c.linear_value_head_dim), jnp.float32)
        t_real = jnp.asarray(T if t_real is None else t_real, jnp.int32)

        def segment(carry, hs):
            tail, S, left = carry
            size = hs.shape[1]
            q, k, v, z, g, beta, padded = self._delta_inputs(p, hs, tail)
            with jax.named_scope("gdn.chunk"):
                o, S = gd.delta_chunk(q, k, v, g, beta, S0=S,
                                      chunk=c.chunk_size, t_real=left)
            tail = conv_tail_at(padded, jnp.clip(left, 0, size), K - 1)
            return (tail, S, left - size), hs + self._delta_output(p, o, z)
        n = -(-T // _CHUNK_TOKENS)
        if n == 1:
            (tail, S, _), h = segment((tail, S0, t_real), h)
            return h, tail, S
        size = -(-T // n)
        hs = jnp.moveaxis(jnp.pad(h, ((0, 0), (0, n * size - T), (0, 0))
                                  ).reshape(B, n, size, -1), 1, 0)
        (tail, S, _), hs = jax.lax.scan(segment, (tail, S0, t_real), hs)
        return jnp.moveaxis(hs, 0, 1).reshape(B, n * size, -1)[:, :T], tail, S

    def _qkv(self, p, h, positions):
        """The stream ``h`` (B, T, D) -> ``(q (B, T, H, hd), gate (B, T, H
        hd), k, v (B, T, Hkv, hd))``: q and k normed a head and rotated over
        their first ``rotary_dim`` dims."""
        c = self.config
        u = self._norm(p["ln1"], h)
        lead = u.shape[:-1]
        heads = lambda x: x.reshape(lead + (-1, c.head_dim))
        qg = _mm(u, p["q_w"]).reshape(lead + (c.n_head, 2, c.head_dim))
        q = _rms0(qg[..., 0, :], p["q_norm"], c.rms_norm_eps)
        k = _rms0(heads(_mm(u, p["k_w"])), p["k_norm"], c.rms_norm_eps)
        cos, sin = self._rope
        q = apply_rotary_pos_emb(q, cos, sin, positions)
        k = apply_rotary_pos_emb(k, cos, sin, positions)
        return q, qg[..., 1, :].reshape(lead + (-1,)), k, heads(_mm(u, p["v_w"]))

    def _attn_output(self, p, h, out, gate):
        with jax.named_scope("attn.gate"):
            out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                out.dtype)
        return h + _mm(out, p["o_w"]).astype(jnp.float32)

    def _moe(self, pm, h, layer, live=None):
        """Expert layer ``layer`` (of the stacked ``pm``) over the stream ``h``
        (B, T, D): ``(h, counters (7,), experts (B T, k))``; ``live`` (B, T)
        bool leaves pad rows and empty slots out of the counts."""
        c = self.config
        u = _rms0(h, pm["ln2"][layer], c.rms_norm_eps)
        x = u.astype(self.dtype).reshape(-1, u.shape[-1])
        act = dropless.activation(c.hidden_act)
        with jax.named_scope("moe.route"):
            # float32, as published: the tenth and the eleventh of 512
            # softmax scores lie within bfloat16's rounding of each other
            logits = jnp.dot(u.astype(jnp.float32).reshape(x.shape),
                             pm["router_w"][layer].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            experts, weights = dropless.route(
                logits, c.num_experts_per_tok, scoring_func="softmax",
                norm_topk_prob=c.norm_topk_prob)
            counts = dropless.route_counters(
                experts, *c.held, width=c.num_experts,
                live=None if live is None else live.reshape(-1))
        with jax.named_scope("moe.experts"):
            routed = dropless.held_experts(
                x, experts, weights, pm["gate_w"], pm["up_w"], pm["down_w"],
                c.held[0], layer=layer, act=c.hidden_act,
                width=c.num_experts)
        with jax.named_scope("moe.shared"):
            shared = _mm(act(_mm(x, pm["shared_gate_w"][layer]))
                         * _mm(x, pm["shared_up_w"][layer]),
                         pm["shared_down_w"][layer])
            shared = shared.astype(jnp.float32) * shared_expert_gate(
                x, pm["shared_gate"][layer])[:, None]
        y = (routed.astype(jnp.float32) + shared).reshape(h.shape)
        return h + y, counts, experts

    def _layers(self, params, h, carry, delta_fn, attn_fn, live=None,
                with_routes=False):
        """The float32 stream ``h`` (B, T, D) through every layer, unrolled (a
        layer's kind is static).  ``delta_fn(p, h, m, carry)`` and
        ``attn_fn(p, h, a, carry)`` run a mixer with its residual and return
        ``(h, carry)``; the expert layer of a long prompt runs in chunks of
        tokens (``Afmoe._over_tokens``: it lays out ``num_experts_per_tok``
        rows a token).  Returns ``(h, carry, counters (7,) summed over the
        layers, routes (layers, B T, k) or None)``; ``with_routes`` is for a
        stream that is not cut into chunks (a decode step)."""
        B, T = h.shape[:2]
        live = jnp.broadcast_to(jnp.ones((), bool) if live is None else live,
                                (B, T))
        counts = jnp.zeros((len(dropless.COUNTERS),), jnp.int32)
        routes = []
        for l, (kind, i) in enumerate(self.layers):
            stack, fn = (("delta", delta_fn) if kind == DELTA
                         else ("attn", attn_fn))
            h, carry = fn(_take(params[stack], i), h, i, carry)
            if with_routes:
                h, n, experts = self._moe(params["moe"], h, l, live=live)
                routes.append(experts)
            else:
                h, n = Afmoe._over_tokens(
                    lambda hc, lc: self._moe(params["moe"], hc, l,
                                             live=lc)[:2], h, live)
            counts = counts + n
        return h, carry, counts, (jnp.stack(routes) if routes else None)

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["wte"][tokens - self.config.vocab_rows[0]].astype(
                jnp.float32)

    def _head(self, params, h):
        with jax.named_scope("lm_head"):
            h = _rms0(h, params["lnf"], self.config.rms_norm_eps)
            return jnp.einsum("...d,vd->...v", h.astype(self.dtype),
                              params["head"].astype(self.dtype),
                              preferred_element_type=jnp.float32)

    def _attend_prompt(self, p, h, positions):
        """A prompt's attention mixer: ``(h with the residual, k, v)``."""
        with jax.named_scope("attention"):
            q, gate, k, v = self._qkv(p, h, positions)
            out = causal_prompt_attention(q, k, v)
            return self._attn_output(p, h, out, gate), k, v

    # --------------------------------------------------------------- forward
    def apply(self, params, tokens, rng=None, deterministic=True,
              return_hidden=False):
        """``tokens`` (B, T) -> logits (B, T, Vh) float32 (no dropout in the
        family).  Differentiable: the chunked rule is ``jax.numpy``."""
        positions = jnp.arange(tokens.shape[1])[None]

        def delta_fn(p, h, m, carry):
            return self._delta(p, h)[0], carry

        def attn_fn(p, h, a, carry):
            return self._attend_prompt(p, h, positions)[0], carry

        h, _, _, _ = self._layers(params, self._embed(params, tokens), (),
                                  delta_fn, attn_fn)
        if return_hidden:
            return _rms0(h, params["lnf"], self.config.rms_norm_eps)
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
    def _recurrent_rows(self, rows, dtype):
        """Per row and DeltaNet layer the convolution's tail and the
        delta-rule state."""
        c = self.config
        Ld = c.count(DELTA)
        return {"conv": jnp.zeros((Ld, rows, c.linear_conv_kernel_dim - 1,
                                   c.conv_dim), dtype),
                "delta": jnp.zeros((Ld, rows, c.linear_num_value_heads,
                                    c.linear_key_head_dim,
                                    c.linear_value_head_dim), jnp.float32)}

    def init_cache(self, batch_size: int, max_len: Optional[int] = None,
                   dtype=None):
        """``InferenceEngine.generate``'s cache: dense K/V for the attention
        layers, the convolution tail and the state for the DeltaNet layers,
        and the write index."""
        c = self.config
        dtype = dtype or self.dtype
        kv = (c.count(ATTENTION), batch_size, max_len or c.max_seq,
              c.n_kv_head, c.head_dim)
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
                **self._recurrent_rows(batch_size, dtype),
                "index": jnp.zeros((), jnp.int32)}

    def apply_with_cache(self, params, tokens, cache):
        """Forward ``tokens`` (B, T) from ``cache['index']``; returns
        ``(logits (B, T, Vh), new_cache)``: prefill (T = prompt) and decode
        (T = 1) alike."""
        T = tokens.shape[1]
        index = cache["index"]
        S = cache["k"].shape[2]
        positions = (index + jnp.arange(T))[None]
        valid = (jnp.arange(S)[None, :] <= positions[0][:, None])

        def delta_fn(p, h, m, carry):
            k, v, conv, delta = carry
            h, tail, state = self._delta(p, h, tail=conv[m], S0=delta[m])
            return h, (k, v, conv.at[m].set(tail.astype(conv.dtype)),
                       delta.at[m].set(state))

        def attn_fn(p, h, a, carry):
            with jax.named_scope("attention"):
                k, v, conv, delta = carry
                q, gate, kn, vn = self._qkv(p, h, positions)
                k = jax.lax.dynamic_update_slice(
                    k, kn[None].astype(k.dtype), (a, 0, index, 0, 0))
                v = jax.lax.dynamic_update_slice(
                    v, vn[None].astype(v.dtype), (a, 0, index, 0, 0))
                out = grouped_attention(q, k[a], v[a], valid)
                return self._attn_output(p, h, out, gate), (k, v, conv, delta)

        h, (k, v, conv, delta), _, _ = self._layers(
            params, self._embed(params, tokens),
            (cache["k"], cache["v"], cache["conv"], cache["delta"]),
            delta_fn, attn_fn)
        return self._head(params, h), {"k": k, "v": v, "conv": conv,
                                       "delta": delta, "index": index + T}

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
        paged ``{k, v}`` pool over the attention layers, per slot the DeltaNet
        layers' convolution tails and ``delta (Ld, slots, Hv, dk, dv)``
        float32, and ``counters``."""
        from ..inference import paged_kv as pk
        c = self.config
        dtype = dtype or self.dtype
        pool = pk.init_pool(c.count(ATTENTION), num_blocks, block_size,
                            c.n_head, c.head_dim, dtype, kv_bits=kv_bits,
                            quant_block=quant_block, n_kv_head=c.n_kv_head)
        return dict(pool, **self._recurrent_rows(batch_slots, dtype),
                    counters=jnp.zeros((len(self.step_counters),), jnp.int32))

    @staticmethod
    def recurrent_state_bytes(pool) -> int:
        return int(pool["conv"].nbytes) + int(pool["delta"].nbytes)

    def state_step_bytes(self) -> int:
        """What a decode step's state update must move for ONE live slot:
        every DeltaNet layer's state read and written."""
        c = self.config
        return c.count(DELTA) * 2 * c.state_bytes_per_layer

    def prefill_attrs(self, prompt_len: int) -> dict:
        """What the chunked rule walks for a prompt, for the prefill's span."""
        return {"delta_tokens": prompt_len,
                "delta_chunks": -(-prompt_len // self.config.chunk_size)}

    def serving_stats(self, pool):
        """What ``ServingEngine.stats()`` reports beside its own."""
        c = self.config
        return {"experts_held": c.held[1], "experts_total": c.num_experts,
                "delta_layers": c.count(DELTA),
                "attention_layers": c.count(ATTENTION),
                "state_bytes_per_stream": self.recurrent_state_bytes(pool)
                // pool["delta"].shape[1]}

    def prefill_paged(self, params, toks, pool, blocks, slot, t_real):
        """One prompt, padded to its bucket, into the pool: the attention
        layers' K/V into ``blocks``, and slot ``slot``'s recurrent rows written
        WHOLE with the state after token ``t_real - 1`` (the pad after it must
        not enter the rule; it is routed like any token and left out of the
        counters).  ``toks``: (1, T); returns ``(logits (1, Vh) at token
        t_real - 1, pool)``."""
        from ..inference import paged_kv as pk
        T = toks.shape[1]
        bucket = blocks.shape[0] * pool["k"].shape[2]
        positions = jnp.arange(T)[None]

        def delta_fn(p, h, m, carry):
            pool, ks, vs = carry
            h, tail, state = self._delta(p, h, t_real=t_real)
            with jax.named_scope("ssm.seat"):
                pool = dict(
                    pool,
                    conv=pool["conv"].at[m, slot].set(
                        tail[0].astype(pool["conv"].dtype)),
                    delta=pool["delta"].at[m, slot].set(state[0]))
            return h, (pool, ks, vs)

        def attn_fn(p, h, a, carry):
            pool, ks, vs = carry
            h, k, v = self._attend_prompt(p, h, positions)
            return h, (pool, ks + (k[0],), vs + (v[0],))

        h, (pool, ks, vs), counts, _ = self._layers(
            params, self._embed(params, toks), (pool, (), ()), delta_fn,
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
        experts each slot's token was routed to, (layers, B, k)."""
        from ..inference import paged_kv as pk
        from ..ops.transformer.paged_attention import paged_attention
        c = self.config
        assert toks.ndim == 1, \
            "a recurrent state has no multi-token window to roll back"
        impl = self.paged_attention_impl()
        active = block_tables[:, 0] != pk.SCRATCH_BLOCK
        positions = lengths[:, None]

        def delta_fn(p, h, m, pool):
            tail = pool["conv"][m]
            q, k, v, z, g, beta, padded = self._delta_inputs(p, h, tail)
            with jax.named_scope("ssm.step"):
                o, delta = gd.delta_step(pool["delta"], m, q[:, 0], k[:, 0],
                                         v[:, 0], g[:, 0], beta[:, 0],
                                         active=active)
                tail = jnp.where(active[:, None, None], padded[:, 1:], tail)
                pool = dict(pool, conv=pool["conv"].at[m].set(tail),
                            delta=delta)
            return h + self._delta_output(p, o[:, None], z), pool

        def attn_fn(p, h, a, pool):
            with jax.named_scope("attention"):
                q, gate, k, v = self._qkv(p, h, positions)  # (B, 1, H | Hkv, hd)
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
                return self._attn_output(p, h, out, gate), pool

        h, pool, counts, routes = self._layers(
            params, self._embed(params, toks)[:, None], pool, delta_fn,
            attn_fn, live=active[:, None], with_routes=True)
        out = self._head(params, h[:, 0]), dict(pool, counters=counts)
        return out + (routes,) if with_routes else out
