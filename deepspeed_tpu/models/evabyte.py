"""EvaByte: a byte-level decoder whose attention folds its own cache.

No reference counterpart (the reference framework ships no such model).  The
forward (HF ``EvaByte/EvaByte``, ``attention_class: eva``; EVA,
arXiv:2302.04542, in the learned deterministic form), for a head of size
``hd``, byte position ``t``, window ``W(t) = t // window_size`` and chunk
``c = t // chunk_size``::

    x = RMS0(h)                       x / sqrt(mean x^2 + eps) * (1 + w)
    q_t, k_t = rope_t(x Wq), rope_t(x Wk)         v_t = x Wv
    for every chunk c of a FULL window (:func:`eva_summarise`, the fold):
        k~_c = mean_{m in c} k_m + mu             mu, phi: (H, hd) a layer
        b^_c = sum_{m in c} softmax_{m in c}(k_m . phi) v_m
    o_t = ONE softmax over {k_m : W(m) = W(t), m <= t} and
          {k~_c : c's window < W(t)}, weighting v_m and b^_c
    h = h + o Wo ;  h = h + SwiGLU(RMS0(h))
    logits = RMS0(h) head^T viewed (num_pred_heads, V): head j reads byte
    t + 1 + j

The residual stream and the logits are float32 whatever the model dtype; the
matmuls, the K/V rows and the weights are in the model dtype, the softmax in
float32 over model-dtype products.  The keys are rotated BEFORE they are
pooled: the cache holds rotated keys.

**The cache** (docs/serving.md#folded-cache).  A stream keeps the exact K/V
rows of its current window; at a window's end they are folded
``chunk_size`` to 1 into summary rows of the SAME shape (``k~`` where a key
goes, ``b^`` where a value goes) and given back.  Both kinds live in ONE
paged pool (``paged_kv.init_pool``): a slot's table is ``[summary blocks |
window blocks]`` (``paged_kv.WindowFold``), so decode attention is
``ops/transformer/paged_attention.py``'s kernel over that table, unchanged,
at table length ``row(t) + 1``.  A prompt is prefilled a window at a time
(:meth:`EvaByte.prefill_paged`): a full window reads the summaries of the
windows before it from the pool and leaves its own there (its exact rows
never touch the pool); only the tail's rows are written.

The 8 prediction heads exist for multi-byte speculative decoding, which the
serving layer has no step for (ROADMAP M8): ``apply`` returns all
``num_pred_heads * V`` logits, the served step computes and samples head 0's.

``EvaByteConfig`` keeps the PUBLISHED key names.  Parameter tree::

    wte (V, D)                       embedding
    blocks.* (L, ...)                the L layers, stacked (q_w, k_w, o_w
                                     (L, out, in) as published; the other
                                     matrices (L, in, out)); phi, mu (L, H, hd)
    lnf (D,)
    head (num_pred_heads * V, D)     row j * V + v: head j, byte v
"""

import dataclasses
import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from .gpt2 import GPT2, layer_slice as _take
from .jamba import _mm, swiglu
from .ouro import _mmt
from .rotary import apply_rotary_pos_emb, rotary_freqs


@dataclasses.dataclass
class EvaByteConfig:
    vocab_size: int = 320
    hidden_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    intermediate_size: int = 11008
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e5
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 32768
    chunk_size: int = 16
    window_size: int = 2048
    num_pred_heads: int = 8
    norm_add_unit_offset: bool = True

    # ---- the names the serving layer and the analysis tools ask for
    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def n_layer(self):
        return self.num_hidden_layers

    @property
    def kv_layers(self):
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


PRESETS = {
    # tests and CPU examples: 4 tokens a chunk, 4 chunks a window, so that a
    # stream of 64 tokens folds three times; the benchmark's family file
    # passes the published keys of the real checkpoint as overrides
    "evabyte-tiny": dict(vocab_size=320, hidden_size=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         num_key_value_heads=4, intermediate_size=256,
                         chunk_size=4, window_size=16, num_pred_heads=8,
                         max_position_embeddings=128),
}

# queries a block of the prompt's attention: the scores of one block against
# the summaries and the window's keys up to its own end are the largest
# array a prefill holds (heads x 512 x 3,968 float32, 260 MB at the
# published size)
_QUERY_BLOCK = 512


def _rms0(x, w, eps):
    """RMSNorm with a unit offset (``norm_add_unit_offset``): the stored
    weight is the departure from 1.  float32 inside, ``x``'s dtype out."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def eva_summarise(k, v, phi, mu, chunk: int):
    """THE FOLD: the rotated keys and the values of whole chunks, ``(..., T,
    H, hd)`` with ``T`` a multiple of ``chunk``, into one summary row a chunk,
    ``(..., T // chunk, H, hd)`` twice: ``k~`` (the chunk's mean key plus
    ``mu``) and ``b^`` (its values under a softmax of ``k . phi`` over the
    chunk; no scale inside).  ``phi``, ``mu``: (H, hd).  float32 inside over
    the inputs as they are (the cache's rows), the inputs' dtype out."""
    with jax.named_scope("eva.summarise"):
        lead, (T, H, hd) = k.shape[:-3], k.shape[-3:]
        f32 = jnp.float32
        kc = k.reshape(lead + (T // chunk, chunk, H, hd)).astype(f32)
        vc = v.reshape(lead + (T // chunk, chunk, H, hd)).astype(f32)
        exact = jax.lax.Precision.HIGHEST     # 16 terms a sum: no cost
        pooled = jnp.mean(kc, axis=-3) + mu.astype(f32)
        a = jax.nn.softmax(jnp.einsum(
            "...chd,hd->...ch", kc, phi.astype(f32), precision=exact),
            axis=-2)
        return (pooled.astype(k.dtype),
                jnp.einsum("...ch,...chd->...hd", a, vc,
                           precision=exact).astype(v.dtype))


def _softmax_over_two(s_sum, v_sum, s_own, v_own, dtype):
    """ONE softmax over two kinds of key: scores (B, H, T, S) float32 with
    the dead keys already at the float32 minimum, values (B, S, H, hd).
    Returns (B, T, H * hd) in ``dtype``."""
    s = jnp.concatenate([s_sum, s_own], axis=-1)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    n = s_sum.shape[-1]
    out = (jnp.einsum("bhts,bshd->bthd", p[..., :n], v_sum)
           + jnp.einsum("bhts,bshd->bthd", p[..., n:], v_own))
    return out.reshape(out.shape[:2] + (-1,))


def _summary_widths(S):
    """The widths of the summary table a prompt's attention is compiled for:
    halvings of the whole table down to an eighth, and none.  Most prompts
    have folded a few windows and read a few hundred of its 1,920 rows."""
    return sorted({0, S // 8, S // 4, S // 2, S})


def eva_prompt_attention(q, k, v, k_sum, v_sum, n_sum, scale):
    """A window's (or a tail's) queries over the summaries of the windows
    before it and its own keys: ``q``, ``k``, ``v`` (B, T, H, hd), causal
    among themselves; ``k_sum``, ``v_sum`` (B, S, H, hd) of which the first
    ``n_sum`` (traced) rows are live.  One normaliser; float32 softmax over
    input-dtype products.  A block of ``_QUERY_BLOCK`` queries at a time,
    each against the keys up to its own end, and against the narrowest of
    ``_summary_widths`` that holds the live summaries (one branch a width:
    the scores against dead summary rows are most of a short prompt's).
    Returns (B, T, H * hd)."""
    T = q.shape[1]
    low = jnp.finfo(jnp.float32).min

    def over(width, q, k, v, k_sum, v_sum, n_sum):
        k_sum, v_sum = k_sum[:, :width], v_sum[:, :width]
        live = (jnp.arange(width) < n_sum)[None, None, None, :]
        outs = []
        for t0 in range(0, T, _QUERY_BLOCK):
            t1 = min(T, t0 + _QUERY_BLOCK)
            qb = q[:, t0:t1]
            s_sum = jnp.einsum("bthd,bshd->bhts", qb, k_sum).astype(
                jnp.float32) * scale
            s_own = jnp.einsum("bthd,bshd->bhts", qb, k[:, :t1]).astype(
                jnp.float32) * scale
            causal = (jnp.arange(t1)[None, :]
                      <= jnp.arange(t0, t1)[:, None])[None, None]
            outs.append(_softmax_over_two(
                jnp.where(live, s_sum, low), v_sum,
                jnp.where(causal, s_own, low), v[:, :t1], q.dtype))
        return jnp.concatenate(outs, axis=1)

    with jax.named_scope("eva.prompt_attention"):
        widths = _summary_widths(k_sum.shape[1])
        return jax.lax.switch(
            jnp.searchsorted(jnp.asarray(widths), n_sum),
            [functools.partial(over, w) for w in widths],
            q, k, v, k_sum, v_sum, n_sum)


def eva_dense_attention(q, k, v, phi, mu, q_pos, window, chunk, scale):
    """EVA attention with no cache to fold: ``q`` (B, T, H, hd) at positions
    ``q_pos`` (T,) over the rotated keys and values of positions ``0..S-1``,
    (B, S, H, hd).  A query reads the exact rows of its own window up to
    itself and the summaries (made here, from the exact rows) of every
    window before its own; rows past the newest query are dead either way.
    ``apply`` and the contiguous cache's path; O(T S) scores."""
    S = k.shape[1]
    full = (S // window) * window
    low = jnp.finfo(jnp.float32).min
    k_pos = jnp.arange(S)
    own = ((k_pos[None, :] // window == q_pos[:, None] // window)
           & (k_pos[None, :] <= q_pos[:, None]))[None, None]
    s_own = jnp.where(own, jnp.einsum("bthd,bshd->bhts", q, k).astype(
        jnp.float32) * scale, low)
    k_sum, v_sum = eva_summarise(k[:, :full], v[:, :full], phi, mu, chunk)
    c_win = jnp.arange(full // chunk) // (window // chunk)
    before = (c_win[None, :] < q_pos[:, None] // window)[None, None]
    s_sum = jnp.where(before, jnp.einsum("bthd,bshd->bhts", q, k_sum).astype(
        jnp.float32) * scale, low)
    return _softmax_over_two(s_sum, v_sum, s_own, v, q.dtype)


def eva_decode_attention(q, pool, tables, lengths, layer, spec):
    """One query a slot, (B, 1, H, hd) at stream positions ``lengths``, over
    the slot's table ``[summary blocks | window blocks]`` up to its newest
    row (``spec.row(lengths)``, written already): ONE softmax over both kinds
    of row, which is what ``paged_attention`` computes over any table.
    Returns (B, 1, H * hd)."""
    from ..ops.transformer.paged_attention import paged_attention
    with jax.named_scope("eva.decode_attention"):
        return paged_attention(q, pool, tables, spec.row(lengths), layer)


class EvaByte:
    """Byte-level decoder LM with EVA attention (params: dict pytree, the
    layers stacked)."""

    supports_paged_decode = True
    # a stream's table is rewritten in mid-life and its summary blocks are
    # no function of 64 token ids: the serving layer refuses what assumes a
    # stream is its growing table's K/V blocks (inference/serving.py)
    has_folded_cache = True

    def __init__(self, config: Optional[EvaByteConfig] = None,
                 preset: str = None, dtype=jnp.bfloat16, **overrides):
        if config is None:
            base = dict(PRESETS[preset or "evabyte-tiny"])
            base.update(overrides)
            config = EvaByteConfig(**base)
        if config.rope_scaling is not None:
            raise ValueError(
                f"rope_scaling = {config.rope_scaling!r}: models/evabyte.py "
                "computes plain rotary positions; only null is run")
        if not config.norm_add_unit_offset:
            raise ValueError("norm_add_unit_offset = False: models/evabyte.py "
                             "computes the unit-offset RMSNorm alone")
        if config.num_key_value_heads != config.num_attention_heads:
            raise ValueError(
                "models/evabyte.py pools one K/V head a query head "
                f"({config.num_attention_heads} and "
                f"{config.num_key_value_heads} given)")
        assert config.window_size % config.chunk_size == 0
        assert config.hidden_size % config.num_attention_heads == 0
        self.config = config
        self.dtype = dtype
        self._rope = rotary_freqs(config.head_dim, config.max_seq,
                                  base=config.rope_theta)
        self._scale = 1.0 / np.sqrt(config.head_dim)

    # ------------------------------------------------------------------ init
    def init(self, rng):
        """Matrices normal(0.02), the output projections (``o_w``,
        ``down_w``) scaled by 1/sqrt(2L) as the other families; ``k_w``
        normal(D^-1/2), so that a key has unit scale; ``phi`` and ``mu``
        normal(1) clipped to +-1 (the public initialiser): against unit keys
        ``k . phi`` then has a deviation of about 8 and a chunk's weights
        ``a`` are far from 1 / chunk_size, so random weights exercise the
        fold (a mean-pooled value does NOT pass for it); norm weights 0 (the
        unit offset makes them 1)."""
        c = self.config
        D, V, F, L = (c.hidden_size, c.vocab_size, c.intermediate_size,
                      c.num_hidden_layers)
        H, hd = c.n_head, c.head_dim
        k = jax.random.split(rng, 11)
        std, proj = 0.02, 0.02 / np.sqrt(2.0 * L)
        f32 = jnp.float32
        n = lambda key, shape, s=std: jax.random.normal(key, shape, f32) * s
        clipped = lambda key: jnp.clip(
            jax.random.normal(key, (L, H, hd), f32), -1.0, 1.0)
        zeros = lambda: jnp.zeros((L, D), f32)
        return {
            "wte": n(k[0], (V, D)),
            "blocks": {
                "ln_in": zeros(),
                "q_w": n(k[1], (L, H * hd, D)),
                "k_w": n(k[2], (L, H * hd, D), D ** -0.5),
                "v_w": n(k[3], (L, D, H * hd)),
                "o_w": n(k[4], (L, H * hd, D), proj),
                "phi": clipped(k[5]),
                "mu": clipped(k[6]),
                "ln_ff": zeros(),
                "gate_w": n(k[7], (L, D, F)),
                "up_w": n(k[8], (L, D, F)),
                "down_w": n(k[9], (L, F, D), proj),
            },
            "lnf": jnp.zeros((D,), f32),
            "head": n(k[10], (c.num_pred_heads * V, D)),
        }

    def num_params(self):
        c = self.config
        D = c.hidden_size
        layer = (4 * D * D + 3 * D * c.intermediate_size + 2 * D
                 + 2 * c.n_head * c.head_dim)
        return (c.num_hidden_layers * layer
                + (1 + c.num_pred_heads) * c.vocab_size * D + D)

    def cache_fold(self, block_size: int):
        """The cache's holding as a function of a stream's length, for the
        serving layer's tables and admission sum."""
        from ..inference.paged_kv import WindowFold
        return WindowFold(self.config.window_size, self.config.chunk_size,
                          block_size)

    # ---------------------------------------------------------------- pieces
    def _qkv(self, p, h, positions):
        """The rotated queries and keys and the values of ``h`` (B, T, D),
        float32, at ``positions`` ((T,) or (B, T)): (B, T, H, hd) each, in
        the model dtype."""
        c = self.config
        u = _rms0(h, p["ln_in"], c.rms_norm_eps).astype(self.dtype)
        split = u.shape[:-1] + (c.n_head, c.head_dim)
        cos, sin = self._rope
        q = apply_rotary_pos_emb(_mmt(u, p["q_w"]).reshape(split), cos, sin,
                                 positions)
        k = apply_rotary_pos_emb(_mmt(u, p["k_w"]).reshape(split), cos, sin,
                                 positions)
        return q, k, _mm(u, p["v_w"]).reshape(split)

    def _layer(self, p, h, positions, attend):
        """One layer over the float32 residual stream ``h`` (B, T, D).
        ``attend(q, k, v)`` returns ``((B, T, H * hd), carry)``."""
        with jax.named_scope("attention"):
            out, carry = attend(*self._qkv(p, h, positions))
            h = h + _mm(out, p["o_w"]).astype(jnp.float32)
        with jax.named_scope("mlp"):
            u = _rms0(h, p["ln_ff"], self.config.rms_norm_eps).astype(
                self.dtype)
            h = h + swiglu(p, u).astype(jnp.float32)
        return h, carry

    def _layers(self, params, h, carry, positions, attn_fn, sliced=False):
        """``h`` through the L layers; ``attn_fn(q, k, v, l, p, carry)``
        attends for layer ``l`` with its slice ``p`` of the stacked weights
        and returns ``(out, carry)``.  The stack is indexed in place
        (serving) or, ``sliced``, scanned over (training)."""
        blocks = params["blocks"]

        def layer(p, l, hc):
            return self._layer(
                p, hc[0], positions,
                lambda q, k, v: attn_fn(q, k, v, l, p, hc[1]))

        L = self.config.num_hidden_layers
        if sliced:
            hc, _ = jax.lax.scan(
                lambda hc, xs: (layer(xs[0], xs[1], hc), None),
                (h, carry), (blocks, jnp.arange(L)))
            return hc
        return jax.lax.fori_loop(
            0, L, lambda l, hc: layer(_take(blocks, l), l, hc), (h, carry))

    @staticmethod
    def _embed(params, tokens):
        with jax.named_scope("embed"):
            return params["wte"][tokens].astype(jnp.float32)

    def _head(self, params, h, heads: Optional[int] = None):
        """float32 logits of the first ``heads`` prediction heads (all of
        them by default): (..., heads * V)."""
        c = self.config
        with jax.named_scope("lm_head"):
            w = params["head"]
            if heads is not None:
                w = w[:heads * c.vocab_size]
            u = _rms0(h, params["lnf"], c.rms_norm_eps).astype(self.dtype)
            return jnp.einsum("...d,vd->...v", u, w.astype(self.dtype),
                              preferred_element_type=jnp.float32)

    # --------------------------------------------------------------- forward
    def apply(self, params, tokens, rng=None, deterministic=True,
              return_hidden=False):
        """``tokens`` (B, T) -> logits (B, T, num_pred_heads * V) float32:
        head ``j``'s ``V`` logits at ``[..., j * V:(j + 1) * V]``."""
        c = self.config
        T = tokens.shape[1]
        pos = jnp.arange(T)

        def attn_fn(q, k, v, l, p, carry):
            return eva_dense_attention(q, k, v, p["phi"], p["mu"], pos,
                                       c.window_size, c.chunk_size,
                                       self._scale), carry

        h, _ = self._layers(params, self._embed(params, tokens), (), pos,
                            attn_fn, sliced=True)
        return h if return_hidden else self._head(params, h)

    def loss(self, params, batch, rng=None):
        """Next-byte LM loss on head 0's logits; ``batch`` as ``GPT2.loss``
        takes it.  (The other heads' losses, bytes t + 2 .. t + 8, belong to
        the multi-byte training the public model had; not here.)"""
        tokens, labels = GPT2._split_batch(batch)
        logits = self._head(params, self.apply(params, tokens,
                                               return_hidden=True), heads=1)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.mean(lse - picked)

    # ---------------------------------------------------- contiguous decoding
    def init_cache(self, batch_size: int, max_len: Optional[int] = None,
                   dtype=None):
        """``InferenceEngine.generate``'s cache: the EXACT rotated keys and
        values of every position (this path folds nothing away: the
        summaries are made again from the exact rows at every call; the
        served cache is the paged one below), and the write index."""
        c = self.config
        kv = (c.num_hidden_layers, batch_size, max_len or c.max_seq,
              c.n_head, c.head_dim)
        dtype = dtype or self.dtype
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
                "index": jnp.zeros((), jnp.int32)}

    def apply_with_cache(self, params, tokens, cache):
        """Forward ``tokens`` (B, T) from ``cache['index']``; returns
        ``(head 0's logits (B, T, V), new_cache)``."""
        c = self.config
        index = cache["index"]
        pos = index + jnp.arange(tokens.shape[1])

        def attn_fn(q, kn, vn, l, p, kv):
            k = jax.lax.dynamic_update_slice(
                kv[0], kn[None].astype(kv[0].dtype), (l, 0, index, 0, 0))
            v = jax.lax.dynamic_update_slice(
                kv[1], vn[None].astype(kv[1].dtype), (l, 0, index, 0, 0))
            return eva_dense_attention(q, k[l], v[l], p["phi"], p["mu"], pos,
                                       c.window_size, c.chunk_size,
                                       self._scale), (k, v)

        h, (k, v) = self._layers(params, self._embed(params, tokens),
                                 (cache["k"], cache["v"]), pos, attn_fn)
        return self._head(params, h, heads=1), {
            "k": k, "v": v, "index": index + tokens.shape[1]}

    # ------------------------------------------------------- paged serving
    def paged_attention_impl(self) -> str:
        """The one form decode attention has here: the paged kernel."""
        return "kernel"

    def init_serving_state(self, batch_slots, num_blocks, block_size,
                           kv_bits=16, quant_block=64, dtype=None):
        """The paged ``{k, v}`` pool: exact rows and summary rows alike
        (``cache_fold`` refuses a block size the fold does not divide)."""
        from ..inference import paged_kv as pk
        c = self.config
        self.cache_fold(block_size)
        return pk.init_pool(c.num_hidden_layers, num_blocks, block_size,
                            c.n_head, c.head_dim, dtype or self.dtype,
                            kv_bits=kv_bits, quant_block=quant_block)

    def serving_stats(self, pool):
        c = self.config
        return {"cache_fold": {"window": c.window_size,
                               "chunk": c.chunk_size,
                               "summaries_per_window":
                               c.window_size // c.chunk_size}}

    def summary_table_blocks(self, block_size: int) -> int:
        """Entries of the summary table a prefill segment is handed: the
        summary blocks of every window a stream of ``max_seq`` can have
        folded before its last."""
        fold = self.cache_fold(block_size)
        return max(1, fold.summary_blocks
                   * ((self.config.max_seq - 1) // fold.window))

    def prefill_paged(self, params, toks, pool, blocks, start, t_real,
                      fold=False):
        """ONE SEGMENT of a prompt through the pool: a whole window
        (``fold``) or the tail after the last whole window, at positions
        ``start .. start + T - 1`` (``start``, traced, a multiple of the
        window; the fifth operand, where a family with a state a slot takes
        its slot).  ``toks`` (1, T), the tail padded to whole blocks;
        ``blocks``: the stream's summary table (:meth:`summary_table_blocks`
        entries, scratch past the ``start // window`` windows folded so far)
        followed by the segment's own blocks: where a whole window leaves its
        summaries (its exact rows never touch the pool), or where the tail's
        rows go.  The only state a segment hands the next is the summary
        blocks it wrote.  Returns ``(head 0's logits (1, V) at the
        segment's token t_real - 1, pool)``."""
        from ..inference import paged_kv as pk
        c = self.config
        T = toks.shape[1]
        bs = pool["k"].shape[2]
        spec = self.cache_fold(bs)
        n_table = blocks.shape[0] - (spec.summary_blocks if fold
                                     else T // bs)
        table, own = blocks[:n_table], blocks[n_table:]
        n_sum = (start // spec.window) * spec.summaries
        assert not fold or T == spec.window, (T, spec.window)
        h = self._embed(params, toks)

        def attn_fn(q, k, v, l, p, pool):
            k, v = k.astype(pool["k"].dtype), v.astype(pool["v"].dtype)
            k_sum, v_sum = pk.gather_kv(pool, l, table[None], self.dtype,
                                        c.n_head)
            out = eva_prompt_attention(q, k, v, k_sum, v_sum, n_sum,
                                       self._scale)
            if fold:
                k, v = eva_summarise(k, v, p["phi"], p["mu"], c.chunk_size)
            with jax.named_scope("kv.seat"):
                pool = pk.write_prefill(pool, own, k[0], v[0], layer=l)
            return out, pool

        h, pool = self._layers(params, h, pool, start + jnp.arange(T),
                               attn_fn)
        row = jax.lax.dynamic_slice_in_dim(h[0], t_real - 1, 1, axis=0)
        return self._head(params, row, heads=1), pool

    def fold_paged(self, params, pool, src, dst):
        """The fold of a window that ended in DECODING: the exact rows in
        the ``src`` blocks (a whole window's, in order) into summary rows in
        the ``dst`` blocks, a layer at a time (all layers at once, the
        compiler copies half the pool).  The caller gives ``src`` back."""
        from ..inference import paged_kv as pk
        c = self.config
        blocks = params["blocks"]

        def layer(l, pool):
            rows = lambda x: x[l, src].reshape(
                (c.window_size, c.n_head, c.head_dim))
            k_sum, v_sum = eva_summarise(
                rows(pool["k"]), rows(pool["v"]), blocks["phi"][l],
                blocks["mu"][l], c.chunk_size)
            with jax.named_scope("kv.seat"):
                return pk.write_prefill(pool, dst, k_sum, v_sum, layer=l)
        return jax.lax.fori_loop(0, c.num_hidden_layers, layer, pool)

    def decode_step_paged(self, params, toks, pool, block_tables, lengths):
        """One decode step for every slot: ``toks`` (B,) at stream positions
        ``lengths``; a slot's newest row goes to table row
        ``fold.row(lengths)`` and ONE paged attention reads the summary rows
        and the window's rows up to it.  Returns ``(head 0's logits (B, V)
        float32, pool)``."""
        from ..inference import paged_kv as pk
        c = self.config
        assert toks.ndim == 1, "one token a slot (ROADMAP M8)"
        assert not pk.is_quantized_pool(pool), \
            "summary rows are kept at 16 bits"
        spec = self.cache_fold(pool["k"].shape[2])
        positions = jnp.minimum(lengths, c.max_seq - 1)[:, None]
        rows = spec.row(lengths)
        h = self._embed(params, toks[:, None])                  # (B, 1, D)

        def attn_fn(q, k, v, l, p, pool):
            with jax.named_scope("kv.seat"):
                pool = pk.write_tokens(pool, l, block_tables, rows, k, v)
            return eva_decode_attention(q, pool, block_tables, lengths, l,
                                        spec), pool

        h, pool = self._layers(params, h, pool, positions, attn_fn)
        return self._head(params, h[:, 0], heads=1), pool
