"""Multi-head latent attention (MLA): the ONE copy two families call
(``models/deepseek_v2.py``, ``models/longcat_flash.py``).

One sub-layer's arithmetic over ITS leaves ``p`` (``q_a_w`` (D, Rq),
``q_norm``, ``q_nope_w`` (H n, Rq), ``q_pe_w`` (H r, Rq), ``kv_a_w`` (D, C +
r), ``kv_norm``, ``k_up_w`` (H, C, n), ``v_up_w`` (H, v, C)), with ``H``
heads, ``n`` / ``r`` the query dims without and with rope, ``C`` the latent
rank::

    c_q = RMS(a W_qa; q_norm) * q_scale      [q_nope | q_pe] = c_q W_qb
    [c_kv | k_pe] = a W_kva                  c_kv = RMS(c_kv; kv_norm) * kv_scale
    q_pe, k_pe = rope(q_pe), rope(k_pe)      (the r rope dims; k_pe ONE head, NOT scaled)
    k_nope[h] = c_kv W_UK[h]^T               v[h] = c_kv W_UV[h]
    out = concat_h(softmax((q_nope . k_nope + q_pe . k_pe) * sm_scale + mask) v)

A token caches ``c_kv`` (after its norm and scale) and ``k_pe`` (after rope):
``C + r`` values, one row for all ``H`` heads.  TWO forms of the same
attention: a prompt runs EXPANDED (:meth:`LatentAttention.attend_expanded`:
keys and values rebuilt from the rows, sixteen heads' (T, S) scores at a
time), one decoded token ABSORBED (:meth:`absorb`: ``q_lat[h] = q_nope[h]
W_UK[h]`` meets the cached rows directly, in place through
``ops/transformer/paged_latent_attention.py``; :meth:`unabsorb` applies
``W_UV[h]`` to the (C,)-wide result).  What differs between the families is
an argument: the rope table (YaRN's or plain), the softmax scale (given at
each call: it is the model's), the two scales on the normed latents (1.0:
DeepSeek-V2; ``sqrt(D / Rq)`` and ``sqrt(D / C)``: LongCat's
``mla_scale_q_lora`` / ``mla_scale_kv_lora``).  A scale of 1.0 adds no
operation: DeepSeek-V2's executables are what they were.
"""

import jax
import jax.numpy as jnp

from .jamba import _mm, _rms
from .ouro import _mmt
from .rotary import apply_rotary_pos_emb

_HEAD_BLOCK = 16      # heads whose (T, S) scores stand at once in a prompt

def _scaled(w, scale):
    """A norm's weight times ``scale``, in float32 (``_rms`` multiplies in
    float32 and rounds once); the weight itself where the scale is 1."""
    return w if scale == 1.0 else w.astype(jnp.float32) * scale


class LatentAttention:
    """The sizes and tables one family's MLA sub-layers share."""

    def __init__(self, *, n_head, kv_lora_rank, eps, rope, q_scale=1.0,
                 kv_scale=1.0):
        self.n_head = n_head
        self.kv_lora_rank = kv_lora_rank
        self.eps = eps
        self.rope = rope                  # (cos, sin), any factor folded in
        self.q_scale = float(q_scale)
        self.kv_scale = float(kv_scale)

    def project(self, p, a, positions):
        """The normed input ``a`` (B, T, D) -> ``(q_nope (B, T, H, n), q_pe
        (B, T, H, r) rotated, c_kv (B, T, C) normed and scaled, k_pe (B, T,
        r) rotated)`` in the model dtype: everything either form of the
        attention needs, and the last two are what a token caches."""
        C = self.kv_lora_rank
        cos, sin = self.rope
        c_q = _rms(_mm(a, p["q_a_w"]), _scaled(p["q_norm"], self.q_scale),
                   self.eps)
        heads = lambda x: x.reshape(a.shape[:-1] + (self.n_head, -1))
        kv = _mm(a, p["kv_a_w"])
        c_kv = _rms(kv[..., :C], _scaled(p["kv_norm"], self.kv_scale),
                    self.eps)
        q_pe = apply_rotary_pos_emb(heads(_mmt(c_q, p["q_pe_w"])), cos, sin,
                                    positions)
        k_pe = apply_rotary_pos_emb(kv[..., None, C:], cos, sin,
                                    positions)[..., 0, :]
        return heads(_mmt(c_q, p["q_nope_w"])), q_pe, c_kv, k_pe

    def attend_expanded(self, p, q_nope, q_pe, c_kv, k_pe, valid, sm_scale):
        """Queries (B, T, H, n | r) over the rows ``c_kv`` (B, S, C) and
        ``k_pe`` (B, S, r), keys and values rebuilt from the rows;
        ``valid`` broadcasts to (B, heads, T, S).  ``_HEAD_BLOCK`` heads at a
        time: 128 heads x 2,560^2 float32 scores are 3.4 GB.  Returns (B, T,
        H v)."""
        with jax.named_scope("mla.attend"):
            B, T, H, n = q_nope.shape
            hb = min(H, _HEAD_BLOCK)
            dt = q_nope.dtype
            groups = lambda x: jnp.moveaxis(
                x.reshape(x.shape[:2] + (H // hb, hb, x.shape[-1])), 2, 0)

            def block(xs):
                qn, qp, k_up, v_up = xs
                k = jnp.einsum("bsc,hcn->bshn", c_kv, k_up.astype(dt))
                v = jnp.einsum("bsc,hvc->bshv", c_kv, v_up.astype(dt))
                s = (jnp.einsum("bthn,bshn->bhts", qn, k)
                     + jnp.einsum("bthr,bsr->bhts", qp, k_pe)
                     ).astype(jnp.float32) * sm_scale
                s = jnp.where(valid, s, jnp.finfo(jnp.float32).min)
                w = jax.nn.softmax(s, axis=-1).astype(dt)
                return jnp.einsum("bhts,bshv->bthv", w, v)

            split = lambda w: w.reshape((H // hb, hb) + w.shape[1:])
            out = jax.lax.map(block, (groups(q_nope), groups(q_pe),
                                      split(p["k_up_w"]), split(p["v_up_w"])))
            return jnp.moveaxis(out, 0, 2).reshape(B, T, -1)

    @staticmethod
    def absorb(p, q_nope, q_pe, width):
        """One token's queries (B, H, n | r) as rows of the cache's layout:
        ``[q_nope W_UK | q_pe | 0]`` (B, H, width)."""
        with jax.named_scope("mla.absorb"):
            from ..inference import paged_kv as pk
            q_lat = jnp.einsum("bhn,hcn->bhc", q_nope,
                               p["k_up_w"].astype(q_nope.dtype))
            return pk.latent_rows(q_lat, q_pe, width)

    def attend_absorbed(self, q_rows, rows, valid, sm_scale):
        """``jax.numpy``'s absorbed attention of ``q_rows`` (B, H, width)
        over gathered or dense ``rows`` (B, S, width), ``valid`` (B, S); what
        the latent kernel computes in place.  Returns ``o_lat`` (B, H, C)."""
        s = jnp.einsum("bhw,bsw->bhs", q_rows, rows).astype(jnp.float32)
        s = jnp.where(valid[:, None, :], s * sm_scale,
                      jnp.finfo(jnp.float32).min)
        w = jax.nn.softmax(s, axis=-1).astype(q_rows.dtype)
        return jnp.einsum("bhs,bsc->bhc", w, rows[..., :self.kv_lora_rank])

    @staticmethod
    def unabsorb(p, o_lat):
        """``o_lat`` (B, H, C) -> (B, H v): ``W_UV`` by head."""
        with jax.named_scope("mla.absorb"):
            o = jnp.einsum("bhc,hvc->bhv", o_lat,
                           p["v_up_w"].astype(o_lat.dtype))
            return o.reshape(o.shape[0], -1)

    # ---------------------------------------------- the three cached forms
    def attend_cached(self, p, q_nope, q_pe, c_kv, k_pe, lat, layer, index,
                      sm_scale, dtype):
        """``InferenceEngine.generate``'s dense cache ``lat`` (layers, B, S,
        C + r): the new rows written at ``index`` of ``layer``; a prompt
        (T > 1) attends expanded over the cached rows, one token absorbed.
        ``dtype``: the model's, at the call (an engine may set it after the
        model is built).  Returns ``((B, T, H v), lat)``."""
        C, T, S = self.kv_lora_rank, q_nope.shape[1], lat.shape[2]
        valid = jnp.arange(S)[None, :] <= index + jnp.arange(T)[:, None]
        new = jnp.concatenate([c_kv, k_pe], axis=-1).astype(lat.dtype)
        lat = jax.lax.dynamic_update_slice(lat, new[None],
                                           (layer, 0, index, 0))
        rows = lat[layer].astype(dtype)
        if T > 1:
            return self.attend_expanded(p, q_nope, q_pe, rows[..., :C],
                                        rows[..., C:], valid, sm_scale), lat
        q_rows = self.absorb(p, q_nope[:, 0], q_pe[:, 0], rows.shape[-1])
        o_lat = self.attend_absorbed(
            q_rows, rows, jnp.broadcast_to(valid, (rows.shape[0], S)),
            sm_scale)
        return self.unabsorb(p, o_lat)[:, None], lat

    def attend_prefill(self, p, q_nope, q_pe, c_kv, k_pe, pool, blocks,
                       layer, causal, sm_scale):
        """One prompt (B 1, T tokens from position 0) into the paged latent
        pool: its rows written into ``blocks`` of ``layer`` (padded to the
        bucket), the attention EXPANDED under ``causal`` (T, T), which the
        caller makes once for all its layers.  Returns ``((1, T, H v),
        pool)``."""
        from ..inference import paged_kv as pk
        T = q_nope.shape[1]
        width = pool[pk.LATENT].shape[-1]
        bucket = blocks.shape[0] * pool[pk.LATENT].shape[2]
        with jax.named_scope("kv.seat"):
            rows = jnp.pad(pk.latent_rows(c_kv[0], k_pe[0], width),
                           ((0, bucket - T), (0, 0)))
            pool = pk.write_latent_prefill(pool, blocks, rows, layer)
        return self.attend_expanded(p, q_nope, q_pe, c_kv, k_pe, causal,
                                    sm_scale), pool

    def attend_decode(self, p, q_nope, q_pe, c_kv, k_pe, pool, block_tables,
                      lengths, layer, sm_scale, impl, dtype):
        """One token a slot (T 1) over the paged latent pool, the attention
        ABSORBED: the token's row written into ``layer`` at its position,
        the queries meeting the cached rows in place (``impl`` ``"kernel"``:
        the latent Pallas kernel; else the gathered ``jax.numpy`` oracle).
        Returns ``((B, 1, H v), pool)``."""
        from ..inference import paged_kv as pk
        from ..ops.transformer.paged_latent_attention import (
            paged_latent_attention)
        width = pool[pk.LATENT].shape[-1]
        with jax.named_scope("kv.seat"):
            pool = pk.write_latent_tokens(pool, layer, block_tables, lengths,
                                          pk.latent_rows(c_kv, k_pe, width))
        q_rows = self.absorb(p, q_nope[:, 0], q_pe[:, 0], width)
        if impl == "kernel":
            with jax.named_scope("mla.attend"):
                o_lat = paged_latent_attention(
                    q_rows, pool, block_tables, lengths, layer,
                    value_width=self.kv_lora_rank, sm_scale=sm_scale)
        else:
            rows = pk.gather_latent(pool, layer, block_tables, dtype)
            valid = jnp.arange(rows.shape[1])[None, :] <= lengths[:, None]
            o_lat = self.attend_absorbed(q_rows, rows, valid, sm_scale)
        return self.unabsorb(p, o_lat)[:, None], pool
