"""In-place paged attention over a LATENT pool — Pallas TPU kernel.

No reference counterpart.  The sibling of ``paged_attention.py`` for a model
with latent attention (MLA, ``models/deepseek_v2.py``) decoding in the
ABSORBED form: every one of the ``H`` query heads reads the SAME cached row
a token and a layer (``paged_kv``'s latent layout: ``[c_kv | k_pe | 0]`` in
whole 128-lane tiles), as key whole and as value in its first ``C =
kv_lora_rank`` columns::

    s[h, t] = q[h] . row[t] * sm_scale        q[h] = [q_nope[h] W_UK[h]^T | q_pe[h] | 0]
    o[h]    = softmax_t(s[h]) row[:, :C]      (the caller multiplies by W_UV[h])

One program per slot (grid ``(B,)``): the slot's LIVE tokens stream from the
pool in HBM in chunks of whole blocks through a triple-buffered
``make_async_copy`` ring (``paged_attention._online_kernel``'s discipline:
chunk c + 2's fetch issues before chunk c's compute), each chunk read ONCE
and used twice — ``(H, row) @ (row, Tc)`` for the scores and ``(H, Tc) @ (Tc,
C)`` for the values — under an fp32 online softmax.  With 128 heads on one
row that is 242 FLOPs a byte: at the v5e's ridge, neither clearly HBM- nor
MXU-bound.  Plain decode only (one query token a slot); the pool is
read-only here.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)
_N_BUF = 3            # DMA ring depth, as paged_attention._N_BUF
_CHUNK_TOKENS = 512   # tokens a chunk: 0.66 MB of rows a fetch at row 640


def _interpret():
    return jax.default_backend() != "tpu"


def _kernel(tables_ref, lengths_ref, layer_ref, q_ref, lat_hbm, o_ref,
            buf, m_ref, l_ref, acc_ref, sem, *, block_size, nb_max, group,
            value_width, sm_scale):
    b = pl.program_id(0)
    lay = layer_ref[0]
    bs, G = block_size, group
    Tc = G * bs
    length = lengths_ref[b]           # the query token's position: its row
    #                                   is already written, positions 0..length
    n_chunks = -(-nb_max // G)
    n_live = jnp.minimum((length + Tc) // Tc, n_chunks)

    def fetches(c, slot):
        # a chunk's tail past the table re-reads its last entry; the mask
        # below drops every position >= nb_max * bs
        return [pltpu.make_async_copy(
            lat_hbm.at[lay, tables_ref[b, jnp.minimum(c * G + g, nb_max - 1)]],
            buf.at[slot, pl.ds(g * bs, bs)], sem.at[slot])
            for g in range(G)]

    def start(c):
        for cp in fetches(c, jax.lax.rem(c, _N_BUF)):
            cp.start()

    q = q_ref[0]                                            # (H, row)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    start(0)
    if n_chunks > 1:
        @pl.when(n_live > 1)
        def _():
            start(1)

    def body(c, carry):
        if n_chunks > 2:
            @pl.when(c + 2 < n_live)
            def _():
                start(c + 2)
        slot = jax.lax.rem(c, _N_BUF)
        for cp in fetches(c, slot):
            cp.wait()
        rows = buf[slot].astype(q.dtype)                    # (Tc, row)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (H, Tc)
        k_pos = c * Tc + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= jnp.minimum(length, nb_max * bs - 1), s,
                      NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(q.dtype), rows[:, :value_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        return carry

    jax.lax.fori_loop(0, n_live, body, 0)
    o_ref[0] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)  # position 0 is
    #                                                          always live


def paged_latent_attention(q, pool, block_tables, lengths, layer, *,
                           value_width, sm_scale, interpret=None):
    """Attention of one query token a slot over the latent pool, read in
    place.

    - ``q``: (B, H, row) queries in the pool's row layout (the absorbed
      ``q_nope W_UK^T`` in the first ``value_width`` columns, the rotated
      ``q_pe`` next, zeros in the row's padding), compute dtype;
    - ``pool``: ``paged_kv.init_latent_pool``'s;
    - ``block_tables`` (B, nb_max) int32, ``lengths`` (B,) int32: the query
      token's position, its own row already written;
    - ``layer``: int or traced scalar.

    Returns (B, H, value_width) in ``q.dtype``: ``softmax(q . row *
    sm_scale) row[:, :value_width]`` over positions ``0..lengths[b]``;
    ``paged_kv.gather_latent`` plus the same arithmetic in ``jax.numpy`` is
    the oracle it is tested against."""
    from ...inference.paged_kv import LATENT
    B, H, row = q.shape
    lat = pool[LATENT]
    bs = lat.shape[2]
    assert lat.shape[3] == row and value_width <= row, (lat.shape, q.shape)
    nb_max = block_tables.shape[1]
    G = min(max(1, _CHUNK_TOKENS // bs), nb_max)
    kernel = functools.partial(
        _kernel, block_size=bs, nb_max=nb_max, group=G,
        value_width=value_width, sm_scale=float(sm_scale))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[pl.BlockSpec((1, H, row), lambda b, *s: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],   # the pool stays in HBM
        out_specs=pl.BlockSpec((1, H, value_width), lambda b, *s: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((_N_BUF, G * bs, row), lat.dtype),
            pltpu.VMEM((H, 1), jnp.float32),            # m (running max)
            pltpu.VMEM((H, 1), jnp.float32),            # l (denominator)
            pltpu.VMEM((H, value_width), jnp.float32),  # acc
            pltpu.SemaphoreType.DMA((_N_BUF,)),
        ])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret() if interpret is None else interpret,
        name="mla_paged_attention",
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, lat)
