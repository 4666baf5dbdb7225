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
``make_async_copy`` ring, each chunk read ONCE and used twice — ``(H, row) @
(row, Tc)`` for the scores and ``(H, Tc) @ (Tc, C)`` for the values — under an
fp32 online softmax.  The walk does live work only, in
``paged_attention._online_kernel``'s words and by a copy of its plan:

- a **dead row** (an empty slot: ``tables[b, 0]`` is the scratch block, the
  fact the model's own ``live=`` reads) issues no DMA, runs no matmul and
  writes zeros;
- the **ring is carried** across programs (the grid is sequential and scratch
  persists: program 0 writes a plan into SMEM — each row's next live row and
  the ring position of its first chunk — and position p's fetch issues before
  position p - 2 computes, whichever row owns it: a row's last chunks compute
  while the next live row's first two are in flight);
- the **tail is trimmed**: a row's last chunk fetches only the blocks that
  hold a live position, its products run over the fewest tiles of
  ``_TAIL_TOKENS`` that cover them, the dead positions masked in the scores
  and zeroed in the value tile before ``P @ V`` (a block that was not fetched
  leaves what the buffer held).  Every chunk before the last is live whole:
  its copies start in one basic block, land under one wait and it runs with
  no mask at all.

With 128 heads on one row that is 218 FLOPs a stored byte: at the v5e's
ridge, and on the chip the two products and the softmax, not the bytes, are
what a call waits for (PERF.md section 6, PR 48: one softmax update a chunk
costs the same whatever its width, so chunks are 1,024 tokens; every width a
tail may take is a branch of straight-line code, so tails come in tiles of
256).  Plain decode only (one query token a slot); the pool is read-only
here.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import SCRATCH_BLOCK

NEG_INF = float(np.finfo(np.float32).min)
_N_BUF = 3            # DMA ring depth, as paged_attention._N_BUF
_CHUNK_TOKENS = 1024  # tokens a chunk: 1.3 MB of rows a fetch at row 640,
#                       one softmax update
_TAIL_TOKENS = 256    # a last chunk's products run over whole multiples of
#                       this many tokens (whole 128-lane score columns)


def _interpret():
    return jax.default_backend() != "tpu"


def _kernel(tables_ref, lengths_ref, layer_ref, q_ref, lat_hbm, o_ref,
            buf, m_ref, l_ref, acc_ref, sem, next_ref, first_ref, *,
            block_size, nb_max, group, tail_widths, value_width, sm_scale):
    """Grid (B,): ONE program per slot; ``paged_attention._online_kernel``'s
    walk over one leaf.  Program 0 writes the plan into SMEM scratch, which
    like the VMEM ring and its DMA semaphores persists across the sequential
    grid: for every row the next live row and the ring position of its first
    chunk (a live row has ``length // chunk + 1`` chunks, a dead row none).
    The chunk at ring position p lands in buffer ``p % 3`` and its fetch
    starts before position p - 2 computes, whichever row that is."""
    b = pl.program_id(0)
    B = tables_ref.shape[0]
    lay = layer_ref[0]
    bs, G = block_size, group
    Tc = G * bs
    n_chunks = -(-nb_max // G)

    def live_units(row, unit, most):
        """Chunks (or blocks) of ``row`` that hold a position <= length, the
        query token's own (its row is already written); 0 for a dead row."""
        n = jnp.minimum(lengths_ref[row] // unit + 1, most)
        return jnp.where(tables_ref[row, 0] == SCRATCH_BLOCK, 0, n)

    @pl.when(b == 0)
    def _():
        # the plan: next_ref[r] the first live row after r (B: none),
        # first_ref[r] the chunks of the rows before r = the ring position
        # of row r's first chunk; first_ref[B] the call's chunks
        def back(k, nxt):
            r = B - 1 - k
            next_ref[r] = nxt
            return jnp.where(tables_ref[r, 0] == SCRATCH_BLOCK, nxt, r)

        def forth(r, pos):
            first_ref[r] = pos
            return pos + live_units(r, Tc, n_chunks)

        next_ref[B] = B
        jax.lax.fori_loop(0, B, back, B)
        first_ref[B] = jax.lax.fori_loop(0, B, forth, 0)

    n_live = live_units(b, Tc, n_chunks)      # this row's chunks; 0: dead
    base = first_ref[b]                       # ring position of chunk 0
    total = first_ref[B]

    def each_copy(row, c, slot, act):
        """Start or await (``act``) chunk c of ``row`` in buffer ``slot``:
        one copy per block that holds a live position, which a live chunk's
        first always does, in a loop (an unrolled chunk is sixteen
        descriptors wherever one is started: seconds of every start-up's
        lowering)."""
        n = jnp.clip(live_units(row, bs, nb_max) - c * G, 1, G)

        def one(g, carry):
            act(pltpu.make_async_copy(
                lat_hbm.at[lay, tables_ref[row, c * G + g]],
                buf.at[slot, g], sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, n, one, 0)

    def start_at(p):
        """Start the fetch of ring position p, if the call has one: a
        chunk of this row or of one of the next two live rows (p is at
        most two past a position of this row, and every live row has a
        chunk)."""
        @pl.when(p < total)
        def _():
            after = next_ref[b]
            row = jnp.where(p < first_ref[after], b, after)
            after = next_ref[after]
            row = jnp.where(p < first_ref[after], row, after)
            each_copy(row, p - first_ref[row], jax.lax.rem(p, _N_BUF),
                      lambda cp: cp.start())

    @pl.when(n_live == 0)
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when(n_live > 0)
    def _():
        @pl.when(base == 0)                   # the first live row
        def _():
            start_at(0)
            start_at(1)

        q = q_ref[0]                                        # (H, row)
        last = jnp.minimum(lengths_ref[b], nb_max * bs - 1)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

        def land(c, whole):
            """Chunk c's buffer, once the fetch two ring positions ahead has
            started and its own, at position ``base + c``, has landed: a
            whole chunk's G copies in ONE wait for their bytes together."""
            p = base + c
            start_at(p + 2)
            slot = jax.lax.rem(p, _N_BUF)
            if whole:
                pltpu.make_async_copy(lat_hbm.at[lay, pl.ds(0, G)],
                                      buf.at[slot], sem.at[slot]).wait()
            else:
                each_copy(b, c, slot, lambda cp: cp.wait())
            return slot

        def attend(c, slot, width, whole):
            """Chunk c's first ``width`` rows into the running softmax, in
            one update.  ``whole``: every row is live."""
            rows = buf[slot, :width // bs].reshape(
                width, q.shape[1]).astype(q.dtype)          # (width, row)
            vals = rows[:, :value_width]
            s = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if not whole:
                # a block that was not fetched leaves what the buffer held,
                # and 0 x NaN is NaN in P @ V: dead positions' values are
                # zeros, their scores the mask's
                k_pos = c * Tc + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(k_pos <= last, s, NEG_INF)
                v_pos = c * Tc + jax.lax.broadcasted_iota(
                    jnp.int32, vals.shape, 0)
                vals = jnp.where(v_pos <= last, vals, jnp.zeros_like(vals))
            m_prev = m_ref[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            prob = jnp.exp(s - m_new)                       # (H, width) fp32
            l_ref[:] = l_ref[:] * alpha + jnp.sum(prob, -1, keepdims=True)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                prob.astype(q.dtype), vals, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:] = m_new

        def whole_chunk(c, carry):
            attend(c, land(c, True), Tc, True)
            return carry

        jax.lax.fori_loop(0, n_live - 1, whole_chunk, 0)
        # the last chunk, at the narrowest width that covers its live tokens
        c = n_live - 1
        slot = land(c, False)
        tail = last + 1 - c * Tc                            # 1 .. Tc
        for lo, width in zip((0,) + tail_widths, tail_widths):
            pl.when(jnp.logical_and(tail > lo, tail <= width))(
                functools.partial(attend, c, slot, width, False))
        o_ref[0] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)  # position 0
        #                                                     is always live


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
    sm_scale) row[:, :value_width]`` over positions ``0..lengths[b]``, and
    zeros for a row whose table starts at the scratch block;
    ``paged_kv.gather_latent`` plus the same arithmetic in ``jax.numpy`` is
    the oracle it is tested against."""
    from ...inference.paged_kv import LATENT
    B, H, row = q.shape
    lat = pool[LATENT]
    bs = lat.shape[2]
    assert lat.shape[3] == row and value_width <= row, (lat.shape, q.shape)
    nb_max = block_tables.shape[1]
    G = min(max(1, _CHUNK_TOKENS // bs), nb_max, lat.shape[1])
    Tc = G * bs
    # the widths a last chunk's products may take: whole blocks AND whole
    # tail tiles, and the chunk itself
    step = int(np.lcm(bs, _TAIL_TOKENS))
    tail_widths = tuple(range(step, Tc, step)) + (Tc,)
    kernel = functools.partial(
        _kernel, block_size=bs, nb_max=nb_max, group=G,
        tail_widths=tail_widths, value_width=value_width,
        sm_scale=float(sm_scale))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[pl.BlockSpec((1, H, row), lambda b, *s: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],   # the pool stays in HBM
        out_specs=pl.BlockSpec((1, H, value_width), lambda b, *s: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((_N_BUF, G, bs, row), lat.dtype),
            pltpu.VMEM((H, 1), jnp.float32),            # m (running max)
            pltpu.VMEM((H, 1), jnp.float32),            # l (denominator)
            pltpu.VMEM((H, value_width), jnp.float32),  # acc
            pltpu.SemaphoreType.DMA((_N_BUF,)),
            pltpu.SMEM((B + 1,), jnp.int32),            # plan: next live row
            pltpu.SMEM((B + 1,), jnp.int32),            # plan: first ring position
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
