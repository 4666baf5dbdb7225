"""Flash attention — Pallas TPU kernel with custom VJP.

Role parity: the reference's fused transformer attention kernels
(``csrc/transformer/softmax_kernels.cu``, attention score path of
``ds_transformer_cuda.cpp``) fuse QK^T → masked softmax → AV to avoid
materializing the (T, T) score matrix.  On TPU this is the classic
flash-attention online-softmax kernel: the score matrix never leaves VMEM,
with fp32 running max/denominator and bf16 MXU matmuls.

Layout: inputs (B, T, H, d) (the model's layout) are processed on a grid
(B*H, q_blocks, k_blocks); the innermost k dimension revisits VMEM scratch
carrying the online-softmax state (m, l, acc).  The backward pass recomputes
probabilities from the saved logsumexp (no (T,T) residuals).

The backward (``_bwd``).  A DENSE call (no LUT, no key or attention bias;
causal or not) is ONE kernel: grid (B*H, k_blocks, q_blocks), each (q block,
k block) pair visited once, and from its one ``q·kᵀ``, one exp, one
``dO·vᵀ`` and one ``dS`` come its shares of dK, dV AND dQ, five products
where two kernels that each recomputed the scores ran seven.  dK and dV
accumulate in VMEM over the q sweep as before.  dQ: where a head's keys are
one block (T up to the block) a visit holds all of a q row's keys and its dQ
is written straight out; else the shares are summed, k block by k block in
ascending order, in one more resident, an fp32 accumulator over the head's
whole padded sequence (1 MB at T 2048, 2 MB at T 4096, a row padded to 128
lanes), and a q block is cast and written at its visit under the last k
block.  TPU grid steps run in order on one core, so this needs no atomics;
both inner grid axes carry state.  ``_fused_backward`` decides from the
shapes the call sees: a sequence whose accumulator is over
``_FUSED_BWD_DQ_ACC_BYTES`` (T past 8192) keeps two kernels, dK/dV (grid over
k blocks) and dQ (grid over q blocks).  So do the LUT, banded, merged and
biased calls, unchanged to the bit: their visits follow per-row LUTs (a k
block's q rows and a q row's k blocks are different lists, so one grid does
not walk both), and a bias tile would be one more resident operand in the
fused kernel's VMEM.  ``tile_census()`` counts the backward calls by form
(``bwd_fused`` / ``bwd_split``).

The causal tile walk (dense causal calls with square blocks): a resident
``(block, block)`` visit is cut into square 256-tiles under a static plan
(``_tile_plan``).  Tiles above the diagonal are never touched, only the
tiles the diagonal crosses pay for a mask, the rest run with none.  The
kernels run a q tile row's contiguous tiles as ONE product (``_strips``),
a block wholly under the diagonal as one product over the block, and a
block above it not at all (the grid's test).  Blocks stay as large as
``_auto_blocks`` makes them, so a block no longer bounds what the causal
mask skips.  Where a head's keys are one block (T up to the block) the
forward keeps no running state either.  Every other call (non-causal, LUT,
banded, merged, biased, ``block_q != block_k``) runs the trivial plan, one
tile under the mask it always had, through the same body.

The windowed forward (``flash_attention_window``; a serving prefill's
sliding-window layers, ``models/afmoe.py``) is a path of its own beside all
of these: a causal window ``0 <= t - s < window`` walked as a static band of
square blocks (``_band_slots``), the grid's inner axis as long as the band,
masks on the two blocks an edge crosses only, grouped K/V heads read through
the index map.  Forward only.

Runs compiled on TPU; ``interpret=True`` under other backends so numerics
tests run on the CPU mesh (SURVEY.md §4: every kernel is tested against a
pure-jnp reference).
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = None   # None → auto-tuned by head_dim/seq (see _auto_blocks)
DEFAULT_BLOCK_K = None
NEG_INF = -1e30
# Mosaic requires the last (lane) dim of a block to be 128-aligned or span
# the array; per-row softmax statistics (lse/delta) are stored broadcast
# across a 128-wide lane dim (same trick as the upstream TPU flash kernel)
MIN_LANES = 128


def _auto_blocks(seq_len, head_dim, block_q, block_k):
    """Measured on v5e: large square blocks amortize the online-softmax
    scratch revisits and the grid step — 1024×1024 hits ~30 TF/s vs ~5 TF/s
    at 128×128, both AT T=4096, where a 1024-block's grid test already
    skips three quarters of the upper triangle.  At T <= the block it
    skipped nothing (train_z1's T=1024 ran the whole square three times a
    layer).  Since PR 37 a block no longer bounds what the causal mask
    skips: the kernels walk the resident block in 256-tiles
    (``_causal_tile``: chosen on the chip for hd 64 and hd 128 alike, the
    three kernels 1.468 -> 1.035 ms a layer at (80, 1024, 64) and 0.919 ->
    0.873 at (16, 2048, 128)), so these sizes are about the grid step and
    VMEM alone.  Cap by head_dim to stay inside VMEM (score block is bq×bk
    fp32).

    NOTE (round-2 lesson): tall-q/narrow-k blocks (bq=T, bk=512) win a
    STANDALONE fwd+bwd microbench by ~2× at T=1024, but LOSE ~3-7% MFU
    inside the full training step (gpt2-350m 0.51→0.48) — XLA's scheduling
    of the surrounding fusions changes.  Trust end-to-end model
    measurements over kernel microbenches here."""
    cap = 512 if head_dim > 64 else 1024
    if block_q is None:
        block_q = min(cap, max(128, seq_len))
    if block_k is None:
        block_k = min(cap, max(128, seq_len))
    return block_q, block_k


# The fused backward's one new resident: dQ summed in fp32 over a head's
# whole padded sequence, as VMEM lays it out (a row padded to 128 lanes:
# 1 MB at T 2048, 2 MB at T 4096, for hd 64 and hd 128 alike).  Over this
# the sequence is too long for one more buffer beside the blocks and their
# score tiles, and the backward stays two kernels.
_FUSED_BWD_DQ_ACC_BYTES = 4 * 2 ** 20


def _fused_backward(dense, nq, nk, block_q, head_dim):
    """Whether a backward is ONE kernel (dQ, dK and dV from one pass over
    the scores) or two (dK/dV, then dQ with the scores computed again).
    Decided by what the call can see, never by a key: the dense plan (no
    LUT, no key or attention bias: those keep the kernels and the bits they
    had) and an accumulator inside its budget.  A head whose keys are one
    block needs no accumulator and always fuses."""
    acc_bytes = nq * block_q * max(head_dim, MIN_LANES) * 4 if nk > 1 else 0
    return dense and acc_bytes <= _FUSED_BWD_DQ_ACC_BYTES


def _interpret():
    return jax.default_backend() != "tpu"


def _pallas(kernel, *, grid, in_specs, out_specs, out_shape, scratch,
            num_prefetch=0, carried_axes=1, name=None):
    """One pallas_call builder for the dense (plain grid) and LUT
    (scalar-prefetch grid) variants — the operand lists must never
    diverge between the two paths.  ``carried_axes``: how many of the
    grid's innermost axes carry state in scratch from step to step.
    ``name``: the kernel's own name in the HLO text and a device trace
    (without one it is named after the scope it sits in)."""
    cp = pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (len(grid) - carried_axes)
        + ("arbitrary",) * carried_axes)
    if num_prefetch:
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=num_prefetch, grid=grid,
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch),
            out_shape=out_shape, compiler_params=cp, interpret=_interpret())
    return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shape,
                          scratch_shapes=scratch, compiler_params=cp,
                          interpret=_interpret(), name=name)


# ======================================================== sparse-layout LUTs
@functools.lru_cache(maxsize=64)
def _sparse_luts(layout_bytes, shape, causal, block_q, block_k):
    """Grid-compression LUTs for a static block layout (reference: the
    Triton kernels' ``make_lut``, ``ops/sparse_attention/matmul.py:288,429``
    — there the LUT drives SDD/DSD tiles; here it drives the Pallas grid so
    skipped blocks skip their K/V DMA entirely, not just their MXU time).

    Returns ``(kmap (H,nq,Lk), klen (H,nq), qmap (H,nk,Lq), qlen (H,nk))``
    int32 numpy arrays: per q-row the live k-blocks (causal-pruned) for the
    forward/dQ grids, and the transpose for the dK/dV grid.

    Rows shorter than the max pad by REPEATING their last live block: the
    Pallas pipeline only issues a DMA when a block's index map value
    CHANGES between grid steps, so padded slots re-visit an already-resident
    block (zero HBM traffic) and their compute is gated off by the length.
    This matters for patterns with global rows (Longformer/BigBird): one
    dense global row forces the padded width to nk, but every other row
    still moves only its live blocks."""
    H, nq, nk = shape
    layout = np.frombuffer(layout_bytes, np.int32).reshape(shape)
    live = layout > 0
    if causal:
        qi = np.arange(nq)[:, None] * block_q + (block_q - 1)
        ki = np.arange(nk)[None, :] * block_k
        live = live & (ki <= qi)[None]
    k_lists = [[np.nonzero(live[h, i])[0] for i in range(nq)]
               for h in range(H)]
    q_lists = [[np.nonzero(live[h, :, j])[0] for j in range(nk)]
               for h in range(H)]
    Lk = max(1, max(len(l) for rows in k_lists for l in [*rows]))
    Lq = max(1, max(len(l) for rows in q_lists for l in [*rows]))

    def fill(dst_map, dst_len, lists):
        for h in range(H):
            for i, l in enumerate(lists[h]):
                dst_map[h, i, :len(l)] = l
                dst_map[h, i, len(l):] = l[-1] if len(l) else 0
                dst_len[h, i] = len(l)
    kmap = np.zeros((H, nq, Lk), np.int32)
    klen = np.zeros((H, nq), np.int32)
    qmap = np.zeros((H, nk, Lq), np.int32)
    qlen = np.zeros((H, nk), np.int32)
    fill(kmap, klen, k_lists)
    fill(qmap, qlen, q_lists)
    return kmap, klen, qmap, qlen


# ============================================================ causal tile walk
_CAUSAL_TILE = 256       # edge of the square sub-tiles (see _causal_tile)


def _causal_tile(causal, block_q, block_k, head_dim, dense=True):
    """Edge of the square sub-tiles a resident ``(block_q, block_k)`` block
    is cut in, or ``None`` for the trivial plan: one tile, the whole block,
    masked as it always was.  Only the dense causal call with square blocks
    gets a plan of its own (the LUT, banded, merged, biased and non-causal
    calls keep their numerics to the bit); the edge may depend on
    ``head_dim`` and the block, on nothing else.

    Measured on v5e (PERF.md §6, PR 37, call A; the three kernels alone,
    ms a layer, parent -> 128 / 256 / 512): hd 64, T 1024, 80 heads 1.468
    -> 1.113 / 1.035 / 1.134; hd 128, T 2048, 16 heads 0.919 -> 0.876 /
    0.873 / 0.896 (a 512-block is its own tile: what is left is the mask
    the blocks under the diagonal no longer pay); hd 64, T 4096, 16 heads
    2.788 -> 2.524 / 2.441 / 2.478.  256 wins at every shape."""
    del head_dim
    if not (causal and dense and block_q == block_k):
        return None
    tile = min(_CAUSAL_TILE, block_q)
    return None if block_q % tile else tile


def _tile_plan(block_q, block_k, tile, diagonal):
    """The static plan of one block visit: a tuple of rows ``(lo, hi,
    masked)``.  Row ``r`` is the ``r``-th q sub-tile; it visits the k tiles
    ``lo .. hi - 1`` with no mask at all and, unless ``masked`` is None,
    the tile ``masked`` (the next one) under the mask.  Tiles it names
    nowhere lie above the diagonal and are never touched.  ``diagonal``:
    the block the diagonal crosses (``qi == kj``); any other visited block
    lies wholly under it."""
    if tile is None:
        return ((0, 0, 0),)
    assert block_q == block_k and block_q % tile == 0, (block_q, block_k, tile)
    n = block_q // tile
    if not diagonal:
        return ((0, n, None),) * n
    return tuple((0, r, r) for r in range(n))


def _plan_counts(plan):
    """(tiles visited, tiles masked, tiles in the block's square)."""
    masked = sum(m is not None for _, _, m in plan)
    return sum(hi - lo for lo, hi, _ in plan) + masked, masked, len(plan) ** 2


def _strips(plan, tile):
    """How the kernels run a diagonal block's plan: a row's tiles are
    contiguous, so each row is ONE product, ``tile`` q rows against the
    row's k columns, with the mask on its last square.  Yields ``(rows,
    cols)`` slices of the resident block.  (A loop over single tiles, the
    plan read literally, lost to the whole square: each tile is a chain of
    product, row maximum, exp, product whose latency nothing hides, and
    its time went with the tile's edge, not its area; PERF.md §6, PR 37.)"""
    for r, (lo, hi, masked) in enumerate(plan):
        assert masked == hi, plan
        yield pl.ds(r * tile, tile), pl.ds(lo * tile, (hi + 1 - lo) * tile)


def _strip_scores(q, k, sm_scale, tile):
    """Scores of one strip: only the last square meets the diagonal, and
    there local positions decide (the strip's last column is its last row).
    No ``k_pos < seq_len`` guard: under a causal mask a padded key meets
    padded queries only, whose rows are cut off and whose ``do`` is zero."""
    s = _scores(q, k, sm_scale)
    last = s[:, -tile:]
    row = jax.lax.broadcasted_iota(jnp.int32, last.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, last.shape, 1)
    last = jnp.where(row >= col, last, NEG_INF)
    if s.shape[1] == tile:
        return last
    return jnp.concatenate([s[:, :-tile], last], axis=1)


# What the plans of the dense calls traced since the last
# ``reset_tile_census`` add up to: ``{call: (visited, masked, square)}``
# over a call's whole grid.  A call traced again (the custom_vjp's primal
# and its forward rule, a rematerialised forward) has the same key and
# counts once.  Beside it the form each distinct backward took, dense or
# not: ``{call: "bwd_fused" | "bwd_split"}``.  Trace-time bookkeeping:
# nothing of it reaches the program.
_tile_census = {}
_bwd_forms = {}


def reset_tile_census():
    _tile_census.clear()
    _bwd_forms.clear()


def tile_census():
    """``{"visited", "masked", "square"}`` summed over the distinct dense
    flash calls traced since :func:`reset_tile_census`, and the distinct
    backward calls by form: ``bwd_fused`` (one kernel for dQ, dK and dV)
    and ``bwd_split`` (two, each recomputing the scores)."""
    sums = [sum(c[i] for c in _tile_census.values()) for i in range(3)]
    forms = list(_bwd_forms.values())
    return {**dict(zip(("visited", "masked", "square"), sums)),
            **{form: forms.count(form) for form in ("bwd_fused", "bwd_split")}}


def _record_tiles(kind, BH, d, nq, nk, block_q, block_k, causal, tile):
    """Books one dense call: the plans of the blocks its grid's test lets
    through, over all ``BH`` heads."""
    counts = [_plan_counts(_tile_plan(block_q, block_k, tile, i == j))
              for i in range(nq) for j in range(nk)
              if not causal or j * block_k <= i * block_q + block_q - 1]
    visited, masked, _ = (sum(c) for c in zip(*counts))
    _tile_census[(kind, BH, d, nq, nk, block_q, block_k, causal, tile)] = (
        BH * visited, BH * masked, BH * nq * nk * counts[0][2])


def _online_softmax(s, v, m_prev, l_prev, acc_prev):
    """One online-softmax update with the scores ``s`` (f32) of a tile."""
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                    # fp32
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_prev * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _scores(q, k, sm_scale):
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * sm_scale


def _p_and_ds(s, v, do, lse, delta, sm_scale):
    """The backward's recomputed probabilities and dS = P * (dP - delta),
    dP = dO V^T, from masked scores."""
    p = jnp.exp(s - lse)                      # fp32
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * sm_scale


# =============================================================== forward kernel
def _unpack_in_refs(refs, n_main, use_kbias, use_abias):
    """Unpack input refs in call order ``main... [kb] [ab]``; returns
    ``(main_refs, kb_ref, ab_ref, next_idx)`` where ``next_idx`` points at
    the first output ref."""
    idx = n_main
    main = refs[:n_main]
    kb_ref = refs[idx] if use_kbias else None
    idx += int(use_kbias)
    ab_ref = refs[idx] if use_abias else None
    idx += int(use_abias)
    return main, kb_ref, ab_ref, idx


def _fwd_kernel(*refs, sm_scale, causal, block_q, block_k, num_k_blocks,
                seq_len, n_heads=1, use_kbias=False,
                use_abias=False, use_lut=False, use_merge=False,
                use_banded=None, num_k_total=None, tile=None):
    """Grid: (BH, nq, nk) with nk innermost (revisits scratch).

    ``tile``: the dense causal call's sub-tile edge (``_causal_tile``);
    ``None`` is the trivial plan, the whole block under today's mask.

    With ``use_lut`` (the block-sparse path; reference
    ``ops/sparse_attention/matmul.py`` SDD/DSD/DDS Triton kernels + their
    ``make_lut`` grid compression) the inner grid dim is the per-row
    LIVE block count: two scalar-prefetch refs ``(kmap, klen)`` lead the
    argument list, the j-th visited k block is ``kmap[h, qi, j]`` (the
    BlockSpec index maps DMA exactly that block), and ``j < klen[h, qi]``
    gates padding slots.  Skipped blocks never touch HBM.

    ``use_kbias``/``use_abias``: additive score biases — (B, T) over keys
    (padding) and (T, T) shared across batch (attention mask) — applied
    in-kernel (reference ``softmax_kernels.cu`` attn_softmax masked paths)."""
    if use_merge:
        kmap_ref, klen_ref, sub0_ref, sub1_ref = refs[:4]
        refs = refs[4:]
        use_lut = True
    elif use_lut:
        kmap_ref, klen_ref = refs[:2]
        refs = refs[2:]
    (q_ref, k_ref, v_ref), kb_ref, ab_ref, idx = \
        _unpack_in_refs(refs, 3, use_kbias, use_abias)
    o_ref, lse_ref, acc_ref, m_ref, l_ref = refs[idx:idx + 5]
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    # a head whose keys are ONE resident block under the tile walk (T up to
    # the block: train_z1's grid is (B*H, 1, 1)) needs no running state:
    # each q strip meets all its keys at once and is written out from
    # registers.  The (rows, 1) statistics fill one lane in 128, so their
    # init, rescale and read-back cost the forward a third of its time.
    stateless = tile is not None and num_k_blocks == 1
    if not stateless:
        @pl.when(kj == 0)
        def _():
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

    if use_banded is not None:
        # static band+global slots with kernel blocks DECOUPLED from the
        # layout blocks: q rows are block_q (auto-sized, e.g. 1024) while
        # k slots stay at the layout block Lb — affine ki and predicates
        # (no SMEM), plus an in-kernel positional band mask for exactness
        W, gcols, Lb = use_banded
        R = block_q // Lb                     # layout rows per kernel row
        W_k = R + W - 1                       # band slots per kernel row
        base = qi * R - (W - 1)               # lowest live layout block
        ki = jnp.clip(base + kj, 0, num_k_total - 1)
        for g, c in enumerate(gcols):
            ki = jnp.where(kj == W_k + g, c, ki)
        is_band = kj < W_k
        should_compute = jnp.logical_and(is_band, base + kj >= 0)
        for g, c in enumerate(gcols):
            # global slot: only when the band does not already cover it
            should_compute = jnp.logical_or(
                should_compute,
                jnp.logical_and(kj == W_k + g, base > c))
    elif use_lut:
        h_idx = pl.program_id(0) % n_heads
        ki = kmap_ref[h_idx, qi, kj]          # actual k-block index
        should_compute = kj < klen_ref[h_idx, qi]
    else:
        ki = kj
        # causal: process only k blocks that intersect the lower triangle
        should_compute = True
        if causal:
            should_compute = ki * block_k <= qi * block_q + (block_q - 1)

    def whole_block():
        q = q_ref[0]          # (block_q, d)
        k = k_ref[0]          # (block_k, d)
        v = v_ref[0]          # (block_k, d)
        s = _scores(q, k, sm_scale)           # (bq, bk)
        if use_kbias:
            s = s + kb_ref[0, 0]              # (1, bk) broadcast over rows
        if use_abias:
            s = s + ab_ref[0, 0]              # (bq, bk)

        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < seq_len               # mask padded key rows
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        if use_banded is not None:
            # positional layout exactness: a kernel q row spans R layout
            # rows whose windows differ — a position is live iff its
            # (q, k) layout cell is in the BAND (layout_row(q) - ki < W ⟺
            # q_pos < (ki + W)·Lb) OR the k block is a GLOBAL column
            # (scalar test: block_k == Lb so the whole slot is one layout
            # column).  The union matters: a band-visited block can also
            # be a global column, whose below-band rows must stay live.
            q_pos_b = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            band_ok = q_pos_b < (ki + W) * Lb
            in_g = False
            for c in gcols:
                in_g = jnp.logical_or(in_g, ki == c)
            valid = jnp.logical_and(valid, jnp.logical_or(band_ok, in_g))
        if use_merge:
            # merged q rows (two layout rows share one kernel row): each
            # half attends this k block only if ITS layout row is live —
            # exactness of the declared layout is preserved
            row_iota = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            # int32 select (Mosaic cannot lower an i1-vector select)
            sel = jnp.where(row_iota < block_q // 2,
                            sub0_ref[h_idx, qi, kj],
                            sub1_ref[h_idx, qi, kj])
            valid = jnp.logical_and(valid, sel > 0)
        s = jnp.where(valid, s, NEG_INF)
        m_ref[:], l_ref[:], acc_ref[:] = _online_softmax(
            s, v, m_ref[:], l_ref[:], acc_ref[:])

    def under_diagonal():
        # every tile unmasked, none skipped: the rows fuse, one product
        m_ref[:], l_ref[:], acc_ref[:] = _online_softmax(
            _scores(q_ref[0], k_ref[0], sm_scale), v_ref[0],
            m_ref[:], l_ref[:], acc_ref[:])

    def diagonal():
        plan = _tile_plan(block_q, block_k, tile, True)
        for rows, cols in _strips(plan, tile):
            s = _strip_scores(q_ref[0, rows], k_ref[0, cols], sm_scale, tile)
            v = v_ref[0, cols]
            if not stateless:
                m_ref[rows], l_ref[rows], acc_ref[rows] = _online_softmax(
                    s, v, m_ref[rows], l_ref[rows], acc_ref[rows])
                continue
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)   # > 0: a row sees itself
            acc = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            o_ref[0, rows] = (acc / l).astype(o_ref.dtype)
            lse_ref[0, rows] = jnp.broadcast_to(m + jnp.log(l),
                                                (tile, MIN_LANES))

    if tile is None:
        pl.when(should_compute)(whole_block)
    else:
        if num_k_blocks > 1:
            pl.when(kj < qi)(under_diagonal)
        pl.when(kj == qi)(diagonal)
    if stateless:
        return

    @pl.when(kj == num_k_blocks - 1)
    def _():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # rows that never saw a live score (merged path: a half-row whose
        # layout row is empty while its sibling is live) have m == NEG_INF
        # and p = exp(s - m) = 1 everywhere — their acc is garbage, not
        # zeros.  Zero them explicitly (the unmerged path gets this for
        # free from compute gating + l == 0).
        row_live = m_ref[:] > NEG_INF * 0.5          # (bq, 1)
        o_ref[0] = jnp.where(row_live, acc_ref[:] / l_safe,
                             0.0).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            jnp.where(row_live, m_ref[:] + jnp.log(l_safe), NEG_INF),
            (block_q, MIN_LANES))


_N_KV_BUF = 3    # triple buffer: slot (j+2)%3 held block j-1 (consumed one
#                  grid step ago), so the j+2 fetch can start BEFORE block
#                  j's compute with no read/write hazard

# full unroll of the slot walk is only worth its compile time on short
# rows: at dense layouts num_k_blocks grows with T/block_k and unroll=True
# emits one copy of the whole matmul+softmax body PER BLOCK — Mosaic
# compile time blows up superlinearly in program size.  Above the
# threshold, unrolling by the ring depth keeps the slot indices cheap
# (every _N_KV_BUF-th iteration reuses the same slot rotation) at O(1)
# program size.
_FULL_UNROLL_MAX_K_BLOCKS = 16


def _slot_walk_unroll(num_k_blocks):
    """fori_loop unroll for the DMA slot walk: full below the threshold,
    ring-depth (_N_KV_BUF) above it."""
    return True if num_k_blocks <= _FULL_UNROLL_MAX_K_BLOCKS else _N_KV_BUF


def _fwd_kernel_dma(*refs, sm_scale, causal, block_q, block_k, num_k_blocks,
                    seq_len, n_heads=1, use_merge=False):
    """LUT forward with MANUAL double-buffered K/V DMA (splash-attention
    style).  The BlockSpec LUT path pays more per visited slot than static
    index maps (no ledger cell measures it: ROADMAP D4) because
    scalar-prefetch-dependent index
    maps serialize the pipeline's DMA issue with the index computation.
    Here K/V stay in HBM (``pltpu.ANY``); the kernel fetches block
    ``kmap[h, qi, j]`` into a 3-deep VMEM ring with explicit
    ``make_async_copy`` — block j+2's fetch is issued before block j's
    compute, so the DMA engine runs a full block ahead of the MXU."""
    if use_merge:
        kmap_ref, klen_ref, sub0_ref, sub1_ref = refs[:4]
        refs = refs[4:]
    else:
        kmap_ref, klen_ref = refs[:2]
        refs = refs[2:]
    q_ref, kv_hbm = refs[:2]
    o_ref, lse_ref = refs[2:4]
    acc_ref, m_ref, l_ref, kv_buf, kv_sem = refs[4:]
    d = q_ref.shape[-1]

    b = pl.program_id(0)
    qi = pl.program_id(1)
    h_idx = jax.lax.rem(b, n_heads)

    # Grid is (BH, nq): ONE grid step processes a WHOLE q row — the slot
    # walk is an in-kernel fori_loop over the row's LUT entries with the
    # triple-buffered DMA ring hiding fetch latency across iterations.
    # (An inner GRID dim of ~3 live slots per row never reaches pipeline
    # steady state: each row paid warmup/drain stalls that measured ~3x
    # the dense kernel's per-step cost.)  NO data-dependent predication:
    # padded LUT slots address the appended all-zeros block at index nk,
    # whose k positions are >= seq_len, so the length mask nullifies
    # their contribution.

    def copies(j, slot):
        # K and V arrive INTERLEAVED, pre-reshaped and per-block
        # transposed (BH, nk+1, 2d, block_k): one DMA + one semaphore per
        # slot moves both; the DMA slices LEADING dims only and the lane
        # dim is the 128-aligned block_k — head_dims < 128 would
        # otherwise hit Mosaic's lane-tiling alignment on the slice
        ki = kmap_ref[h_idx, qi, j]
        return pltpu.make_async_copy(
            kv_hbm.at[b, ki], kv_buf.at[slot], kv_sem.at[slot])

    def start(j):
        copies(j, jax.lax.rem(j, _N_KV_BUF)).start()

    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    start(0)
    if num_k_blocks > 1:
        start(1)

    def body(kj, carry):
        if num_k_blocks > 2:
            @pl.when(kj + 2 < num_k_blocks)
            def _():
                start(kj + 2)
        slot = jax.lax.rem(kj, _N_KV_BUF)
        copies(kj, slot).wait()
        ki = kmap_ref[h_idx, qi, kj]
        q = q_ref[0]                  # (block_q, d)
        k = kv_buf[slot, :d]          # (d, block_k) — transposed block
        v = kv_buf[slot, d:]          # (d, block_k)
        s = jax.lax.dot_general(
            q, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < seq_len
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        if use_merge:
            row_iota = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            sel = jnp.where(row_iota < block_q // 2,
                            sub0_ref[h_idx, qi, kj],
                            sub1_ref[h_idx, qi, kj])
            valid = jnp.logical_and(valid, sel > 0)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        return carry

    jax.lax.fori_loop(0, num_k_blocks, body, 0,
                      unroll=_slot_walk_unroll(num_k_blocks))

    l = l_ref[:]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    row_live = m_ref[:] > NEG_INF * 0.5
    o_ref[0] = jnp.where(row_live, acc_ref[:] / l_safe,
                         0.0).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(
        jnp.where(row_live, m_ref[:] + jnp.log(l_safe), NEG_INF),
        (block_q, MIN_LANES))


def _tile_kbias(kb, T, Tp, block_k):
    """(B, T) additive key bias → (B, nk, 1, block_k) tile-major view whose
    trailing block dims EQUAL the array dims (always Mosaic-legal, any
    block size)."""
    B = kb.shape[0]
    kb = jnp.pad(kb.astype(jnp.float32), ((0, 0), (0, Tp - T)))
    return kb.reshape(B, Tp // block_k, 1, block_k)


def _tile_abias(ab, T, Tp, block_q, block_k):
    """(T, T) additive score bias → (nq, nk, block_q, block_k) tiles."""
    ab = jnp.pad(ab.astype(jnp.float32), ((0, Tp - T), (0, Tp - T)))
    return (ab.reshape(Tp // block_q, block_q, Tp // block_k, block_k)
            .transpose(0, 2, 1, 3))


def _pad_t(x, Tp):
    T = x.shape[1]
    if T == Tp:
        return x
    return jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0)))


def _fwd(q, k, v, sm_scale, causal, block_q, block_k,
         n_heads=None, k_bias=None, attn_bias=None, kmap=None, klen=None,
         sub01=None, banded=None):
    """q,k,v: (BH, T, d) → (out (BH, T, d), lse (BH, T)).

    ``kmap``/``klen``: optional grid-compression LUT (``_sparse_luts``) —
    the inner grid shrinks to the max live-block count and skipped blocks
    skip their DMA.
    ``k_bias``: optional (B, T) additive key bias (padding mask).
    ``attn_bias``: optional (T, T) additive score bias (attention mask)."""
    BH, T, d = q.shape
    use_lut = kmap is not None
    if banded is not None:
        # banded carries its own forward q-block size (decoupled from the
        # layout blocks the bwd LUT kernels use)
        W_b, gcols_b, Lb_b, bq_fwd = banded
        block_q = bq_fwd
        banded = (W_b, gcols_b, Lb_b)
    block_q, block_k = _auto_blocks(T, d, block_q, block_k)
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    # pallas clamps out-of-range blocks (dynamic-slice semantics), which would
    # silently shift uneven tails — pad to block multiples and mask in-kernel.
    # pad to a multiple of BOTH block sizes (lcm), else the smaller-block
    # grid still has an out-of-range tail block that dynamic-slice clamping
    # would silently shift
    blk = np.lcm(block_q, block_k)
    Tp = int(np.ceil(T / blk) * blk)
    assert not use_lut or Tp == T   # layout blocks always divide T
    q, k, v = _pad_t(q, Tp), _pad_t(k, Tp), _pad_t(v, Tp)
    nq = pl.cdiv(Tp, block_q)
    nk = pl.cdiv(Tp, block_k)
    H = n_heads or 1

    use_merge = sub01 is not None
    # manual-DMA LUT variant: K/V stay in HBM, the kernel runs its own
    # triple-buffered fetch ring (compiled TPU only — the interpreter
    # executes the BlockSpec variant, same numerics)
    use_dma = (use_lut and banded is None and not _interpret()
               and k_bias is None and attn_bias is None)
    if banded is not None:
        # STATIC band+global index maps (no LUT, no scalar prefetch):
        # kernel q rows are auto-sized (block_q, typically 1024) while k
        # slots stay at the layout block Lb == block_k; slot j visits
        # layout block base+j (clamped; predicated off when base+j < 0),
        # slot W_k+g the global column gcols[g].  Affine maps keep
        # Mosaic's pipeline at dense-kernel efficiency — the LUT grid's
        # apparent per-slot overhead was really the layout-block-sized
        # (512) kernel blocks; static maps let the q block grow past them.
        assert k_bias is None and attn_bias is None and not use_merge
        W, gcols, Lb = banded
        assert block_k == Lb and block_q % Lb == 0, (block_q, block_k, Lb)
        R = block_q // Lb
        W_k = R + W - 1

        def _band_ki(i, j):
            ki = jnp.clip(i * R - (W - 1) + j, 0, nk - 1)
            for g, c in enumerate(gcols):
                ki = jnp.where(j == W_k + g, c, ki)
            return ki
        kv_idx = lambda b, i, j: (b, _band_ki(i, j), 0)
        q_idx = lambda b, i, j: (b, i, 0)
        n_inner = W_k + len(gcols)
        use_lut = False
    elif use_merge:
        assert k_bias is None and attn_bias is None, \
            "merged-row path composes with the unbiased kernel only"
        # merged-row LUT: 4 scalar-prefetch refs (kmap, klen, sub0, sub1)
        kv_idx = lambda b, i, j, km, kl, s0, s1: \
            (b, km[jax.lax.rem(b, H), i, j], 0)
        q_idx = lambda b, i, j, km, kl, s0, s1: (b, i, 0)
        n_inner = kmap.shape[2]
    elif use_lut:
        # index maps receive the scalar-prefetch refs appended after the
        # grid ids; the j-th visited block is kmap[h, i, j]
        kv_idx = lambda b, i, j, km, kl: (b, km[jax.lax.rem(b, H), i, j], 0)
        q_idx = lambda b, i, j, km, kl: (b, i, 0)
        kb_idx = lambda b, i, j, km, kl: (
            jax.lax.div(b, H), km[jax.lax.rem(b, H), i, j], 0, 0)
        ab_idx = lambda b, i, j, km, kl: (i, km[jax.lax.rem(b, H), i, j], 0, 0)
        n_inner = kmap.shape[2]
    else:
        kv_idx = lambda b, i, j: (b, j, 0)
        q_idx = lambda b, i, j: (b, i, 0)
        kb_idx = lambda b, i, j: (jax.lax.div(b, H), j, 0, 0)
        ab_idx = lambda b, i, j: (i, j, 0, 0)
        n_inner = nk

    if use_dma:
        # block-major, per-block TRANSPOSED view (BH, nk+1, d, block_k):
        # DMA slices leading dims only and the lane dim is block_k
        # (128-aligned) — d < 128 would otherwise violate Mosaic's
        # lane-tiling on the slice.  The APPENDED all-zeros block at
        # index nk is what padded LUT slots fetch: its k positions are
        # >= seq_len, so the kernel's length mask nullifies them — no
        # SMEM-dependent predication anywhere in the steady state.  One
        # XLA transpose+concat per call (~2 passes over K+V, ≈0.02 ms at
        # T=4096) — charged to the sparse path honestly
        nk_blocks = Tp // block_k
        kv = jnp.concatenate(
            [k.reshape(BH, nk_blocks, block_k, d).swapaxes(2, 3),
             v.reshape(BH, nk_blocks, block_k, d).swapaxes(2, 3)], axis=2)
        kv = jnp.concatenate(
            [kv, jnp.zeros((BH, 1, 2 * d, block_k), k.dtype)], axis=1)
        slots = jnp.arange(kmap.shape[2])[None, None, :]
        kmap = jnp.where(slots < klen[..., None], kmap, nk_blocks)
        # 2-D grid (BH, nq): the q/out index maps drop the inner grid id
        if use_merge:
            q_idx = lambda b, i, km, kl, s0, s1: (b, i, 0)
        else:
            q_idx = lambda b, i, km, kl: (b, i, 0)
        in_specs = [
            pl.BlockSpec((1, block_q, d), q_idx),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ]
    else:
        in_specs = [
            pl.BlockSpec((1, block_q, d), q_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
        ]
    args = (q, k, v)
    if k_bias is not None:                    # (B, T) → (B, nk, 1, bk)
        k_bias = _tile_kbias(k_bias, T, Tp, block_k)
        in_specs.append(pl.BlockSpec((1, 1, 1, block_k), kb_idx))
        args = args + (k_bias,)
    if attn_bias is not None:                 # (T, T) → (nq, nk, bq, bk)
        attn_bias = _tile_abias(attn_bias, T, Tp, block_q, block_k)
        in_specs.append(pl.BlockSpec((1, 1, block_q, block_k), ab_idx))
        args = args + (attn_bias,)
    dense = not use_lut and banded is None
    tile = _causal_tile(causal, block_q, block_k, d,
                        dense and k_bias is None and attn_bias is None)
    if dense:
        _record_tiles("fwd", BH, d, nq, nk, block_q, block_k, causal, tile)
    if use_dma:
        kernel = functools.partial(
            _fwd_kernel_dma, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_k_blocks=n_inner,
            seq_len=T, n_heads=H, use_merge=use_merge)
    else:
        kernel = functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_k_blocks=n_inner,
            seq_len=T, n_heads=H, use_kbias=k_bias is not None,
            use_abias=attn_bias is not None,
            use_lut=use_lut and not use_merge, use_merge=use_merge,
            use_banded=banded, num_k_total=nk, tile=tile)
    out_specs = [
        pl.BlockSpec((1, block_q, d), q_idx),
        pl.BlockSpec((1, block_q, MIN_LANES), q_idx),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((BH, Tp, d), q.dtype),
        jax.ShapeDtypeStruct((BH, Tp, MIN_LANES), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
    ]
    if use_dma:
        args = (q, kv)
        scratch += [
            pltpu.VMEM((_N_KV_BUF, 2 * d, block_k), kv.dtype),
            pltpu.SemaphoreType.DMA((_N_KV_BUF,)),
        ]
    grid = (BH, nq) if use_dma else (BH, nq, n_inner)
    call = _pallas(kernel, grid=grid, in_specs=in_specs,
                   out_specs=out_specs, out_shape=out_shape, scratch=scratch,
                   num_prefetch=(4 if use_merge else 2) if use_lut else 0)
    if use_merge:
        out, lse = call(kmap, klen, sub01[0], sub01[1], *args)
    elif use_lut:
        out, lse = call(kmap, klen, *args)
    else:
        out, lse = call(*args)
    return out[:, :T], lse[:, :T, 0]


# =================================================== windowed forward (a band)
# Edge of the windowed forward's square blocks.  Measured on v5e (PERF.md
# section 6, PR 53; 48 query heads over 8 K/V heads of 128, a window of 4,096,
# ms a layer at 8,192 tokens): 256 -> 23.2, 512 -> 11.1, 1024 -> 6.47 (6.27
# as handed in; the ``jax.numpy`` band 10.9).  A grid step is one chain of
# product, row maximum, exp, product that nothing overlaps with the next
# step's, and only inside a block this large does the compiler hide the
# vector work under the MXU's.  Cutting the two blocks an edge crosses into
# strips that skip their unseen tiles (the dense call's tile plan) LOST to
# masking them whole (6.88 against 6.47): a strip is a shorter chain, not a
# cheaper one.
_WINDOW_BLOCK = 1024


def _band_slots(window, block):
    """The static band walk of a causal window over square blocks: a q
    block meets ``slots`` key blocks, the one ``back`` blocks behind it at
    slot ``slots - 1 - back``, its own (the diagonal) last.  Returns
    ``(slots, edges)``; ``edges``: ``{back: limit}`` of the blocks the
    LOWER edge crosses, where a key is seen iff ``row - col < limit`` in
    the block's own coordinates.  Every block between those and the
    diagonal is seen whole and runs with no mask."""
    slots = -(-(window - 1) // block) + 1
    edges = {back: window - back * block for back in range(1, slots)
             if back * block + block - 1 >= window}
    return slots, edges


def _window_fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                       sm_scale, window, block, num_slots):
    """Grid ``(B H, q blocks, num_slots)``: query ``t`` sees key ``s`` iff
    ``0 <= t - s < window``, and the inner axis is as long as the BAND
    (``_band_slots``), not as the sequence.  Slot ``kj`` holds key block
    ``qi - (num_slots - 1 - kj)`` (the index map's, clipped at 0); one
    wholly before key 0 costs its grid step and no product.  Which slots
    the band's two edges cross is static: those pay for a mask, at the
    token; the slots between run with none.  Forward only: no log-sum-exp
    leaves it."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    _, edges = _band_slots(window, block)
    slot_of = lambda back: num_slots - 1 - back
    # rows before the window's first block: their slot lies before key 0
    on_keys = kj >= slot_of(qi)

    @pl.when(kj == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def visit(causal=False, limit=None):
        """The slot's block; ``causal``: it is the diagonal's (``row >=
        col`` is seen); ``limit``: the lower edge crosses it (``row - col <
        limit`` is seen)."""
        def run():
            s = _scores(q_ref[0], k_ref[0], sm_scale)
            if causal or limit is not None:
                ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                         - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
                seen = ahead >= 0 if causal else ahead < limit
                if causal and limit is not None:
                    seen = jnp.logical_and(seen, ahead < limit)
                s = jnp.where(seen, s, NEG_INF)
            m_ref[:], l_ref[:], acc_ref[:] = _online_softmax(
                s, v_ref[0], m_ref[:], l_ref[:], acc_ref[:])
        return run

    edge_slots = {slot_of(back): limit for back, limit in edges.items()
                  if slot_of(back) >= 0}
    for slot, limit in edge_slots.items():
        pl.when(jnp.logical_and(kj == slot, on_keys))(visit(limit=limit))
    first_whole = max(edge_slots, default=-1) + 1
    if first_whole < num_slots - 1:
        pl.when(jnp.logical_and(
            jnp.logical_and(kj >= first_whole, kj < num_slots - 1),
            on_keys))(visit())
    pl.when(kj == num_slots - 1)(
        visit(causal=True, limit=window if window < block else None))

    @pl.when(kj == num_slots - 1)
    def _():
        # a row sees itself: l > 0, and what a slot with no seen key left
        # behind (exp(NEG_INF - NEG_INF) = 1) the first seen key wiped
        o_ref[0] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _window_fwd(q, k, v, window, sm_scale, block, name):
    """``q`` (B, T, H, d) over ``k`` / ``v`` (B, T, Hkv, d), each stacked to
    ``(B heads, T, d)`` as the dense call's; query head ``h`` reads K/V
    head ``h // (H // Hkv)`` through the index map alone: no repeated K/V.
    Returns (B, T, H, d).  Jitted so that a program's layers of one shape
    trace and lower the kernel ONCE (four window layers in each of a
    server's prefill buckets: the kernel's body is what a start-up's
    ``trace+lower`` would pay four times a bucket)."""
    B, T, H, d = q.shape
    G = H // k.shape[2]
    block = min(block, T)
    Tp = -(-T // block) * block
    nq = Tp // block
    num_slots = min(_band_slots(window, block)[0], nq)
    stack = lambda x: _pad_t(x.transpose(0, 2, 1, 3).reshape(-1, T, d), Tp)
    q_spec = pl.BlockSpec((1, block, d), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec(
        (1, block, d),
        lambda b, i, j: (b // G, jnp.maximum(i - (num_slots - 1) + j, 0), 0))
    call = _pallas(
        functools.partial(_window_fwd_kernel, sm_scale=sm_scale,
                          window=window, block=block, num_slots=num_slots),
        grid=(B * H, nq, num_slots),
        in_specs=[q_spec, kv_spec, kv_spec], out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Tp, d), q.dtype),
        scratch=[pltpu.VMEM((block, d), jnp.float32),
                 pltpu.VMEM((block, 1), jnp.float32),
                 pltpu.VMEM((block, 1), jnp.float32)], name=name)
    out = call(stack(q), stack(k), stack(v))
    return out[:, :T].reshape(B, H, T, d).transpose(0, 2, 1, 3)


# ============================================================== backward kernels
def _bwd_dkdv_kernel(*refs, sm_scale, causal, block_q, block_k, num_q_blocks,
                     seq_len, n_heads=1, use_kbias=False,
                     use_abias=False, use_lut=False, tile=None,
                     num_k_blocks=None):
    """Grid: (BH, nk, nq) with nq innermost; accumulates dK/dV for one k block.
    ``use_lut``: inner dim is the live q-block count; scalar-prefetch
    ``(qmap, qlen)`` lead the args and pick the visited q block.
    ``tile``: as in :func:`_fwd_kernel`.

    ``num_k_blocks``: given, this is the FUSED backward (dense calls only,
    ``_fused_backward``): the visit's one ``dS`` also yields its share of
    dQ, ``dS·K``, so no second kernel recomputes the scores.  A third
    output ``dq`` follows ``dk`` and ``dv``.  Where the head's keys are one
    block the visit holds all of a q row's keys and dQ is written straight
    out; else the shares add up in ``dq_acc``, fp32 over the head's whole
    padded sequence ``(nq, block_q, d)``, k block by k block in ascending
    order (grid steps run in order on one core: both inner axes carry
    state), and q block ``i`` is cast and written at its visit under the
    LAST k block, which is where its ``dq`` block index first leaves 0."""
    if use_lut:
        qmap_ref, qlen_ref = refs[:2]
        refs = refs[2:]
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), \
        kb_ref, ab_ref, idx = \
        _unpack_in_refs(refs, 6, use_kbias, use_abias)
    fused = num_k_blocks is not None
    dk_ref, dv_ref = refs[idx:idx + 2]
    dq_ref = refs[idx + 2] if fused else None
    dk_acc, dv_acc, *rest = refs[idx + (3 if fused else 2):]
    dq_acc = rest[0] if rest else None        # only past one k block
    ki = pl.program_id(1)
    qj = pl.program_id(2)

    @pl.when(qj == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if dq_acc is not None:
        @pl.when(ki == 0)
        def _():
            dq_acc[qj] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

    if use_lut:
        h_idx = pl.program_id(0) % n_heads
        qi = qmap_ref[h_idx, ki, qj]
        should_compute = qj < qlen_ref[h_idx, ki]
    else:
        qi = qj
        should_compute = True
        if causal:
            should_compute = qi * block_q + (block_q - 1) >= ki * block_k

    def masked_scores(q, k):
        s = _scores(q, k, sm_scale)
        if use_kbias:
            s = s + kb_ref[0, 0]
        if use_abias:
            s = s + ab_ref[0, 0]
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = jnp.logical_and(q_pos < seq_len, k_pos < seq_len)
        if causal:
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        return jnp.where(valid, s, NEG_INF)

    def accumulate(q, k, v, do, lse, delta, dk, dv, scores):
        p, ds = _p_and_ds(scores(q, k), v, do, lse, delta, sm_scale)
        ds = ds.astype(q.dtype)
        # dK += dS^T Q ; dV += P^T dO ; and the visit's share of dQ, dS K
        contract_rows = (((0,), (0,)), ((), ()))
        dk = dk + jax.lax.dot_general(
            ds, q, contract_rows, preferred_element_type=jnp.float32)
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, contract_rows,
            preferred_element_type=jnp.float32)
        dq = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) if fused else None
        return dk, dv, dq

    def add_dq(rows, dq):
        if dq_acc is not None:
            dq_acc[qj, rows] += dq
        elif fused:       # the head's one k block: all of these rows' keys
            dq_ref[0, rows] = dq.astype(dq_ref.dtype)

    def whole_block(scores):
        def visit():
            dk_acc[:], dv_acc[:], dq = accumulate(
                q_ref[0], k_ref[0], v_ref[0], do_ref[0],
                lse_ref[0][:, :1],           # (bq, 1) — lane-broadcast stat
                delta_ref[0][:, :1], dk_acc[:], dv_acc[:], scores)
            add_dq(slice(None), dq)
        return visit

    def diagonal():
        plan = _tile_plan(block_q, block_k, tile, True)
        for rows, cols in _strips(plan, tile):
            dk_acc[cols], dv_acc[cols], dq = accumulate(
                q_ref[0, rows], k_ref[0, cols], v_ref[0, cols],
                do_ref[0, rows], lse_ref[0, rows][:, :1],
                delta_ref[0, rows][:, :1], dk_acc[cols], dv_acc[cols],
                lambda q, k: _strip_scores(q, k, sm_scale, tile))
            add_dq(rows, dq)

    if tile is None:
        pl.when(should_compute)(whole_block(masked_scores))
    else:
        if num_q_blocks > 1:      # under the diagonal: no tile masked
            pl.when(qj > ki)(whole_block(
                lambda q, k: _scores(q, k, sm_scale)))
        pl.when(qj == ki)(diagonal)

    @pl.when(qj == num_q_blocks - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    if dq_acc is not None:
        @pl.when(ki == num_k_blocks - 1)
        def _():
            dq_ref[0] = dq_acc[qj].astype(dq_ref.dtype)


def _bwd_dq_kernel(*refs, sm_scale, causal, block_q, block_k, num_k_blocks,
                   seq_len, n_heads=1, use_kbias=False,
                   use_abias=False, use_lut=False, tile=None):
    """Grid: (BH, nq, nk) with nk innermost; accumulates dQ for one q block.
    ``use_lut``: inner dim is the live k-block count (same LUT as forward).
    ``tile``: as in :func:`_fwd_kernel`."""
    if use_lut:
        kmap_ref, klen_ref = refs[:2]
        refs = refs[2:]
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), \
        kb_ref, ab_ref, idx = \
        _unpack_in_refs(refs, 6, use_kbias, use_abias)
    dq_ref, dq_acc = refs[idx:idx + 2]
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    if use_lut:
        h_idx = pl.program_id(0) % n_heads
        ki = kmap_ref[h_idx, qi, kj]
        should_compute = kj < klen_ref[h_idx, qi]
    else:
        ki = kj
        should_compute = True
        if causal:
            should_compute = ki * block_k <= qi * block_q + (block_q - 1)

    def masked_scores(q, k):
        s = _scores(q, k, sm_scale)
        if use_kbias:
            s = s + kb_ref[0, 0]
        if use_abias:
            s = s + ab_ref[0, 0]
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = jnp.logical_and(q_pos < seq_len, k_pos < seq_len)
        if causal:
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        return jnp.where(valid, s, NEG_INF)

    def accumulate(q, k, v, do, lse, delta, dq, scores):
        _, ds = _p_and_ds(scores(q, k), v, do, lse, delta, sm_scale)
        return dq + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def whole_block(scores):
        def visit():
            dq_acc[:] = accumulate(
                q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0][:, :1],
                delta_ref[0][:, :1], dq_acc[:], scores)
        return visit

    def diagonal():
        plan = _tile_plan(block_q, block_k, tile, True)
        for rows, cols in _strips(plan, tile):
            dq_acc[rows] = accumulate(
                q_ref[0, rows], k_ref[0, cols], v_ref[0, cols],
                do_ref[0, rows], lse_ref[0, rows][:, :1],
                delta_ref[0, rows][:, :1], dq_acc[rows],
                lambda q, k: _strip_scores(q, k, sm_scale, tile))

    if tile is None:
        pl.when(should_compute)(whole_block(masked_scores))
    else:
        if num_k_blocks > 1:      # under the diagonal: no tile masked
            pl.when(kj < qi)(whole_block(
                lambda q, k: _scores(q, k, sm_scale)))
        pl.when(kj == qi)(diagonal)

    @pl.when(kj == num_k_blocks - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd(sm_scale, causal, block_q, block_k, residuals, dout,
         n_heads=None, dlse=None, k_bias=None, attn_bias=None,
         luts=None):
    q, k, v, out, lse = residuals
    BH, T, d = q.shape
    use_lut = luts is not None
    if use_lut:
        kmap, klen, qmap, qlen = luts
    block_q, block_k = _auto_blocks(T, d, block_q, block_k)
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    # pad to a multiple of BOTH block sizes (lcm), else the smaller-block
    # grid still has an out-of-range tail block that dynamic-slice clamping
    # would silently shift
    blk = np.lcm(block_q, block_k)
    Tp = int(np.ceil(T / blk) * blk)
    nq = pl.cdiv(Tp, block_q)
    nk = pl.cdiv(Tp, block_k)

    # delta_i = rowsum(dO * O) — cheap, fused by XLA
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if dlse is not None:
        # lse is ALSO a primal output (flash_attention_with_lse):
        # ∂lse/∂s = p, so the lse cotangent enters as ds += p·dlse — i.e. the
        # kernels' ds = p·(dp − delta) absorbs it via delta ← delta − dlse
        delta = delta - dlse.astype(jnp.float32)
    if Tp != T:
        pad2 = lambda x: jnp.pad(x, ((0, 0), (0, Tp - T)))
        q, k, v, dout = (_pad_t(a, Tp) for a in (q, k, v, dout))
        lse, delta = pad2(lse), pad2(delta)
    # stats enter the kernels lane-broadcast (Mosaic 128-lane tiling)
    bcast = lambda x: jnp.broadcast_to(x[:, :, None], (BH, Tp, MIN_LANES))
    lse, delta = bcast(lse), bcast(delta)

    H = n_heads or 1
    dense = not use_lut and k_bias is None and attn_bias is None
    tile = _causal_tile(causal, block_q, block_k, d, dense)
    fused = _fused_backward(dense, nq, nk, block_q, d)
    call_key = (BH, d, nq, nk, block_q, block_k, causal, tile)
    _bwd_forms[call_key + (use_lut, k_bias is None, attn_bias is None)] = (
        "bwd_fused" if fused else "bwd_split")
    if not use_lut:
        for kind in ("bwd",) if fused else ("dkdv", "dq"):
            _record_tiles(kind, *call_key)
    if use_lut:
        # dK/dV grid: (BH, nk, live-q); the visited q block is qmap[h, j, i]
        qrow_idx = lambda b, j, i, qm, ql: (b, qm[jax.lax.rem(b, H), j, i], 0)
        kcol_idx = lambda b, j, i, qm, ql: (b, j, 0)
        kb_ji = lambda b, j, i, qm, ql: (jax.lax.div(b, H), j, 0, 0)
        ab_ji = lambda b, j, i, qm, ql: (qm[jax.lax.rem(b, H), j, i], j, 0, 0)
        n_inner_q = qmap.shape[2]
    else:
        qrow_idx = lambda b, j, i: (b, i, 0)
        kcol_idx = lambda b, j, i: (b, j, 0)
        kb_ji = lambda b, j, i: (jax.lax.div(b, H), j, 0, 0)
        ab_ji = lambda b, j, i: (i, j, 0, 0)
        n_inner_q = nq
    stat_spec_ji = pl.BlockSpec((1, block_q, MIN_LANES), qrow_idx)
    dkdv_specs = [
        pl.BlockSpec((1, block_q, d), qrow_idx),   # q
        pl.BlockSpec((1, block_k, d), kcol_idx),   # k
        pl.BlockSpec((1, block_k, d), kcol_idx),   # v
        pl.BlockSpec((1, block_q, d), qrow_idx),   # do
        stat_spec_ji,                              # lse
        stat_spec_ji,                              # delta
    ]
    if k_bias is not None:
        k_bias = _tile_kbias(k_bias, k_bias.shape[1], Tp, block_k)
    if attn_bias is not None:
        attn_bias = _tile_abias(attn_bias, attn_bias.shape[0], Tp,
                                block_q, block_k)
    dkdv_args = (q, k, v, dout, lse, delta)
    if k_bias is not None:
        dkdv_specs.append(pl.BlockSpec((1, 1, 1, block_k), kb_ji))
        dkdv_args = dkdv_args + (k_bias,)
    if attn_bias is not None:
        dkdv_specs.append(pl.BlockSpec((1, 1, block_q, block_k), ab_ji))
        dkdv_args = dkdv_args + (attn_bias,)
    dkdv_kernel = functools.partial(
        _bwd_dkdv_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_q_blocks=n_inner_q,
        seq_len=T, n_heads=H, use_kbias=k_bias is not None,
        use_abias=attn_bias is not None, use_lut=use_lut, tile=tile,
        num_k_blocks=nk if fused else None)
    dkdv_out_specs = [
        pl.BlockSpec((1, block_k, d), kcol_idx),
        pl.BlockSpec((1, block_k, d), kcol_idx),
    ]
    dkdv_out_shape = [
        jax.ShapeDtypeStruct((BH, Tp, d), k.dtype),
        jax.ShapeDtypeStruct((BH, Tp, d), v.dtype),
    ]
    dkdv_scratch = [
        pltpu.VMEM((block_k, d), jnp.float32),
        pltpu.VMEM((block_k, d), jnp.float32),
    ]
    if fused:
        # q block i's dq is whole at its visit under the LAST k block and
        # is written there; until then the block index rests at 0, so no
        # half-summed block travels to HBM
        dkdv_out_specs.append(pl.BlockSpec(
            (1, block_q, d),
            lambda b, j, i: (b, jnp.where(j == nk - 1, i, 0), 0)))
        dkdv_out_shape.append(jax.ShapeDtypeStruct((BH, Tp, d), q.dtype))
        if nk > 1:
            dkdv_scratch.append(pltpu.VMEM((nq, block_q, d), jnp.float32))
    call = _pallas(dkdv_kernel, grid=(BH, nk, n_inner_q),
                   in_specs=dkdv_specs, out_specs=dkdv_out_specs,
                   out_shape=dkdv_out_shape, scratch=dkdv_scratch,
                   num_prefetch=2 if use_lut else 0,
                   carried_axes=2 if fused else 1)
    if fused:
        dk, dv, dq = call(*dkdv_args)
        return dq[:, :T], dk[:, :T], dv[:, :T]
    dk, dv = (call(qmap, qlen, *dkdv_args) if use_lut
              else call(*dkdv_args))

    if use_lut:
        q_ij = lambda b, i, j, km, kl: (b, i, 0)
        kv_ij = lambda b, i, j, km, kl: (b, km[jax.lax.rem(b, H), i, j], 0)
        kb_ij = lambda b, i, j, km, kl: (
            jax.lax.div(b, H), km[jax.lax.rem(b, H), i, j], 0, 0)
        ab_ij = lambda b, i, j, km, kl: (i, km[jax.lax.rem(b, H), i, j], 0, 0)
        n_inner_k = kmap.shape[2]
    else:
        q_ij = lambda b, i, j: (b, i, 0)
        kv_ij = lambda b, i, j: (b, j, 0)
        kb_ij = lambda b, i, j: (jax.lax.div(b, H), j, 0, 0)
        ab_ij = lambda b, i, j: (i, j, 0, 0)
        n_inner_k = nk
    stat_spec_ij = pl.BlockSpec((1, block_q, MIN_LANES), q_ij)
    dq_specs = [
        pl.BlockSpec((1, block_q, d), q_ij),
        pl.BlockSpec((1, block_k, d), kv_ij),
        pl.BlockSpec((1, block_k, d), kv_ij),
        pl.BlockSpec((1, block_q, d), q_ij),
        stat_spec_ij,
        stat_spec_ij,
    ]
    dq_args = (q, k, v, dout, lse, delta)
    if k_bias is not None:
        dq_specs.append(pl.BlockSpec((1, 1, 1, block_k), kb_ij))
        dq_args = dq_args + (k_bias,)
    if attn_bias is not None:
        dq_specs.append(pl.BlockSpec((1, 1, block_q, block_k), ab_ij))
        dq_args = dq_args + (attn_bias,)
    dq_kernel = functools.partial(
        _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k_blocks=n_inner_k,
        seq_len=T, n_heads=H, use_kbias=k_bias is not None,
        use_abias=attn_bias is not None, use_lut=use_lut, tile=tile)
    dq_out_spec = pl.BlockSpec((1, block_q, d), q_ij)
    dq_out_shape = jax.ShapeDtypeStruct((BH, Tp, d), q.dtype)
    dq_scratch = [pltpu.VMEM((block_q, d), jnp.float32)]
    call = _pallas(dq_kernel, grid=(BH, nq, n_inner_k), in_specs=dq_specs,
                   out_specs=dq_out_spec, out_shape=dq_out_shape,
                   scratch=dq_scratch, num_prefetch=2 if use_lut else 0)
    dq = call(kmap, klen, *dq_args) if use_lut else call(*dq_args)

    return dq[:, :T], dk[:, :T], dv[:, :T]


# ================================================================== public API
def _named_residuals(out, lse, residual):
    """``out`` and ``lse`` under a name a ``save_only_these_names`` remat
    policy can list: the backward kernels read exactly these two, so a
    policy that saves them leaves the rematerialised backward no forward
    kernel to run again.  ``residual``: ``(name, n_heads)`` or ``None``.
    ``out`` is named in the model's layout ``(B, T, H·d)``, which the
    caller's next matmul reads anyway, and handed on as a transpose of
    that: a stacked ``(B·H, T, d)`` with ``d`` under 128 is padded to 128
    lanes, twice its bytes, which on ``train_z1`` made XLA rematerialise a
    matmul to fit and took back most of the gain (PERF.md §6, PR 33)."""
    if residual is None:
        return out, lse
    name, H = residual
    BH, T, d = out.shape
    B = BH // H
    btd = checkpoint_name(
        out.reshape(B, H, T, d).transpose(0, 2, 1, 3).reshape(B, T, H * d),
        name)
    out = btd.reshape(B, T, H, d).transpose(0, 2, 1, 3).reshape(BH, T, d)
    return out, checkpoint_name(lse, name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhtd(q, k, v, sm_scale, causal, block_q, block_k,
                residual=None):
    out, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k)
    return out


def _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k, residual):
    out, lse = _named_residuals(
        *_fwd(q, k, v, sm_scale, causal, block_q, block_k), residual)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(sm_scale, causal, block_q, block_k, residual,
                    residuals, dout):
    return _bwd(sm_scale, causal, block_q, block_k, residuals, dout)


_flash_bhtd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal=True, sm_scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    key_padding_bias=None, attn_bias=None,
                    residual_name=None):
    """Flash attention over (B, T, H, d) tensors (the model layout).

    Returns (B, T, H, d).  fp32 softmax statistics, input-dtype matmuls.
    ``key_padding_bias`` (B, T) and ``attn_bias`` (T, T) are ADDITIVE score
    biases applied in-kernel (use ``NEG_INF`` entries to mask) — the
    reference's masked softmax kernels (``softmax_kernels.cu``).

    ``residual_name``: the ``checkpoint_name`` under which the two
    residuals only the forward kernel can make, its output (named as
    ``(B, T, H·d)``) and the log-sum-exp ``(B·H, T)`` float32, are handed
    to the backward.  Under ``jax.checkpoint`` with a
    ``save_only_these_names`` policy that lists it they are saved
    (``2·H·d + 4·H`` bytes a token in bfloat16) and the rematerialised
    backward runs one kernel a call (two, dK/dV and dQ, past the fused
    backward's budget: ``_fused_backward``) and no second forward;
    with any other policy, or ``None`` here, the name changes nothing.
    Not carried by the biased variants.
    """
    if key_padding_bias is not None or attn_bias is not None:
        return _biased_call(q, k, v, None, key_padding_bias, attn_bias,
                            sm_scale, causal, block_q, block_k)
    B, T, H, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    block_q, block_k = _auto_blocks(T, d, block_q, block_k)
    # (B, T, H, d) → (B*H, T, d)
    to_bhtd = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, d)
    out = _flash_bhtd(to_bhtd(q), to_bhtd(k), to_bhtd(v),
                      float(sm_scale), bool(causal), int(block_q), int(block_k),
                      residual_name and (residual_name, H))
    return out.reshape(B, H, T, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse_bhtd(q, k, v, sm_scale, causal, block_q, block_k,
                    residual=None):
    return _fwd(q, k, v, sm_scale, causal, block_q, block_k)


def _flash_lse_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k,
                        residual):
    out, lse = _named_residuals(
        *_fwd(q, k, v, sm_scale, causal, block_q, block_k), residual)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd_rule(sm_scale, causal, block_q, block_k, residual,
                        residuals, cts):
    dout, dlse = cts
    return _bwd(sm_scale, causal, block_q, block_k, residuals, dout,
                dlse=dlse)


_flash_lse_bhtd.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def flash_attention_with_lse(q, k, v, *, causal=True, sm_scale=None,
                             block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                             residual_name=None):
    """Flash attention returning ``(out (B,T,H,d), lse (B,H,T))`` with BOTH
    outputs differentiable — the building block for ring attention, where
    per-device partial results merge via their logsumexp statistics.
    ``residual_name``: as in :func:`flash_attention`."""
    B, T, H, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    block_q, block_k = _auto_blocks(T, d, block_q, block_k)
    to_bhtd = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, d)
    out, lse = _flash_lse_bhtd(to_bhtd(q), to_bhtd(k), to_bhtd(v),
                               float(sm_scale), bool(causal), int(block_q),
                               int(block_k),
                               residual_name and (residual_name, H))
    return (out.reshape(B, H, T, d).transpose(0, 2, 1, 3),
            lse.reshape(B, H, T))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _window_call(q, k, v, window, sm_scale, name):
    return _window_fwd(q, k, v, window, sm_scale, _WINDOW_BLOCK, name)


def _window_no_grad(*_):
    raise NotImplementedError(
        "flash_attention_window is forward-only: no windowed backward kernel "
        "exists (flash_attention's causal backward is another function's)")


_window_call.defvjp(_window_no_grad, _window_no_grad)


def flash_attention_window(q, k, v, *, window, sm_scale=None, name=None):
    """The flash FORWARD under a causal window: ``q`` (B, T, H, d) over
    ``k`` / ``v`` (B, T, Hkv, d) with ``Hkv`` dividing ``H`` (query head
    ``h`` reads K/V head ``h // (H // Hkv)``); query ``t`` sees key ``s``
    iff ``0 <= t - s < window``, the edge at the TOKEN whatever the block.
    Returns (B, T, H, d).  A block of queries walks only the key blocks
    between the band's lower edge and the diagonal (``_band_slots``:
    ``ceil((window - 1) / block) + 1`` grid steps a block of
    ``_WINDOW_BLOCK`` queries, 5 at a window of 4,096, of which the 2 an edge
    crosses are masked); the scores never leave VMEM; fp32 statistics, input-dtype products, as
    :func:`flash_attention`.  ``T <= window`` is the causal triangle, walked
    the same way.

    FORWARD ONLY: under ``jax.grad`` it raises ``NotImplementedError`` by
    name.  ``name``: the kernel's name in a device trace."""
    window = int(window)
    H, Hkv = q.shape[2], k.shape[2]
    if window < 1 or H % Hkv or k.shape != v.shape \
            or k.shape != q.shape[:2] + (Hkv, q.shape[3]):
        raise ValueError(
            f"flash_attention_window: window {window}, q {q.shape}, k "
            f"{k.shape}, v {v.shape}: a window of at least 1 and K/V (B, T, "
            "Hkv, d) with Hkv dividing q's heads")
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    return _window_call(q, k, v, window, float(sm_scale), name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def _sparse_bhtd(q, k, v, kmap, klen, qmap, qlen, sm_scale, causal, block_q,
                 block_k, n_heads, banded=None):
    out, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k,
                  n_heads=n_heads, kmap=kmap, klen=klen, banded=banded)
    return out


def _sparse_fwd_rule(q, k, v, kmap, klen, qmap, qlen, sm_scale, causal,
                     block_q, block_k, n_heads, banded=None):
    out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k,
                    n_heads=n_heads, kmap=kmap, klen=klen, banded=banded)
    return out, (q, k, v, out, lse, kmap, klen, qmap, qlen)


def _sparse_bwd_rule(sm_scale, causal, block_q, block_k, n_heads, banded,
                     residuals, dout):
    q, k, v, out, lse, kmap, klen, qmap, qlen = residuals
    dq, dk, dv = _bwd(sm_scale, causal, block_q, block_k, (q, k, v, out, lse),
                      dout, n_heads=n_heads, luts=(kmap, klen, qmap, qlen))
    return dq, dk, dv, None, None, None, None


_sparse_bhtd.defvjp(_sparse_fwd_rule, _sparse_bwd_rule)


@functools.lru_cache(maxsize=64)
def _banded_structure(layout_bytes, shape, causal):
    """Detect a causal BAND + GLOBAL-COLUMNS structure in a shared-head
    layout: live(i, j) ⟺ 0 <= i-j < W  OR  (j ∈ gcols and j <= i).

    Fixed/BSLongformer sliding-window layouts have exactly this shape, and
    it compiles to STATIC affine index maps — no LUT, no scalar prefetch,
    dense-kernel pipelining.  (Measured: the scalar-prefetch LUT grid costs
    ~2-3x per visited slot vs static maps regardless of predication, DMA
    strategy, or grid shape — small-T sparse wins need the static form.)
    Returns (W, gcols) or None when the layout is not band-expressible.
    """
    H, nq, nk = shape
    if H != 1 or nq != nk or not causal:
        return None
    lay = np.frombuffer(layout_bytes, np.int32).reshape(shape)[0] > 0
    ii, jj = np.meshgrid(np.arange(nq), np.arange(nk), indexing="ij")
    live = lay & (jj <= ii)                       # causal block pruning
    # global columns: live in EVERY causal row
    causal_rows = ii >= jj
    gcols = tuple(int(c) for c in range(nk)
                  if np.array_equal(live[:, c], causal_rows[:, c]))
    rest = live.copy()
    rest[:, list(gcols)] = False
    deltas = np.unique((ii - jj)[rest])
    W = int(deltas.max()) + 1 if deltas.size else 0
    if deltas.size and not np.array_equal(deltas, np.arange(W)):
        return None                               # non-contiguous band
    implied = (((ii - jj) >= 0) & ((ii - jj) < W))
    for c in gcols:
        implied[:, c] |= causal_rows[:, c]
    if not np.array_equal(implied, live):
        return None
    if W + len(gcols) >= nk:                      # no sparsity to exploit
        return None
    return W, gcols


def _layout_luts(layout, T, H, causal, block_q, block_k):
    """Host-static layout → per-head jnp LUTs (cached by layout content)."""
    layout = np.asarray(layout, np.int32)   # raises on traced layouts: the
    # block pattern must be static — it sizes the Pallas grid
    Lh, nq, nk = layout.shape
    assert Lh in (1, H), \
        f"layout has {Lh} head layouts; expected 1 (shared) or H={H}"
    if Lh == 1 and H > 1:
        layout = np.broadcast_to(layout, (H, nq, nk))
    layout = np.ascontiguousarray(layout)
    kmap, klen, qmap, qlen = _sparse_luts(
        layout.tobytes(), layout.shape, bool(causal), block_q, block_k)
    return (jnp.asarray(kmap), jnp.asarray(klen),
            jnp.asarray(qmap), jnp.asarray(qlen))


@functools.lru_cache(maxsize=64)
def _merged_luts_cached(layout_bytes, shape, causal, block_q, block_k):
    """Merged-row grid LUTs: pairs of layout q-rows share one kernel row
    of 2x block_q (union of their live k blocks), with per-half-row
    sub-masks preserving the declared layout exactly.  Halving the q-row
    count halves the kernel's fixed per-row cost (the padded-slot waste
    VERDICT r3 #5 names) without touching which tokens attend."""
    layout = np.frombuffer(layout_bytes, np.int32).reshape(shape)
    H, nq, nk = shape
    assert nq % 2 == 0
    merged = np.maximum(layout[:, 0::2, :], layout[:, 1::2, :])
    kmap, klen, _, _ = _sparse_luts(
        np.ascontiguousarray(merged).tobytes(), merged.shape, causal,
        2 * block_q, block_k)
    # per-half-row liveness at the visited block: sub0 = upper (even) row.
    # Vectorized gather (kmap is (H, nq/2, slots) of k-block ids): a Python
    # triple loop here costs millions of interpreter iterations at
    # production shapes — a multi-second trace-time stall per layout.
    sub0 = np.take_along_axis(layout[:, 0::2, :], kmap, axis=2)
    sub1 = np.take_along_axis(layout[:, 1::2, :], kmap, axis=2)
    return kmap, klen, sub0, sub1


@functools.partial(jax.custom_vjp, nondiff_argnums=(11, 12, 13, 14, 15))
def _sparse_merged_bhtd(q, k, v, kmapM, klenM, sub0, sub1, kmap, klen,
                        qmap, qlen, sm_scale, causal, block_q, block_k, H):
    out, _ = _fwd(q, k, v, sm_scale, causal, 2 * block_q, block_k,
                  n_heads=H, kmap=kmapM, klen=klenM, sub01=(sub0, sub1))
    return out


def _sparse_merged_fwd_rule(q, k, v, kmapM, klenM, sub0, sub1, kmap, klen,
                            qmap, qlen, sm_scale, causal, block_q, block_k,
                            H):
    # merged forward ALSO runs for the residual lse (same program)
    out, lse = _fwd(q, k, v, sm_scale, causal, 2 * block_q, block_k,
                    n_heads=H, kmap=kmapM, klen=klenM, sub01=(sub0, sub1))
    return out, (q, k, v, out, lse, kmap, klen, qmap, qlen)


def _sparse_merged_bwd_rule(sm_scale, causal, block_q, block_k, H,
                            residuals, dout):
    q, k, v, out, lse, kmap, klen, qmap, qlen = residuals
    # backward runs the ORIGINAL (unmerged) LUT kernels — bit-identical
    # gradients to the unmerged path
    dq, dk, dv = _bwd(sm_scale, causal, block_q, block_k,
                      (q, k, v, out, lse), dout, n_heads=H,
                      luts=(kmap, klen, qmap, qlen))
    none4 = (None, None, None, None)
    return (dq, dk, dv) + none4 + none4


_sparse_merged_bhtd.defvjp(_sparse_merged_fwd_rule, _sparse_merged_bwd_rule)


def sparse_flash_attention(q, k, v, layout, *, causal=True, sm_scale=None,
                           block_q=None, block_k=None, block_q_merge=1,
                           key_padding_bias=None, attn_bias=None):
    """Block-sparse flash attention over (B, T, H, d).

    ``layout``: (n_heads_or_1, nq, nk) HOST-STATIC int block mask from a
    SparsityConfig (reference ``ops/sparse_attention/sparsity_config.py``
    hierarchy).  The block size is implied: block_q = T // nq, block_k =
    T // nk.  The layout compiles into per-row LUTs that SIZE the Pallas
    grid (reference: the Triton kernels' ``make_lut``,
    ``ops/sparse_attention/matmul.py:288,429``): the inner grid dimension is
    the max live-block count per q row, the BlockSpec index maps follow the
    LUT, and skipped blocks skip their K/V DMA entirely — HBM traffic and
    MXU time both scale with density.  TPU note: MXU efficiency needs
    layout blocks >= 128 (ideally 256-512); GPU-oriented block=16 layouts
    run correct but slow.
    """
    B, T, H, d = q.shape
    Lh, nq, nk = layout.shape
    if block_q is None:
        block_q = T // nq
    if block_k is None:
        block_k = T // nk
    assert block_q * nq == T and block_k * nk == T, \
        f"layout {layout.shape} incompatible with T={T}"
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    luts = _layout_luts(layout, T, H, causal, int(block_q), int(block_k))
    if key_padding_bias is not None or attn_bias is not None:
        assert block_q_merge == 1, \
            "block_q_merge composes with the unbiased path only"
        return _biased_call(q, k, v, luts, key_padding_bias, attn_bias,
                            sm_scale, causal, block_q, block_k)
    to_bhtd = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, d)
    if block_q_merge > 1:
        assert block_q_merge == 2 and nq % 2 == 0, \
            "block_q_merge=2 is the supported row-merge factor"
        lay = np.asarray(layout, np.int32)
        if lay.shape[0] == 1 and H > 1:
            lay = np.ascontiguousarray(np.broadcast_to(lay, (H, nq, nk)))
        mk, ml, s0, s1 = _merged_luts_cached(
            lay.tobytes(), lay.shape, bool(causal), int(block_q),
            int(block_k))
        out = _sparse_merged_bhtd(
            to_bhtd(q), to_bhtd(k), to_bhtd(v),
            jnp.asarray(mk), jnp.asarray(ml), jnp.asarray(s0),
            jnp.asarray(s1), *luts, float(sm_scale), bool(causal),
            int(block_q), int(block_k), int(H))
        return out.reshape(B, H, T, d).transpose(0, 2, 1, 3)
    # band+global layouts (Fixed/BSLongformer windows) compile to static
    # affine index maps — dense-kernel pipelining, no LUT machinery; the
    # forward q block grows past the layout block (the LUT grid's real
    # per-slot handicap) while k slots stay layout-sized for block-
    # granular skipping
    banded = None
    lay_np = np.ascontiguousarray(np.asarray(layout, np.int32))
    st = _banded_structure(lay_np.tobytes(), lay_np.shape, bool(causal))
    if st is not None and block_q == block_k:
        # q block stays at the layout block: growing it to 1024 measured
        # SLOWER (masked-dead halves of tall rows compute; 0.464 vs 0.329
        # ms at T=4096) — the (bq, Lb) shape sweet spot is the layout's
        banded = (st[0], st[1], int(block_k), int(block_q))
    out = _sparse_bhtd(to_bhtd(q), to_bhtd(k), to_bhtd(v), *luts,
                       float(sm_scale), bool(causal), int(block_q),
                       int(block_k), int(H), banded)
    return out.reshape(B, H, T, d).transpose(0, 2, 1, 3)


# ----------------------------------------------------- biased (masked) paths
@functools.lru_cache(maxsize=None)
def _make_biased_bhtd(has_luts, has_kb, has_ab):
    """custom_vjp'd flash attention with optional in-kernel additive biases.

    One cached instance per (luts?, key-bias?, attn-bias?) combination so
    unused operands never materialize.  Bias cotangents are zeros: masks are
    constants (the reference's mask tensors carry no grad either)."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13))
    def f(q, k, v, kmap, klen, qmap, qlen, kb, ab, sm_scale, causal,
          block_q, block_k, H):
        out, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k,
                      n_heads=H,
                      kmap=kmap if has_luts else None,
                      klen=klen if has_luts else None,
                      k_bias=kb if has_kb else None,
                      attn_bias=ab if has_ab else None)
        return out

    def fwd_rule(q, k, v, kmap, klen, qmap, qlen, kb, ab, sm_scale, causal,
                 block_q, block_k, H):
        out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k,
                        n_heads=H,
                        kmap=kmap if has_luts else None,
                        klen=klen if has_luts else None,
                        k_bias=kb if has_kb else None,
                        attn_bias=ab if has_ab else None)
        return out, (q, k, v, out, lse, kmap, klen, qmap, qlen, kb, ab)

    def bwd_rule(sm_scale, causal, block_q, block_k, H, res, dout):
        q, k, v, out, lse, kmap, klen, qmap, qlen, kb, ab = res
        dq, dk, dv = _bwd(sm_scale, causal, block_q, block_k,
                          (q, k, v, out, lse), dout, n_heads=H,
                          luts=((kmap, klen, qmap, qlen) if has_luts
                                else None),
                          k_bias=kb if has_kb else None,
                          attn_bias=ab if has_ab else None)
        return (dq, dk, dv, None, None, None, None,
                jnp.zeros_like(kb), jnp.zeros_like(ab))

    f.defvjp(fwd_rule, bwd_rule)
    return f


def _biased_call(q, k, v, luts, key_padding_bias, attn_bias, sm_scale,
                 causal, block_q, block_k):
    """(B, T, H, d) entry shared by the dense and block-sparse biased paths."""
    B, T, H, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    block_q, block_k = _auto_blocks(T, d, block_q, block_k)
    has_luts = luts is not None
    has_kb = key_padding_bias is not None
    has_ab = attn_bias is not None
    dummy_i = jnp.zeros((1, 1, 1), jnp.int32)
    dummy_l = jnp.zeros((1, 1), jnp.int32)
    dummy_f = jnp.zeros((1, 1), jnp.float32)
    kmap, klen, qmap, qlen = luts if has_luts else (dummy_i, dummy_l,
                                                    dummy_i, dummy_l)
    fn = _make_biased_bhtd(has_luts, has_kb, has_ab)
    to_bhtd = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, d)
    out = fn(to_bhtd(q), to_bhtd(k), to_bhtd(v), kmap, klen, qmap, qlen,
             jnp.asarray(key_padding_bias, jnp.float32) if has_kb else dummy_f,
             jnp.asarray(attn_bias, jnp.float32) if has_ab else dummy_f,
             float(sm_scale), bool(causal), int(block_q), int(block_k), int(H))
    return out.reshape(B, H, T, d).transpose(0, 2, 1, 3)


def attention_reference(q, k, v, *, causal=True, sm_scale=None):
    """Pure-jnp oracle for numerics tests."""
    B, T, H, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


def sparse_attention_reference(q, k, v, layout, *, causal=True, sm_scale=None):
    """Dense oracle: expand the block layout to an element mask."""
    B, T, H, d = q.shape
    Lh, nq, nk = layout.shape
    bq, bk = T // nq, T // nk
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    mask = jnp.kron(jnp.asarray(layout, jnp.float32),
                    jnp.ones((bq, bk), jnp.float32)) > 0  # (Lh, T, T)
    if Lh == 1 and H > 1:
        mask = jnp.broadcast_to(mask, (H, T, T))
    if causal:
        mask = jnp.logical_and(mask, jnp.tril(jnp.ones((T, T), bool))[None])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)
