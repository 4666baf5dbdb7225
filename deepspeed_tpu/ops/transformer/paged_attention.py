"""In-place paged attention — Pallas TPU kernel over the shared KV pool.

Role parity: the reference's fused inference attention
(``csrc/transformer/inference/csrc/softmax.cu`` + the workspace
``layer_past`` walk) generalized to the serving layer's paged pool
(``inference/paged_kv.py``).  The gather-based paged decode
(``paged_kv.gather_kv``) materializes each slot's dense
``(B, nb_max·block_size, H, hd)`` K/V view per layer per step — written
once and read once, 4× the slot's KV bytes of HBM traffic, growing with
the batch (``analysis/roofline.py``: ``gather_materialization_bytes``;
the ledger's ``kernels.paged_attention_roofline`` is this kernel's).  This
kernel deletes the copy: per-slot **block tables and lengths enter as
scalar-prefetch operands**, K/V blocks are DMA'd **directly from the
pool in HBM**, int8 pools dequantize **in-kernel** from the fp32 block
scales (payload reads priced at 1 byte/element; the scales alone are
gathered), and the softmax accumulates over the slot's block walk — no
gathered K/V copy, the pool untouched (read-only; donation of the pool
through the decode step is unaffected).

Two modes, one call (written the way ``flash_attention.py`` carries its
BlockSpec-LUT and manual-DMA variants side by side):

- ``online`` — the compiled TPU path: grid ``(B,)``, one program per
  slot, the slot's **live** tokens (``length + W`` — short slots skip
  their tail entirely) fetched in chunks through a triple-buffered VMEM
  ring with explicit ``make_async_copy`` from the HBM-resident pool,
  masked **online-softmax** (fp32 running max/denominator), one update a
  chunk.  The ``(R, Tc)`` **score tile is chosen from the input**
  (:func:`score_tile`, a plain function of the pool's width in bytes, the
  block size, the table's length and the score rows): a chunk costs about
  the same whatever it carries, so ``Tc`` is the fewest whole blocks and
  whole 128-lane score columns whose K rows carry ``_CHUNK_BYTES`` — 128
  tokens of a bfloat16 pool 2,048 lanes wide, 256 at 1,024 or 1,280 lanes,
  1,024 at 256, 2,048 at 128 — and ``R`` is the real (window row, head)
  pairs, packed and rounded up once to a sublane tile.  The walk does live work
  only: a **dead row** (an empty slot: ``tables[b, 0]`` is the scratch
  block) issues no DMA, runs no matmul and writes zeros; the **ring is
  carried** across programs (the grid is sequential and scratch
  persists: program 0 writes a plan into SMEM — each row's next live
  row and the ring position of its first chunk — and position p's
  fetch issues before position p - 2 computes, whichever row owns it,
  the ``_fwd_kernel_dma`` discipline stretched over the whole call);
  and the **tail is trimmed**: a chunk fetches only the blocks that
  hold a live position (in a loop over them), a row's last chunk runs
  its products over the fewest tiles of ``_TAIL_TOKENS`` that cover its
  live positions, the value tile's dead rows zeroed before ``P @ V``;
  every chunk before the last is live whole, lands under one wait a leaf
  and runs with no mask.  Every tile keeps the pool's ``(rows, H·hd)``
  shape: Mosaic DMAs and slices in whole 128-lane tiles, so a 64-lane head is never
  cut out — heads are separated by a block-diagonal query operand on
  the MXU (``_online_kernel``);
- ``exact`` — the interpreter-only fallback (non-TPU backends / tests):
  grid ``(B, nb_max)`` with the pallas pipeline DMA-ing blocks via
  scalar-prefetch index maps, scores accumulated into a full
  ``(H, W, S)`` row and the epilogue mirroring
  ``GPT2._masked_attend`` **op-for-op** (input-dtype score matmul →
  fp32 cast → scale → mask → softmax → probs cast to compute dtype →
  AV) — **bit-exact** against the ``gather_kv`` oracle on bf16/fp16 and
  int8 pools, within 4 ulp on fp32 (tests/test_paged_attention.py),
  which is what keeps CPU tier-1 exact when the serving decode routes
  through here.

A **window** layer (``window=w``: key ``s`` is live for the token at
position ``t`` iff ``0 <= t - s < w``) keeps its table as a RING: the table's
``nb_max`` entries hold the newest ``nb_max`` blocks of the stream, logical
block ``j`` in entry ``j % nb_max`` (``paged_kv.ring_blocks`` sizes it so that
the window and the block being written always fit).  The online walk then
starts at the chunk that holds the row's first live position, looks each
block up at its ring entry, fetches no block that lies wholly before the
window and masks the dead positions of the first live block; dead rows and
the carried DMA ring are as above.  One query token a slot (W = 1).

``mode="auto"`` resolves to ``online`` on compiled TPU and ``exact``
under the interpreter.  Queries are a ``(B, W, H, hd)`` window —
``W=1`` is the serving decode step (``inference/serving.py``), a wider
window is chunked prefill's to take (ROADMAP S3, D17) — masked causally
inside the window:
key position ``s`` is live for window row ``w`` iff
``s <= lengths[b] + w``.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...runtime.comm.quantized import dequantize_blockwise

# the oracle's mask value (GPT2._masked_attend uses finfo(f32).min;
# flash's -1e30 would break exact-mode bit-equality)
NEG_INF = float(np.finfo(np.float32).min)

_N_BUF = 3    # DMA ring depth (flash_attention._N_KV_BUF): slot (j+2)%3
#               held block j-1 (consumed one grid step ago), so the j+2
#               fetch can start BEFORE block j's compute with no hazard

# inference/paged_kv.SCRATCH_BLOCK (not imported: inference imports the
# models, which import this package): the block a table is padded with and
# the one an empty slot's whole table names
SCRATCH_BLOCK = 0

# the online walk's tile (score_tile; PERF.md section 6, PR 49)
_CHUNK_BYTES = 512 * 1024   # K rows a chunk: 128 bf16 tokens of a pool 2,048
#                             lanes wide (what its chunk always carried), 256
#                             at 1,024 or 1,280 lanes, 1,024 at 256, 2,048 at 128
_TAIL_TOKENS = 128          # a last chunk's products run over whole multiples
#                             of this many tokens (whole score columns)
_SCORE_BYTES = 512 * 1024   # the (R, Tc) fp32 scores of one softmax update:
#                             (128, 1024) held, (128, 4096) spilled (PR 48)


def _interpret():
    return jax.default_backend() != "tpu"


def resolve_mode(mode: str) -> str:
    """``auto`` → ``online`` on compiled TPU, ``exact`` interpreted."""
    if mode == "auto":
        return "exact" if _interpret() else "online"
    assert mode in ("exact", "online"), \
        f"paged-attention mode must be auto|exact|online, got {mode!r}"
    return mode


# ============================================================== exact kernel
def _exact_kernel(*refs, block_size, nb_max, n_head, head_dim, n_window,
                  scale_attn, compute_dtype, quantized, n_kv_head,
                  window=None):
    """Grid (B, nb_max), block walk innermost (revisits scratch).

    Scores land in a full (H, W, S) fp32 row; the last block's visit
    runs the epilogue as the gather oracle computes it, op-for-op —
    the exactness contract (module docstring).  Interpreter only: the
    in-kernel ``reshape`` of a block to (bs, H, hd) is not a layout
    Mosaic keeps."""
    if quantized:
        (tables_ref, lengths_ref, layer_ref, q_ref, k_ref, v_ref,
         ks_ref, vs_ref, o_ref, scores_ref, vrow_ref) = refs
    else:
        (tables_ref, lengths_ref, layer_ref, q_ref, k_ref, v_ref,
         o_ref, scores_ref, vrow_ref) = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(1)
    bs, W = block_size, n_window

    def block(x_ref, s_ref):
        """One pool block in the compute dtype, heads split out.  int8
        payloads dequantize with EXACTLY ``paged_kv.gather_kv``'s
        formula, so kernel and oracle read identical values."""
        x = x_ref[0, 0]
        x = (dequantize_blockwise(x, s_ref[0, 0], bits=8,
                                  out_dtype=compute_dtype)
             if quantized else x.astype(compute_dtype))
        if n_kv_head == n_head:
            return x.reshape(bs, n_head, head_dim)
        # grouped / multi-query: query head h reads KV head h // group
        return jnp.repeat(x.reshape(bs, n_kv_head, head_dim),
                          n_head // n_kv_head, axis=1)

    k = block(k_ref, ks_ref)
    v = block(v_ref, vs_ref)
    q = q_ref[0]                                    # (W, H, hd)
    # per-(h, w, k) scores: same per-element hd-length contraction (and
    # operand layout) as the oracle's einsum("bqhd,bkhd->bhqk") — the
    # input-dtype matmul result casts to fp32 AFTER, like _masked_attend
    s_cols = jnp.einsum("whd,khd->hwk", q, k)
    scores_ref[:, :, pl.ds(j * bs, bs)] = s_cols.astype(jnp.float32)
    vrow_ref[pl.ds(j * bs, bs)] = v

    @pl.when(j == nb_max - 1)
    def _():
        s = scores_ref[...]
        if scale_attn:
            s = s / np.sqrt(head_dim)
        S = nb_max * bs
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (n_head, W, S), 2)
        w_pos = jax.lax.broadcasted_iota(jnp.int32, (n_head, W, S), 1)
        if window is not None:
            # column (e, off) of the ring holds the newest logical block j
            # with j % nb_max == e, none (a position below 0) if the stream
            # has not reached entry e yet
            k_pos = ring_positions(k_pos // bs, k_pos % bs,
                                   lengths_ref[b] + W - 1, nb_max, bs)
            valid = (k_pos <= lengths_ref[b] + w_pos) & (k_pos >= 0) & (
                k_pos > lengths_ref[b] + w_pos - window)
        else:
            valid = k_pos <= lengths_ref[b] + w_pos
        s = jnp.where(valid, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(compute_dtype)
        out = jnp.einsum("hwk,khd->whd", p, vrow_ref[...])
        o_ref[0] = out.astype(o_ref.dtype)


def ring_positions(entry, offset, newest, ring, block_size):
    """The position held at ``offset`` of ring entry ``entry`` when the
    newest position written is ``newest``: logical block ``j`` lives in entry
    ``j % ring``, so the entry holds the largest ``j <= newest // block_size``
    of its residue (negative: nothing yet)."""
    last = newest // block_size
    return (last - jax.lax.rem(last - entry + ring, ring)) * block_size + offset


def _exact_call(q, pool, tables, lengths, layer_arr, *, scale_attn,
                window=None):
    B, W, H, hd = q.shape
    bs, HD = pool["k"].shape[2:]
    nb_max = tables.shape[1]
    S = nb_max * bs
    quantized = "k_scale" in pool

    def kv_idx(b, j, tbl, lens, lay):
        return (lay[0], tbl[b, j], 0, 0)

    def q_idx(b, j, tbl, lens, lay):
        return (b, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, W, H, hd), q_idx),
        pl.BlockSpec((1, 1, bs, HD), kv_idx),
        pl.BlockSpec((1, 1, bs, HD), kv_idx),
    ]
    args = [q, pool["k"], pool["v"]]
    if quantized:
        nsc = pool["k_scale"].shape[-1]
        in_specs += [pl.BlockSpec((1, 1, bs, nsc), kv_idx)] * 2
        args += [pool["k_scale"], pool["v_scale"]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B, nb_max),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, W, H, hd), q_idx),
        scratch_shapes=[
            pltpu.VMEM((H, W, S), jnp.float32),
            pltpu.VMEM((S, H, hd), q.dtype),
        ])
    kernel = functools.partial(
        _exact_kernel, block_size=bs, nb_max=nb_max, n_head=H, head_dim=hd,
        n_window=W, scale_attn=scale_attn, compute_dtype=q.dtype,
        quantized=quantized, n_kv_head=HD // hd, window=window)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, W, H, hd), q.dtype),
        interpret=True,
    )(tables, lengths, layer_arr, *args).reshape(B, W, H * hd)


# ============================================================= online kernel
def _round_up(x, m):
    return -(-x // m) * m


def score_tile(token_bytes, block_size, nb_max, rows):
    """The ``(Tc, R)`` score tile of the online walk, from what the call reads
    of its input: ``token_bytes`` the pool's width in bytes (one token's K
    row), the block size, the table's length and the real score rows (window
    rows x K/V heads, or quantization blocks).

    ``Tc``, a chunk's tokens: the fewest whole blocks and whole 128-lane score
    columns whose K rows carry ``_CHUNK_BYTES`` (a chunk costs about the same
    whatever it carries, so a narrow pool takes more tokens a chunk), no wider
    than ``_SCORE_BYTES`` of float32 scores and no longer than the table.
    ``R``: the real rows rounded up ONCE to a float32 sublane tile."""
    R = _round_up(rows, 8)
    unit = int(np.lcm(block_size, 128))
    want = _round_up(-(-_CHUNK_BYTES // token_bytes), unit)
    fit = max(_SCORE_BYTES // (4 * R) // unit, 1) * unit
    return min(want, fit, nb_max * block_size), R


def _online_kernel(*refs, block_size, nb_max, head_dim, scale_attn,
                   compute_dtype, quant_block, group, rows_per_token,
                   tail_widths, q_per_kv=1, window=None):
    """Grid (B,): ONE program per slot walks the slot's LIVE tokens in
    chunks of ``group`` blocks (:func:`score_tile`) through a triple-buffered
    make_async_copy ring from the HBM pool, carrying fp32 online-softmax
    state (m, l, acc) per row: one update a chunk.  A dead row (its table
    names the scratch block: the fact ``inference/serving.py``'s own step
    reads) fetches nothing, computes nothing and writes zeros.

    The ring is ONE ring over the whole call.  Program 0 writes the plan
    into SMEM scratch, which like the VMEM buffers and the DMA semaphores
    persists across the sequential grid: for every row the next live row
    and the ring position of its first chunk (a live row has
    ``ceil((length + n_tok) / chunk)`` chunks, a dead row none).  The
    chunk at ring position p lands in buffer ``p % 3`` and its fetch
    starts before position p - 2 computes, whichever row that is — so a
    row's last chunks compute while the next live row's first two are in
    flight.  A chunk fetches only the blocks that hold a live position, in
    a LOOP over them (128 unrolled descriptors would be seconds of every
    start-up's lowering).  Every chunk before a row's last (under a
    window: between its first and its last) is live whole: it lands under
    one wait a leaf and runs with no mask; the last chunk's products run
    over the fewest tiles of ``_TAIL_TOKENS`` that cover its live positions
    (``tail_widths``: a branch of straight-line code each), dead positions
    masked in the scores and zeroed in the value tile (a block that was not
    fetched leaves what the buffer held).

    Every tile keeps the pool's (rows, H*hd) shape — no per-head slice,
    reshape or transpose of a sub-128-lane head (Mosaic keeps none of
    them at hd=64).  Heads are separated on the MXU instead: the query
    window is expanded to a BLOCK-DIAGONAL (R, H*hd) operand whose row
    ``(w, h)`` holds ``q[w, h]`` in head h's columns and zeros
    elsewhere, so ``Qbd @ K^T`` is every head's scores at once and the
    diagonal blocks of ``P @ V`` are every head's outputs.  That is
    H times the FLOPs the math needs, on a kernel whose time is the KV
    DMA.  Rows are PACKED: row ``w * rows_per_token + h``, the whole
    rounded up once (:func:`score_tile`'s R); a window row reaches its rows, and the
    rows' diagonal blocks their window row, through a 0/1 matmul (W > 1).

    int8 pools: a row is one QUANTIZATION block (``quant_block``
    columns; ``hd // quant_block`` rows per head; ``rows_per_token`` is
    padded to whole sublane tiles and heads, as the scale tiles are).
    Payloads cast to the compute dtype (exact: |q| <= 127) and the fp32
    scales multiply the (R, chunk) score/probability tiles, never a
    (chunk, H*hd) one: they arrive already transposed to (rows, positions)
    (:func:`_scale_rows`), one tile a chunk, started and awaited with the
    chunk's payload.  Rows of one head sum their partial scores
    through a 0/1 matmul before the softmax.

    Grouped / multi-query pools (``q_per_kv`` > 1 query heads to a KV
    head; the pool's width is the KV heads alone): the ``q_per_kv`` query
    heads of one KV head ride the WINDOW axis — the caller hands in
    ``W * q_per_kv`` query rows of the pool's width — and share window
    token ``w``'s causal limit.

    ``window`` (module docstring): the table is a ring of ``nb_max``
    entries and the walk covers the chunks from the one that holds position
    ``length - window + 1`` on; one token a slot."""
    quantized = quant_block is not None
    if quantized:
        (tables_ref, lengths_ref, layer_ref, q_ref,
         k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
         kbuf, vbuf, ksbuf, vsbuf, kst, vst, m_ref, l_ref, acc_ref,
         sem, next_ref, first_ref) = refs
    else:
        (tables_ref, lengths_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
         kbuf, vbuf, m_ref, l_ref, acc_ref, sem, next_ref, first_ref) = refs
    b = pl.program_id(0)
    B = tables_ref.shape[0]
    lay = layer_ref[0]
    bs, G, Rw, R = block_size, group, rows_per_token, m_ref.shape[0]
    W, HD = q_ref.shape[1:]
    n_tok = W // q_per_kv                     # window tokens
    qb = quant_block if quantized else head_dim
    per_head = head_dim // qb                 # rows per head
    Tc = G * bs
    sm_scale = (1.0 / np.sqrt(head_dim)) if scale_attn else 1.0
    n_chunks = -(-nb_max // G)
    exact = (jax.lax.Precision.HIGHEST if compute_dtype == jnp.float32
             else None)

    def live_units(row, unit, most):
        """Chunks (or blocks) of ``row`` that hold a position <= length +
        n_tok - 1, the window's last row; everything past is masked for
        every row.  0 for a dead row."""
        n = (lengths_ref[row] + n_tok + unit - 1) // unit
        if window is None:            # a ring's positions outrun its table
            n = jnp.minimum(n, most)
        return jnp.where(tables_ref[row, 0] == SCRATCH_BLOCK, 0, n)

    def first_unit(row, unit):
        """The chunk (or block) that holds ``row``'s first live position."""
        if window is None:
            return 0
        return jnp.maximum(lengths_ref[row] - (window - 1), 0) // unit

    def row_chunks(row):
        """Chunks ``row``'s walk covers: all that hold a live position, or,
        under a window, those from the first live one on."""
        return jnp.maximum(live_units(row, Tc, n_chunks)
                           - first_unit(row, Tc), 0)

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
            return pos + row_chunks(r)

        next_ref[B] = B
        jax.lax.fori_loop(0, B, back, B)
        first_ref[B] = jax.lax.fori_loop(0, B, forth, 0)

    n_live = row_chunks(b)                    # this row's chunks; 0: dead
    base = first_ref[b]                       # ring position of chunk 0
    total = first_ref[B]

    def scale_copies(row, c, slot):
        """An int8 pool's scale tiles go whole with their chunk: a whole
        number of 128-lane tiles, Tc itself, or (a table shorter than one
        tile) the single padded chunk at 0."""
        first = pl.multiple_of(c * Tc, 128) if n_chunks > 1 else 0
        cols = pl.ds(first, ksbuf.shape[-1])
        return [pltpu.make_async_copy(ks_hbm.at[row, :, cols], ksbuf.at[slot],
                                      sem.at[slot, 2]),
                pltpu.make_async_copy(vs_hbm.at[row, :, cols], vsbuf.at[slot],
                                      sem.at[slot, 3])]

    def each_copy(row, c, slot, act):
        """Start or await (``act``) the ``row``'s c-th chunk in buffer
        ``slot``: one copy a leaf per block that holds a live position (a
        chunk of the walk always has one), in a loop."""
        c = c + first_unit(row, Tc)
        lo = 0 if window is None else jnp.clip(
            first_unit(row, bs) - c * G, 0, G - 1)
        hi = jnp.clip(live_units(row, bs, nb_max) - c * G, lo + 1, G)

        def one(g, carry):
            at = c * G + g
            ki = tables_ref[row, at if window is None
                            else jax.lax.rem(at, nb_max)]
            act(pltpu.make_async_copy(k_hbm.at[lay, ki], kbuf.at[slot, g],
                                      sem.at[slot, 0]))
            act(pltpu.make_async_copy(v_hbm.at[lay, ki], vbuf.at[slot, g],
                                      sem.at[slot, 1]))
            return carry

        jax.lax.fori_loop(lo, hi, one, 0)
        if quantized:
            for cp in scale_copies(row, c, slot):
                act(cp)

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

        length = lengths_ref[b]
        # row r = (w, i): window row w, quantization block i of the merged
        # head dim (16-bit pools: i is the head); rows past W * Rw pad R
        row = jax.lax.broadcasted_iota(jnp.int32, (R, HD), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (R, HD), 1)
        diag = jnp.logical_and(col // qb == row % Rw, row < W * Rw)
        if W == 1:
            q_rows = jnp.broadcast_to(q_ref[0].astype(jnp.float32), (R, HD))
        else:
            # rows <- their window row, and back: 0/1 matmuls (exact)
            mine = (jax.lax.broadcasted_iota(jnp.int32, (R, W), 0) // Rw
                    == jax.lax.broadcasted_iota(jnp.int32, (R, W), 1))
            mine_t = (jax.lax.broadcasted_iota(jnp.int32, (W, R), 1) // Rw
                      == jax.lax.broadcasted_iota(jnp.int32, (W, R), 0))
            q_rows = jax.lax.dot_general(
                mine.astype(compute_dtype), q_ref[0],
                (((1,), (0,)), ((), ())), precision=exact,
                preferred_element_type=jnp.float32)
        qbd = jnp.where(diag, q_rows, 0.0).astype(compute_dtype)  # (R, HD)
        if per_head > 1:
            same_head = (
                jax.lax.broadcasted_iota(jnp.int32, (R, R), 0) // per_head
                == jax.lax.broadcasted_iota(jnp.int32, (R, R), 1) // per_head
            ).astype(jnp.float32)
        # the window TOKEN of a row (a pad row: the last, so that it masks
        # what the real rows mask)
        w_tok = jnp.minimum(
            jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
            // (Rw * q_per_kv), n_tok - 1)

        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

        def land(c, whole):
            """Chunk c's buffer, once the fetch two ring positions ahead
            has started and its own, at position ``base + c``, has landed:
            a whole chunk's G copies a leaf in ONE wait for their bytes
            together."""
            p = base + c
            start_at(p + 2)
            slot = jax.lax.rem(p, _N_BUF)
            if whole:
                pltpu.make_async_copy(k_hbm.at[lay, pl.ds(0, G)],
                                      kbuf.at[slot], sem.at[slot, 0]).wait()
                pltpu.make_async_copy(v_hbm.at[lay, pl.ds(0, G)],
                                      vbuf.at[slot], sem.at[slot, 1]).wait()
                if quantized:
                    for cp in scale_copies(b, c, slot):
                        cp.wait()
            else:
                each_copy(b, c, slot, lambda cp: cp.wait())
            return slot

        def per_window(x, width):
            """(Rw, >= width) per-token rows -> (R, width): one copy per
            window row (int8: Rw is a whole number of sublane tiles)."""
            x = x[:, :width]
            return x if W == 1 else jnp.concatenate([x] * W, axis=0)

        def attend(c, slot, width, whole):
            """The first ``width`` positions of the row's c-th chunk into
            the running softmax, in one update.  ``whole``: every block was
            fetched and every position is live for the window's first
            token."""
            c = c + first_unit(b, Tc)         # positions are the stream's
            if quantized:
                def stage(g, carry):
                    rows = pl.ds(pl.multiple_of(g * bs, bs), bs)
                    kst[rows, :] = kbuf[slot, g].astype(jnp.float32).astype(
                        compute_dtype)
                    vst[rows, :] = vbuf[slot, g].astype(jnp.float32).astype(
                        compute_dtype)
                    return carry

                jax.lax.fori_loop(0, width // bs, stage, 0)
                k, v = kst[:width], vst[:width]
            else:
                k = kbuf[slot, :width // bs].reshape(width, HD).astype(
                    compute_dtype)
                v = vbuf[slot, :width // bs].reshape(width, HD).astype(
                    compute_dtype)
            if not whole:
                # a block that was not fetched leaves what the buffer held,
                # and 0 x NaN is NaN in P @ V: dead positions' values are
                # zeros (their keys are covered by the where on the scores)
                v_pos = c * Tc + jax.lax.broadcasted_iota(
                    jnp.int32, (width, HD), 0)
                v_live = v_pos < length + n_tok
                if window is not None:
                    v_live = jnp.logical_and(v_live, v_pos > length - window)
                v = jnp.where(v_live, v, jnp.zeros_like(v))
            s = jax.lax.dot_general(
                qbd, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # (R, width)
            if quantized:
                s = s * per_window(ksbuf[slot], width)
            if per_head > 1:
                s = jax.lax.dot_general(
                    same_head, s, (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
            s = s * sm_scale
            if not whole or n_tok > 1:
                k_pos = c * Tc + jax.lax.broadcasted_iota(
                    jnp.int32, (R, width), 1)
                if window is None:
                    last = jnp.minimum(length + w_tok, nb_max * bs - 1)
                    s = jnp.where(k_pos <= last, s, NEG_INF)
                else:
                    s = jnp.where(jnp.logical_and(
                        k_pos <= length + w_tok,
                        k_pos > length + w_tok - window), s, NEG_INF)
            m_prev = m_ref[:]                                 # (R, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                            # (R, width) fp32
            l_ref[:] = l_ref[:] * alpha + jnp.sum(p, -1, keepdims=True)
            if quantized:
                p = p * per_window(vsbuf[slot], width)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                p.astype(compute_dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:] = m_new

        def whole_chunk(c, carry):
            attend(c, land(c, True), Tc, True)
            return carry

        if window is None:
            jax.lax.fori_loop(0, n_live - 1, whole_chunk, 0)
        else:
            # the first chunk holds the positions the window has slid past
            pl.when(n_live > 1)(
                lambda: attend(0, land(0, False), Tc, False))
            jax.lax.fori_loop(1, n_live - 1, whole_chunk, 0)
        # the last chunk, at the narrowest width that covers its live tokens
        c = n_live - 1
        slot = land(c, False)
        live_end = length + n_tok
        if window is None:
            live_end = jnp.minimum(live_end, nb_max * bs)
        tail = live_end - (c + first_unit(b, Tc)) * Tc        # 1 .. Tc
        if len(tail_widths) == 1:
            attend(c, slot, Tc, False)
        else:
            for lo, width in zip((0,) + tail_widths, tail_widths):
                pl.when(jnp.logical_and(tail > lo, tail <= width))(
                    functools.partial(attend, c, slot, width, False))

        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)                 # never 0: k_pos
        heads = jnp.where(diag, acc_ref[:] / l_safe, 0.0)    # 0 always live
        if W == 1:
            out = jnp.sum(heads, axis=0, keepdims=True)
        else:
            # one row has a value in any column: the sum is a selection,
            # exact in the output's own precision
            out = jax.lax.dot_general(
                mine_t.astype(o_ref.dtype), heads.astype(o_ref.dtype),
                (((1,), (0,)), ((), ())), precision=exact,
                preferred_element_type=jnp.float32)           # (W, HD)
        o_ref[0] = out.astype(o_ref.dtype)


def _scale_rows(scale, layer, tables, n_rows, n_cols):
    """One layer's block scales for every slot's table, transposed to
    (B, rows, positions) and zero-padded to ``(n_rows, n_cols)`` — the
    lane-dense form the kernel multiplies score tiles by.  The only
    gathered copy on the kernel path: fp32 scales are ``4 / qb`` of the
    int8 payload bytes."""
    s = scale[layer][tables]                      # (B, nb_max, bs, HD//qb)
    B, nb, bs, n = s.shape
    s = s.reshape(B, nb * bs, n).transpose(0, 2, 1)
    return jnp.pad(s, ((0, 0), (0, n_rows - n), (0, n_cols - nb * bs)))


def _online_call(q, pool, tables, lengths, layer_arr, *, scale_attn,
                 interpret, q_per_kv=1, window=None, name="paged_attention"):
    B, W, H, hd = q.shape
    n_blocks, bs, HD = pool["k"].shape[1:]
    if HD != H * hd:
        # grouped / multi-query: (B, W, Hkv, G, hd) -> (B, W * G, Hkv * hd),
        # the G query heads of a KV head as extra window rows (the kernel
        # masks them by their token, not their row)
        n_kv, G_q = HD // hd, H * hd // HD
        qg = q.reshape(B, W, n_kv, G_q, hd).transpose(0, 1, 3, 2, 4)
        out = _online_call(qg.reshape(B, W * G_q, n_kv, hd), pool, tables,
                           lengths, layer_arr, scale_attn=scale_attn,
                           interpret=interpret, q_per_kv=G_q, window=window,
                           name=name)
        out = out.reshape(B, W, G_q, n_kv, hd).transpose(0, 1, 3, 2, 4)
        return out.reshape(B, W, H * hd)
    nb_max = tables.shape[1]
    quantized = "k_scale" in pool
    qb = HD // pool["k_scale"].shape[-1] if quantized else hd
    # row stride of one window row in the kernel's (R, .) tiles: one row per
    # head, packed; an int8 pool's one per quantization block, padded to
    # whole fp32 sublane tiles and heads as its scale tiles are
    Rw = _round_up(HD // qb, 8 * (hd // qb)) if quantized else H
    Tc, R = score_tile(HD * pool["k"].dtype.itemsize, bs, nb_max, W * Rw)
    G = min(Tc // bs, n_blocks)
    Tc = G * bs
    # the widths a last chunk's products may take: whole blocks AND whole
    # tail tiles, and the chunk itself
    step = int(np.lcm(bs, _TAIL_TOKENS))
    tail_widths = tuple(range(step, Tc, step)) + (Tc,)

    in_specs = [
        pl.BlockSpec((1, W, HD), lambda b, *s: (b, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),         # k pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),         # v pool stays in HBM
    ]
    args = [q.reshape(B, W, HD), pool["k"], pool["v"]]
    scratch = [
        pltpu.VMEM((_N_BUF, G, bs, HD), pool["k"].dtype),
        pltpu.VMEM((_N_BUF, G, bs, HD), pool["v"].dtype),
    ]
    if quantized:
        Tcs = _round_up(Tc, 128)                   # scale columns per DMA
        n_cols = (-(-nb_max // G) - 1) * Tc + Tcs
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        args += [_scale_rows(pool[n], layer_arr[0], tables, Rw, n_cols)
                 for n in ("k_scale", "v_scale")]
        scratch += [
            pltpu.VMEM((_N_BUF, Rw, Tcs), jnp.float32),
            pltpu.VMEM((_N_BUF, Rw, Tcs), jnp.float32),
            pltpu.VMEM((Tc, HD), q.dtype),         # k chunk, compute dtype
            pltpu.VMEM((Tc, HD), q.dtype),         # v chunk, compute dtype
        ]
    scratch += [
        pltpu.VMEM((R, 1), jnp.float32),           # m (running max)
        pltpu.VMEM((R, 1), jnp.float32),           # l (denominator)
        pltpu.VMEM((R, HD), jnp.float32),          # acc
        pltpu.SemaphoreType.DMA((_N_BUF, 4 if quantized else 2)),
        pltpu.SMEM((B + 1,), jnp.int32),           # plan: next live row
        pltpu.SMEM((B + 1,), jnp.int32),           # plan: first ring position
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, W, HD), lambda b, *s: (b, 0, 0)),
        scratch_shapes=scratch)
    kernel = functools.partial(
        _online_kernel, block_size=bs, nb_max=nb_max, head_dim=hd,
        scale_attn=scale_attn, compute_dtype=q.dtype,
        quant_block=qb if quantized else None, group=G, rows_per_token=Rw,
        tail_widths=tail_widths, q_per_kv=q_per_kv, window=window)
    cp = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, W, HD), q.dtype),
        compiler_params=cp, interpret=interpret, name=name,
    )(tables, lengths, layer_arr, *args)


# ================================================================ public API
def paged_attention(q, pool, block_tables, lengths, layer, *,
                    scale_attn=True, mode="auto", interpret=None,
                    window=None, name="paged_attention"):
    """Masked attention of a ``(B, W)`` query window over the paged pool,
    reading K/V blocks in place (no gathered copy).

    - ``q``: (B, W, H, hd) in the attention compute dtype (W=1: the
      serving decode step);
    - ``pool``: the ``paged_kv`` pool pytree (16-bit or int8+scales); its
      width is ``n_kv_head * hd``, with ``n_kv_head`` a divisor of H
      (H itself: multi-head; 1: multi-query);
    - ``block_tables``: (B, nb_max) int32 pool block ids (scratch-0
      padded); ``lengths``: (B,) int32 — position of the FIRST window
      token (its K/V already written, so ``k_pos <= lengths + w`` is
      the causal mask for window row ``w``);
    - ``layer``: int or traced scalar (called inside the layer scan);
    - ``window``: None, or the layer's sliding window: ``block_tables`` is
      then a ring (module docstring) and W is 1;
    - ``name``: the compiled call's name in a device trace.

    Returns (B, W, H·hd) in ``q.dtype`` — same contract as
    ``gather_kv`` + ``GPT2._masked_attend``, which remains the oracle
    this kernel is tested against (exact mode to the last ulp on 16-bit
    pools, tolerance-bounded online/int8)."""
    B, W, H, hd = q.shape
    HD = pool["k"].shape[3]
    # the pool holds the KV heads: all H of them, or (grouped / multi-query
    # attention) a divisor of H, each shared by H // n_kv query heads
    assert HD % hd == 0 and (H * hd) % HD == 0, (pool["k"].shape, q.shape)
    mode = resolve_mode(mode)
    if window is not None:
        assert W == 1 and "k_scale" not in pool, \
            "a window layer attends one token a slot over a 16-bit pool"
        bs, ring = pool["k"].shape[2], block_tables.shape[1]
        assert (ring - 1) * bs >= window - 1, \
            f"a ring of {ring} blocks of {bs} cannot hold a window of {window}"
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if mode == "exact":
        return _exact_call(q, pool, tables, lengths, layer_arr,
                           scale_attn=scale_attn, window=window)
    return _online_call(q, pool, tables, lengths, layer_arr,
                        scale_attn=scale_attn,
                        interpret=_interpret() if interpret is None
                        else interpret, window=window, name=name)
