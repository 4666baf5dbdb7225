"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464; Qwen3-Next's linear
layers): the chunked form of a prompt, the one-token update of a decode step.

No reference counterpart.  The recurrence, per value head of ``dv`` channels
over keys of ``dk``, with a state ``S`` of ``(dk, dv)`` float32::

    S'  = exp(g_t) S_{t-1}                  g_t <= 0, one a head a token
    d_t = beta_t (v_t - S'^T k_t)           the DELTA: what the state already
                                            says of k_t is taken off v_t
    S_t = S' + k_t (x) d_t
    o_t = S_t^T q_t

Beside Mamba-2 (``ops/mamba2.py``), whose update is ``S <- a S + x (x) B``:
what is WRITTEN there does not depend on what the state holds; here the state
is read (``S'^T k``) before it is written, so neither ``ssm_step`` nor
``ssd_scan`` expresses it.  ``q``, ``k`` come in already normed (and ``q``
scaled) and already repeated to the value heads; nothing here knows of key
heads, convolutions or gates.

Three pieces:

- :func:`delta_scan_jnp` — the recurrence above, a ``lax.scan`` over tokens
  in float32: the oracle of the other two.
- :func:`delta_chunk` — a prompt in chunks of ``C`` tokens (the WY / UT
  transform).  With ``G`` the running sum of ``g`` inside a chunk and ``S`` the
  state the chunk meets::

      L = tril_strict((K_beta K^T) o exp(G_i - G_j))      K_beta = beta K
      T = (I + L)^-1                                      unit lower triangular
      U = T V_beta,   W = T (K_beta o exp(G))
      D = U - W S                                         the chunk's deltas
      O = (Q o exp(G)) S + tril((Q K^T) o exp(G_i - G_j)) D
      S <- exp(G_C) S + (K o exp(G_C - G))^T D

  The products run in the operands' dtype (bfloat16 as served) with float32
  accumulation; ``G``, ``T`` and the carried ``S`` are float32.  ``T`` by
  :func:`unit_lower_inverse`'s scheme: the 16 x 16 diagonal blocks by the
  four products ``(I + N)(I + N^2)(I + N^4)(I + N^8)`` of the nilpotent ``N =
  -L``, then pairs of blocks merged (``T21 = -T22 L21 T11``) up to the
  chunk: the six products over a whole chunk of 64 pass through powers a
  thousand times the result's size when the keys are alike (silu leaves them
  mostly positive), and a forward substitution is 64 passes over every
  chunk's matrix.  Two forms behind the one entry (``impl``):

  * ``"kernel"``, every prompt on a TPU: ONE ``pallas_call`` named
    ``gated_delta_chunk_scan`` (scope ``gdn.chunk`` in the model), grid
    (batch, groups of ``_CHUNK_HEADS`` heads, chunks) with the chunk axis
    ``arbitrary``.  A head's ``(dk, dv)`` float32 state is the kernel's state
    output block, which does not move along the chunk axis: in VMEM from
    chunk 0 (read from ``S0``) to the last (written out once), and of a chunk
    nothing but ``O`` goes back to HBM: ``L``, ``T``, ``U``, ``W``, the
    scores and ``D`` live and die in VMEM.  q, k, v and o cross the call as
    ``(B, tokens, H d)``, a head's channels at lane ``h d`` as the projection
    leaves them (a chunk's block is ``(C, heads d)``: no transpose to a
    head-major order before the call or after it).  Heads go in PAIRS side by
    side so that a pair's (C, C) matrices fill a vreg's 128 lanes and a
    product of two such is one MXU-wide product against a block diagonal
    (:func:`_chunk_factors`).  Outside the kernel, in ``jax.numpy``: the pad to
    whole chunks and :func:`mask_pads` (``t_real``), and the running sum
    ``G`` a chunk, handed over twice (0.5 MB each at 4,096 tokens): tokens by
    heads beside ``beta``, as they come, and heads by tokens.  The inverse's
    float32 products are at ``Precision.HIGHEST`` whatever the operands'
    dtype, as :func:`unit_lower_inverse` has them.  There is no backward
    kernel: under ``jax.grad`` the forward is the kernel and the backward is
    the ``"jnp"`` form's over the same operands.
  * ``"jnp"`` (:func:`delta_chunk_jnp`), off the chip, the kernel's backward
    and its oracle: ``L``, ``T``, ``U``, ``W`` and the two masked score
    matrices of EVERY chunk as batched products, each written to HBM, then a
    ``lax.scan`` over the chunks with three products each.
- :func:`delta_step` — one token for every slot over ONE layer's rows of the
  serving state ``(layers, slots, Hv, dk, dv)`` float32, IN PLACE: the TPU
  form (``pallas_call`` ``gated_delta_state_update``) leaves the leaf in HBM,
  aliased to its output, brings 8 slots' 16 MB a grid step through three VMEM
  buffers with reads and writes taking turns (``mamba2_state_update``'s
  scheme, which this chip's HBM asks for: PERF.md section 6, PR 44), and for
  each head holds the ``(dk, dv)`` state once for ``S'^T k`` (a column
  broadcast over the lanes, a sum down the sublanes), the rank-one update (a
  column times a row) and ``S^T q``.  A dead slot is handed ``g`` 0 and
  ``beta`` 0, which leave its rows what they were.  ``impl="jnp"`` is its
  oracle and the CPU path.

A pad token is handed ``g = 0`` and ``beta = 0`` (:func:`mask_pads`): the state
then stays what it was after the last real token.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mamba2 import _smem_block

STEP_KERNEL = "gated_delta_state_update"
_STEP_BUF = 48 << 20      # the update's three buffers of the state in VMEM
_STEP_VMEM = 64 << 20     # those, and the per-slot operands beside them
_INVERSE_BLOCK = 16       # diagonal blocks inverted by products of powers
CHUNK_KERNEL = "gated_delta_chunk_scan"
_CHUNK_HEADS = 8          # heads a grid step of the chunked kernel: four pairs
_CHUNK_VMEM = 32 << 20


def _interpret():
    return jax.default_backend() != "tpu"


def mask_pads(g, beta, t_real):
    """``g``, ``beta`` (B, T, H) with every position from ``t_real`` on set
    to zero: no decay and no write, whatever the pad holds."""
    keep = (jnp.arange(g.shape[1]) < t_real)[None, :, None]
    return jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0)


# ------------------------------------------------------- the token recurrence
def delta_scan_jnp(q, k, v, g, beta, S0=None):
    """``q``, ``k`` (B, T, H, dk); ``v`` (B, T, H, dv); ``g``, ``beta`` (B, T,
    H); ``S0`` (B, H, dk, dv) or None.  Returns ``(o (B, T, H, dv) in
    v.dtype, S (B, H, dk, dv) float32)``.  float32 throughout."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None, None] * S
        d = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * d[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)
    S0 = jnp.zeros((B, H, dk, dv), f32) if S0 is None else S0.astype(f32)
    by_token = lambda a: jnp.moveaxis(a.astype(f32), 1, 0)
    S, o = jax.lax.scan(token, S0, tuple(map(by_token, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype), S


# ------------------------------------------------------------ the chunked form
def _mm32(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def unit_lower_inverse(L):
    """``(I + L)^-1`` for ``L`` (..., C, C) STRICTLY lower triangular, float32
    (module docstring).  ``C`` is ``_INVERSE_BLOCK`` times a power of two, or
    a power of two below it.  Every step is a product of whole (C, C)
    matrices under a mask of blocks: 16 x 16 operands would stand in tiles
    of 8 x 128 at eight times their size."""
    C = L.shape[-1]
    b = min(_INVERSE_BLOCK, C)
    assert C % b == 0 and (C // b) & (C // b - 1) == 0 and b & (b - 1) == 0, C
    at = jnp.arange(C)
    same_block = lambda size: at[:, None] // size == at[None, :] // size
    N = -jnp.where(same_block(b), L, 0.0)
    T, P = jnp.eye(C, dtype=L.dtype) + N, N
    for _ in range(b.bit_length() - 2):
        P = _mm32(P, P)
        T = T + _mm32(T, P)
    while b < C:
        # [[T11, 0], [T21, T22]] of each pair of blocks: T21 = -T22 L21 T11
        L21 = jnp.where(same_block(2 * b) & ~same_block(b), L, 0.0)
        T = T - _mm32(_mm32(T, L21), T)
        b *= 2
    return T


def _chunk_size(T, chunk):
    """The chunk a prompt of ``T`` tokens is cut by: ``chunk``, or the power
    of two that holds a prompt shorter than it."""
    return min(chunk, 1 << max(T - 1, 0).bit_length())


def delta_chunk_jnp(q, k, v, g, beta, S0=None, chunk=64, t_real=None):
    """:func:`delta_chunk` in ``jax.numpy``: every chunk's ``L``, ``T``,
    ``U``, ``W`` and score matrices as batched products, then a ``lax.scan``
    over the chunks.  The CPU path, the differentiable one, and the oracle of
    the kernel."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    cd = q.dtype
    C = _chunk_size(T, chunk)
    nc = -(-T // C)
    g, beta = g.astype(f32), beta.astype(f32)
    if t_real is not None:
        g, beta = mask_pads(g, beta, t_real)
    # (B, T, H, x) -> (nc, B, H, C, x): a chunk's tokens next to its channels
    cut = lambda a: jnp.moveaxis(jnp.pad(
        a, ((0, 0), (0, nc * C - T)) + ((0, 0),) * (a.ndim - 2)
    ).reshape((B, nc, C) + a.shape[2:]), (1, 3), (0, 2))
    qc, kc, vc = cut(q), cut(k), cut(v)
    G = jnp.cumsum(cut(g[..., None]), axis=-2)              # (nc, B, H, C, 1)
    bc = cut(beta[..., None])
    seg = G - jnp.swapaxes(G, -1, -2)                       # G_i - G_j
    below = jnp.tril(jnp.ones((C, C), bool), -1)
    decay = lambda mask: jnp.exp(jnp.where(mask, seg, -jnp.inf))
    dot = lambda a, b: jnp.einsum("...ik,...jk->...ij", a, b,
                                  preferred_element_type=f32)
    kb = (kc.astype(f32) * bc).astype(cd)
    Tm = unit_lower_inverse(dot(kb, kc) * decay(below)).astype(cd)
    mm = lambda a, b: jnp.matmul(a, b, preferred_element_type=f32)
    U = mm(Tm, (vc.astype(f32) * bc).astype(cd))            # (.., C, dv)
    W = mm(Tm, (kb.astype(f32) * jnp.exp(G)).astype(cd)).astype(cd)
    qk = (dot(qc, kc) * decay(below | jnp.eye(C, dtype=bool))).astype(cd)
    qg = (qc.astype(f32) * jnp.exp(G)).astype(cd)
    G_end = G[..., -1:, :]
    k_end = (kc.astype(f32) * jnp.exp(G_end - G)).astype(cd)

    def one(S, x):
        U, W, qk, qg, k_end, kept = x
        D = (U - mm(W, S.astype(cd))).astype(cd)
        O = mm(qg, S.astype(cd)) + mm(qk, D)
        S = kept * S + jnp.einsum("...ck,...cv->...kv", k_end, D,
                                  preferred_element_type=f32)
        return S, O
    S0 = jnp.zeros((B, H, dk, dv), f32) if S0 is None else S0.astype(f32)
    S, O = jax.lax.scan(one, S0, (U, W, qk, qg, k_end, jnp.exp(G_end)))
    O = jnp.moveaxis(O, (0, 2), (1, 3)).reshape(B, nc * C, H, dv)[:, :T]
    return O.astype(v.dtype), S


# ------------------------------------------------- the chunked form, a kernel
def _block_diagonal(Y, same):
    """``Y`` (r, W), ``W / r`` blocks of (r, r) side by side, as the (W, W)
    matrix with those blocks on its diagonal; ``same`` (W, W) says which
    entries lie in a diagonal block.  ``X @ _block_diagonal(Y)`` is then
    every block of ``X`` (.., W) times its own block of ``Y``, side by side:
    one product of the MXU's width where the blocks alone would each fill a
    part of it."""
    r, W = Y.shape
    return jnp.where(same, jnp.concatenate([Y] * (W // r), axis=0),
                     jnp.zeros((), Y.dtype))


def _blockwise(X, Y, same):
    """``X @ _block_diagonal(Y)`` for float32 ``X`` (m, W), ``Y`` (r, W) at
    ``Precision.HIGHEST``: the inverse's products, float32 whatever the
    operands' dtype, as :func:`unit_lower_inverse` has them."""
    return jnp.dot(X, _block_diagonal(Y, same),
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("H",))
def _chunk_factors(cols, rows, q, k, v, *, H):
    """What a chunk's ``hb`` heads need before they meet the state: ``cols``
    (C, lanes) and ``rows`` (hb / 2, 2 C) as :func:`_chunk_kernel` reads them
    (this group's heads already at lanes 0.. and H..), ``q``, ``k``, ``v`` a
    (C, d) block a head, in pairs.  Returns, a pair or a head each, ``G``
    (C, 1) and ``exp G``, the masked scores ``[[QK0, 0], [0, QK1]]``, ``U``
    (2 C, dv) float32 and ``W`` (2 C, dk).  Traced ONCE a process (a
    ``jax.jit`` inside the kernel's body, which Mosaic's lowering inlines):
    the body is the same whatever the number of chunks, and every prefill
    executable would trace its two thousand equations again.

    TWO HEADS SIDE BY SIDE.  A head's (C, C) matrices (``L``, ``T``, the
    scores, the decays) fill half a vreg's lanes at C 64 and a quarter of the
    MXU, so the heads go in pairs: ``[X0 | X1]`` (C, 2 C), every elementwise
    step over whole vregs, and a product of two such matrices is ONE of
    (C, 2 C) x (2 C, 2 C) against the right side's blocks on a diagonal
    (:func:`_block_diagonal`).  Both heads' scores come out of one product
    too: ``[K0 beta; K1 beta; Q0; Q1] [K0; K1]^T`` holds ``Kb0 K0^T`` and
    ``Kb1 K1^T`` in two of its corners.  The (C, d)-shaped operands of the
    pair stand one above the other, ``[X0; X1]``, and ``T`` and the masked
    scores meet them as ``[[T0, 0], [0, T1]]``.

    THE INVERSE is :func:`unit_lower_inverse`'s scheme and precision: the
    16 x 16 diagonal blocks by ``(I + N)(I + N^2)(I + N^4)(I + N^8)``, all
    eight of a pair as one strip (16, 2 C) whose products stream 16 or 32
    rows where the whole matrices would stream 64 (``[T; P] P`` gives ``T P``
    and ``P^2`` at once), then pairs of blocks merged, ``T21 = -T22 L21
    T11``, twice: each time only the pairs' lower blocks' rows, half of
    ``T``'s, go through the two products."""
    f32 = jnp.float32
    cd = q[0][0].dtype
    C, W = cols.shape[0], rows.shape[1]
    b = min(_INVERSE_BLOCK, C)
    prec = jax.lax.Precision.HIGHEST if cd == f32 else None
    above = lambda xs: jnp.concatenate(xs, axis=0)
    scaled = lambda x, by: (x.astype(f32) * by).astype(cd)
    pairs, two = range(len(q)), (0, 1)

    iota = lambda shape, axis: jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    row, lane = iota((C, W), 0), iota((C, W), 1)
    left, col = lane < C, lane % C
    upto, below = row >= col, row > col
    in_block = {size: row // size == col // size
                for size in [b << i for i in range((C // b).bit_length())]}
    wide = lambda size: iota((W, W), 0) // size == iota((W, W), 1) // size
    wide_b, wide_C = wide(b), wide(C)
    eye_strip = (iota((b, W), 0) == iota((b, W), 1) % b).astype(f32)
    # [X0 | X1] -> [[X0, 0], [0, X1]]
    apart = lambda X: above([jnp.where(left, X, jnp.zeros((), X.dtype)),
                             jnp.where(left, jnp.zeros((), X.dtype), X)])
    # one product a pair at every step below, the pairs' one after the other:
    # a pair's chain is a dozen products long and each waits for the last
    each = lambda f, *lists: [f(*xs) for xs in zip(*lists)]
    blockwise = lambda Xs, Ys, same: each(
        functools.partial(_blockwise, same=same), Xs, Ys)

    heads = [(2 * pair, 2 * pair + 1) for pair in pairs]
    G = [[cols[:, h:h + 1] for h in hs] for hs in heads]        # (C, 1) each
    beta = [[cols[:, H + h:H + h + 1] for h in hs] for hs in heads]
    kb = [[scaled(k[p][i], beta[p][i]) for i in two] for p in pairs]
    scores = [jax.lax.dot_general(
        above(kb[p] + q[p]), above(k[p]), (((1,), (1,)), ((), ())),
        preferred_element_type=f32, precision=prec) for p in pairs]  # (4C, 2C)
    decay = [jnp.exp(jnp.where(upto, jnp.where(left, *G[p])
                               - rows[p:p + 1], -jnp.inf)) for p in pairs]
    L = [jnp.where(left, scores[p][:C], scores[p][C:W])
         * jnp.where(below, decay[p], 0.0) for p in pairs]
    qk = [apart((jnp.where(left, scores[p][W:W + C], scores[p][W + C:])
                 * decay[p]).astype(cd)) for p in pairs]

    # T = (I + L)^-1: the diagonal blocks as a strip, then the merges
    N = [jnp.where(in_block[b], -l, 0.0) for l in L]
    N = [functools.reduce(jnp.add, (n[i:i + b] for i in range(0, C, b)))
         for n in N]
    T = [n + eye_strip for n in N]
    P = N
    rounds = b.bit_length() - 2
    for i in range(rounds):
        if i == 0:
            P = blockwise(P, P, wide_b)
        last = i == rounds - 1
        both = blockwise(T if last else each(lambda t, p: above([t, p]), T, P),
                         P, wide_b)
        T = each(lambda t, tp: t + tp[:b], T, both)
        P = [tp[b:] for tp in both]               # P^2 came with T P
    T = [jnp.where(in_block[b], above([t] * (C // b)), 0.0) for t in T]
    size = b
    while size < C:
        # L21's rows are each pair's LOWER block's, and so are T L21's: half
        # the rows go through the two products and change
        L21 = [jnp.where(in_block[2 * size] & ~in_block[size], l, 0.0)
               for l in L]
        lower = range(size, C, 2 * size)
        T21 = blockwise(blockwise(
            [above([t[i:i + size] for i in lower]) for t in T], L21, wide_C),
            T, wide_C)
        T = [above([x for n, i in enumerate(lower) for x in (
            t[i - size:i], t[i:i + size] - t21[n * size:(n + 1) * size])])
            for t, t21 in zip(T, T21)]
        size *= 2
    Tm = [apart(t.astype(cd)) for t in T]                       # (2 C, 2 C)

    eG = [[jnp.exp(x) for x in G[p]] for p in pairs]
    dot = functools.partial(jnp.dot, preferred_element_type=f32,
                            precision=prec)
    U = [dot(Tm[p], above([scaled(v[p][i], beta[p][i]) for i in two]))
         for p in pairs]
    Wm = [dot(Tm[p], above([scaled(kb[p][i], eG[p][i]) for i in two]))
          .astype(cd) for p in pairs]
    return G, eG, qk, U, Wm


def _chunk_kernel(cols_ref, rows_ref, q_ref, k_ref, v_ref, s0_ref, o_ref,
                  s_ref, *, H):
    """Grid (B, H / hb, chunks), the chunks one after the other: ``hb`` heads
    of one chunk of ``C`` tokens a step.  ``cols`` (C, lanes) float32, EVERY
    head's running sum ``G`` down the sublanes at lane ``h`` and its ``beta``
    at lane ``H + h`` (as the model hands them over: tokens by heads);
    ``rows`` (hb / 2, 2 C) float32: ``G`` again along the lanes, two heads a
    row; ``q``, ``k`` (C, hb dk), ``v``, ``o`` (C, hb dv): a head's channels at
    lane ``h dk``, as the projection leaves them; ``s0``, ``s`` (hb, dk, dv)
    float32.  ``s``'s block does not move along the chunk axis: it is the
    state, in VMEM from chunk 0 (when it is read from ``s0``) to the last
    (after which the pipeline writes it out), and nothing else of a chunk
    but ``o`` goes back to HBM.  :func:`_chunk_factors` is the chunk up to
    ``U`` and ``W``; what meets the state is here."""
    f32 = jnp.float32
    cd = q_ref.dtype
    C, hb = cols_ref.shape[1], s_ref.shape[1]
    dk, dv = q_ref.shape[2] // hb, v_ref.shape[2] // hb
    prec = jax.lax.Precision.HIGHEST if cd == f32 else None
    dot = functools.partial(jnp.dot, preferred_element_type=f32,
                            precision=prec)
    above = lambda xs: jnp.concatenate(xs, axis=0)
    scaled = lambda x, by: (x.astype(f32) * by).astype(cd)
    pairs, two = range(hb // 2), (0, 1)
    heads = [(2 * pair, 2 * pair + 1) for pair in pairs]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    # this group's heads to lanes 0.. and H..: the group is a grid index and
    # a lane is sliced statically
    lanes = cols_ref.shape[2]
    cols = pltpu.roll(cols_ref[0], (lanes - pl.program_id(1) * hb) % lanes, 1)
    q = [[q_ref[0, :, h * dk:(h + 1) * dk] for h in hs] for hs in heads]
    k = [[k_ref[0, :, h * dk:(h + 1) * dk] for h in hs] for hs in heads]
    v = [[v_ref[0, :, h * dv:(h + 1) * dv] for h in hs] for hs in heads]
    G, eG, qk, U, Wm = _chunk_factors(cols, rows_ref[0, 0, 0], q, k, v, H=H)
    # a head's state meets W and (Q o exp G) in one product: [W; Q] S
    Sc = [[s_ref[0, h].astype(cd) for h in hs] for hs in heads]
    WS = [[dot(above([Wm[p][i * C:(i + 1) * C], scaled(q[p][i], eG[p][i])]),
               Sc[p][i]) for i in two] for p in pairs]
    D = [[(U[p][i * C:(i + 1) * C] - WS[p][i][:C]).astype(cd) for i in two]
         for p in pairs]
    for p in pairs:
        Dm = above(D[p])
        for i, h in enumerate(heads[p]):
            O = WS[p][i][C:] + dot(qk[p][i * C:(i + 1) * C], Dm)
            o_ref[0, :, h * dv:(h + 1) * dv] = O.astype(o_ref.dtype)
            G_end = G[p][i][C - 1:C]
            # (1, 1) over the lanes, then down the sublanes: Mosaic has no
            # broadcast both ways at once
            kept = jnp.exp(jnp.broadcast_to(G_end, (1, dv)))
            s_ref[0, h] = kept * s_ref[0, h] + jax.lax.dot_general(
                scaled(k[p][i], jnp.exp(G_end - G[p][i])), D[p][i],
                (((0,), (0,)), ((), ())), preferred_element_type=f32,
                precision=prec)


def _chunk_heads(H):
    """Heads a grid step: pairs, as many as ``_CHUNK_HEADS`` allows."""
    return next(hb for hb in (_CHUNK_HEADS, 4, 2) if H % hb == 0)


def _chunk_scan(cols, rows, q, k, v, S0, *, interpret):
    """The ``pallas_call``: ``q``, ``k`` (B, tokens, H dk), ``v`` (B, tokens, H
    dv) in whole chunks, ``cols`` and ``rows`` as :func:`_chunk_kernel` reads
    them.  Returns ``(o (B, tokens, H dv), S (B, H, dk, dv) float32)``."""
    B, nc, n_groups, pairs, C = rows.shape
    hb, C = 2 * pairs, C // 2
    H, dk, dv = S0.shape[1:]
    per_chunk = lambda width: pl.BlockSpec(
        (1, C, width), lambda b, h, c: (b, c, h))
    state = pl.BlockSpec((1, hb, dk, dv), lambda b, h, c: (b, h, 0, 0))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, H=H),
        grid=(B, n_groups, nc),
        in_specs=[pl.BlockSpec((1, C, cols.shape[2]),
                               lambda b, h, c: (b, c, 0)),
                  pl.BlockSpec((1, 1, 1, pairs, 2 * C),
                               lambda b, h, c: (b, c, h, 0, 0)),
                  per_chunk(hb * dk), per_chunk(hb * dk), per_chunk(hb * dv),
                  state],
        out_specs=[per_chunk(hb * dv), state],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(S0.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_CHUNK_VMEM),
        interpret=interpret, name=CHUNK_KERNEL,
    )(cols, rows, q, k, v, S0)


def _chunk_operands(q, k, v, g, beta, C):
    """What :func:`_chunk_scan` takes, from the rule's operands (``g``,
    ``beta`` float32 and already masked): the pad to whole chunks (zeros: no
    decay, no write) and ``G``, a chunk's running sum, twice: tokens by
    heads beside ``beta`` (a whole number of vregs wide), and heads by
    tokens, a pair of heads a row.  q, k and v keep their order."""
    B, T, H, _ = q.shape
    nc = -(-T // C)
    hb = _chunk_heads(H)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, nc * C - T))
                            + ((0, 0),) * (a.ndim - 2))
    flat = lambda a: pad(a).reshape(B, nc * C, -1)
    G = jnp.cumsum(pad(g).reshape(B, nc, C, H), axis=2)
    cols = jnp.pad(jnp.concatenate([G.reshape(B, nc * C, H), pad(beta)], -1),
                   ((0, 0), (0, 0), (0, -2 * H % 128)))
    rows = jnp.moveaxis(G, 2, 3).reshape(B, nc, H // hb, hb // 2, 2 * C)
    return cols, rows, flat(q), flat(k), flat(v)


@functools.partial(jax.jit, static_argnames=("C", "interpret"))
def _chunk_call(q, k, v, g, beta, S0, *, C, interpret):
    B, T, H, _ = q.shape
    o, S = _chunk_scan(*_chunk_operands(q, k, v, g, beta, C), S0,
                       interpret=interpret)
    return o.reshape(B, o.shape[1], H, -1)[:, :T], S


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _chunk_forward(q, k, v, g, beta, S0, C, interpret):
    return _chunk_call(q, k, v, g, beta, S0, C=C, interpret=interpret)


def _chunk_saved(q, k, v, g, beta, S0, C, interpret):
    return (_chunk_call(q, k, v, g, beta, S0, C=C, interpret=interpret),
            (q, k, v, g, beta, S0))


def _chunk_backward(C, interpret, saved, cotangents):
    """No backward kernel: the ``jax.numpy`` form's, over the saved operands
    (``g`` and ``beta`` as the kernel met them, masked already)."""
    return jax.vjp(functools.partial(delta_chunk_jnp, chunk=C),
                   *saved)[1](cotangents)


_chunk_forward.defvjp(_chunk_saved, _chunk_backward)


def _chunk_kernel_lowers(H, C, dk, dv):
    """Whether Mosaic takes the kernel's blocks: heads in pairs, a pair's
    (C, 2 C) matrices and a head's channels whole vregs wide."""
    return H % 2 == 0 and C % 64 == 0 and dk % 128 == 0 and dv % 128 == 0


def delta_chunk(q, k, v, g, beta, S0=None, chunk=64, t_real=None,
                impl="auto", interpret=None):
    """The recurrence of :func:`delta_scan_jnp` in chunks of ``chunk`` tokens
    (module docstring): same operands, same returns.  ``t_real`` (a traced
    scalar) leaves the positions from it on out of the state
    (:func:`mask_pads`); a length that is no multiple of ``chunk`` is padded
    the same way.  Products in ``q.dtype`` accumulated in float32.  ``impl``:
    ``"kernel"`` (``gated_delta_chunk_scan``, an even number of heads; its
    backward is ``"jnp"``'s) / ``"jnp"`` force a form; ``"auto"`` is the kernel on a TPU at
    widths Mosaic tiles (the published ones), ``"jnp"`` anywhere else."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    C = _chunk_size(T, chunk)
    if impl == "auto":
        impl = "kernel" if not _interpret() and _chunk_kernel_lowers(
            H, C, dk, v.shape[-1]) else "jnp"
    if impl == "jnp":
        return delta_chunk_jnp(q, k, v, g, beta, S0, chunk, t_real)
    if H % 2:
        raise ValueError(f"{CHUNK_KERNEL} takes heads in pairs, not {H}")
    g, beta = g.astype(f32), beta.astype(f32)
    if t_real is not None:
        g, beta = mask_pads(g, beta, t_real)
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), f32) if S0 is None \
        else S0.astype(f32)
    return _chunk_forward(q, k, v, g, beta, S0, C,
                          _interpret() if interpret is None
                          else bool(interpret))


# ------------------------------------------------------ the one-token update
def delta_step_jnp(q, k, v, g, beta, S):
    """One token for every row: ``q``, ``k`` (Bt, H, dk); ``v`` (Bt, H, dv);
    ``g``, ``beta`` (Bt, H); ``S`` (Bt, H, dk, dv) float32.  Returns ``(o
    (Bt, H, dv) float32, new S)``."""
    o, S = delta_scan_jnp(q[:, None], k[:, None],
                          v[:, None].astype(jnp.float32), g[:, None],
                          beta[:, None], S)
    return o[:, 0], S


def _step_kernel(layer_ref, ab_ref, cols_ref, v_ref, s_hbm, o_ref, s_out_hbm,
                 buf, sem, *, H):
    """Grid (slots / bs,): ``bs`` slots' rows of one layer a step.  ``ab``
    (2 bs H,) float32 in SMEM, each slot's ``exp(g)`` a head and then each
    slot's ``beta``; ``cols`` (bs, dk, 2 H) float32, ``dk`` on the sublanes
    and per slot ``k`` and then ``q`` of every head on the lanes; ``v``, ``o``
    (bs, H, dv) float32; the state (layers, slots H, dk, dv) float32 stays in
    HBM, in and (aliased) out, and a step's ``bs H`` heads pass through one of
    ``buf``'s three (bs H, dk, dv).

    A head's state (16 vregs at 128 x 128) is held once: scaled, multiplied by
    ``k`` as a column and summed down the sublanes (vreg-wise addition and one
    fold of 8 sublanes), given the rank-one update (a column times a row),
    stored, and multiplied by ``q`` the same way.

    READS AND WRITES TAKE TURNS, as ``ops/mamba2.py::_step_kernel`` says and
    for its reason: step ``i`` updates its rows under the read of step ``i +
    1``'s, writes its own back when that read has landed, and starts the read
    of step ``i + 2``'s when they are written; each turn goes as two copies
    and the next turn starts between them."""
    i, n = pl.program_id(0), pl.num_programs(0)
    layer, bs, heads = layer_ref[0], cols_ref.shape[0], buf.shape[1]
    cuts = ((0, heads - 1), (heads - 1, 1)) if heads > 1 else ((0, 1),)

    def turn(j, out):
        for part, (at, size) in enumerate(cuts):
            vmem = buf.at[j % 3, pl.ds(at, size)]
            rows = pl.ds(j * heads + at, size)
            if out:
                yield pltpu.make_async_copy(vmem, s_out_hbm.at[layer, rows],
                                            sem.at[2 + part])
            else:
                yield pltpu.make_async_copy(s_hbm.at[layer, rows], vmem,
                                            sem.at[part])

    @pl.when(i == 0)
    def _():
        for c in turn(0, False):
            c.start()
        for c in turn(0, False):
            c.wait()

        @pl.when(n > 1)
        def _():
            for c in turn(1, False):
                c.start()

    mine = buf.at[i % 3]

    def slot(j, carry):
        cols = cols_ref[j]                                   # (dk, 2 H)
        for h in range(H):                # unrolled: a column is a STATIC lane
            r = j * H + h
            kc, qc = cols[:, h:h + 1], cols[:, H + h:H + h + 1]
            S = mine[r] * ab_ref[r]
            d = ab_ref[bs * H + r] * (
                v_ref[j, h:h + 1, :] - jnp.sum(S * kc, axis=0, keepdims=True))
            S = S + kc * d
            mine[r] = S
            o_ref[j, h:h + 1, :] = jnp.sum(S * qc, axis=0, keepdims=True)
        return carry
    jax.lax.fori_loop(0, bs, slot, 0)

    *read_head, read_last = turn(i + 1, False)
    *write_head, write_last = turn(i, True)

    @pl.when(i + 1 < n)
    def _():
        for c in read_head:
            c.wait()
    for c in (*write_head, write_last):
        c.start()

    @pl.when(i + 1 < n)
    def _():
        read_last.wait()
    for c in write_head:
        c.wait()

    @pl.when(i + 2 < n)
    def _():
        for c in turn(i + 2, False):
            c.start()
    write_last.wait()


def _step_slots(slots, state_bytes):
    """Slots a grid step: the most of 8, 4, 2, 1 that divide ``slots`` and
    whose state, three times (the kernel's ``buf``), fits ``_STEP_BUF``."""
    for bs in (8, 4, 2):
        if slots % bs == 0 and 3 * bs * state_bytes <= _STEP_BUF:
            return bs
    return 1


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(state, layer, decay, beta, q, k, v, *, interpret):
    f32 = jnp.float32
    layers, slots, H, dk, dv = state.shape
    bs = _step_slots(slots, 4 * H * dk * dv)
    n_ab = _smem_block(2 * bs * H)
    by_step = lambda a: a.astype(f32).reshape(slots // bs, bs * H)
    ab = jnp.pad(jnp.concatenate([by_step(decay), by_step(beta)], axis=1),
                 ((0, 0), (0, n_ab - 2 * bs * H))).reshape(-1)
    # per slot: dk on the sublanes, (k | q, head) on the lanes
    cols = jnp.concatenate([k.astype(f32), q.astype(f32)],
                           axis=1).swapaxes(1, 2)            # (slots, dk, 2 H)
    per_slot = lambda *dims: pl.BlockSpec((bs,) + dims,
                                          lambda i, layer: (i, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    o, heads = pl.pallas_call(
        functools.partial(_step_kernel, H=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(slots // bs,),
            in_specs=[pl.BlockSpec((n_ab,), lambda i, layer: (i,),
                                   memory_space=pltpu.SMEM),
                      per_slot(dk, 2 * H), per_slot(H, dv), in_hbm],
            out_specs=[per_slot(H, dv), in_hbm],
            scratch_shapes=[pltpu.VMEM((3, bs * H, dk, dv), f32),
                            pltpu.SemaphoreType.DMA((4,))]),
        out_shape=[jax.ShapeDtypeStruct((slots, H, dv), f32),
                   jax.ShapeDtypeStruct((layers, slots * H, dk, dv), f32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_STEP_VMEM),
        interpret=interpret, name=STEP_KERNEL,
    )(jnp.asarray(layer, jnp.int32).reshape(1), ab, cols, v.astype(f32),
      state.reshape(layers, slots * H, dk, dv))
    return o, heads.reshape(state.shape)


def delta_step(state, layer, q, k, v, g, beta, active=None, impl="auto",
               interpret=None):
    """One token for every slot over layer ``layer``'s rows of the serving
    state ``state`` (layers, slots, H, dk, dv) float32, in place.  ``q``,
    ``k`` (slots, H, dk); ``v`` (slots, H, dv); ``g``, ``beta`` (slots, H)
    float32; ``active`` (slots,) bool or None: a dead slot's rows stay as
    they are.  Returns ``(o (slots, H, dv) float32, state)``.  ``impl``:
    ``"kernel"`` / ``"jnp"`` force a form, ``"auto"`` is the kernel on a TPU.
    float32 throughout in either form; the kernel sums its ``dk`` terms in
    another order."""
    f32 = jnp.float32
    g, beta = g.astype(f32), beta.astype(f32)
    if active is not None:
        g = jnp.where(active[:, None], g, 0.0)
        beta = jnp.where(active[:, None], beta, 0.0)
    if impl == "auto":
        impl = "jnp" if _interpret() else "kernel"
    if impl == "jnp":
        o, S = delta_step_jnp(q, k, v, g, beta, state[layer])
        return o, state.at[layer].set(S)
    return _step_call(state, layer, jnp.exp(g), beta, q, k, v,
                      interpret=_interpret() if interpret is None
                      else bool(interpret))
