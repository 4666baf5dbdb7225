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

  ``L``, ``T``, ``U``, ``W`` and the two masked score matrices of EVERY chunk
  are batched products; the chunks are then walked one after the other with
  three products each.  The products run in the operands' dtype (bfloat16 as
  served) with float32 accumulation; ``G``, ``T`` and the carried ``S`` are
  float32.  ``T`` by :func:`unit_lower_inverse`: the 16 x 16 diagonal blocks
  by the four products ``(I + N)(I + N^2)(I + N^4)(I + N^8)`` of the
  nilpotent ``N = -L``, then pairs of blocks merged (``T21 = -T22 L21 T11``)
  up to the chunk: the six products over a whole chunk of 64 pass through
  powers a thousand times the result's size when the keys are alike (silu
  leaves them mostly positive), and a forward substitution is 64 passes over
  every chunk's matrix.  ``jax.numpy`` throughout (scope ``gdn.chunk`` in the
  model): a kernel of the name ``gated_delta_chunk_scan`` is a later PR's.
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


def delta_chunk(q, k, v, g, beta, S0=None, chunk=64, t_real=None):
    """The recurrence of :func:`delta_scan_jnp` in chunks of ``chunk`` tokens
    (module docstring): same operands, same returns.  ``t_real`` (a traced
    scalar) leaves the positions from it on out of the state
    (:func:`mask_pads`); a length that is no multiple of ``chunk`` is padded
    the same way.  Products in ``q.dtype`` accumulated in float32."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    cd = q.dtype
    C = min(chunk, 1 << max(T - 1, 0).bit_length())
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
