"""Mamba-2 (state-space duality): the chunked scan of a prompt, the one-token
update of a decode step, the grouped gated norm.

No reference counterpart (the upstream kernels live in ``mamba_ssm``).  The
recurrence, per head ``h`` of ``P`` channels, in group ``g = h // (H / G)``,
over a state of ``N`` columns::

    S_t[h]  = exp(dt_t[h] * A[h]) * S_{t-1}[h] + dt_t[h] * x_t[h] (x) B_t[g]
    y_t[h]  = S_t[h] C_t[g] + D[h] * x_t[h]

``S[h]`` is ``(P, N)`` float32.  Beside Mamba-1 (``ops/selective_scan.py``):

==================  ==========================  ============================
                    Mamba-1 (Jamba2-3B)         Mamba-2 (Nemotron-3-Nano)
==================  ==========================  ============================
decay               one a channel and column    one a HEAD (64 heads of 64)
``B_t``, ``C_t``    16 scalars a token          vectors of 128, one a GROUP
                                                of 8 heads (8 groups)
state a layer       ``(16, 5120)`` float32      ``(64, 64, 128)`` float32
                    327,680 bytes a stream      2,097,152 bytes a stream
  as it is served   the same                    ``(8, 128, 512)``: a group,
                                                ``N``, the group's (head,
                                                channel)
conv carry          ``(3, 5120)``               ``(3, 6144)`` (x, B and C)
prompt              a recurrence on the VPU     chunks of 128 on the MXU
==================  ==========================  ============================

Layout.  The scan works and hands its state back in the published ``(H, P,
N)`` orientation: ``N`` = 128 is the minor dim, exactly one lane tile, ``P``
rides the sublanes.  The SERVING state, which only the one-token update reads,
is kept as ``(G, N, (H / G) P)`` (:func:`step_layout`, :func:`to_step_layout`):
``N`` rides the sublanes and a group's 8 heads x 64 channels = 512 the lanes.
``y[h, p] = sum_n S[h, p, n] C[g, n]`` contracts over ``N``: with ``N`` on the
lanes every vreg of the state pays a lane broadcast of ``dt x`` and a lane
reduction for ``y`` (the XLU's work; it held the update at 69 % of its copy,
PERF.md section 6, PR 44); with ``N`` on the sublanes ``dt x`` and the decay
are rows, ``B`` and ``C`` columns, and the sum over ``N`` is vreg-wise
addition.

Three pieces, each in two forms of one signature:

- :func:`ssd_scan` — a prompt, in chunks of ``chunk`` tokens: inside a chunk
  the ``(C B^T o decay) X`` products of each group and head, between chunks a
  carried ``(H, P, N)`` float32 state a sequence.  :func:`ssd_scan_jnp` is the
  ``jax.numpy`` form (the CPU, the tests, any call with an initial state);
  :func:`ssd_scan_kernel` the TPU form, one ``pallas_call`` named
  ``mamba2_ssd_scan``: grid ``(batch, groups, chunks)``, chunks innermost, a
  group's 8 states in VMEM scratch, everything TRANSPOSED (channels on the
  sublanes, the chunk's tokens on the lanes) so that each head's slice is a
  whole number of sublane tiles and every per-token factor is a row.
- :func:`ssm_step` — one token for every slot over ONE layer's rows of the
  serving state ``(layers, slots, G, N, (H / G) P)``, IN PLACE: the TPU form
  (``pallas_call`` ``mamba2_state_update``, the leaf left in HBM and aliased
  to its output, the layer a prefetched scalar, 8 slots' 16 MB a grid step
  through three VMEM buffers, reads and writes taking turns) reads and writes
  the state once; a dead slot is handed ``decay`` 1 and ``dt x`` 0, which
  leave its rows what they were bit for bit.  The ``jax.numpy`` form goes
  through :func:`ssm_step_jnp` over the published orientation and lays the
  result out again: one leaf for the CPU, the tests and the chip.
- :func:`gated_group_norm` — ``RMSNorm(y * silu(z))`` with the mean square
  taken over each GROUP's channels.

The convolution is ``selective_scan.causal_conv`` over the ``x | B | C``
channels together (its carry the last ``K - 1`` inputs).

As ``selective_scan``: the state handed back is the state after the last
token whose ``dt`` is non-zero, so a caller that pads a prompt zeroes ``dt``
from the true length on (``selective_scan.mask_delta``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .selective_scan import causal_conv, conv_tail_at, mask_delta  # noqa: F401

SCAN_KERNEL = "mamba2_ssd_scan"
STEP_KERNEL = "mamba2_state_update"
_STEP_BUF = 48 << 20      # the update's three buffers of the state in VMEM
_STEP_VMEM = 64 << 20     # those, and the per-slot operands beside them


def _interpret():
    return jax.default_backend() != "tpu"


def gated_group_norm(y, z, w, groups, eps):
    """``RMSNorm(y * silu(z); w)`` over the last dim, the mean square taken
    over each of ``groups`` equal runs of channels (gate first, then the
    norm).  float32 inside; returns ``y.dtype``."""
    f32 = jnp.float32
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    lead, Di = g.shape[:-1], g.shape[-1]
    g = g.reshape(lead + (groups, Di // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(lead + (Di,)) * w.astype(f32)).astype(y.dtype)


# ---------------------------------------------------------- the chunked scan
def _smem_block(n):
    """A rank-1 SMEM block is a whole number of the 1,024-word tiles XLA lays
    a long float32 vector out in."""
    return -(-n // 1024) * 1024


def _chunks(T, chunk):
    L = min(chunk, T)
    return L, -(-T // L)


def ssd_scan_jnp(x, dt, A, B, C, D, h0=None, chunk=128):
    """``x`` (Bt, T, H, P); ``dt`` (Bt, T, H) float32, after softplus; ``A``
    (H,) float32 (negative); ``B``, ``C`` (Bt, T, G, N); ``D`` (H,); ``h0``
    (Bt, H, P, N) float32 or None.  Returns ``(y (Bt, T, H, P) in x.dtype,
    state (Bt, H, P, N) float32)``.  float32 throughout."""
    f32 = jnp.float32
    Bt, T, H, P = x.shape
    G, N = B.shape[2:]
    L, nc = _chunks(T, chunk)
    pad = lambda a: jnp.pad(a.astype(f32), ((0, 0), (0, nc * L - T))
                            + ((0, 0),) * (a.ndim - 2))
    cut = lambda a: pad(a).reshape((Bt, nc, L) + a.shape[2:])
    xs, dts = cut(x), cut(dt)                     # a pad token: dt = 0
    heads = lambda a: jnp.repeat(cut(a), H // G, axis=3)   # (Bt, nc, L, H, N)
    Bh, Ch = heads(B), heads(C)
    cs = jnp.cumsum(dts * A.astype(f32), axis=2)           # (Bt, nc, L, H)
    xdt = xs * dts[..., None]
    # inside a chunk: token t reads token s <= t through exp(cs_t - cs_s)
    seg = cs[:, :, :, None] - cs[:, :, None]               # (Bt, nc, t, s, H)
    causal = jnp.tril(jnp.ones((L, L), bool))[:, :, None]
    M = jnp.einsum("bcthn,bcshn->bctsh", Ch, Bh) \
        * jnp.exp(jnp.where(causal, seg, -jnp.inf))
    y = jnp.einsum("bctsh,bcshp->bcthp", M, xdt)
    # what a chunk adds to the state, and what it leaves of the state it met
    to_end = jnp.exp(cs[:, :, -1:] - cs)
    added = jnp.einsum("bcsh,bcshp,bcshn->bchpn", to_end, xdt, Bh)
    kept = jnp.exp(cs[:, :, -1])                           # (Bt, nc, H)

    def step(S, inp):
        k, a = inp
        return k[..., None, None] * S + a, S
    S0 = jnp.zeros((Bt, H, P, N), f32) if h0 is None else h0.astype(f32)
    S, met = jax.lax.scan(step, S0, (kept.swapaxes(0, 1),
                                     added.swapaxes(0, 1)))
    y = y + jnp.einsum("bcthn,cbhpn->bcthp", Ch, met) * jnp.exp(cs)[..., None]
    y = y.reshape(Bt, nc * L, H, P)[:, :T] \
        + D.astype(f32)[:, None] * x.astype(f32)
    return y.astype(x.dtype), S


def _ssd_kernel(end_ref, xT_ref, dt_ref, cs_ref, csc_ref, b_ref, c_ref,
                yT_ref, s_out_ref, s_ref, *, heads, P):
    """Grid (batch, groups, chunks), chunks innermost; one group's ``heads``
    heads over one chunk of ``L`` tokens, transposed.  Blocks: ``end`` (2
    heads,) float32 in SMEM, each head's cumulative sum at the chunk's end
    and its exponential; ``xT`` (heads P, L); ``dt``, ``cs`` (heads, L)
    float32 rows and ``csc`` (L, heads) the same cumulative sums as columns;
    ``b``, ``c`` (L, N); ``yT`` (heads P, L) float32; state out and scratch
    (heads, P, N) float32."""
    c = pl.program_id(2)
    f32 = jnp.float32

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    Bm, Cm = b_ref[...], c_ref[...]
    cd = Bm.dtype
    L = Bm.shape[0]
    nt = (((1,), (1,)), ((), ()))
    # (B C^T)[s, t], shared by the group's heads
    bct = jax.lax.dot_general(Bm, Cm, nt, preferred_element_type=f32)
    s_id = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    t_id = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = t_id >= s_id
    for j in range(heads):
        rows = slice(j * P, (j + 1) * P)
        cs_row = cs_ref[j:j + 1, :]                          # (1, L) over t
        cs_col = csc_ref[:, j:j + 1]                         # (L, 1) over s
        dt_row = dt_ref[j:j + 1, :]
        xT = xT_ref[rows, :].astype(f32)                     # (P, L)
        S = s_ref[j]                                         # (P, N)
        decay = jnp.exp(jnp.where(causal, cs_row - cs_col, -jnp.inf))
        y = jnp.dot((xT * dt_row).astype(cd), (bct * decay).astype(cd),
                    preferred_element_type=f32)
        y = y + jax.lax.dot_general(S.astype(cd), Cm, nt,
                                    preferred_element_type=f32) \
            * jnp.exp(cs_row)
        yT_ref[rows, :] = y
        xw = (xT * (dt_row * jnp.exp(end_ref[j] - cs_row))).astype(cd)
        s_ref[j] = end_ref[heads + j] * S + jnp.dot(
            xw, Bm, preferred_element_type=f32)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out_ref[...] = s_ref[...]


def ssd_scan_kernel(x, dt, A, B, C, D, chunk=128, *, interpret=None):
    """The Pallas path of :func:`ssd_scan_jnp` for ``h0 = None``: same
    operands, same returns.  The matmuls run in ``x.dtype`` (bfloat16 as
    served), the decays, the cumulative sums and the carried state in
    float32.  ``D x`` and the transposes in and out are XLA's."""
    return _scan_call(x, dt, A, B, C, D, chunk=int(chunk),
                      interpret=_interpret() if interpret is None
                      else bool(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _scan_call(x, dt, A, B, C, D, *, chunk, interpret):
    f32 = jnp.float32
    Bt, T, H, P = x.shape
    G, N = B.shape[2:]
    per = H // G
    L, nc = _chunks(T, chunk)
    Tp = nc * L
    pad = lambda a: jnp.pad(a, ((0, 0), (0, Tp - T)) + ((0, 0),) * (a.ndim - 2))
    dtp = pad(dt.astype(f32))                                # a pad: dt = 0
    cs = jnp.cumsum((dtp * A.astype(f32)).reshape(Bt, nc, L, H), axis=2)
    end = cs[:, :, -1].reshape(Bt, nc, G, per).swapaxes(1, 2)  # (Bt,G,nc,per)
    end = jnp.concatenate([end, jnp.exp(end)], axis=-1)
    n_end = _smem_block(2 * per)           # a 1-D SMEM block: whole tiles
    end = jnp.pad(end, ((0, 0),) * 3 + ((0, n_end - 2 * per),)).reshape(-1)
    cs = cs.reshape(Bt, Tp, G, per)
    rows = lambda a: a.transpose(0, 2, 3, 1)                 # (Bt, G, per, Tp)
    xT = pad(x).reshape(Bt, Tp, H * P).swapaxes(1, 2)        # (Bt, H P, Tp)
    by_group = lambda a: pad(a.astype(x.dtype)).swapaxes(1, 2)  # (Bt,G,Tp,N)
    row = pl.BlockSpec((None, None, per, L), lambda b, g, c: (b, g, 0, c))
    tok = pl.BlockSpec((None, None, L, N), lambda b, g, c: (b, g, c, 0))
    chan = pl.BlockSpec((None, per * P, L), lambda b, g, c: (b, g, c))
    yT, S = pl.pallas_call(
        functools.partial(_ssd_kernel, heads=per, P=P),
        grid=(Bt, G, nc),
        in_specs=[pl.BlockSpec((n_end,),
                               lambda b, g, c: ((b * G + g) * nc + c,),
                               memory_space=pltpu.SMEM),
                  chan, row, row,
                  pl.BlockSpec((None, None, L, per),
                               lambda b, g, c: (b, g, c, 0)),
                  tok, tok],
        out_specs=[chan, pl.BlockSpec((None, per, P, N),
                                      lambda b, g, c: (b, g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((Bt, H * P, Tp), f32),
                   jax.ShapeDtypeStruct((Bt, H, P, N), f32)],
        scratch_shapes=[pltpu.VMEM((per, P, N), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=SCAN_KERNEL,
    )(end, xT, rows(dtp.reshape(Bt, Tp, G, per)), rows(cs),
      cs.transpose(0, 2, 1, 3), by_group(B), by_group(C))
    y = yT.swapaxes(1, 2)[:, :T].reshape(Bt, T, H, P) \
        + D.astype(f32)[:, None] * x.astype(f32)
    return y.astype(x.dtype), S


def ssd_scan(x, dt, A, B, C, D, h0=None, chunk=128, impl="auto"):
    """Dispatch: the kernel on a TPU for a fresh state (``impl="auto"``), the
    ``jax.numpy`` form otherwise; ``"kernel"`` / ``"jnp"`` force one."""
    if impl == "auto":
        impl = "kernel" if h0 is None and not _interpret() else "jnp"
    if impl == "kernel":
        assert h0 is None, "the kernel starts from a zero state"
        return ssd_scan_kernel(x, dt, A, B, C, D, chunk)
    return ssd_scan_jnp(x, dt, A, B, C, D, h0, chunk)


# ------------------------------------------------------ the one-token update
def ssm_step_jnp(x, dt, A, B, C, D, S):
    """One token for every row: ``x`` (Bt, H, P); ``dt`` (Bt, H) float32;
    ``B``, ``C`` (Bt, G, N); ``S`` (Bt, H, P, N) float32.  Returns ``(y (Bt,
    H, P) in x.dtype, new S)``."""
    f32 = jnp.float32
    H, G = x.shape[1], B.shape[1]
    xf, dt = x.astype(f32), dt.astype(f32)
    heads = lambda a: jnp.repeat(a.astype(f32), H // G, axis=1)
    S = jnp.exp(dt * A.astype(f32))[..., None, None] * S \
        + (dt[..., None] * xf)[..., None] * heads(B)[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", S, heads(C)) + D.astype(f32)[:, None] * xf
    return y.astype(x.dtype), S


def step_layout(H, P, N, G):
    """One slot's rows of the serving state, per layer: ``(G, N, (H / G)
    P)``, the ``(H, P, N)`` state with each group's heads and channels on the
    lanes and ``N`` on the sublanes (:func:`to_step_layout`)."""
    return (G, N, H // G * P)


def to_step_layout(S, G):
    """``(..., H, P, N)`` -> ``(..., G, N, (H / G) P)``: head ``h``'s channel
    ``p`` is lane ``(h % (H / G)) P + p`` of group ``h // (H / G)``."""
    lead, (H, P, N) = S.shape[:-3], S.shape[-3:]
    return jnp.moveaxis(S.reshape(lead + (G, H // G * P, N)), -1, -2)


def from_step_layout(L, H):
    """The inverse of :func:`to_step_layout`: ``(..., G, N, (H / G) P)`` ->
    ``(..., H, P, N)``."""
    lead, (G, N, W) = L.shape[:-3], L.shape[-3:]
    return jnp.moveaxis(L, -1, -2).reshape(lead + (H, G * W // H, N))


def _step_kernel(layer_ref, da_ref, dtx_ref, cols_ref, s_hbm, y_ref, s_out_hbm,
                 buf, sem, *, H):
    """Grid (slots / bs,): ``bs`` slots' rows of one layer a step.  ``da``
    (bs H,) float32 in SMEM, each slot's decay a head; ``dtx`` (bs, G, W)
    float32, ``dt x`` with a group's ``W = (H / G) P`` (head, channel) on the
    lanes; ``cols`` (N, bs 2 G) float32, ``N`` on the sublanes and per slot
    ``B`` and then ``C`` on the lanes; ``y`` (bs, G, W) float32; the state
    (layers, slots G, N, W) float32 stays in HBM, in and (aliased) out, and a
    step's ``bs G`` groups pass through one of ``buf``'s three (bs G, N, W).

    A group's state is multiplied and added to vreg for vreg (a row broadcast
    over the sublanes, a column over the lanes), and the sum over ``N`` is
    vreg-wise addition with one fold of 8 sublanes a lane tile at its end.

    READS AND WRITES TAKE TURNS.  This chip reads at 92 % of its bandwidth
    while nothing is written and moves 80 % of it, reads and writes together,
    as soon as a write is in flight (PERF.md section 6, PR 44).  So step ``i``
    updates its rows under the read of step ``i + 1``'s, which began in step
    ``i - 1``, writes its own back when that read has landed, and starts the
    read of step ``i + 2``'s when they are written.  Each turn goes as two
    copies, all but the last group and the last group, and the next turn
    starts between them: the pipe does not drain where the turn changes."""
    i, n = pl.program_id(0), pl.num_programs(0)
    layer, (bs, G, W), groups = layer_ref[0], dtx_ref.shape, buf.shape[1]
    per = H // G
    # which of its group's heads a lane belongs to
    head = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1) // (W // per)
    cuts = ((0, groups - 1), (groups - 1, 1)) if groups > 1 else ((0, 1),)

    def turn(j, out):
        for part, (at, size) in enumerate(cuts):
            vmem = buf.at[j % 3, pl.ds(at, size)]
            rows = pl.ds(j * groups + at, size)
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

    for r in range(groups):               # unrolled: a column is a STATIC lane
        j, g = divmod(r, G)
        decay = jnp.zeros((1, W), jnp.float32)
        for k in range(per):
            decay = jnp.where(head == k, da_ref[j * H + g * per + k], decay)
        at = j * 2 * G + g
        b, c = cols_ref[:, at:at + 1], cols_ref[:, at + G:at + G + 1]
        S = mine[r] * decay + b * dtx_ref[j, g:g + 1, :]
        mine[r] = S
        y_ref[j, g:g + 1, :] = jnp.sum(S * c, axis=0, keepdims=True)

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
def _step_call(ssm, layer, decay, dtx, B, C, *, interpret):
    f32 = jnp.float32
    _, slots, G, N, W = ssm.shape
    H = decay.shape[1]
    bs = _step_slots(slots, 4 * G * N * W)
    n_da = _smem_block(bs * H)
    da = jnp.pad(decay.astype(f32).reshape(slots // bs, bs * H),
                 ((0, 0), (0, n_da - bs * H))).reshape(-1)
    # a step's columns in one tile: N on the sublanes, (slot, B | C, group)
    # on the lanes (128 of them at 8 slots of 8 groups: nothing padded)
    cols = jnp.concatenate([B.astype(f32), C.astype(f32)], axis=1).reshape(
        slots // bs, bs * 2 * G, N).swapaxes(1, 2)
    per_slot = lambda *dims: pl.BlockSpec((bs,) + dims,
                                          lambda i, layer: (i, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    y, groups = pl.pallas_call(
        functools.partial(_step_kernel, H=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(slots // bs,),
            in_specs=[pl.BlockSpec((n_da,), lambda i, layer: (i,),
                                   memory_space=pltpu.SMEM),
                      per_slot(G, W),
                      pl.BlockSpec((None, N, bs * 2 * G),
                                   lambda i, layer: (i, 0, 0)), in_hbm],
            out_specs=[per_slot(G, W), in_hbm],
            scratch_shapes=[pltpu.VMEM((3, bs * G, N, W), f32),
                            pltpu.SemaphoreType.DMA((4,))]),
        out_shape=[jax.ShapeDtypeStruct((slots, G, W), f32),
                   jax.ShapeDtypeStruct((ssm.shape[0], slots * G, N, W), f32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_STEP_VMEM),
        interpret=interpret, name=STEP_KERNEL,
    )(jnp.asarray(layer, jnp.int32).reshape(1), da,
      dtx.astype(f32).reshape(slots, G, W), cols,
      ssm.reshape(ssm.shape[0], slots * G, N, W))
    return y.reshape(dtx.shape), groups.reshape(ssm.shape)


def ssm_step(ssm, layer, x, dt, A, B, C, D, active=None, impl="auto",
             interpret=None):
    """One token for every slot over layer ``layer``'s rows of the serving
    state ``ssm`` (layers, slots, G, N, (H / G) P) float32
    (:func:`step_layout`), in place.  ``x`` (slots, H, P); ``dt`` (slots, H)
    float32; ``B``, ``C`` (slots, G, N); ``active`` (slots,) bool or None: a
    dead slot's rows stay as they are.  Returns ``(y (slots, H, P) in
    x.dtype, ssm)``; ``impl`` as :func:`ssd_scan`.  float32 throughout in
    either form; the kernel's ``y`` sums its 128 terms in another order."""
    f32 = jnp.float32
    if impl == "auto":
        impl = "jnp" if _interpret() else "kernel"
    if impl == "jnp":
        S = from_step_layout(ssm[layer], x.shape[1])
        y, S2 = ssm_step_jnp(x, dt, A, B, C, D, S)
        if active is not None:
            S2 = jnp.where(active[:, None, None, None], S2, S)
        return y, ssm.at[layer].set(to_step_layout(S2, B.shape[1]))
    dt, xf = dt.astype(f32), x.astype(f32)
    decay, dtx = jnp.exp(dt * A.astype(f32)), dt[..., None] * xf
    if active is not None:
        decay = jnp.where(active[:, None], decay, 1.0)
        dtx = jnp.where(active[:, None, None], dtx, 0.0)
    y, ssm = _step_call(ssm, layer, decay, dtx, B, C,
                        interpret=_interpret() if interpret is None
                        else bool(interpret))
    return (y + D.astype(f32)[:, None] * xf).astype(x.dtype), ssm
