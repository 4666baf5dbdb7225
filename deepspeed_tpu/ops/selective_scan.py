"""Selective state-space scan (Mamba-1) and its causal depthwise convolution.

No reference counterpart: the reference framework ships no state-space
kernel (the upstream CUDA ``selective_scan_fn`` lives in ``mamba_ssm``).
The recurrence, per channel ``d`` and state column ``n``::

    S_t[n, d] = exp(delta_t[d] * A[n, d]) * S_{t-1}[n, d]
                + delta_t[d] * x_t[d] * B_t[n]
    y_t[d]    = sum_n S_t[n, d] * C_t[n] + D[d] * x_t[d]
    out_t[d]  = y_t[d] * silu(z_t[d])

With ``z = None`` every entry point hands back ``y`` itself, ungated (the
upstream ``selective_scan_fn(z=None)``): a caller that needs ``y`` before the
gate (a layer whose scan output other layers read) gates it outside.

Layout: the state is ``(N, Di)`` — the channel dim ``Di`` on the 128 lanes,
the 16 state columns outside it.  The published ``(Di, N)`` orientation
would pad a 16-wide minor dim to 128 lanes on a TPU, eight times the bytes.

Two implementations of one signature:

- :func:`selective_scan_jnp` — a plain ``lax.scan`` over time.  What the
  CPU, the tests, ``jax.grad`` and any call with an initial state use.
- :func:`selective_scan_kernel` — the Pallas TPU kernel for prefill (no
  initial state).  Channels fold to ``(8, Di/8)`` sublane x lane tiles; the
  grid is ``(batch, Di/1024, T/chunk)`` with the chunk axis innermost, so one
  program carries a ``(N, 8, 128)`` float32 state — 16 vregs, in registers
  across a chunk's tokens and in VMEM scratch between chunks — while ``x``,
  ``delta``, ``z`` stream through once and ``y`` streams out once.  ``B_t``
  and ``C_t`` are scalars per token: they arrive in SMEM and are splat, so
  every vector operation is a dense ``(8, 128)`` tile and nothing is
  transposed or reduced across lanes.

The state handed back is the state **after the last token whose delta is
non-zero**: a caller that pads a prompt up to a bucket zeroes ``delta`` from
the true length on (``exp(0 * A) = 1`` and ``0 * x * B = 0`` freeze the
state), so the padded tail cannot leak into the recurrence.  :func:`mask_delta`
does that.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUB, _LANE = 8, 128
_GROUP = 4        # tokens unrolled per loop trip (the body is traced anew
#                   for every prefill bucket: 8 doubles a server's set-up)
_CHUNK = 64       # tokens per grid step


def _interpret():
    return jax.default_backend() != "tpu"


def mask_delta(delta, t_real):
    """``delta`` (B, T, Di) with every position from ``t_real`` on set to
    zero: the scan's state then stays what it was after token
    ``t_real - 1``, whatever the padded tail holds."""
    keep = jnp.arange(delta.shape[1]) < t_real
    return jnp.where(keep[None, :, None], delta, 0.0)


# ------------------------------------------------------------------ plain scan
def selective_scan_jnp(x, delta, A, B, C, D, z, h0=None):
    """``x``, ``z``: (Bt, T, Di); ``delta``: (Bt, T, Di) float32, after
    softplus; ``A``: (N, Di) float32 (negative); ``B``, ``C``: (Bt, T, N);
    ``D``: (Di,); ``h0``: (Bt, N, Di) float32 or None; ``z`` None: no gate.
    Returns ``(out (Bt, T, Di) in x.dtype, state (Bt, N, Di) float32)``."""
    f32 = jnp.float32
    Bt, T, Di = x.shape
    N = A.shape[0]
    if h0 is None:
        h0 = jnp.zeros((Bt, N, Di), f32)
    A = A.astype(f32)

    def step(S, inp):
        x_t, d_t, b_t, c_t = inp                     # (Bt, Di) / (Bt, N)
        dA = jnp.exp(d_t[:, None, :] * A[None])
        S = dA * S + (d_t * x_t)[:, None, :] * b_t[:, :, None]
        return S, jnp.einsum("bnd,bn->bd", S, c_t)

    xs = (x.astype(f32).swapaxes(0, 1), delta.astype(f32).swapaxes(0, 1),
          B.astype(f32).swapaxes(0, 1), C.astype(f32).swapaxes(0, 1))
    S, y = jax.lax.scan(step, h0.astype(f32), xs)
    y = y.swapaxes(0, 1) + D.astype(f32) * x.astype(f32)
    if z is None:
        return y.astype(x.dtype), S
    zf = z.astype(f32)
    return (y * zf * jax.nn.sigmoid(zf)).astype(x.dtype), S


def selective_step(x, delta, A, B, C, D, z, S):
    """One token for every row: ``x``, ``z``, ``delta``: (Bt, Di); ``B``,
    ``C``: (Bt, N); ``S``: (Bt, N, Di) float32; ``z`` None: no gate.  Returns
    ``(out (Bt, Di) in x.dtype, new S)``.  Plain ``jax.numpy``: XLA fuses it into one pass
    over the state (decode's update; PERF.md says how far from its bytes)."""
    f32 = jnp.float32
    xf, d = x.astype(f32), delta.astype(f32)
    dA = jnp.exp(d[:, None, :] * A.astype(f32)[None])
    S = dA * S + (d * xf)[:, None, :] * B.astype(f32)[:, :, None]
    y = jnp.einsum("bnd,bn->bd", S, C.astype(f32)) + D.astype(f32) * xf
    if z is None:
        return y.astype(x.dtype), S
    zf = z.astype(f32)
    return (y * zf * jax.nn.sigmoid(zf)).astype(x.dtype), S


# ---------------------------------------------------------------- the kernel
def _scan_kernel(bc_ref, x_ref, dl_ref, *refs, n_state, chunk, seq_len,
                 gated=True):
    """Grid (batch, Di tiles, chunks), chunks innermost.  Blocks: ``x``,
    ``delta``, ``z`` (``gated`` alone), ``y`` (1, chunk, 8, 128); ``A`` (N, 8,
    128); ``D`` (8, 128); ``bc`` (chunk * 2N,) float32 in SMEM, token-major
    ``[B_t, C_t]``; state out (1, N, 8, 128); scratch ``s_ref`` (N, 8,
    128)."""
    z_ref = refs[0] if gated else None
    a_ref, d_ref, y_ref, s_out_ref, s_ref = refs[1:] if gated else refs
    c = pl.program_id(2)
    N = n_state

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    A = [a_ref[n] for n in range(N)]
    Dv = d_ref[...]
    # the last chunk of a length that is no multiple of ``chunk`` holds
    # fewer tokens (always a whole number of groups)
    n_groups = jnp.minimum(chunk, seq_len - c * chunk) // _GROUP

    def group(g, S):
        S = list(S)
        for j in range(_GROUP):
            t = g * _GROUP + j
            dl = dl_ref[0, t]                                # (8, 128) f32
            xv = x_ref[0, t].astype(jnp.float32)
            dx = dl * xv
            acc = Dv * xv
            base = t * (2 * N)
            for n in range(N):
                S[n] = jnp.exp(dl * A[n]) * S[n] + dx * bc_ref[base + n]
                acc = acc + S[n] * bc_ref[base + N + n]
            if gated:
                zv = z_ref[0, t].astype(jnp.float32)
                acc = acc * zv * jax.nn.sigmoid(zv)
            y_ref[0, t] = acc.astype(y_ref.dtype)
        return tuple(S)

    S = jax.lax.fori_loop(0, n_groups, group,
                          tuple(s_ref[n] for n in range(N)))
    for n in range(N):
        s_ref[n] = S[n]

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out_ref[0] = s_ref[...]


def kernel_supports(seq_len, d_inner):
    """Shapes the kernel takes: whole groups of tokens, whole tiles of
    channels.  Anything else goes to the plain scan."""
    return seq_len % _GROUP == 0 and d_inner % (_SUB * _LANE) == 0


def selective_scan_kernel(x, delta, A, B, C, D, z, *, interpret=None):
    """The Pallas path of :func:`selective_scan_jnp` for ``h0 = None``.
    Same operands, same returns; float32 inside.  Jitted, so that the runs
    of layers of one model trace the kernel's body once between them."""
    return _kernel_call(x, delta, A, B, C, D, z,
                        interpret=_interpret() if interpret is None
                        else bool(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kernel_call(x, delta, A, B, C, D, z, *, interpret):
    Bt, T, Di = x.shape
    N = A.shape[0]
    assert kernel_supports(T, Di), (T, Di)
    f32 = jnp.float32
    W = Di // _SUB                                   # lanes per sublane row
    chunk = min(_CHUNK, T)
    n_chunks = -(-T // chunk)
    fold = lambda a: a.reshape(a.shape[:-1] + (_SUB, W))
    # [B_t, C_t] token-major, padded to whole chunks, flat: SMEM is 1-D
    bc = jnp.concatenate([B.astype(f32), C.astype(f32)], axis=-1)
    bc = jnp.pad(bc, ((0, 0), (0, n_chunks * chunk - T), (0, 0)))
    bc = bc.reshape(Bt * n_chunks * chunk * 2 * N)
    per = chunk * 2 * N

    tok = pl.BlockSpec((1, chunk, _SUB, _LANE), lambda b, d, c: (b, c, 0, d))
    gated = z is not None
    kernel = functools.partial(_scan_kernel, n_state=N, chunk=chunk,
                               seq_len=T, gated=gated)
    toks = [x, delta] + ([z] if gated else [])
    y, S = pl.pallas_call(
        kernel,
        grid=(Bt, W // _LANE, n_chunks),
        in_specs=[
            pl.BlockSpec((per,), lambda b, d, c: (b * n_chunks + c,),
                         memory_space=pltpu.SMEM),
            *[tok] * len(toks),
            pl.BlockSpec((N, _SUB, _LANE), lambda b, d, c: (0, 0, d)),
            pl.BlockSpec((_SUB, _LANE), lambda b, d, c: (0, d)),
        ],
        out_specs=[
            tok,
            pl.BlockSpec((1, N, _SUB, _LANE), lambda b, d, c: (b, 0, 0, d)),
        ],
        out_shape=[jax.ShapeDtypeStruct((Bt, T, _SUB, W), f32),
                   jax.ShapeDtypeStruct((Bt, N, _SUB, W), f32)],
        scratch_shapes=[pltpu.VMEM((N, _SUB, _LANE), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="selective_scan",
    )(bc, *[fold(t.astype(f32)) for t in toks], fold(A.astype(f32)),
      fold(D.astype(f32)))
    return y.reshape(Bt, T, Di).astype(x.dtype), S.reshape(Bt, N, Di)


def selective_scan(x, delta, A, B, C, D, z, h0=None, impl="auto"):
    """Dispatch: the kernel on a TPU for a fresh state and shapes it takes
    (``impl="auto"``), the plain scan otherwise; ``"kernel"`` / ``"jnp"``
    force one."""
    if impl == "auto":
        impl = ("kernel" if h0 is None and not _interpret()
                and kernel_supports(x.shape[1], x.shape[2]) else "jnp")
    if impl == "kernel":
        assert h0 is None, "the kernel starts from a zero state"
        return selective_scan_kernel(x, delta, A, B, C, D, z)
    return selective_scan_jnp(x, delta, A, B, C, D, z, h0)


# ------------------------------------------------------- causal depthwise conv
def causal_conv(x, w, b, tail=None):
    """Causal depthwise convolution of width ``K`` over time.  ``x``: (Bt,
    T, Di); ``w``: (K, Di), ``w[K-1]`` meets the current token; ``b``:
    (Di,); ``tail``: the ``K-1`` inputs before ``x`` (Bt, K-1, Di), zeros
    when None.  Returns ``(y (Bt, T, Di), padded)`` where ``padded`` is
    ``[tail, x]`` along time: a caller cuts the next tail out of it."""
    K = w.shape[0]
    Bt, T, Di = x.shape
    if tail is None:
        tail = jnp.zeros((Bt, K - 1, Di), x.dtype)
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(jnp.float32)
    y = b.astype(jnp.float32)
    for k in range(K):
        y = y + padded[:, k:k + T].astype(jnp.float32) * wf[k]
    return y.astype(x.dtype), padded


def conv_tail_at(padded, t_real, width):
    """The ``width`` inputs that end at token ``t_real - 1``, out of
    ``causal_conv``'s ``padded`` (whose first ``width`` rows are the
    incoming tail): rows ``t_real .. t_real + width`` of it."""
    Bt, _, Di = padded.shape
    return jax.lax.dynamic_slice(padded, (0, t_real, 0), (Bt, width, Di))
