"""A grouped matrix product on the TPU: rows sorted by group, each times its
group's matrix, an empty group's matrix never read.

The routed experts' products (``moe/dropless.py::grouped_product`` is the one
caller and chooses the tiles).  The algorithm is that of the grouped matmul
JAX ships (``jax.experimental.pallas.ops.tpu.megablox.gmm``, which ran here
until PR 43): a grid ``(tiles of N, visits, tiles of K)`` whose middle axis
walks the (group, tile of rows) pairs that hold a row, K innermost into a
float32 accumulator, and at the last K tile the rows of the visit's group
stored over what the output tile held (a tile of rows that two groups share
is visited once for each, one after the other, so it is still resident).

What differs is the table of visits and what a trace costs.  ``megablox``
builds the table from a histogram, two ``repeat``\\ s and a roll, 115 of the
143 ms it takes to TRACE one call, and a call is traced again for every
count of rows: on ``serve_batch_deepseek_v2`` (23 executables, a prefill
bucket each) ``setup.trace_lower_s`` rose 26.9 -> 34.8 s over ``ragged_dot``
and a warm ``setup_s`` 52 -> 61 s (PERF.md section 6, PR 43).  :func:`visits`
says the same in two cumulative sums and one comparison of every step with
every group, in ``lax`` alone; the function is a ``jax.jit`` of its own, so
products of one shape inside an executable (an expert's gate and up) are
traced and lowered once; with all of K in one tile the body carries no
accumulator.  The same cell: 25.4 -> 27.2 s and 53.1 -> 53.1 s; alone the
kernel reads 2 to 7 % under ``megablox``'s at the same tiles.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def visits(sizes, tiles_m, tm):
    """Which (group, tile of rows) each step of the grid's middle axis works
    on: ``(group, tile, start, end, count)``.  ``group`` / ``tile``
    (tiles_m + G - 1,) int32, the visits in order (groups ascending, a
    group's tiles ascending: a tile's visits are consecutive), whatever lies
    past ``count`` = the number of visits never run; ``start`` / ``end``
    (G,) the rows ``[start, end)`` of each group.  A group of ``n`` rows
    whose first lies in tile ``a`` and last in tile ``b`` is visited ``b - a
    + 1`` times, an empty one never: at most one visit a tile and one more a
    group that starts inside one.  In ``lax`` alone and with no gather: a
    ``jax.numpy`` operator or index costs a millisecond to trace, and this is
    traced for every product of every executable."""
    G = sizes.shape[0]
    steps = tiles_m + G - 1
    end = lax.cumsum(sizes)
    start = lax.sub(end, sizes)
    first = lax.div(start, jnp.int32(tm))
    last = lax.div(lax.sub(end, jnp.int32(1)), jnp.int32(tm))
    tiles = lax.select(lax.gt(sizes, jnp.int32(0)),
                       lax.add(lax.sub(last, first), jnp.int32(1)),
                       lax.full_like(sizes, 0))
    upto = lax.cumsum(tiles)                    # visits of groups 0 .. g
    before = lax.sub(upto, tiles)
    over = lambda x: lax.broadcast_in_dim(x, (steps, G), (1,))
    step = lax.broadcasted_iota(jnp.int32, (steps, G), 0)
    mine = lax.bitwise_and(lax.ge(step, over(before)),
                           lax.lt(step, over(upto)))
    pick = lambda x: lax.reduce_sum(
        lax.select(mine, over(x), lax.full_like(step, 0)), (1,))
    group = pick(lax.iota(jnp.int32, G))
    tile = lax.add(lax.iota(jnp.int32, steps), pick(lax.sub(first, before)))
    return (group, lax.clamp(jnp.int32(0), tile, jnp.int32(tiles_m - 1)),
            start, end, upto[-1])


def _kernel(group, tile, start, end, lhs, rhs, out, *acc, tm, tiles_k,
            transposed):
    v, k_i = pl.program_id(1), pl.program_id(2)
    product = lax.dot_general(
        lhs[...], rhs[...],
        (((1,), (1 if transposed else 0,)), ((), ())),
        preferred_element_type=jnp.float32)

    def store(total):
        g = group[v]
        row = tile[v] * tm + lax.broadcasted_iota(jnp.int32, total.shape, 0)
        mine = (row >= start[g]) & (row < end[g])
        # the rows of this tile that are another group's keep what that
        # group's visit stored (or will store over this)
        out[...] = jnp.where(mine, total,
                             out[...].astype(jnp.float32)).astype(out.dtype)

    if tiles_k == 1:                # all of K in one tile: nothing to carry
        store(product)
        return
    acc, = acc

    @pl.when(k_i == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += product

    @pl.when(k_i == tiles_k - 1)
    def _():
        store(acc[...])


@functools.partial(jax.jit, static_argnames=("tiles", "transposed",
                                             "interpret"))
def grouped_matmul(rows, w, sizes, tiles, transposed=False, interpret=False):
    """``rows`` (M, K), M a multiple of ``tiles[0]``, sorted by group;
    ``w`` (G, K, N), or (G, N, K) ``transposed`` (read as stored); ``sizes``
    (G,) int32.  ``tiles`` = (rows, tk, tn) a tile: ``tk`` divides K and
    ``tn`` N, each a multiple of 128 or the whole dim.  Returns (M, N) in
    ``rows.dtype``, accumulated in float32; rows in no group come back
    unspecified.  One ``pallas_call`` named ``gmm`` (the name the device
    trace and the executables have had for it since PR 42)."""
    M, K = rows.shape
    G = w.shape[0]
    N = w.shape[1 if transposed else 2]
    tm, tk, tn = tiles
    assert M % tm == 0 and K % tk == 0 and N % tn == 0, (rows.shape, w.shape,
                                                          tiles)
    group, tile, start, end, count = visits(sizes.astype(jnp.int32), M // tm,
                                            tm)
    if transposed:
        w_spec = pl.BlockSpec((None, tn, tk),
                              lambda n, v, k, g, t, s, e: (g[v], n, k))
    else:
        w_spec = pl.BlockSpec((None, tk, tn),
                              lambda n, v, k, g, t, s, e: (g[v], k, n))
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tiles_k=K // tk,
                          transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((M, N), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda n, v, k, g, t, s, e: (t[v], k)),
                      w_spec],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, v, k, g, t, s, e: (t[v], n)),
            grid=(N // tn, count, K // tk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]
            if K // tk > 1 else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name="gmm",
    )(group, tile, start, end, rows, w)
