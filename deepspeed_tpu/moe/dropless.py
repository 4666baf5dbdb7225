"""Dropless top-k routing and an expert layer that is TOLD which experts it
holds.

No reference counterpart: the reference's gate (``sharded_moe.py``,
``TopKGate``) routes top-1 / top-2 into a capacity-padded ``(E x C, M)``
buffer and drops what overflows; it stays, for ``gpt2_moe``.  Here:

- :func:`route` scores every token over ALL ``E`` experts in float32
  (``softmax`` over the experts, or an independent ``sigmoid`` each) and
  picks its ``k``: plain ``greedy`` top-k, or ``group_limited_greedy`` (the
  experts in ``n_group`` groups; a token keeps the ``topk_group`` groups whose
  best expert scores highest and picks its ``k`` among those: DeepSeek-V2's
  device-limited routing, arXiv:2405.04434 section 2.1.2).  A selection
  ``bias`` (one value an expert, kept by a load balancer and not by the
  gradient) moves the PICK and never the weight.  Nothing is ever dropped:
  there is no capacity.
- :func:`held_experts` computes, for the ``count`` experts ``first ..
  first + count - 1`` that THIS chip holds, their part of every token's
  output: the token-expert pairs are sorted by expert, the pairs of held
  experts run through three grouped matrix products
  (:func:`grouped_product`: on a TPU one Pallas call each, which reads an
  expert's matrices only for the rows routed to it, in tiles chosen from
  the matrices' dims; TWO for a non-gated expert, ``down(act(up(x)))``), and
  each token's pairs are weighted and summed.  What the absent experts would
  add is left out:
  that is the other chips' part of an expert-parallel layer, and nothing here
  stands in for them or for the exchange.  With every expert held
  (``first`` 0, ``count`` ``E``) it is the whole layer.
- :func:`zero_experts` is the part of ZERO-COMPUTE experts: a router may be
  wider than its real experts (``models/longcat_flash.py``: 768 outputs, 512
  experts with matrices and 256 IDENTITY experts, ids 512..767), and a pair
  that falls to an identity expert returns the token's own input times the
  routing weight.  That part is the token's own: it needs no matrices and no
  exchange, every chip computes it whole, and :func:`held_experts` never
  sees it (an id past the real experts is in no group, like an absent
  one's).  :func:`zero_pairs` counts such pairs apart from the pairs that
  fell to another chip.
"""

import collections
import functools
import math
import time

import jax
import jax.numpy as jnp

TOPK_METHODS = ("greedy", "group_limited_greedy")
SCORING_FUNCS = ("softmax", "sigmoid")
# an expert's activation, by its published name
ACTIVATIONS = {"silu": jax.nn.silu,
               "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def activation(name):
    """The activation ``name``; an unknown one is refused by name."""
    if name not in ACTIVATIONS:
        raise ValueError(f"activation = {name!r}: moe/dropless.py computes "
                         f"{tuple(ACTIVATIONS)}")
    return ACTIVATIONS[name]


def check_route(topk_method, scoring_func):
    """Refuse, by name, a route this module does not compute."""
    if topk_method not in TOPK_METHODS:
        raise ValueError(f"topk_method = {topk_method!r}: moe/dropless.py "
                         f"routes {TOPK_METHODS}")
    if scoring_func not in SCORING_FUNCS:
        raise ValueError(f"scoring_func = {scoring_func!r}: moe/dropless.py "
                         f"scores with {SCORING_FUNCS}")


def route(logits, k, *, topk_method="greedy", n_group=1, topk_group=1,
          scoring_func="softmax", norm_topk_prob=False,
          routed_scaling_factor=1.0, bias=None, scale_normed=False):
    """``logits`` (N, E), the router's outputs -> ``(experts (N, k) int32,
    weights (N, k) float32)``.

    Scores are ``softmax`` over all ``E``, or ``sigmoid`` of each, in
    float32.  ``bias`` (E,) is added to the scores for the PICK alone (groups
    and experts); the weights are the picked scores WITHOUT it, renormalised
    to sum to 1 (``norm_topk_prob``, for k > 1) or else multiplied by
    ``routed_scaling_factor``: one or the other, as HF ``DeepseekV2MoEGate``
    does; ``scale_normed`` multiplies the renormalised weights by the factor
    too, for a family that says both.  An unknown ``topk_method`` or
    ``scoring_func`` is refused by name."""
    check_route(topk_method, scoring_func)
    N, E = logits.shape
    logits = logits.astype(jnp.float32)
    scores = (jax.nn.softmax(logits, axis=-1) if scoring_func == "softmax"
              else jax.nn.sigmoid(logits))
    pick_from = scores if bias is None else scores + bias.astype(jnp.float32)
    if topk_method == "group_limited_greedy":
        assert E % n_group == 0 and topk_group <= n_group, (E, n_group)
        best = pick_from.reshape(N, n_group, E // n_group).max(axis=-1)
        _, groups = jax.lax.top_k(best, topk_group)            # (N, topk_group)
        kept = jnp.zeros((N, n_group), bool).at[
            jnp.arange(N)[:, None], groups].set(True)
        # a biased score may be negative: a dropped group's must lie below
        pick_from = jnp.where(jnp.repeat(kept, E // n_group, axis=1),
                              pick_from, 0.0 if bias is None else -jnp.inf)
    weights, experts = jax.lax.top_k(pick_from, k)
    if bias is not None:
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if k > 1 and norm_topk_prob:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
        if scale_normed:
            weights = weights * routed_scaling_factor
    else:
        weights = weights * routed_scaling_factor
    return experts.astype(jnp.int32), weights


# the counters an expert layer reports, in this order (int32 each)
COUNTERS = ("routed_pairs", "pairs_elsewhere", "experts_touched",
            "experts_idle", "tokens_unrouted", "calls_compacted",
            "calls_whole")


def route_counters(experts, first, count, live=None, width=None, rows=None):
    """What fell where, for one layer: ``(7,)`` int32 in :data:`COUNTERS`'
    order.  ``routed_pairs``: token-expert pairs of held experts;
    ``pairs_elsewhere``: the others (together ``k`` x the live tokens);
    ``experts_touched`` / ``experts_idle``: held experts with a token and
    with none; ``tokens_unrouted``: live tokens none of whose experts is
    held.  ``live`` (N,) bool leaves rows out (pad rows, empty slots).

    ``calls_compacted`` / ``calls_whole``: the calls of :func:`held_experts`
    over these ``experts`` (``width`` as given to it; one call, or one every
    ``rows`` tokens where the family cuts a long prompt) whose held pairs
    took ONE slab of the narrow width and more than one, by the same
    :func:`compact_width` and the same count: every row's pairs, live or
    not, for the call gathers those too.  Both 0 where the call keeps the
    whole-width path."""
    N, k = experts.shape
    live = jnp.ones((N,), bool) if live is None else live
    local = experts - first
    seen = (local >= 0) & (local < count)
    held = seen & live[:, None]
    per_expert = jnp.zeros((count,), jnp.int32).at[
        jnp.where(held, local, count)].add(1, mode="drop")
    touched = (per_expert > 0).sum()
    n_held = held.sum()
    rows = N if rows is None else rows
    C = compact_width(rows, k, count, width)
    compacted = whole = 0
    if C is not None:
        calls = -(-N // rows)
        in_call = jnp.pad(seen, ((0, calls * rows - N), (0, 0))).reshape(
            calls, rows * k).sum(axis=1)
        compacted = (in_call <= C).sum()
        whole = calls - compacted
    return jnp.stack([
        n_held, k * live.sum() - n_held, touched, count - touched,
        (live & ~held.any(axis=1)).sum(), compacted, whole]).astype(jnp.int32)


def zero_experts(x, experts, weights, n_real):
    """The identity experts' part of the routed output: ``x[n] * sum_i
    weights[n, i]`` over the pairs whose id is ``n_real`` or more (the ids
    past the experts that have matrices).  ``x`` (N, D) is what the REAL
    experts read too (the normed tokens), not the residual stream.  Returns
    (N, D) float32: it joins a float32 stream, and a weight of up to 6 on
    the token's own input is no place to round."""
    w = jnp.where(experts >= n_real, weights, 0.0).sum(axis=-1, keepdims=True)
    return x.astype(jnp.float32) * w


def zero_pairs(experts, n_real, live=None):
    """How many token-expert pairs fell to a zero-compute expert (an id of
    ``n_real`` or more): int32 scalar.  :func:`route_counters` counts them
    among ``pairs_elsewhere`` (they are not held); a family with such
    experts takes them out of it and reports them beside it.  ``live`` as
    there."""
    zero = experts >= n_real
    if live is not None:
        zero = zero & live[:, None]
    return zero.sum().astype(jnp.int32)


_LANES = 128            # a tile's dim is a multiple of this, or the whole dim
_GMM_ROWS = 128         # the rows of a tile: a held expert meets 5 to 130
_TILE_BYTES = 9 << 19   # the most one copy of a tile of the matrices holds:
#                         4.5 MiB, 3,072 x 768 bfloat16
_VMEM_BYTES = 15 << 20  # what a plan may hold of the 16 MiB Mosaic scopes a call
# :func:`compact_width`: a call of up to this many token-expert pairs keeps
# the whole-width path, and the narrow width is this many times the held
# experts' even share of the pairs.  Measured (ms a call of ``held_experts``
# alone on a v5e, bfloat16, the merged stack, even routing, whole width ->
# one slab of the narrow width; PERF.md section 6, PR 57):
#   Qwen3-Next 4,096 tokens x 10 (64 of 512 held)  5.03 -> 2.22;
#     2,048 tokens                                  2.25 -> 1.21
#   LongCat 2,048 x 12 (16 of 768)  8.23 -> 3.16;  256 x 12     2.13 -> 1.81
#   DeepSeek-V2 1,024 x 6 (20 of 160)  2.51 -> 1.86;  384 x 6   1.59 -> 1.51
#   Trinity 4,096 x 4 (32 of 256)  4.92 -> 4.42
#   Nemotron 1,024 x 6 (32 of 128: half the width)  1.58 -> 1.45;
#     384 x 6 (2,304 pairs, the smallest call over the threshold) 1.138 -> 1.108
# A factor of 1.5 read 1 to 10 % under 2 and 1.25 another 1 to 3 %; 2 keeps
# a prompt whose routing is twice as skewed as even in one pass.  No shape
# over 2,048 pairs lost, and every decode step of the five served cells
# (1,920 pairs at most) lies under it.
_COMPACT_MIN_PAIRS = 2048
_COMPACT_FACTOR = 2


def compact_width(N, k, count, width):
    """The narrow width ``C`` of :func:`held_experts` over ``N`` tokens of
    ``k`` picks each, ``count`` experts held of the ``width`` the router
    picks from; ``None`` where the call keeps the whole-width path.

    From what the call can see and nothing else: ``count / width`` of the
    ``N k`` pairs fall to the held experts if the routing is even, and ``C``
    is :data:`_COMPACT_FACTOR` times that, rounded up to the grouped
    product's tile of rows.  No narrow width where the router's width is
    not given, where the whole width is :data:`_COMPACT_MIN_PAIRS` pairs or
    fewer (every decode step of the served cells lowers to the operations
    it had), where ``C`` is over half the whole width (the way back by token
    for under half the rows: Nemotron's quarter held stands at the edge and
    reads a tie), or
    where a token picks more experts than a tile has rows
    (:func:`_sum_by_token` finds a token's rows in two tiles at most)."""
    pairs = N * k
    if width is None or pairs <= _COMPACT_MIN_PAIRS or k > _GMM_ROWS:
        return None
    C = math.ceil(_COMPACT_FACTOR * pairs * count / width)
    C = -(-C // _GMM_ROWS) * _GMM_ROWS
    return C if 2 * C <= pairs else None


# every grouped product traced in this process, oldest first:
# (time.monotonic(), "gmm 128x5120x384 of 768x5120x1536/120")
_traced = collections.deque(maxlen=4096)


def _on_tpu():
    return jax.default_backend() == "tpu"


def _tile(n, most):
    """The largest multiple of 128 up to ``most`` that divides ``n`` (128
    where none does); ``n`` itself where 128 does not divide it: a block may
    span a whole dim, and one that is no multiple of 128 must."""
    if n % _LANES:
        return n
    fits = [t for t in range(_LANES, min(n, most) + 1, _LANES) if n % t == 0]
    return fits[-1] if fits else _LANES


def plan_vmem_bytes(plan, itemsize=2):
    """What the Pallas grouped matmul holds in VMEM under ``plan`` = (rows,
    tk, tn): two copies each of a tile of the rows, of the matrices and of
    the result, and the float32 accumulator."""
    tm, tk, tn = plan
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def tile_plan(K, N, itemsize=2):
    """``(rows, tk, tn)``: the tiles of the Pallas grouped matmul over
    matrices (K, N), from the shapes alone.  128 rows; **all of K in one
    tile** and as much of N beside it as 4.5 MiB hold; where N is no
    multiple of 128 (or K alone is too long) all of N, and K in tiles of up
    to 4.5 MiB.

    Why, measured (ms a product on a v5e, bfloat16, median of 5 x 16 calls,
    the stack merged into ``layers x count`` groups of which one layer's
    have rows, ``layer`` traced; PERF.md section 6, PR 43; ``ragged`` is
    XLA's kernel for ``ragged_dot`` in the same form; the first three plans
    through ``megablox.gmm``, ``here`` the plan through
    ``ops/grouped_matmul.py``):

    ================================  ======  =========  =========  =========  =====
    K x N / groups, rows (held pairs) ragged  PR 42's    N whole    plan       here
    ================================  ======  =========  =========  =========  =====
    DeepSeek-V2 5120x1536/120                 1024x768   1280x1536  5120x384
      decode 768 (96 over 20 experts) 0.766   0.567      0.528      0.496      0.476
      prefill 6,144 (768)             1.149   0.686      0.642      0.572      0.544
    DeepSeek-V2 1536x5120/120                 768x1024   384x5120   1536x1280
      decode                          0.784   0.547      0.505      0.499      0.486
      prefill                         1.159   0.664      0.612      0.565      0.544
    Trinity 3072x3072/128                     1024x1024  512x3072   3072x768
      decode 384 (48 over 13 of 32)   0.501   0.442      0.416      0.401      0.390
      prefill 4,096 (512)             2.062   1.060      0.987      0.926      0.909
      prefill 32,768 (4,096)          2.555   1.905      1.770      1.388      1.363
    Nemotron 2688x1856/224 (out, in)          896x1856              the same
      decode 1,536 (384)              4.140   0.561                            0.522
      prefill 6,144 (1,536)           4.498   0.712                            0.664
    Nemotron 1856x2688/224                    1856x896              the same
      decode                          3.722   0.527                            0.490
      prefill                         4.085   0.628                            0.583
    ================================  ======  =========  =========  =========  =====

    With K in one tile a group whose rows cross a tile's edge finds its
    matrices still resident (the block index does not change between the two
    visits) and the sums run in ``ragged_dot``'s order: the results were
    equal to the bit at every such plan.  With K whole, tiles of 2.5 to 4.5
    MiB read within 3 % of each other and 1.5 MiB costs up to 13 %; K cut in
    pieces costs 5 to 35 %.  Fewer rows a tile (32, 64) lose 1 to 60 %, 256
    rows gain 2 % on an 8k document and lose 3 % at 38 rows an expert, 512 do
    not fit; the count of rows, the count of groups (the merged stack costs
    3 % over one layer's matrices alone) and a transposed stack move no
    choice, so they are no argument.  Nemotron's two shapes come out as PR
    42 measured them."""
    most = _TILE_BYTES // itemsize
    if N % _LANES == 0 and K * _LANES <= most:
        plan = _GMM_ROWS, K, _tile(N, most // K)
    else:
        tn = _tile(N, 512)
        plan = _GMM_ROWS, _tile(K, most // tn), tn
    if plan_vmem_bytes(plan, itemsize) > _VMEM_BYTES:
        raise ValueError(
            f"grouped product over ({K}, {N}) matrices: tiles {plan} hold "
            f"{plan_vmem_bytes(plan, itemsize)} bytes of VMEM, over "
            f"{_VMEM_BYTES} (a dim that is no multiple of 128 goes in whole)")
    return plan


def products_traced(t0, t1):
    """``{"gmm 128x5120x384 of 768x5120x1536/120": calls}``: the grouped
    products traced between two readings of ``time.monotonic()``, each by
    its kernel, its tiles (rows x tk x tn) and its shapes (M x K x N /
    groups).  ``CachedStep`` puts it on the ``compile.lower`` row of the
    executable that was being traced."""
    seen = collections.Counter(what for t, what in _traced if t0 <= t <= t1)
    return dict(seen)


def grouped_product(rows, w, sizes, transposed=False, interpret=None):
    """``rows`` (M, K), sorted by group, each times its group's matrix: ``w``
    (G, K, N), or (G, N, K) ``transposed``; ``sizes`` (G,) the rows a group,
    the rows past their sum in no group (what comes back for those is
    unspecified).  Returns (M, N) in ``rows.dtype``, accumulated in float32.
    EVERY grouped product of :func:`held_experts` is this one.

    ONE kernel a backend, and no selector on widths.  On a TPU the Pallas
    grouped matmul of ``ops/grouped_matmul.py`` (``gmm`` in an executable
    and in a device trace: ``megablox.gmm``'s algorithm under a table of
    visits that traces in a sixth of the time) under :func:`tile_plan`'s
    tiles: it reads a group's matrices only where rows were routed to it,
    and with K in one tile reads them once.  XLA's own kernel for
    ``jax.lax.ragged_dot`` (``ragged-dot-none``) was 1.3 to 2.3 x slower at
    widths that are multiples of 128 (DeepSeek-V2's 5,120 x 1,536, Trinity's
    3,072 x 3,072) and 7 to 8 x at Nemotron-3-Nano's 2,688 x 1,856, which
    leave it tiles of 128 x 128 (:func:`tile_plan`'s table; PERF.md section
    6, PRs 42 and 43).  Off
    the chip (the CPU's tests, the plain references) ``jax.lax.ragged_dot``;
    ``interpret=True`` / ``False`` forces the Pallas call anywhere.  Each
    trace notes kernel, tiles and shapes for :func:`products_traced`.

    ``transposed`` reads an (out, in) stack as stored: the chip keeps a
    (D, 1856) stack with D minor whichever way it is declared, and a product
    that wants the 1,856 minor re-lays ALL of it first (2.2 GB a layer a
    step, found by compiling for a v5e)."""
    M, K = rows.shape
    G = w.shape[0]
    N = w.shape[1 if transposed else 2]
    shapes = f"{M}x{K}x{N}/{G}"
    if interpret is None:
        if not _on_tpu():
            _traced.append((time.monotonic(), f"ragged_dot of {shapes}"))
            if transposed:
                w = jnp.swapaxes(w, -1, -2)
            return jax.lax.ragged_dot(rows, w, sizes)
        interpret = False
    from ..ops.grouped_matmul import grouped_matmul
    plan = tile_plan(K, N, rows.dtype.itemsize)
    _traced.append((time.monotonic(),
                    "gmm {}x{}x{} of {}".format(*plan, shapes)))
    rows = jnp.pad(rows, ((0, -M % plan[0]), (0, 0)))
    return grouped_matmul(rows, w, sizes, plan, transposed, interpret)[:M]


def held_experts(x, experts, weights, gate_w, up_w, down_w, first,
                 layer=None, act="silu", width=None):
    """The held experts' part of the routed output.

    - ``x`` (N, D): the normed tokens, in the compute dtype;
    - ``experts`` / ``weights`` (N, k): :func:`route`'s;
    - ``gate_w``, ``up_w`` (count, D, F) and ``down_w`` (count, F, D): the
      matrices of experts ``first .. first + count - 1``, each expert
      ``down(act(gate(x)) * up(x))`` (``act`` by name: :data:`ACTIVATIONS`);
      ``gate_w`` None: a NON-GATED expert, ``down(act(up(x)))``, whose
      ``up_w`` is (count, F, D), (out, in) as published.  Or, with
      ``layer`` (a scalar, traced inside a layer loop), every layer's,
      stacked (layers, count, ...).  A slice of such a stack is a COPY in
      front of a grouped product (315 MB a matrix at DeepSeek-V2's widths:
      found by compiling for a v5e), so the stack goes in whole, its two
      leading dims merged, as ``layers x count`` groups of which only
      ``layer``'s have rows;
    - ``width``: how many experts the router picks from (its outputs), from
      which :func:`compact_width` reckons how many of the ``N k`` pairs the
      held experts meet if the routing is even.

    Returns (N, D): ``sum_i weights[n, i] * Expert^{experts[n, i]}(x[n])``
    over the pairs whose expert is held.  The pairs are sorted by expert
    (the absent experts' pairs last, in no group), so each held expert's
    matrices meet only the rows routed to it.

    Where :func:`compact_width` gives a narrow width ``C`` the held pairs,
    which the sort puts first, are worked a SLAB of ``C`` of the sorted list
    at a time, as many slabs as hold a held pair (``ceil(held / C)``: a loop
    whose count is the call's own; one pass wherever the held pairs are
    within twice their even share, none where no pair is held): a slab's
    ``C`` rows of D are gathered, multiplied out (``C`` rows a product, each
    group's rows those of it that lie in the slab) and summed, weighted, by
    token (:func:`_sum_by_token`) into a float32 (N, D).  Nothing of ``N k``
    rows by D or F is written or read on any path, only int32 vectors of that
    length; nothing is dropped and there is no capacity.  One set of grouped
    products serves every count (a second branch at another width would
    trace and lower three more Mosaic calls an executable: 30 to 50 % of a
    prefill executable's trace on DeepSeek-V2 and LongCat, whose layers are
    one loop body; PERF.md section 6, PR 57).  A token's pairs are summed in
    float32 either way, in the whole form in pair order and here a tile of
    the token-sorted rows at a time and slab after slab: the same sum of at
    most ``k`` terms within float32 rounding, and the same from run to run.

    The body is a ``jax.jit`` of its own (inlined by XLA, so a caller's
    executable holds the same operations): a model that unrolls its layers
    traces and lowers the layer's body once an executable and not once a
    layer."""
    N, k = experts.shape
    return _held_experts(
        x, experts, weights, gate_w, up_w, down_w, first, layer, act=act,
        C=compact_width(N, k, up_w.shape[-3], width))


@functools.partial(jax.jit, static_argnames=("act", "C"))
def _held_experts(x, experts, weights, gate_w, up_w, down_w, first, layer, *,
                  act, C):
    """:func:`held_experts` with its narrow width ``C`` (or None) worked
    out."""
    N, k = experts.shape
    act = activation(act)
    count = up_w.shape[-3]
    local = experts.reshape(-1) - first                          # (N k,)
    held = (local >= 0) & (local < count)
    local = jnp.where(held, local, count)                        # absent: last
    order = jnp.argsort(local, stable=True)
    sizes = jnp.zeros((count,), jnp.int32).at[local].add(1, mode="drop")
    if layer is not None:
        n = up_w.shape[0] * count
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n,), jnp.int32), sizes, (layer * count,))
        gate_w, up_w, down_w = (
            None if w is None else w.reshape((n,) + w.shape[2:])
            for w in (gate_w, up_w, down_w))
    dt = x.dtype

    def products(rows, sizes):
        if gate_w is None:
            h = act(grouped_product(rows, up_w.astype(dt), sizes,
                                    transposed=True))
        else:
            h = act(grouped_product(rows, gate_w.astype(dt), sizes)) \
                * grouped_product(rows, up_w.astype(dt), sizes)
        return grouped_product(h, down_w.astype(dt), sizes)

    if C is None:
        with jax.named_scope("moe.gather"):
            rows = x[order // k]                                 # (N k, D)
        out = products(rows, sizes)                              # (N k, D)
        with jax.named_scope("moe.gather"):
            # back to token order: pair j of the sorted list is pair order[j]
            back = jnp.zeros((N * k,), jnp.int32).at[order].set(
                jnp.arange(N * k, dtype=jnp.int32))
            out = out[back].reshape(N, k, -1).astype(jnp.float32)
            # an absent expert's pair took part in no group: whatever its
            # row holds is not read
            w = jnp.where(held.reshape(N, k), weights, 0.0)[..., None]
            return jnp.where(w != 0, out * w, 0.0).sum(axis=1).astype(dt)

    n_held = sizes.sum()
    end = jnp.cumsum(sizes)          # a group's rows of the sorted list are
    start = end - sizes              # [start, end)
    order = jnp.pad(order, (0, -(N * k) % C))
    flat = weights.reshape(-1)

    def slab(s, total):
        lo = s * C
        with jax.named_scope("moe.gather"):
            pairs = jax.lax.dynamic_slice(order, (lo,), (C,))
            token = pairs // k
            rows = _rows(x, token)                               # (C, D)
        out = products(rows, jnp.clip(end, lo, lo + C)
                       - jnp.clip(start, lo, lo + C))            # (C, D)
        with jax.named_scope("moe.gather"):
            # a row past the held pairs took part in no group: not read
            real = lo + jnp.arange(C) < n_held
            w = jnp.where(real, _rows(flat, pairs), 0.0)
            return total + _sum_by_token(out, w, jnp.where(real, token, N), N)

    return jax.lax.fori_loop(
        0, (n_held + C - 1) // C, slab,
        jnp.zeros((N, x.shape[1]), jnp.float32)).astype(dt)


def _sum_by_token(out, w, token, N):
    """``sum_j w[j] * out[j]`` over the rows ``j`` of each token: ``out``
    (C, D) the result rows of the held pairs in the sorted list's order,
    ``w`` (C,) float32 their routing weights, ``token`` (C,) int32 whose row
    each is (``N``, with weight 0, for a row that is no pair's: whatever it
    holds is not read), ``C`` a multiple of the tile of rows.  Returns
    (N, D) float32.

    No scatter: XLA:TPU runs a scatter-add of rows one row after another,
    0.09 us a row of 2,048 float32 and 1.3 us a row of 5,120 (0.92 ms a
    layer at Qwen3-Next's 10,240 rows, 2.0 ms at DeepSeek-V2's 1,536, where
    the whole-width form's way back took 0.7; a sort by token in front of
    ``segment_sum`` is the same scatter: PERF.md section 6, PR 57).  The rows
    are sorted by token instead, each tile of 128 of them is summed by token
    on the MXU (a 128 x 128 matrix that holds row ``r``'s weight at (the
    rank of its token within the tile, ``r``), times the tile, in float32:
    0.14 to 0.66 ms at the same shapes), and a token, whose at most ``k <=
    128`` rows lie in one tile or in two neighbours, reads its one or two
    partial sums."""
    C, D = out.shape
    R, lax = _GMM_ROWS, jax.lax
    # (in ``lax``, few operations: this is traced for every prefill bucket,
    # and a ``jax.numpy`` index costs 5 ms of it)
    token, w, by_token = lax.sort(
        (token, w, lax.iota(jnp.int32, C)), num_keys=1)
    out = jnp.where((w != 0).reshape(C, 1), _rows(out, by_token), 0)
    # the rank of a row's token among the tokens of its tile
    before = lax.concatenate([lax.full((1,), -1, jnp.int32),
                              lax.slice(token, (0,), (C - 1,))], 0)
    rank = lax.cumsum((token != before).astype(jnp.int32)).reshape(C // R, R)
    slot = rank - lax.slice(rank, (0, 0), (C // R, 1))           # (tiles, R)
    weigh = jnp.where(
        slot.reshape(C // R, 1, R) == lax.iota(jnp.int32, R).reshape(R, 1),
        w.reshape(C // R, 1, R), 0.0)                            # slot x row
    part = lax.dot_general(
        weigh, out.astype(jnp.float32).reshape(C // R, R, D),
        (((2,), (1,)), ((0,), (0,))), precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).reshape(C, D)
    # token n's rows are [below[n], below[n + 1]) of the sorted list
    below = (token.reshape(1, C)
             < lax.iota(jnp.int32, N + 1).reshape(N + 1, 1)).sum(
        axis=1, dtype=jnp.int32)
    start, end = lax.slice(below, (0,), (N,)), lax.slice(below, (1,), (N + 1,))
    ends = lax.concatenate([start, lax.max(end - 1, start)], 0)  # (2 N,)
    ends = lax.min(ends, jnp.int32(C - 1))
    tile = ends // R
    both = _rows(part, tile * R + _rows(slot.reshape(C), ends))  # (2 N, D)
    half = lambda a, i: lax.slice_in_dim(a, i * N, (i + 1) * N)
    two = (half(tile, 0) != half(tile, 1)).reshape(N, 1)
    return jnp.where((end > start).reshape(N, 1),
                     half(both, 0) + jnp.where(two, half(both, 1), 0.0), 0.0)


def _rows(a, index):
    """``a[index]`` for an int32 vector ``index`` of rows in bounds, as one
    ``lax.gather`` (``jax.numpy`` indexing traces 5 ms a time)."""
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=tuple(range(1, a.ndim)), collapsed_slice_dims=(0,),
        start_index_map=(0,))
    return jax.lax.gather(a, index.reshape(-1, 1), dnums,
                          (1,) + a.shape[1:], mode="promise_in_bounds")
