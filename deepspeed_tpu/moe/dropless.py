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
  (:func:`grouped_product`: on a TPU one Mosaic call each, which reads an
  expert's matrices only for the rows routed to it, XLA's or Pallas' by the
  matrices' dims; TWO for a non-gated expert, ``down(act(up(x)))``), and
  each token's pairs are weighted and summed.  What the absent experts would
  add is left out:
  that is the other chips' part of an expert-parallel layer, and nothing here
  stands in for them or for the exchange.  With every expert held
  (``first`` 0, ``count`` ``E``) it is the whole layer.
"""

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
            "experts_idle", "tokens_unrouted")


def route_counters(experts, first, count, live=None):
    """What fell where, for one layer: ``(5,)`` int32 in :data:`COUNTERS`'
    order.  ``routed_pairs``: token-expert pairs of held experts;
    ``pairs_elsewhere``: the others (together ``k`` x the live tokens);
    ``experts_touched`` / ``experts_idle``: held experts with a token and
    with none; ``tokens_unrouted``: live tokens none of whose experts is
    held.  ``live`` (N,) bool leaves rows out (pad rows, empty slots)."""
    N, k = experts.shape
    live = jnp.ones((N,), bool) if live is None else live
    local = experts - first
    held = (local >= 0) & (local < count) & live[:, None]
    per_expert = jnp.zeros((count,), jnp.int32).at[
        jnp.where(held, local, count)].add(1, mode="drop")
    touched = (per_expert > 0).sum()
    n_held = held.sum()
    return jnp.stack([
        n_held, k * live.sum() - n_held, touched, count - touched,
        (live & ~held.any(axis=1)).sum()]).astype(jnp.int32)


_GMM_ROWS = 128         # the rows of a Pallas tile: a held expert meets a dozen
_LANES = 128            # what XLA's own grouped kernel tiles a dim by


def _on_tpu():
    return jax.default_backend() == "tpu"


def _tile(n, cap=1024):
    """The largest multiple of 128 up to ``cap`` that divides ``n``; ``n``
    itself where none does (a block may span a whole dim)."""
    fits = [t for t in range(_LANES, min(n, cap) + 1, _LANES) if n % t == 0]
    return fits[-1] if fits else n


def pallas_grouped(K, N):
    """Whether a grouped product over ``(K, N)`` matrices runs as the Pallas
    grouped matmul on a TPU: where a dim is no multiple of 128.  Decided from
    the shapes alone, so a gated expert of such a width takes the same path
    as a non-gated one."""
    return bool(K % _LANES or N % _LANES)


def grouped_product(rows, w, sizes, transposed=False, interpret=None):
    """``rows`` (M, K), sorted by group, each times its group's matrix: ``w``
    (G, K, N), or (G, N, K) ``transposed``; ``sizes`` (G,) the rows a group,
    the rows past their sum in no group (what comes back for those is
    unspecified).  Returns (M, N) in ``rows.dtype``, accumulated in float32.
    EVERY grouped product of :func:`held_experts` is this one.

    ``jax.lax.ragged_dot`` (on a TPU XLA's own Mosaic call,
    ``ragged-dot-none`` in a trace), except on a TPU where
    :func:`pallas_grouped` says the dims defeat it: XLA tiles its kernel by
    divisors of the dims, and 2,688 = 21 x 128 beside 1,856 = 14.5 x 128
    (Nemotron-3-Nano's experts) leave it tiles of 128 x 128: 10,000 grid
    steps and as many 32 KB copies an expert layer a product, 3.6 ms where
    the bytes take 0.4 (72 % of the traced window; PERF.md section 6, PR 42).
    There the Pallas grouped matmul JAX ships runs (``megablox.gmm``: ``gmm``
    in an executable and in a trace) with tiles of this file's choosing: all
    of a dim that is no multiple of 128 and up to 1,024 of one that is
    (3.3 MB a copy at those widths).  Where both dims are multiples of 128
    (DeepSeek-V2's 5,120 x 1,536, Trinity's 3,072 x 3,072) XLA's kernel is
    what ran before and what is measured: PERF.md section 6 sets the two
    side by side at all three widths.  ``transposed`` reads an (out, in)
    stack as stored: the chip keeps a (D, 1856) stack with D minor whichever
    way it is declared, and a product that wants the 1,856 minor re-lays ALL
    of it first (2.2 GB a layer a step, found by compiling for a v5e)."""
    M, K = rows.shape
    N = w.shape[1 if transposed else 2]
    if interpret is None:
        if not (_on_tpu() and pallas_grouped(K, N)):
            if transposed:
                w = jnp.swapaxes(w, -1, -2)
            return jax.lax.ragged_dot(rows, w, sizes)
        interpret = False
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    rows = jnp.pad(rows, ((0, -M % _GMM_ROWS), (0, 0)))
    out = gmm(rows, w, sizes, preferred_element_type=rows.dtype,
              tiling=(_GMM_ROWS, _tile(K), _tile(N)),
              transpose_rhs=transposed, interpret=interpret)
    return out[:M]


def held_experts(x, experts, weights, gate_w, up_w, down_w, first,
                 layer=None, act="silu"):
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
      ``layer``'s have rows.

    Returns (N, D): ``sum_i weights[n, i] * Expert^{experts[n, i]}(x[n])``
    over the pairs whose expert is held.  The pairs are sorted by expert
    (the absent experts' pairs last, in no group), so each held expert's
    matrices meet only the rows routed to it."""
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
    rows = x[order // k]                                         # (N k, D)
    dt = x.dtype
    if gate_w is None:
        h = act(grouped_product(rows, up_w.astype(dt), sizes, transposed=True))
    else:
        h = act(grouped_product(rows, gate_w.astype(dt), sizes)) \
            * grouped_product(rows, up_w.astype(dt), sizes)
    out = grouped_product(h, down_w.astype(dt), sizes)           # (N k, D)
    # back to token order: pair j of the sorted list is pair order[j]
    back = jnp.zeros((N * k,), jnp.int32).at[order].set(
        jnp.arange(N * k, dtype=jnp.int32))
    out = out[back].reshape(N, k, -1).astype(jnp.float32)
    # an absent expert's pair took part in no group: whatever its row holds
    # is not read
    w = jnp.where(held.reshape(N, k), weights, 0.0)[..., None]
    return jnp.where(w != 0, out * w, 0.0).sum(axis=1).astype(dt)
