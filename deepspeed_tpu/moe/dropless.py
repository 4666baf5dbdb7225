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
  (``jax.lax.ragged_dot``: on a TPU one Mosaic call each, which reads an
  expert's matrices only for the rows routed to it), and each token's pairs
  are weighted and summed.  What the absent experts would add is left out:
  that is the other chips' part of an expert-parallel layer, and nothing here
  stands in for them or for the exchange.  With every expert held
  (``first`` 0, ``count`` ``E``) it is the whole layer.
"""

import jax
import jax.numpy as jnp

TOPK_METHODS = ("greedy", "group_limited_greedy")
SCORING_FUNCS = ("softmax", "sigmoid")


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


def held_experts(x, experts, weights, gate_w, up_w, down_w, first,
                 layer=None):
    """The held experts' part of the routed output.

    - ``x`` (N, D): the normed tokens, in the compute dtype;
    - ``experts`` / ``weights`` (N, k): :func:`route`'s;
    - ``gate_w``, ``up_w`` (count, D, F) and ``down_w`` (count, F, D): the
      SwiGLU matrices of experts ``first .. first + count - 1``; or, with
      ``layer`` (a scalar, traced inside a layer loop), every layer's,
      stacked (layers, count, ...).  A slice of such a stack is a COPY in
      front of a grouped product (315 MB a matrix at DeepSeek-V2's widths:
      found by compiling for a v5e), so the stack goes in whole, its two
      leading dims merged, as ``layers x count`` groups of which only
      ``layer``'s have rows.

    Returns (N, D): ``sum_i weights[n, i] * SwiGLU^{experts[n, i]}(x[n])``
    over the pairs whose expert is held.  The pairs are sorted by expert
    (the absent experts' pairs last, in no group), so each held expert's
    matrices meet only the rows routed to it."""
    N, k = experts.shape
    count = gate_w.shape[-3]
    local = experts.reshape(-1) - first                          # (N k,)
    held = (local >= 0) & (local < count)
    local = jnp.where(held, local, count)                        # absent: last
    order = jnp.argsort(local, stable=True)
    sizes = jnp.zeros((count,), jnp.int32).at[local].add(1, mode="drop")
    if layer is not None:
        n = gate_w.shape[0] * count
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n,), jnp.int32), sizes, (layer * count,))
        gate_w, up_w, down_w = (w.reshape((n,) + w.shape[2:])
                                for w in (gate_w, up_w, down_w))
    rows = x[order // k]                                         # (N k, D)
    dt = x.dtype
    h = jax.nn.silu(jax.lax.ragged_dot(rows, gate_w.astype(dt), sizes)) \
        * jax.lax.ragged_dot(rows, up_w.astype(dt), sizes)
    out = jax.lax.ragged_dot(h, down_w.astype(dt), sizes)        # (N k, D)
    # back to token order: pair j of the sorted list is pair order[j]
    back = jnp.zeros((N * k,), jnp.int32).at[order].set(
        jnp.arange(N * k, dtype=jnp.int32))
    out = out[back].reshape(N, k, -1).astype(jnp.float32)
    # an absent expert's pair took part in no group: whatever its row holds
    # is not read
    w = jnp.where(held.reshape(N, k), weights, 0.0)[..., None]
    return jnp.where(w != 0, out * w, 0.0).sum(axis=1).astype(dt)
