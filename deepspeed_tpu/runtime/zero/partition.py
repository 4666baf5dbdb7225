"""ZeRO stages as sharding placement rules.

TPU-native re-design of the reference's ZeRO machinery (SURVEY.md §7
"sharding, not hooks"):

- reference stage 1 (``zero/stage_1_and_2.py:92``: flattened fp16 groups +
  per-rank fp32 partition) → optimizer state + fp32 master params sharded on
  the ``fsdp`` mesh axis; compute params stay replicated.
- reference stage 2 (bucketed reduce-scatter fired by grad hooks,
  ``stage_1_and_2.py:777,1198``) → gradients constrained to the same fsdp
  sharding BEFORE the optimizer update; XLA's SPMD partitioner then emits a
  reduce-scatter instead of an all-reduce — the entire hook/bucket/stream
  apparatus disappears into one sharding constraint.
- reference stage 3 (``zero/stage3.py:228`` + ``partition_parameters.py:555``
  ``zero.Init`` param interception + ``partitioned_param_coordinator.py``
  fetch/prefetch/release state machine) → parameters themselves sharded on
  ``fsdp`` everywhere, and the MODEL says where a layer's weights become
  whole: :func:`gather_layer` inside the rematerialised block of its layer
  scan (the fetch; the release is the end of the block: the gathered copy
  is no residual of the scan, and the backward gathers again), with the
  hidden stream held to the batch axes (:func:`shard_stream`).  Left to
  itself the SPMD partitioner is free to keep the weights sharded and
  move ACTIVATIONS instead (tensor parallelism over ``fsdp``): the CPU's
  does so in every layer, and the TPU's did so for the tied head (a
  411 MB logit all-reduce in each loss chunk, PERF.md §6, PR 31), while
  gathering the block matrices by its own choice.  The constraint's
  transpose hands each layer's weight gradient back reduce-scattered.
  ``param_persistence_threshold`` keeps small params replicated exactly
  like the reference's persistence threshold.

The sharding rule for a single array: shard the LARGEST axis divisible by the
fsdp extent (falls back to replicated if none divides), composing with any
tensor-parallel spec the model declares.
"""

import contextlib
from typing import Optional

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...parallel import mesh as M


def shardable_axis(shape, extent: int, taken_axes=()) -> Optional[int]:
    """Largest axis divisible by ``extent``, excluding axes already sharded."""
    if extent <= 1 or not shape:
        return None
    best = None
    for i, dim in enumerate(shape):
        if i in taken_axes:
            continue
        if dim % extent == 0:
            if best is None or dim > shape[best]:
                best = i
    return best


def fsdp_spec(shape, fsdp_size: int, *, persistence_threshold: int = 0,
              base_spec: Optional[P] = None) -> P:
    """PartitionSpec sharding one array over the fsdp axis.

    ``base_spec`` carries tensor-parallel axes already assigned by the model;
    fsdp composes onto a remaining axis.  Arrays with fewer elements than
    ``persistence_threshold`` stay replicated (parity: reference
    ``param_persistence_threshold``, ``zero/config.py``).
    """
    base = tuple(base_spec) if base_spec is not None else ()
    base = base + (None,) * (len(shape) - len(base))
    if int(np.prod(shape or (1,))) < persistence_threshold:
        return P(*base)
    taken = tuple(i for i, s in enumerate(base) if s is not None)
    axis = shardable_axis(shape, fsdp_size, taken_axes=taken)
    if axis is None:
        return P(*base)
    new = list(base)
    existing = new[axis]
    if existing is None:
        new[axis] = "fsdp"
    elif isinstance(existing, str):
        new[axis] = (existing, "fsdp")
    else:
        new[axis] = tuple(existing) + ("fsdp",)
    return P(*new)


def _spec_tree(params, fn):
    return jax.tree_util.tree_map(lambda p: fn(np.shape(p)), params)


def param_specs(params, stage: int, fsdp_size: int, *,
                persistence_threshold: int = 0, tp_specs=None):
    """Sharding specs for the COMPUTE parameters by ZeRO stage.

    Stage 0/1/2: replicated (modulo tensor-parallel specs).
    Stage 3:     fsdp-sharded (reference param partitioning).
    """
    def one(shape, base):
        if stage >= 3:
            return fsdp_spec(shape, fsdp_size, persistence_threshold=persistence_threshold,
                             base_spec=base)
        return base if base is not None else P()

    if tp_specs is None:
        return _spec_tree(params, lambda s: one(s, None))
    return jax.tree_util.tree_map(lambda p, sp: one(np.shape(p), sp), params, tp_specs)


def master_specs(params, stage: int, fsdp_size: int, *, tp_specs=None):
    """Sharding specs for fp32 master params + optimizer moments.

    Stage >= 1: fsdp-sharded (reference per-rank fp32 partition,
    ``stage_1_and_2.py:228-270``).  Stage 0: replicated.
    """
    def one(shape, base):
        if stage >= 1:
            return fsdp_spec(shape, fsdp_size, base_spec=base)
        return base if base is not None else P()

    if tp_specs is None:
        return _spec_tree(params, lambda s: one(s, None))
    return jax.tree_util.tree_map(lambda p, sp: one(np.shape(p), sp), params, tp_specs)


def grad_specs(params, stage: int, fsdp_size: int, *, tp_specs=None):
    """Sharding constraint applied to gradients before the update.

    Stage >= 2: fsdp-sharded → XLA emits reduce-scatter (reference stage-2
    bucketed reduce-scatter).  Stage < 2: same placement as params → plain
    all-reduce (reference allreduce_bucket).
    """
    if stage >= 2:
        return master_specs(params, 1, fsdp_size, tp_specs=tp_specs)
    return param_specs(params, min(stage, 2), fsdp_size, tp_specs=tp_specs)


def _entry_axes(entry) -> tuple:
    """The mesh axes one PartitionSpec entry names."""
    return (entry,) if isinstance(entry, str) else tuple(entry or ())


def _has_fsdp(spec: P) -> bool:
    return any("fsdp" in _entry_axes(entry) for entry in spec)


def relayout_report(params, stage: int, old_fsdp: int, new_fsdp: int, *,
                    persistence_threshold: int = 0, tp_specs=None) -> dict:
    """Summarize how ZeRO placements change across an fsdp-extent change
    (the elastic reshard-on-resize path, docs/elasticity.md).

    The placement rules are pure functions of (shape, stage, fsdp extent)
    — arXiv 1910.02054's observation that a ZeRO shard layout is derivable
    from the world size alone — so a resize is a deterministic
    re-partition: recompute the specs at the new extent and ``device_put``
    the full (gathered) checkpoint arrays under them.  This report names
    what that re-partition does: how many leaves change their spec, and
    how many lose their fsdp sharding entirely because no axis divides the
    new extent (they fall back to replicated — still correct, but
    memory-relevant, so the resume path logs it).
    """
    def counts(old_specs, new_specs):
        olds = jax.tree_util.tree_leaves(
            old_specs, is_leaf=lambda x: isinstance(x, P))
        news = jax.tree_util.tree_leaves(
            new_specs, is_leaf=lambda x: isinstance(x, P))
        changed = sum(1 for o, n in zip(olds, news) if tuple(o) != tuple(n))
        fallback = sum(1 for o, n in zip(olds, news)
                       if _has_fsdp(o) and not _has_fsdp(n))
        return {"leaves": len(news), "respec": changed,
                "replicated_fallback": fallback}

    report = {"old_fsdp": old_fsdp, "new_fsdp": new_fsdp}
    report["params"] = counts(
        param_specs(params, stage, old_fsdp,
                    persistence_threshold=persistence_threshold,
                    tp_specs=tp_specs),
        param_specs(params, stage, new_fsdp,
                    persistence_threshold=persistence_threshold,
                    tp_specs=tp_specs))
    report["master"] = counts(
        master_specs(params, stage, old_fsdp, tp_specs=tp_specs),
        master_specs(params, stage, new_fsdp, tp_specs=tp_specs))
    return report


def to_named(specs, mesh: Mesh):
    return jax.tree_util.tree_map(lambda sp: NamedSharding(mesh, sp), specs,
                                  is_leaf=lambda x: isinstance(x, P))


def constrain(tree, specs, mesh: Optional[Mesh] = None):
    """with_sharding_constraint over a pytree of PartitionSpecs.

    ``mesh`` is required unless a mesh context is already set (jax.set_mesh);
    with it, specs are bound into NamedShardings.
    """
    if mesh is not None:
        bind = lambda sp: NamedSharding(mesh, sp)
    else:
        bind = lambda sp: sp
    return jax.tree_util.tree_map(
        lambda x, sp: jax.lax.with_sharding_constraint(x, bind(sp)), tree, specs)


def _less_fsdp(spec: P) -> P:
    dims = []
    for entry in spec:
        axes = tuple(a for a in _entry_axes(entry) if a != "fsdp")
        dims.append(axes if len(axes) > 1 else (axes[0] if axes else None))
    return P(*dims)


def gather_layer(layer_params, layer_specs):
    """One layer's weights made whole over ``fsdp`` at the point of use.

    ``layer_specs`` state the target: the model's own tensor-parallel
    spec for each leaf (a traced leaf does not show its sharding), any
    ``fsdp`` entry taken out.  Called INSIDE the rematerialised block of
    a layer scan this is ZeRO-3's fetch: the partitioner all-gathers the
    layer's slice of the sharded stack there, forward and again in the
    rematerialised backward, the gathered copy is no residual of the
    scan, and the constraint's transpose hands the layer's weight
    gradient back sharded as the stack is (a reduce-scatter).  A leaf
    that never was ``fsdp``-sharded (stages 0-2, under the persistence
    threshold, already gathered by the qwZ route) has the sharding it is
    constrained to: nothing is emitted.  Identity with no mesh."""
    return jax.tree_util.tree_map(
        lambda x, sp: M.maybe_constrain(x, _less_fsdp(sp)),
        layer_params, layer_specs)


_rank_rows = False


@contextlib.contextmanager
def one_rank_rows():
    """While the engine traces ONE data-parallel rank's rows (the qgZ
    partials, vmapped over ranks) a stream's batch dim is local to a
    device: :func:`shard_stream` is the identity."""
    global _rank_rows
    before, _rank_rows = _rank_rows, True
    try:
        yield
    finally:
        _rank_rows = before


def shard_stream(x):
    """The hidden stream ``(batch, ...)`` held to the batch axes on its
    batch dim, every other dim left as it is (a ``seq``- or
    ``tensor``-sharded stream keeps what it has).  With whole weights
    this is what makes the partitioner move weights, not activations.
    Identity with no mesh, or where the batch does not divide."""
    am = jax.sharding.get_abstract_mesh()
    if _rank_rows or am.empty or x.shape[0] % M.dp_world_size(am):
        return x
    return M.maybe_constrain(
        x, P(M.BATCH_AXES, *[P.UNCONSTRAINED] * (x.ndim - 1)))
