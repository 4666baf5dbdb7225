"""Device-mesh construction and axis bookkeeping.

TPU-native replacement for the reference's NCCL process-group construction
(``deepspeed/utils/groups.py:107-258``, ``runtime/pipe/topology.py:252``
``PipelineParallelGrid``).  Instead of building torch.distributed groups per
parallelism kind, we build ONE ``jax.sharding.Mesh`` with named axes

    ('pipe', 'data', 'fsdp', 'expert', 'seq', 'tensor')

and express every parallel strategy as a sharding over those axes:

- data         : pure data parallel (ZeRO-0 replication; grads psum'd)
- fsdp         : ZeRO axis — optimizer states (stage 1), gradients (stage 2),
                 parameters (stage 3) sharded here
- tensor       : Megatron-style tensor parallelism (column/row sharding);
                 first-class here, unlike the reference which delegates to mpu
- expert       : MoE expert parallelism (all_to_all rides this axis)
- pipe         : pipeline stages (ppermute rides this axis)
- seq          : sequence/context parallelism (ring attention / Ulysses) —
                 NEW relative to the reference vintage (SURVEY.md §2.2)

Axis ORDER matters on hardware: the innermost (last) axes map to the most
tightly-coupled ICI neighbors.  We place ``tensor`` innermost (highest
bandwidth demand per byte), ``seq``/``expert`` next, and ``pipe``/``data``
outermost so that the outer axes can cross DCN on multi-slice systems.
"""

import os
from typing import Dict, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.logging import logger

# Outer → inner hardware order.
MESH_AXES = ("pipe", "data", "fsdp", "expert", "seq", "tensor")

# Single source of truth for "which axes shard the batch dimension".
# ``expert`` is included: expert parallelism is a sub-grouping of data
# parallelism exactly as in the reference (every rank is data-parallel and EP
# groups partition the DP ranks, ``utils/groups.py:107-258``) — tokens shard
# over the expert axis and expert-stacked params shard their expert dim on it.
BATCH_AXES = ("data", "fsdp", "expert")


def resolve_axis_sizes(axes: Optional[Dict[str, int]] = None,
                       n_devices: Optional[int] = None) -> Dict[str, int]:
    """Fill in ``-1`` axes and validate the product matches the device count.

    At most one axis may be ``-1`` (absorbs remaining devices, like the
    reference's implicit "data parallel gets the rest" rule in
    ``utils/groups.py:160-205``).
    """
    if n_devices is None:
        n_devices = jax.device_count()
    axes = dict(axes or {})
    sizes = {name: int(axes.get(name, 1)) for name in MESH_AXES}
    if "data" not in (axes or {}):
        sizes["data"] = -1  # default: data absorbs the remainder

    wild = [name for name, v in sizes.items() if v == -1]
    if len(wild) > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {wild}")
    fixed = int(np.prod([v for v in sizes.values() if v != -1]))
    if wild:
        if n_devices % fixed != 0:
            raise ValueError(
                f"Device count {n_devices} not divisible by fixed axes product {fixed}")
        sizes[wild[0]] = n_devices // fixed
    else:
        if fixed != n_devices:
            raise ValueError(
                f"Mesh axes product {fixed} != device count {n_devices}: {sizes}")
    return sizes


def make_mesh(axes: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the global device mesh.

    Device order: ``jax.devices()`` enumerates TPU chips in torus-contiguous
    order, so reshaping into (pipe, data, fsdp, expert, seq, tensor) gives
    inner axes the tightest ICI rings.
    """
    if devices is None:
        devices = jax.devices()
    sizes = resolve_axis_sizes(axes, len(devices))
    shape = tuple(sizes[name] for name in MESH_AXES)
    dev_array = np.asarray(devices).reshape(shape)
    mesh = Mesh(dev_array, MESH_AXES)
    logger.info(f"Created mesh {dict(zip(MESH_AXES, shape))} over {len(devices)} devices")
    return mesh


def single_device_mesh() -> Mesh:
    return make_mesh({"data": 1})


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1)


def dp_world_size(mesh: Mesh) -> int:
    """Data-parallel extent = product of batch axes (reference 'dp_world_size')."""
    return int(np.prod([mesh_axis_size(mesh, a) for a in BATCH_AXES]))


def batch_spec() -> P:
    """PartitionSpec sharding the leading batch dim over ``BATCH_AXES``."""
    return P(BATCH_AXES)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec())


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def local_batch_size(mesh: Mesh, global_batch: int) -> int:
    ws = dp_world_size(mesh)
    if global_batch % ws != 0:
        raise ValueError(f"Global batch {global_batch} not divisible by dp world size {ws}")
    return global_batch // ws


def maybe_constrain(x, spec: P):
    """``with_sharding_constraint`` that degrades to identity when no mesh is
    active or the mesh lacks the referenced axes (single-device eager use)."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return x
    names = set(am.axis_names)
    for entry in spec:
        if entry is P.UNCONSTRAINED:
            continue
        for ax in ((entry,) if isinstance(entry, str) else (entry or ())):
            if ax not in names:
                return x
    return jax.lax.with_sharding_constraint(x, spec)


def per_device(fn, specs, out_spec):
    """``fn`` made to run once per device, on that device's shard of
    operands laid out as ``specs`` — what a Pallas kernel needs inside
    an SPMD-jitted step: XLA refuses to partition a Mosaic custom call
    ("wrap the call in a shard_map"), so the call goes manual over the
    mesh.  Identity when no mesh is active or it holds one device.  A
    spec names the axes an operand dim MAY be sharded over; those the
    active mesh lacks, or an enclosing ``shard_map`` already made
    manual, are dropped."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty or am.size == 1:
        return fn
    free = set(am.axis_names) - set(am.manual_axes)
    if not free:
        return fn

    def present(spec):
        dims = []
        for entry in spec:
            axes = (entry,) if isinstance(entry, str) else (entry or ())
            axes = tuple(a for a in axes if a in free)
            dims.append(axes if len(axes) > 1 else (axes[0] if axes
                                                    else None))
        return P(*dims)

    return jax.shard_map(fn, mesh=am, axis_names=frozenset(free),
                         in_specs=tuple(present(sp) for sp in specs),
                         out_specs=present(out_spec), check_vma=False)


class MeshContext:
    """Holds the mesh + derived extents; passed through engines.

    Replaces the reference's grid objects (``PipelineParallelGrid``,
    ``utils/groups.py`` module state) with one immutable context.
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    @property
    def world_size(self) -> int:
        return self.mesh.size

    @property
    def dp_world_size(self) -> int:
        return dp_world_size(self.mesh)

    @property
    def fsdp_size(self) -> int:
        return mesh_axis_size(self.mesh, "fsdp")

    @property
    def tensor_size(self) -> int:
        return mesh_axis_size(self.mesh, "tensor")

    @property
    def expert_size(self) -> int:
        return mesh_axis_size(self.mesh, "expert")

    @property
    def pipe_size(self) -> int:
        return mesh_axis_size(self.mesh, "pipe")

    @property
    def seq_size(self) -> int:
        return mesh_axis_size(self.mesh, "seq")

    def __repr__(self):
        return f"MeshContext({dict(self.mesh.shape)})"


_GLOBAL_MESH: Optional[MeshContext] = None


def set_global_mesh(mesh: Mesh) -> MeshContext:
    global _GLOBAL_MESH
    _GLOBAL_MESH = MeshContext(mesh)
    return _GLOBAL_MESH


def get_global_mesh() -> Optional[MeshContext]:
    return _GLOBAL_MESH
