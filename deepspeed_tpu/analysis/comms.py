"""Per-step collective census + declarative comms budget.

The census is taken at two levels:

  - **jaxpr level**: explicit named-axis collectives (``psum``,
    ``all_gather``, ...) from ``shard_map``/``pmap`` regions — the
    explicitly scheduled paths (pipeline, ring attention, MoE dispatch);
  - **compiled-HLO level**: the collectives XLA's SPMD partitioner
    inserted for sharding constraints (``all-reduce``, ``reduce-scatter``,
    ...) — the implicit ZeRO traffic.

A :class:`CommsBudget` declares per-kind ceilings (op count and payload
bytes per step); :func:`check_budget` turns census overruns into
findings.  ZeRO's comms-volume math (1x / 1x / 1.5x parameter bytes for
stages 1/2/3, ZeRO arXiv:1910.02054 §7) makes these budgets writable in
advance of a bench run.
"""

from dataclasses import dataclass, field
from typing import Optional

from .findings import Finding

# canonical kind names; both jaxpr primitives and HLO opcodes map here
# (jax 0.9.0 under shard_map's vma typing spells psum / all_gather of a
# varying operand ``psum_invariant`` / ``all_gather_invariant``, and the
# unreduced-sharding forms ``unreduced_psum`` / ``all_gather_reduced`` /
# ``unreduced_reduce_scatter``)
KIND_ALIASES = {
    "psum": "all_reduce", "psum_invariant": "all_reduce",
    "unreduced_psum": "all_reduce", "pmax": "all_reduce",
    "pmin": "all_reduce", "all-reduce": "all_reduce",
    "all_gather": "all_gather", "all_gather_invariant": "all_gather",
    "all_gather_reduced": "all_gather", "all-gather": "all_gather",
    "psum_scatter": "reduce_scatter", "reduce_scatter": "reduce_scatter",
    "unreduced_reduce_scatter": "reduce_scatter",
    "reduce-scatter": "reduce_scatter",
    "all_to_all": "all_to_all", "all-to-all": "all_to_all",
    "ppermute": "collective_permute", "pshuffle": "collective_permute",
    "collective-permute": "collective_permute",
    "pbroadcast": "broadcast", "collective-broadcast": "broadcast",
}

COLLECTIVE_KINDS = tuple(sorted(set(KIND_ALIASES.values())))


def canonical_kind(name: str) -> Optional[str]:
    return KIND_ALIASES.get(name)


# wire dtypes that mark a QUANTIZED collective (the int8/int4 payloads of
# runtime/comm/quantized.py; u16 excluded — bf16 parses as u16 in HLO)
QUANT_DTYPE_NAMES = frozenset({"s8", "u8", "int8", "uint8", "s4", "u4",
                               "int4", "uint4"})


@dataclass
class CensusEntry:
    kind: str                 # canonical kind
    op: str                   # raw primitive / HLO opcode name
    axes: tuple = ()          # named axes (jaxpr level; empty for HLO)
    bytes: int = 0            # payload bytes (sum of output aval bytes)
    eqn_path: Optional[str] = None
    level: str = "jaxpr"      # "jaxpr" | "hlo"
    dtypes: tuple = ()        # payload dtype names (classification)
    groups: int = 0           # replica-group count (HLO; 0 = unknown).
    #                           >1 marks a sub-axis ("two-level") phase
    shapes: tuple = ()        # the payload's arrays, (dims, bytes) each (HLO)
    loop: Optional[str] = None  # the while body the op runs in (HLO level)
    trips: int = 1            # times a step it runs: enclosing trip counts

    @property
    def quantized(self) -> bool:
        """True when every payload dtype is an int8/int4 wire format."""
        return bool(self.dtypes) and all(d in QUANT_DTYPE_NAMES
                                         for d in self.dtypes)

    def to_dict(self):
        return {"kind": self.kind, "op": self.op, "axes": list(self.axes),
                "bytes": self.bytes, "eqn_path": self.eqn_path,
                "level": self.level, "dtypes": list(self.dtypes),
                "groups": self.groups, "quantized": self.quantized}


def summarize(census) -> dict:
    """{kind: {"count", "bytes", "quantized_count", "quantized_bytes"}}
    over both census levels."""
    out = {}
    for e in census:
        rec = out.setdefault(e.kind, {"count": 0, "bytes": 0,
                                      "quantized_count": 0,
                                      "quantized_bytes": 0})
        rec["count"] += 1
        rec["bytes"] += e.bytes
        if e.quantized:
            rec["quantized_count"] += 1
            rec["quantized_bytes"] += e.bytes
    return out


def _param_shaped(dims, param_shapes) -> bool:
    """Is an array of ``dims`` a parameter, one layer of a stacked one,
    or a vector?  Unit dims do not count, and a dim may be up to a
    twentieth larger than the parameter's (the TPU compiler pads what
    it reduce-scatters)."""
    dims = tuple(d for d in dims if d != 1)
    if len(dims) < 2:
        return True
    for shape in param_shapes:
        for cand in (shape, shape[1:]):
            cand = tuple(d for d in cand if d != 1)
            if len(cand) == len(dims) and all(
                    c <= d <= c + c // 20 for c, d in zip(cand, dims)):
                return True
    return False


def step_collectives(census, param_shapes=()) -> dict:
    """What one step of a compiled program moves, each entry weighted by
    the trip counts of the loops it sits in: payload bytes of the
    all-gathers, of the reductions (reduce-scatter + all-reduce), of
    everything else, and ``non_param_bytes``: of arrays that have no
    parameter's shape (nor a layer's slice of one, nor a vector's).  A
    ZeRO-3 step that gathers weights reads about 0 there and 1x / 0.5x
    the 16-bit parameter bytes a pass in the first two
    (arXiv:1910.02054 §7); one that moves activations, logits or token
    ids instead grows there with batch x T."""
    out = {"all_gather_bytes": 0, "reduce_bytes": 0, "other_bytes": 0,
           "non_param_bytes": 0, "collectives": 0}
    for e in census:
        n = e.trips
        key = {"all_gather": "all_gather_bytes",
               "reduce_scatter": "reduce_bytes",
               "all_reduce": "reduce_bytes"}.get(e.kind, "other_bytes")
        out[key] += e.bytes * n
        out["collectives"] += n
        out["non_param_bytes"] += n * sum(
            nbytes for dims, nbytes in e.shapes
            if not _param_shaped(dims, param_shapes))
    return out


def wire_report(census, *, full_itemsize: int = 4) -> dict:
    """Wire vs logical accounting for a (possibly compressed) step.

    ``wire_bytes`` is what the census actually measured; for quantized
    entries ``logical_bytes`` re-prices the payload at ``full_itemsize``
    bytes/element (int8: numel == wire bytes; packed int4 is counted as
    its int8 equivalent — the census cannot see through the packing).
    ``grouped`` counts sub-axis (two-level) collective phases.
    """
    wire = logical = q_wire = grouped = 0
    for e in census:
        wire += e.bytes
        if e.quantized:
            q_wire += e.bytes
            logical += e.bytes * full_itemsize
        else:
            logical += e.bytes
        if e.groups > 1:
            grouped += 1
    return {"wire_bytes": wire, "logical_bytes": logical,
            "quantized_wire_bytes": q_wire,
            "quantized_fraction": (q_wire / wire if wire else 0.0),
            "grouped_collectives": grouped,
            "by_kind": summarize(census)}


@dataclass
class CommsBudget:
    """Declarative per-step ceilings, checked against the census.

    ``per_kind`` maps a canonical kind (see :data:`COLLECTIVE_KINDS`) to
    ``{"max_count": int|None, "max_bytes": int|None}``; ``None`` (or a
    missing kind) means unlimited.  ``total_max_bytes`` bounds the sum
    over every kind.
    """
    per_kind: dict = field(default_factory=dict)
    total_max_bytes: Optional[int] = None

    def __post_init__(self):
        for kind in self.per_kind:
            assert kind in COLLECTIVE_KINDS, \
                f"unknown collective kind {kind!r}; known: {COLLECTIVE_KINDS}"


def check_budget(census, budget: CommsBudget):
    """Census overruns → findings (rule DSTPU203)."""
    findings = []
    summary = summarize(census)
    for kind, limits in budget.per_kind.items():
        got = summary.get(kind, {"count": 0, "bytes": 0})
        max_count = limits.get("max_count")
        if max_count is not None and got["count"] > max_count:
            findings.append(Finding(
                "DSTPU203", "error",
                f"comms budget exceeded: {got['count']} {kind} ops per step "
                f"(budget {max_count})",
                eqn_path=f"census/{kind}",
                extra={"kind": kind, "count": got["count"],
                       "max_count": max_count}))
        max_bytes = limits.get("max_bytes")
        if max_bytes is not None and got["bytes"] > max_bytes:
            findings.append(Finding(
                "DSTPU203", "error",
                f"comms budget exceeded: {got['bytes']} {kind} payload "
                f"bytes per step (budget {max_bytes})",
                eqn_path=f"census/{kind}",
                extra={"kind": kind, "bytes": got["bytes"],
                       "max_bytes": max_bytes}))
    if budget.total_max_bytes is not None:
        total = sum(rec["bytes"] for rec in summary.values())
        if total > budget.total_max_bytes:
            findings.append(Finding(
                "DSTPU203", "error",
                f"comms budget exceeded: {total} total collective payload "
                f"bytes per step (budget {budget.total_max_bytes})",
                eqn_path="census/total",
                extra={"bytes": total,
                       "max_bytes": budget.total_max_bytes}))
    return findings
