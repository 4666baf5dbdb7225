"""Quantized expert-parallel dispatch wire: int8 all_to_all for MoE.

Parity role: the reference's ``deepspeed/moe/sharded_moe.py:85 _AllToAll``
autograd op — the expert-parallel dispatch/combine exchange — upgraded
per ZeRO++ (arXiv:2306.10209) block quantization and the Frontier
low-bandwidth-partitioning result (arXiv:2501.04266): once the
``expert`` mesh axis spans a slow wire (DCN), the full-width
dispatch/combine all_to_all is THE dominant distributed cost, and
shrinking its bytes-on-wire is the lever.

The constraint-only spelling in ``moe/layer.py`` (pin the ``(E, C, M)``
buffer to ``P('expert', ...)`` and let the SPMD partitioner insert the
exchange) moves compute-dtype bytes and leaves the schedule to the
partitioner.  This module replaces it with an EXPLICIT ``shard_map``
exchange whose payload is int8 codes + per-block f32 scales:

- **dispatch** (tokens → expert shards): each device quantizes its
  LOCAL token rows once (the gate runs full-width OUTSIDE the wire, so
  routing/capacity numerics are untouched), replicates them masked per
  destination chunk, and exchanges COMPACT payloads — tokens, never
  the ``cf``×-padded capacity buffer:

  * level 1 (intra): ``lax.all_to_all`` over the ``expert`` axis of
    ``(e, k, S/shards, M)`` int8 codes + per-block f32 scales + int32
    slot addresses — source i's block d holds exactly i's tokens
    routed to chunk d (others masked to the drop sentinel), so this IS
    the reference ``_AllToAll``'s permutation traffic at 1
    byte/element; each receiver then scatters the dequantized rows
    into its own ``(E/e·C, M)`` chunk at their local addresses;
  * level 2 (inter): when tokens are also sharded over outer,
    DCN-crossing axes (``data``; ``fsdp`` rides the fast wire between
    them), the scattered 1/e-size chunk re-quantizes — all-zero blocks
    carry scale **0** so the per-device partials, whose nonzero rows
    are globally DISJOINT (every capacity slot is owned by exactly one
    token), sum EXACTLY in int8 — and ``psum``-reduces over those
    axes: the slow wire sees e× fewer, 4×-narrower bytes.
    ``hierarchical: false`` is the single-level baseline: the old
    full-buffer spelling (scatter locally, quantize the ``(E*C, M)``
    partial, psum it over the outer axes FIRST, then the expert
    all_to_all + segment sum of buffer chunks).

- **combine** (expert shards → tokens): the inverse permutation.  A
  tiny ``all_gather`` of the slot addresses tells each chunk owner
  which rows every peer's tokens claimed; the owner gathers those rows
  from its shard (zeros for rows it does not own), quantizes with
  zero-scale blocks, and the same compact ``all_to_all`` returns
  ``(e, k, S/shards, M)`` per-source partials — at most ONE source is
  non-zero per row (each slot lives in exactly one chunk), so the
  int8 partials sum exactly and each device dequantizes only ITS
  tokens' rows.  No full-buffer broadcast in either direction.

Both directions are wrapped in ``custom_vjp`` pairs: the backward of
dispatch IS the combine-direction exchange of the cotangent and vice
versa, so the backward wire is quantized too.  Differentiating through
``convert_element_type(f32→s8)`` would silently yield zero gradients
(the qwZ lesson, ``quantized.py``); the pair spelling keeps gradients
flowing straight-through while never re-touching the full-width tensor.

The ACTIVE wire is process-global (``set_active``/``get_active``),
installed by the engine from its ``comms_compression`` policy before
each step dispatch and cleared on ``engine.close()`` — mirroring
``parallel/mesh.set_global_mesh``.  The policy is part of the
compile-cache key (``CollectiveRouter.describe``), so flipping it can
never silently reuse a stale executable.
"""
# dstpu: disable-file=DSTPU102 (reviewed: this IS a comms-layer module --
# the MoE wire schedules its own collectives by design, exactly like
# quantized.py's qwZ/qgZ ops)

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import quantized as Q
from ...parallel import mesh as M


class MoEWire:
    """One engine's expert-exchange policy: mesh + quantization knobs.

    ``dispatch``/``combine`` are the only entry points ``moe/layer.py``
    calls; both are trace-time no-ops when :meth:`supports` rejects the
    shape (the layer falls back to the constraint-only full-width
    path — compression must never be a correctness cliff)."""

    def __init__(self, mesh, *, bits: int = 8, block_size: int = 1024,
                 hierarchical: bool = True):
        assert bits == 8, "the MoE wire is an int8 scheme (bits=8)"
        self.mesh = mesh
        self.bits = int(bits)
        self.block_size = int(block_size)
        self.hierarchical = bool(hierarchical)
        self.expert_size = M.mesh_axis_size(mesh, "expert")
        # token-sharding axes OTHER than expert, ordered inner → outer so
        # the hierarchical reduce crosses the slow (outer) wire last and
        # smallest; extent-1 axes emit no collective and are dropped
        self._outer_axes = tuple(
            a for a in ("fsdp", "data")
            if a in mesh.shape and M.mesh_axis_size(mesh, a) > 1)
        # per-step expected wire bytes, recorded at trace time (one entry
        # per traced exchange site+direction) — feeds the engine's
        # CommsBudget after the first cold trace (docs/comms-compression.md)
        self.trace_log = []

    # ------------------------------------------------------------ policy
    def supports(self, E: int, C: int, Mdim: int) -> bool:
        """True when this (E, C, M) exchange can ride the int8 wire:
        the expert dim must tile the ``expert`` axis (the all_to_all
        splits it into per-rank chunks) and there must be a wire to
        compress at all (expert extent > 1)."""
        e = self.expert_size
        return e > 1 and E % e == 0 and Mdim > 0 and C > 0

    def describe(self) -> dict:
        return {"bits": self.bits, "block_size": self.block_size,
                "hierarchical": self.hierarchical,
                "expert_size": self.expert_size}

    # --------------------------------------------------------- accounting
    def _record(self, tag: str, direction: str, E: int, C: int, Mdim: int,
                S: int, k: int, site: int):
        """Trace-time census expectation for one exchange site.

        ``direction`` is the WIRE direction, not the autodiff pass:
        ``"scatter"`` (tokens → expert shards: the compact token
        all_to_all + outer chunk psums — the forward dispatch AND the
        combine backward) or ``"gather"`` (expert shards → tokens: the
        address all_gather + the inverse compact all_to_all — the
        forward combine AND the dispatch backward).  Bytes follow the
        census convention (``analysis/comms.py``: an entry's bytes =
        its OUTPUT aval bytes): the compact all_to_all conserves the
        ``k*S*M`` int8 token payload (+ ``4/B`` f32 scales + 4-byte
        int32 addresses), the outer psums move the scattered ``E/e``
        chunk, the single-level (``hierarchical: false``) baseline
        moves the full ``E*C*M`` buffer instead.  One record per
        unique (tag, site, shape): a retrace (eval twin, warm
        re-specialization) must not inflate the per-step expectation,
        but distinct call sites — two same-shaped MoE layers in one
        model — each emit their own exchanges, so ``site`` (the
        layer's wire id) is part of the identity."""
        key = (tag, site, (E, C, Mdim, S, k))
        if any(ev["site"] == key for ev in self.trace_log):
            return
        e = self.expert_size
        B = Q.pick_block(Mdim, self.block_size)
        n_buf = E * C * Mdim                  # full-buffer int8 elements
        ns_buf = 4 * (n_buf // B)
        n_tok = k * S * Mdim                  # compact token payload
        ns_tok = 4 * k * S * (Mdim // B)
        n_pos = 4 * k * S                     # int32 slot addresses
        if direction == "scatter":            # tokens → expert shards
            if self.hierarchical:
                ev = {"all_to_all": n_tok + ns_tok + n_pos}
                outer = sum(n_buf // e + ns_buf // e
                            for _ in self._outer_axes)
                if outer:
                    ev["all_reduce"] = outer
            else:                             # full-buffer baseline
                ev = {"all_to_all": n_buf + ns_buf}
                outer = sum(n_buf + ns_buf for _ in self._outer_axes)
                if outer:
                    ev["all_reduce"] = outer
        else:                                 # expert shards → tokens
            ev = {"all_gather": n_pos,
                  "all_to_all": n_tok + ns_tok}
        self.trace_log.append({"site": key, "tag": tag,
                               "shape": (E, C, Mdim, S, k), "bytes": ev})

    def expected_wire_bytes(self) -> dict:
        """Per-kind int8-wire byte expectation summed over every traced
        exchange (both directions, forward AND backward).  Empty until
        the first cold trace — a compile-cache warm start skips tracing,
        so budget-driven flows (``--audit-step moe``)
        run one cold step first.  A (tag, site) pair recorded at several
        SHAPES is the same exchange re-specialized (an eval twin at a
        different batch shape, a warm re-specialization) — one compiled
        program runs one variant per step, so the expectation keeps the
        largest variant per pair instead of summing them; distinct
        sites (layers) still sum."""
        per_pair = {}
        for ev in self.trace_log:
            pair = (ev["tag"], ev["site"][1])
            best = per_pair.get(pair)
            if best is None or sum(ev["bytes"].values()) > \
                    sum(best["bytes"].values()):
                per_pair[pair] = ev
        out = {}
        for ev in per_pair.values():
            for kind, b in ev["bytes"].items():
                out[kind] = out.get(kind, 0) + b
        return out

    # ------------------------------------------------------ wire internals
    def _specs(self):
        tok = P(tuple(M.BATCH_AXES))
        return tok, P("expert", None, None)

    def _scatter_reduce(self, vals, pos, E: int, C: int, *, tag: str,
                        site: int = 0):
        """(k, S, M) route payloads + (k, S) global slot addresses →
        ``(E, C, M)`` buffer sharded ``P('expert')``: the quantized
        dispatch-direction exchange (also the combine backward)."""
        mesh = self.mesh
        k, S, Mdim = vals.shape
        e = self.expert_size
        block = Q.pick_block(Mdim, self.block_size)
        out_dtype = vals.dtype
        self._record(tag, "scatter", E, C, Mdim, S, k, site)
        tok, buf_spec = self._specs()
        chunk = (E // e) * C
        vals = M.maybe_constrain(vals, P(None, tuple(M.BATCH_AXES), None))
        pos = M.maybe_constrain(pos, P(None, tuple(M.BATCH_AXES)))

        def a2a(t):
            return jax.lax.all_to_all(t, "expert", split_axis=0,
                                      concat_axis=0, tiled=True)

        def body_compact(v_l, pos_l):
            # compact permutation traffic (module docstring): quantize
            # the LOCAL token rows once, replicate masked per
            # destination chunk — block d of the a2a payload holds
            # exactly this rank's tokens routed to chunk d
            s_l = v_l.shape[1]
            q, s = Q.quantize_blockwise(v_l.astype(jnp.float32),
                                        block_size=block, bits=8,
                                        zero_scale=0.0)
            dest = pos_l // chunk             # >= e for dropped routes
            sel = dest[None] == jnp.arange(e, dtype=dest.dtype)[:, None,
                                                                None]
            qd = jnp.where(sel[..., None], q[None], jnp.int8(0))
            sd = jnp.where(sel[..., None], s[None], jnp.float32(0))
            pd = jnp.where(sel, pos_l[None], E * C)   # drop sentinel
            qd, sd, pd = a2a(qd), a2a(sd), a2a(pd)
            rows = Q.dequantize_blockwise(
                qd.reshape(-1, Mdim), sd.reshape(e * k * s_l, -1),
                bits=8, out_dtype=jnp.float32)
            # every received row is addressed to THIS chunk (or the
            # sentinel, whose rel lands >= chunk and drops)
            rel = pd.reshape(-1) - jax.lax.axis_index("expert") * chunk
            flat = jnp.zeros((chunk, Mdim), jnp.float32)
            flat = flat.at[rel].add(rows, mode="drop")
            if self._outer_axes:
                # level 2: only the 1/e-size chunk crosses the outer
                # (DCN-class) axes; zero-scale blocks keep the
                # (globally disjoint) partials summing exactly in int8
                q2, s2 = Q.quantize_blockwise(flat, block_size=block,
                                              bits=8, zero_scale=0.0)
                for a in self._outer_axes:
                    q2 = jax.lax.psum(q2, a)
                    s2 = jax.lax.psum(s2, a)
                flat = Q.dequantize_blockwise(q2, s2, bits=8,
                                              out_dtype=jnp.float32)
            return flat.astype(out_dtype).reshape(E // e, C, Mdim)

        def body_fullbuf(v_l, pos_l):
            # single-level baseline: scatter locally into the FULL
            # (E*C, M) buffer, quantize, cross the outer axes first,
            # then the expert all_to_all + segment sum of buffer chunks
            flat = jnp.zeros((E * C, Mdim), jnp.float32)
            for r in range(k):
                flat = flat.at[pos_l[r]].add(v_l[r].astype(jnp.float32),
                                             mode="drop")
            q, s = Q.quantize_blockwise(flat, block_size=block, bits=8,
                                        zero_scale=0.0)
            q = q.reshape(E, C, Mdim)
            s = s.reshape(E, C, -1)

            def expert_a2a(t):
                # cast back to the wire dtype: jnp.sum promotes int8 →
                # int32, and disjoint rows (at most one non-zero
                # source per element) mean the cast never clips
                dt = t.dtype
                t = a2a(t)
                return t.reshape((e, E // e) + t.shape[1:]) \
                        .sum(axis=0).astype(dt)

            for ax in self._outer_axes:
                q = jax.lax.psum(q, ax)
                s = jax.lax.psum(s, ax)
            q, s = expert_a2a(q), expert_a2a(s)
            return Q.dequantize_blockwise(
                q.reshape(-1, Mdim), s.reshape(-1, s.shape[-1]),
                bits=8, out_dtype=out_dtype).reshape(E // e, C, Mdim)

        body = body_compact if self.hierarchical else body_fullbuf
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, tok[0], None), P(None, tok[0])),
            out_specs=buf_spec, check_vma=False)(vals, pos)

    def _gather_rows(self, buf, pos, *, tag: str, site: int = 0):
        """``(E, C, M)`` expert-sharded buffer + (k, S) addresses →
        (k, S, M) token-sharded rows: the quantized combine-direction
        exchange (also the dispatch backward).  OOB addresses (dropped
        routes) return exact-zero rows — callers additionally weight
        them by the gate's 0."""
        mesh = self.mesh
        E, C, Mdim = buf.shape
        e = self.expert_size
        k, S = pos.shape
        block = Q.pick_block(Mdim, self.block_size)
        out_dtype = buf.dtype
        self._record(tag, "gather", E, C, Mdim, S, k, site)
        tok, buf_spec = self._specs()
        chunk = (E // e) * C
        pos = M.maybe_constrain(pos, P(None, tuple(M.BATCH_AXES)))

        def body(b_l, pos_l):
            s_l = pos_l.shape[1]
            # tiny int32 side-channel: owners learn every peer's
            # claimed slots (the data-dependent return addresses)
            pall = jax.lax.all_gather(pos_l, "expert", axis=1,
                                      tiled=True)          # (k, e*s_l)
            rel = pall - jax.lax.axis_index("expert") * chunk
            own = (rel >= 0) & (rel < chunk)
            flat = b_l.reshape(chunk, Mdim).astype(jnp.float32)
            rows = flat[jnp.clip(rel, 0, chunk - 1).reshape(-1)]
            rows = jnp.where(own.reshape(-1, 1), rows, jnp.float32(0))
            q, s = Q.quantize_blockwise(rows, block_size=block, bits=8,
                                        zero_scale=0.0)
            # (k·e·s_l, ·) → (e, k, s_l, ·): block j = rank j's tokens'
            # rows from THIS chunk; the inverse a2a routes them home
            q = q.reshape(k, e, s_l, Mdim).transpose(1, 0, 2, 3)
            s = s.reshape(k, e, s_l, -1).transpose(1, 0, 2, 3)
            q = jax.lax.all_to_all(q, "expert", split_axis=0,
                                   concat_axis=0, tiled=True)
            s = jax.lax.all_to_all(s, "expert", split_axis=0,
                                   concat_axis=0, tiled=True)
            # per-source partials for MY tokens: each slot lives in
            # exactly one chunk, so at most one source is non-zero per
            # row and the int8 sum is exact (never clips)
            q = q.sum(axis=0, dtype=jnp.int32).astype(jnp.int8)
            s = s.sum(axis=0)
            out = Q.dequantize_blockwise(
                q.reshape(-1, Mdim), s.reshape(k * s_l, -1),
                bits=8, out_dtype=out_dtype)
            return out.reshape(k, s_l, Mdim)

        return jax.shard_map(
            body, mesh=mesh, in_specs=(buf_spec, P(None, tok[0])),
            out_specs=P(None, tok[0], None), check_vma=False)(buf, pos)

    # ------------------------------------------------------- entry points
    def dispatch(self, x, pos, E: int, C: int, site: int = 0):
        """Token activations ``x (S, M)`` + per-route global slot
        addresses ``pos (k, S)`` (``E*C`` = dropped) → the dispatched
        ``(E, C, M)`` buffer sharded over the ``expert`` axis, int8 on
        every wire hop.  Backward: the cotangent rides the quantized
        combine-direction gather."""
        EC = E * C

        def value(v):
            b = jnp.broadcast_to(v[None], (pos.shape[0],) + v.shape)
            return self._scatter_reduce(b, pos, E, C, tag="dispatch",
                                        site=site)

        @jax.custom_vjp
        def go(v):
            return value(v)

        def fwd(v):
            return value(v), None

        def bwd(_, g):
            rows = self._gather_rows(g, pos, tag="dispatch_bwd", site=site)
            keep = (pos < EC)[..., None].astype(rows.dtype)
            return ((rows * keep).sum(axis=0).astype(x.dtype),)

        go.defvjp(fwd, bwd)
        return go(x)

    def combine(self, buf, pos, site: int = 0):
        """Expert outputs ``buf (E, C, M)`` (expert-sharded) + addresses
        ``pos (k, S)`` → per-route token rows ``(k, S, M)``; callers
        weight them by the gate (0 for dropped routes).  Backward: the
        cotangent rides the quantized dispatch-direction reduce."""
        E, C = buf.shape[0], buf.shape[1]

        @jax.custom_vjp
        def go(b):
            return self._gather_rows(b, pos, tag="combine", site=site)

        def fwd(b):
            return self._gather_rows(b, pos, tag="combine", site=site), None

        def bwd(_, g):
            return (self._scatter_reduce(g, pos, E, C, tag="combine_bwd",
                                         site=site).astype(buf.dtype),)

        go.defvjp(fwd, bwd)
        return go(buf)


# ------------------------------------------------------ active-wire registry
_ACTIVE: Optional[MoEWire] = None


def set_active(wire: Optional[MoEWire]):
    """Install (or clear, with None) the process-global MoE wire.  The
    engine calls this from ``initialize`` and again before each step
    dispatch (a retrace must see the OWNING engine's policy, not the
    most recently built engine's), and clears it in ``close()``."""
    global _ACTIVE
    _ACTIVE = wire


def get_active() -> Optional[MoEWire]:
    return _ACTIVE
