"""MoE layer: gate + dispatch + experts + combine.

Parity: reference ``deepspeed/moe/layer.py:18`` (``MoE``) and
``sharded_moe.py:443`` (``MOELayer``).  TPU re-design:

- The reference's ``_AllToAll`` autograd op over an expert-parallel NCCL
  group (``sharded_moe.py:85``, applied :525,:542) becomes a *sharding
  constraint*: the dispatched ``(E, C, M)`` tensor is constrained to
  ``P('expert', ...)`` while tokens are sharded over the batch axes, and
  XLA's SPMD partitioner inserts the all-to-all pair on the ``expert`` mesh
  axis (differentiable for free — no custom autograd Function).
- Expert-parallel process groups (``utils/groups.py:107
  _create_expert_and_data_parallel``) are replaced by the ``expert`` mesh
  axis; "EP as a sub-grouping of DP ranks" is expressed by including
  ``expert`` in the batch sharding axes (see ``parallel/mesh.py``).
- PR-MoE residual path (``layer.py:154-161``): softmax-weighted sum of the
  expert output and a dense residual MLP via a learned 2-way coefficient.

``MoE.apply`` returns ``(output, l_aux, exp_counts)`` exactly like the
reference's ``MoE.forward`` (``return_overflow=True`` appends the
capacity-drop count); the internal ``MOELayer.apply`` always returns the
4-tuple.
"""

import itertools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .experts import Experts
from .sharded_moe import TopKGate, tokens_overflowed
from ..parallel.mesh import maybe_constrain
from ..utils.logging import log_dist


class MOELayer:
    """GShard Algorithm 2 over the ``expert`` mesh axis.

    ``dispatch_impl``:

    - ``"scatter"`` (default): tokens scatter into the (E, C, M) buffer by
      their (expert, slot) address and gather back weighted — O(S·M) data
      movement.  TPU-native replacement for the reference's ``_AllToAll``
      dispatch (``sharded_moe.py:85,525``): the sharding constraint on the
      scattered buffer makes XLA emit the all-to-all.
    - ``"einsum"``: the GShard one-hot formulation — an S×(E·C) matmul each
      way, O(S²·M·cf) FLOPs.  MXU-friendly but quadratic in tokens; kept as
      the numerics oracle (``scatter``'s test reference).
    """

    # per-instance wire site ids: distinct layers (even same-shaped ones
    # in one model) must each contribute their own exchange to the wire's
    # census expectation, while retraces of the SAME layer dedup
    # (moe_wire.MoEWire._record)
    _wire_sites = itertools.count()

    def __init__(self, gate: TopKGate, experts: Experts,
                 dispatch_impl: str = "scatter"):
        assert dispatch_impl in ("scatter", "einsum"), dispatch_impl
        self.gate = gate
        self.experts = experts
        self.dispatch_impl = dispatch_impl
        self._wire_site = next(MOELayer._wire_sites)

    def init(self, rng):
        g, e = jax.random.split(rng)
        return {"gate": self.gate.init(g), "experts": self.experts.init(e)}

    def _active_wire(self, E: int, C: int, d_model: int):
        """The engine-installed quantized expert wire, iff it applies
        here: scatter dispatch only, shape supported, and the trace is
        actually running under the wire's mesh (a leaked wire from
        another engine's mesh must fall back, never mis-shard)."""
        from ..runtime.comm import moe_wire as mw
        wire = mw.get_active()
        if wire is None or not wire.supports(E, C, d_model):
            return None
        am = jax.sharding.get_abstract_mesh()
        if am.empty or dict(am.shape) != dict(wire.mesh.shape):
            return None
        return wire

    def apply(self, params, x, rng=None, used_token=None, train: bool = True):
        d_model = x.shape[-1]
        reshaped = x.reshape(-1, d_model)

        if rng is not None:
            gate_rng, expert_rng = jax.random.split(rng)
        else:
            gate_rng = expert_rng = None

        wire = None
        if self.dispatch_impl == "scatter":
            l_aux, routes, exp_counts, C = self.gate.apply_routes(
                params["gate"], reshaped, rng=gate_rng,
                used_token=used_token, train=train)
            E = self.gate.num_experts
            # dispatch: scatter each kept token to its (expert, slot) row;
            # dropped routes (weight 0) address the OOB row and vanish
            positions = []
            for idx, loc, w in routes:
                pos = jnp.where(w > 0, idx * C + loc, E * C)
                positions.append((pos, w))
            wire = self._active_wire(E, C, d_model)
            if wire is not None:
                # quantized expert exchange (runtime/comm/moe_wire.py):
                # int8 + per-block scales on every all_to_all hop, the
                # gate/capacity math above untouched
                pos_stack = jnp.stack([pos for pos, _ in positions])
                dispatched = wire.dispatch(reshaped, pos_stack, E, C,
                                           site=self._wire_site)
            else:
                flat = jnp.zeros((E * C, d_model), x.dtype)
                for pos, _ in positions:
                    flat = flat.at[pos].set(reshaped, mode="drop")
                dispatched = flat.reshape(E, C, d_model)
        else:
            l_aux, combine_weights, dispatch_mask, exp_counts = \
                self.gate.apply(params["gate"], reshaped, rng=gate_rng,
                                used_token=used_token, train=train)
            C = dispatch_mask.shape[2]
            # dispatch: (S,E,C) × (S,M) → (E,C,M)
            dispatched = jnp.einsum("sec,sm->ecm",
                                    dispatch_mask.astype(x.dtype), reshaped)

        # constraining the expert axis makes XLA emit the forward
        # all-to-all (reference :525); the quantized wire already landed
        # the buffer expert-sharded
        dispatched = maybe_constrain(dispatched, P("expert", None, None))
        expert_output = self.experts.apply(params["experts"], dispatched,
                                           rng=expert_rng)
        expert_output = maybe_constrain(expert_output, P("expert", None, None))

        if self.dispatch_impl == "scatter":
            if wire is not None:
                rows = wire.combine(expert_output, pos_stack,
                                    site=self._wire_site)       # (k, S, M)
                combined = 0.0
                for r, (_, w) in enumerate(positions):
                    combined = combined + rows[r] * \
                        w[:, None].astype(x.dtype)
            else:
                flat_out = expert_output.reshape(-1, d_model)
                combined = 0.0
                for pos, w in positions:
                    row = flat_out[jnp.clip(pos, 0, flat_out.shape[0] - 1)]
                    combined = combined + row * w[:, None].astype(x.dtype)
        else:
            # combine: (S,E,C) × (E,C,M) → (S,M); the contraction back to
            # token-sharded output is the reverse all-to-all (reference :542)
            combined = jnp.einsum("sec,ecm->sm",
                                  combine_weights.astype(x.dtype),
                                  expert_output)
        # capacity drops are detectable: exp_counts is pre-thinning demand
        overflow = tokens_overflowed(exp_counts, C)
        return combined.reshape(x.shape), l_aux, exp_counts, overflow

    def partition_specs(self, params):
        return {"gate": jax.tree_util.tree_map(lambda p: P(), params["gate"]),
                "experts": self.experts.partition_specs(params["experts"])}


class MoE:
    """User-facing MoE layer (reference ``deepspeed/moe/layer.py:18``).

    ``expert`` follows the layer protocol (``.init``/``.apply``) and must map
    ``(..., hidden_size) → (..., hidden_size)``.
    """

    def __init__(self, hidden_size: int, expert, num_experts: int = 1,
                 ep_size: int = 1, k: int = 1, capacity_factor: float = 1.0,
                 eval_capacity_factor: float = 1.0, min_capacity: int = 4,
                 use_residual: bool = False,
                 noisy_gate_policy: Optional[str] = None,
                 drop_tokens: bool = True, use_rts: bool = True,
                 max_capacity: Optional[int] = None,
                 dispatch_impl: str = "scatter"):
        self.hidden_size = hidden_size
        self.num_experts = num_experts
        # ep_size is advisory here: actual expert parallelism is the mesh's
        # ``expert`` axis extent; kept for config/API parity (the reference
        # builds NCCL groups from it, ``layer.py:113``)
        self.ep_size = min(ep_size, num_experts)
        self.use_residual = use_residual
        assert noisy_gate_policy is None or noisy_gate_policy in \
            ("None", "Jitter", "RSample"), \
            "Unsupported noisy_gate_policy: " + str(noisy_gate_policy)

        log_dist(f"Creating MoE layer with num_experts: {num_experts} | "
                 f"expert_parallel_size (advisory): {self.ep_size}", ranks=[0])

        self.expert = expert
        self.moe_layer = MOELayer(
            TopKGate(hidden_size, num_experts, k, capacity_factor,
                     eval_capacity_factor, min_capacity, noisy_gate_policy,
                     drop_tokens, use_rts, max_capacity=max_capacity),
            Experts(expert, num_experts), dispatch_impl=dispatch_impl)

    def init(self, rng):
        r_moe, r_mlp, r_coef = jax.random.split(rng, 3)
        params = {"moe": self.moe_layer.init(r_moe)}
        if self.use_residual:
            params["mlp"] = self.expert.init(r_mlp)
            scale = 0.02
            params["coefficient"] = {
                "w": jax.random.normal(r_coef, (self.hidden_size, 2),
                                       jnp.float32) * scale,
                "b": jnp.zeros((2,), jnp.float32)}
        return params

    def apply(self, params, x, rng=None, used_token=None, train: bool = True,
              return_overflow: bool = False):
        """Returns ``(output, l_aux, exp_counts)`` (reference ``MoE.forward``).

        ``return_overflow=True`` appends the number of tokens dropped by
        capacity thinning this call (exact for top-1) — the runtime signal
        for a too-small ``max_capacity`` / skewed routing under
        ``drop_tokens=False``."""
        output, l_aux, exp_counts, overflow = self.moe_layer.apply(
            params["moe"], x, rng=rng, used_token=used_token, train=train)
        if self.use_residual:
            out_mlp = self.expert.apply(params["mlp"], x, rng=rng)
            if isinstance(out_mlp, tuple):
                out_mlp = out_mlp[0]
            coef = (x @ params["coefficient"]["w"].astype(x.dtype)
                    + params["coefficient"]["b"].astype(x.dtype))
            coef = jax.nn.softmax(coef, axis=-1)
            output = output * coef[..., 0:1] + out_mlp * coef[..., 1:]
        if return_overflow:
            return output, l_aux, exp_counts, overflow
        return output, l_aux, exp_counts

    def partition_specs(self, params):
        specs = {"moe": self.moe_layer.partition_specs(params["moe"])}
        if self.use_residual:
            specs["mlp"] = jax.tree_util.tree_map(lambda p: P(), params["mlp"])
            specs["coefficient"] = jax.tree_util.tree_map(
                lambda p: P(), params["coefficient"])
        return specs
