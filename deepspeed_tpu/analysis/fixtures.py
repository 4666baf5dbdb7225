"""Shared probe models for the audit stages.

One source of truth for the tiny engines that ``--audit-step`` builds:
keeping a single parameterized fixture (instead
of per-caller near-twins) means a change to the MoE constructor
signature or the ``partition_specs`` contract lands everywhere at once.
Imports stay inside methods — the analysis CLI must not pull jax in for
a lint-only run.
"""


class MoEProbeModel:
    """MoE regression model: linear in → top-1 MoE → linear out.

    ``dim`` is the MoE (expert) width, ``io`` the data/projection width
    (defaults to ``dim``), ``expert_mult`` the expert-MLP hidden
    multiplier.  Callers pick the shape for their purpose:

    - ``--audit-step moe`` (``analysis/__main__.py``) uses
      ``MoEProbeModel(dim, n_experts)`` — square, big enough that the
      expert exchange dominates the budget floors so the tightness
      check has margin.
    - a wire probe passes ``io`` well under ``dim`` so the dense-grad
      all-reduce is noise next to the dispatch/combine payload: on a
      pure ``expert=8`` mesh the expert params are EP-sharded (their
      grads never cross the wire), and the exchange IS the wire being
      measured.
    """

    def __init__(self, dim=16, num_experts=8, io=None, expert_mult=4):
        from ..moe import MoE

        class _Expert:
            def init(self, rng):
                import jax
                import jax.numpy as jnp
                k1, k2 = jax.random.split(rng)
                h = expert_mult * dim
                return {"w1": jax.random.normal(k1, (dim, h),
                                                jnp.float32) * 0.1,
                        "w2": jax.random.normal(k2, (h, dim),
                                                jnp.float32) * 0.1}

            def apply(self, params, x, rng=None):
                import jax
                h = jax.nn.relu(x @ params["w1"])
                return h @ params["w2"]

        self.dim = dim if io is None else io
        self.moe_dim = dim
        self.moe = MoE(dim, _Expert(), num_experts=num_experts, k=1,
                       capacity_factor=2.0, min_capacity=0, use_rts=False)

    def init(self, rng):
        import jax
        import jax.numpy as jnp
        import numpy as np
        k1, k2, k3 = jax.random.split(rng, 3)
        n = lambda k, s: jax.random.normal(k, s, jnp.float32) / np.sqrt(s[0])
        return {"p_in": n(k1, (self.dim, self.moe_dim)),
                "moe": self.moe.init(k2),
                "p_out": n(k3, (self.moe_dim, self.dim))}

    def loss(self, params, batch, rng):
        import jax.numpy as jnp
        x, y = batch
        h = x @ params["p_in"]
        h, l_aux, _ = self.moe.apply(params["moe"], h, rng=rng)
        p = h @ params["p_out"]
        return jnp.mean(jnp.square(p - y)) + 0.01 * l_aux

    def partition_specs(self, params):
        from jax.sharding import PartitionSpec as P
        return {"p_in": P(), "p_out": P(),
                "moe": self.moe.partition_specs(params["moe"])}
