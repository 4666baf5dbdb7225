#!/usr/bin/env python3
"""The controls of the routed comparison for the Qwen3-Next family, at a
cell's own size (the benchmark's own runs never run this):

    python3 benchmark/control_qwen3next.py --workload <cell> --seed 1 --fault delta_not_subtracted

the PROGRAM, served as the cell serves it, with one mechanism of a layer
computed otherwise, through the runner's ``check`` itself, against the sound
reference (the weights are the program's own):

* ``delta_not_subtracted``: ``d_t = beta_t v_t``: plain gated linear
  attention, what the state already says of ``k_t`` is not taken off;
* ``state_not_decayed``: ``alpha = 1`` (``g = 0``) in every DeltaNet layer;
* ``no_l2norm``: q and k enter the rule as the convolution leaves them (q
  still over ``sqrt(dk)``); at the published widths the rule then DIVERGES
  (keys that are no unit vectors make ``I - beta k k^T`` expand): the logits
  are not finite, the server quarantines every request, opens its circuit
  breaker and this script ends with the engine's ``CircuitOpenError`` and no
  line (PERF.md section 6, PR 54);
* ``gate_before_norm``: the DeltaNet output is ``RMS(o * silu(z))``, Mamba-2's
  order, where the family norms first;
* ``norm_not_zero_centred``: every ``RMS0`` multiplies by ``w`` for ``1 + w``;
* ``rope_all_dims``: all of a head's dims are rotated, not the first quarter;
* ``attn_gate_dropped``: the attention's output goes to ``o_proj`` ungated;
* ``shared_gate_dropped``: the shared expert is added whole;
* ``topk_not_renormalised``: the picked probabilities are the weights;
* ``state_not_carried``: a prefill seats a ZERO delta state (the
  convolution's carry is kept), so decode starts from nothing;
* ``state_bf16``: the delta state is rounded to bfloat16 wherever it is
  written (the prefill's seat, every decode step).

``--fault none`` plants nothing: the sound program through the same check
without the window before it, which is how the limits' first reading (the
program's largest over many seeds) is taken cheaply.

    python3 benchmark/control_qwen3next.py --workload <cell> --seed 1 --witness bf16_matmuls

is no control but the witness of the sound program's floor: the plain
REFERENCE in the program's place with the inputs of every matmul rounded to
bfloat16 (one pass of the MXU, float32 accumulation: what the program's
matmuls are) and everything else as it is, float32 stream, router scores,
delta rule and all; its routes its own picks, through the runner's
``compare`` against the reference at ``highest``.  No line of the program
runs: what it reads is what bfloat16 matmuls alone do to these weights over
these prompts (PERF.md section 6, PR 54).  It means something on the chip
alone: the CPU multiplies in float32 whatever it is told.

One JSON line: ``correct`` and the check's facts.  A control is worth what it
reads: the traffic file's notes say which of these the comparison can tell
from the program at the precision served; ``tests/test_qwen3_next_faults.py``
holds every one at float32 on the CPU.  The 8-bit control of the two logit
limits is ``control_routed.py --precision int8``.
"""

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):      # run as a script: find ``benchmark``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

FAULTS = ("delta_not_subtracted", "state_not_decayed", "no_l2norm",
          "gate_before_norm", "norm_not_zero_centred", "rope_all_dims",
          "attn_gate_dropped", "shared_gate_dropped", "topk_not_renormalised",
          "state_not_carried", "state_bf16")


def plant(fault):
    """Put ``fault`` into the program's functions, which the model looks up
    at every call.  Returns a function that takes it out again."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import qwen3_next
    from deepspeed_tpu.models.jamba import _mm, _rms
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops import gated_delta as gd
    Model = qwen3_next.Qwen3Next
    undo = []

    def patch(owner, name, value):
        undo.append((owner, name, getattr(owner, name)))
        setattr(owner, name, value)

    if fault == "delta_not_subtracted":
        def linear(q, k, v, g, beta, S0=None):
            """``delta_scan_jnp`` without the delta: ``S <- a S + k (x) beta
            v``, token by token."""
            f32 = jnp.float32
            B, T, H, dk = q.shape

            def token(S, x):
                q_t, k_t, v_t, g_t, b_t = x
                S = jnp.exp(g_t)[..., None, None] * S \
                    + k_t[..., :, None] * (b_t[..., None] * v_t)[..., None, :]
                return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)
            S0 = jnp.zeros((B, H, dk, v.shape[-1]), f32) if S0 is None else S0
            by_token = lambda a: jnp.moveaxis(a.astype(f32), 1, 0)
            S, o = jax.lax.scan(token, S0.astype(f32),
                                tuple(map(by_token, (q, k, v, g, beta))))
            return jnp.moveaxis(o, 0, 1), S

        def chunk(q, k, v, g, beta, S0=None, chunk=64, t_real=None):
            if t_real is not None:
                g, beta = gd.mask_pads(g.astype(jnp.float32),
                                       beta.astype(jnp.float32), t_real)
            o, S = linear(q, k, v, g, beta, S0)
            return o.astype(v.dtype), S

        def step(state, layer, q, k, v, g, beta, active=None, **_):
            if active is not None:
                g = jnp.where(active[:, None], g, 0.0)
                beta = jnp.where(active[:, None], beta, 0.0)
            o, S = linear(q[:, None], k[:, None], v[:, None], g[:, None],
                          beta[:, None], state[layer])
            return o[:, 0], state.at[layer].set(S)
        patch(gd, "delta_chunk", chunk)
        patch(gd, "delta_step", step)
    elif fault == "state_not_decayed":
        inputs = Model._delta_inputs

        def undecayed(self, p, h, tail):
            q, k, v, z, g, beta, padded = inputs(self, p, h, tail)
            return q, k, v, z, jnp.zeros_like(g), beta, padded
        patch(Model, "_delta_inputs", undecayed)
    elif fault == "no_l2norm":
        patch(qwen3_next, "_l2norm", lambda x: x.astype(jnp.float32))
    elif fault == "gate_before_norm":
        def gate_first(o, z, w, eps):
            f32 = jnp.float32
            y = o.astype(f32) * jax.nn.silu(z.astype(f32))
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                                  + eps)
            return (y * w.astype(f32)).astype(z.dtype)
        patch(qwen3_next, "gated_head_norm", gate_first)
    elif fault == "norm_not_zero_centred":
        patch(qwen3_next, "_rms0", _rms)
    elif fault == "rope_all_dims":
        from deepspeed_tpu.models.rotary import rotary_freqs
        init = Model.__init__

        def all_dims(self, *a, **kw):
            init(self, *a, **kw)
            c = self.config
            self._rope = rotary_freqs(c.head_dim, c.max_seq,
                                      base=c.rope_theta)
        patch(Model, "__init__", all_dims)
    elif fault == "attn_gate_dropped":
        patch(Model, "_attn_output", lambda self, p, h, out, gate:
              h + _mm(out, p["o_w"]).astype(jnp.float32))
    elif fault == "shared_gate_dropped":
        patch(qwen3_next, "shared_expert_gate",
              lambda x, w: jnp.ones((x.shape[0],), jnp.float32))
    elif fault == "topk_not_renormalised":
        route = dropless.route
        patch(dropless, "route", lambda logits, k, **kw: route(
            logits, k, **dict(kw, norm_topk_prob=False)))
    elif fault == "state_not_carried":
        prefill = Model.prefill_paged

        def unseated(self, params, toks, pool, blocks, slot, t_real):
            row, pool = prefill(self, params, toks, pool, blocks, slot,
                                t_real)
            return row, dict(pool, delta=pool["delta"].at[:, slot].set(0.0))
        patch(Model, "prefill_paged", unseated)
    elif fault == "state_bf16":
        # NOT ``S.astype(bfloat16).astype(float32)``: on the chip XLA drops
        # that pair of converts (control_nemotron.py says how that was found)
        coarse = lambda S: jax.lax.reduce_precision(
            S, exponent_bits=8, mantissa_bits=7)
        chunk, step = gd.delta_chunk, gd.delta_step

        def chunk_coarse(*a, **kw):
            o, S = chunk(*a, **kw)
            return o, coarse(S)

        def step_coarse(state, layer, *a, **kw):
            o, state = step(state, layer, *a, **kw)
            return o, state.at[layer].set(coarse(state[layer]))
        patch(gd, "delta_chunk", chunk_coarse)
        patch(gd, "delta_step", step_coarse)
    elif fault != "none":
        raise SystemExit(f"no fault {fault!r}: {FAULTS}")

    def unplant():
        for owner, name, old in reversed(undo):
            setattr(owner, name, old)
    return unplant


def read_fault(bench, cell, seed, fault, log, config=None, traffic=None):
    """The cell's server with ``fault`` planted, warmed for the check's
    prompts alone, through the runner's ``check``.  (The tests hand in a
    tiny configuration and mix of their own.)"""
    from benchmark import harness, serving
    from deepspeed_tpu.runtime import compile_cache
    compile_cache.use_persistent_cache()
    ctx = harness.RunContext(bench, cell, seed, 0.0, False, time.monotonic(),
                             log=log, config=config, traffic=traffic)
    runner = harness.load_plugin("runners", ctx.traffic["kind"])
    unplant = plant(fault)
    try:
        model, eng, srv = serving.build(ctx)
        items = runner.backlog(ctx.traffic, ctx.seed, ctx.dims["vocab_size"])
        serving.warm_up(srv, serving.check_picks(
            items, ctx.traffic["check"]["slots"]))
        ok, facts = runner.check(ctx, model, eng, srv, items)
        eng.close()
    finally:
        unplant()
    return {"workload": cell["name"], "seed": seed, "fault": fault,
            "correct": ok, "facts": facts}


def read_witness(bench, cell, seed, config=None, traffic=None):
    """``control_routed.read_precision`` with the matmuls' precision in the
    place of the weights': the reference's logits and scores of the check's
    prompts at ``highest``, then with every matmul at ``bfloat16``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import harness, serving
    cfg = config or harness.load_config(bench, cell["config"])
    traffic = traffic or harness.load_traffic(cell["traffic"])
    runner = harness.load_plugin("runners", traffic["kind"])
    family, reference = harness.family(cfg), harness.reference(cfg)
    model = family.build(cfg, jnp.bfloat16)
    params = harness.seeded_weights(model, seed, jnp.bfloat16)
    items = runner.backlog(traffic, seed, family.dims(cfg)["vocab_size"])
    picks = serving.check_picks(items, traffic["check"]["slots"])
    padded, last = serving.padded_rows([it.prompt for it in picks])
    at = lambda precision: tuple(np.asarray(x, np.float32) for x in jax.jit(
        lambda p: reference.logits_and_scores_at(
            cfg, p, jnp.asarray(padded), jnp.asarray(last), precision))(
        params))
    ref, scores = at("highest")
    got, coarse = at("bfloat16")
    n, layers, E = coarse.shape
    routes = runner.route_ids(np.asarray(reference.picks(
        cfg, jnp.asarray(coarse.reshape(n * layers, E)))).reshape(
            n, layers, E))
    ok, facts = runner.compare(traffic["check"], cfg, reference, got, ref,
                               routes, scores)
    return {"workload": cell["name"], "seed": seed,
            "witness": "bf16_matmuls", "correct": ok, "facts": facts}


def main(argv=None):
    from benchmark import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    what = ap.add_mutually_exclusive_group(required=True)
    what.add_argument("--fault", choices=FAULTS + ("none",))
    what.add_argument("--witness", choices=("bf16_matmuls",))
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.cell_by_name(bench, args.workload)
    if args.witness:
        out = read_witness(bench, cell, args.seed)
    else:
        from deepspeed_tpu.utils.logging import route_logs_to_stderr
        route_logs_to_stderr()
        out = read_fault(bench, cell, args.seed, args.fault,
                         lambda msg: print(f"[control] {msg}",
                                           file=sys.stderr, flush=True))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
