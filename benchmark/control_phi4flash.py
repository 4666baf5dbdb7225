#!/usr/bin/env python3
"""The controls of ``serve_backlog_hybrid``'s comparison for the ``phi4flash``
family, at a cell's own size (the benchmark's own runs never run this):

    python3 benchmark/control_phi4flash.py --workload <cell> --seed 1 --fault window_plus_one ssm_bf16

the PROGRAM, served as the cell serves it, with one mechanism of a layer
computed otherwise, through the runner's ``check`` itself, against the sound
reference (the weights are the program's own).  One JSON line a fault:

* ``lambda_dropped``: plain attention: the second softmax map is not
  subtracted (``lambda`` read as 0);
* ``a2_over_k1``: the second map's queries score against ``k1``, not ``k2``;
* ``m_after_gate``: the Gated Memory Units read layer ``L/2``'s scan output
  AFTER its output gate;
* ``window_plus_one``: the window layers see ``sliding_window + 1`` keys;
* ``gmu_other_stream``: a Gated Memory Unit is fed another row's ``m`` (a
  full forward's: the position before; a decode step's: the slot before);
* ``inner_norms``: Jamba's three inner RMSNorms (unit weights) left in on
  ``dt``, ``B`` and ``C``;
* ``ssm_bf16``: the recurrent state is rounded to bfloat16 wherever it is
  written (the prefill's seat, every decode step);
* ``prefill_m_before``: a PREFILL's Gated Memory Units read layer ``L/2``'s
  scan output of the position BEFORE the prompt's last; every decode step is
  sound, so only the comparison of the prefill's own row tells it.

``none`` plants nothing: the sound program through the same check without the
window before it, which is how the limits' first reading (the program's
largest over many seeds) is taken cheaply.  ``int8_weights`` is the 8-bit
control of the two logit limits: ``control_serial.py``'s reading (the plain
reference with every weight matrix rounded to int8 with a scale an output
channel, in the program's place, one tree on the device at a time) over the
check's own prompts, with the verdict under the cell's limits beside it.
``control_serial.py`` itself does not take this cell: it rounds a stacked
leaf whole beside itself, and ``fc1_w`` is 3.4 GB of a 7.7 GB tree.

A control is worth what it reads: the traffic file's notes say which of these
the comparison can tell from the program at the precision served;
``tests/test_phi4flash.py`` holds every one at float32 on the CPU (and one
more that the serving path cannot hold: a cross layer reading K/V of its
own).
"""

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):      # run as a script: find ``benchmark``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

FAULTS = ("lambda_dropped", "a2_over_k1", "m_after_gate", "window_plus_one",
          "gmu_other_stream", "inner_norms", "ssm_bf16", "prefill_m_before")


def plant(fault):
    """Put ``fault`` into the program's functions, which the model looks up
    at every call.  Returns a function that takes it out again."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import phi4flash
    from deepspeed_tpu.ops import selective_scan as ss
    Model = phi4flash.Phi4Flash
    undo = []

    def patch(owner, name, value):
        undo.append((owner, name, getattr(owner, name)))
        setattr(owner, name, value)

    if fault == "lambda_dropped":
        combine = Model._combine

        def plain(self, p, out, l):
            # exp(a) - exp(a) + lambda_init - lambda_init: lambda is 0, the
            # norm's factor 1 - lambda_init stays
            o = out.reshape(out.shape[:-1] + (self.config.n_head // 2, 2, -1))
            o = o.at[..., 1, :].set(0)
            return combine(self, p, o.reshape(out.shape), l)
        patch(Model, "_combine", plain)
    elif fault == "a2_over_k1":
        def both_on_k1(q, hd):
            q = q * jnp.asarray(hd ** -0.5, q.dtype)
            return jnp.concatenate([q, jnp.zeros_like(q)], axis=-1)
        patch(phi4flash, "pad_queries", both_on_k1)
    elif fault == "m_after_gate":
        out = Model._scan_output

        def gated_m(self, p, h, y, z, keep):
            h, m = out(self, p, h, y, z, keep)
            return h, (m * jax.nn.silu(z) if keep else m)
        patch(Model, "_scan_output", gated_m)
    elif fault == "window_plus_one":
        init = Model.__init__

        def wider(self, *a, **kw):
            init(self, *a, **kw)
            self.config.sliding_window += 1
        patch(Model, "__init__", wider)
    elif fault == "gmu_other_stream":
        cross = Model._cross_decoder
        patch(Model, "_cross_decoder",
              lambda self, params, h, m, cross_fn: cross(
                  self, params, h,
                  jnp.roll(m, 1, axis=0 if m.shape[1] == 1 else 1), cross_fn))
    elif fault == "inner_norms":
        def rms(x):
            x32 = x.astype(jnp.float32)
            return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                                        + 1e-6)).astype(x.dtype)

        def normed(self, p, h, tail):
            # ``Phi4Flash._scan_inputs`` with Jamba's three norms put back
            c = self.config
            N, R = c.mamba_d_state, c.mamba_dt_rank
            mm = phi4flash._mm
            x, z = jnp.split(mm(self._norm(p, h), p["in_w"]), 2, axis=-1)
            x, padded = ss.causal_conv(x, p["conv_w"], p["conv_b"], tail)
            x = jax.nn.silu(x)
            dt, Bm, Cm = (rms(t) for t in jnp.split(mm(x, p["x_w"]),
                                                     [R, R + N], axis=-1))
            delta = jax.nn.softplus(mm(dt, p["dt_w"]).astype(jnp.float32)
                                    + p["dt_b"].astype(jnp.float32))
            return x, z, delta, Bm, Cm, padded
        patch(Model, "_scan_inputs", normed)
    elif fault == "ssm_bf16":
        # NOT ``S.astype(bfloat16).astype(float32)``: on the chip XLA drops
        # that pair of converts (xla_allow_excess_precision) and nothing is
        # planted (benchmark/control_nemotron.py)
        coarse = lambda S: jax.lax.reduce_precision(
            S, exponent_bits=8, mantissa_bits=7)
        scan, step = ss.selective_scan, ss.selective_step

        def scan_coarse(*a, **kw):
            y, S = scan(*a, **kw)
            return y, coarse(S)

        def step_coarse(*a, **kw):
            y, S = step(*a, **kw)
            return y, coarse(S)
        patch(ss, "selective_scan", scan_coarse)
        patch(ss, "selective_step", step_coarse)
    elif fault == "prefill_m_before":
        decoder = Model._self_decoder

        def shifted(self, params, h, carry, mamba_fn, attn_fn, at=None):
            def mamba_shifted(p, h, i, keep, carry):
                h, m, carry = mamba_fn(p, h, i, keep, carry)
                return h, (jnp.roll(m, 1, axis=1) if keep else m), carry
            return decoder(self, params, h, carry,
                           mamba_fn if at is None else mamba_shifted,
                           attn_fn, at)
        patch(Model, "_self_decoder", shifted)
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
        serving.warm_up(srv, runner.check_prompts(ctx.traffic["check"],
                                                  items))
        ok, facts = runner.check(ctx, model, eng, srv, items)
        eng.close()
    finally:
        unplant()
    return {"workload": cell["name"], "seed": seed, "fault": fault,
            "correct": ok, "facts": facts}


def coarser_in_place(params):
    """``control.coarser(params, "int8")`` (a scale an output channel of each
    layer's matrix), a LAYER of a stacked leaf at a time, each put back into
    its leaf: ``control_serial.coarser_in_place`` stacks a leaf's rounded
    layers beside the leaf, and this family's ``fc1_w`` alone is 3.4 GB.
    ``params`` (a dict of dicts) is emptied as it goes."""
    from benchmark import control
    rounded = lambda x: control.coarser({"x": x}, "int8")["x"]

    def walk(tree):
        for key in list(tree):
            leaf = tree.pop(key)
            if isinstance(leaf, dict):
                leaf = walk(leaf)
            elif leaf.ndim < 3:
                leaf = rounded(leaf)
            else:
                for i in range(leaf.shape[0]):
                    leaf = leaf.at[i].set(rounded(leaf[i]))
            tree[key] = leaf
        return tree
    return walk(params)


def read_int8(bench, cell, seed):
    """The 8-bit control of the two logit limits, as ``control_serial.py``
    reads it (the plain reference with every weight matrix rounded to int8,
    in the program's place; one tree on the device at a time), over the
    prompts the runner's check seats, with the verdict under the cell's
    limits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import harness, serving
    cfg = harness.load_config(bench, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    family, reference = harness.family(cfg), harness.reference(cfg)
    runner = harness.load_plugin("runners", traffic["kind"])
    params = harness.seeded_weights(family.build(cfg, jnp.bfloat16), seed,
                                    jnp.bfloat16)
    items = runner.backlog(traffic, seed, family.dims(cfg)["vocab_size"])
    picks = runner.check_prompts(traffic["check"], items)
    padded, last = serving.padded_rows([it.prompt for it in picks])
    fn = jax.jit(lambda p: reference.logits_at(cfg, p, jnp.asarray(padded),
                                               jnp.asarray(last)))
    ref = np.asarray(fn(params), np.float32)
    got = np.asarray(fn(coarser_in_place(params)), np.float32)
    err, rms = serving.logit_errors(got, ref)
    check = traffic["check"]
    return {"workload": cell["name"], "seed": seed, "fault": "int8_weights",
            "precision": "int8", "limit": check["logit_tol"],
            "rms_limit": check["logit_rms_tol"], "control": err,
            "control_rms": rms, "rows": [len(it.prompt) for it in picks],
            "correct": bool(err <= check["logit_tol"]
                            and rms <= check["logit_rms_tol"])}


def main(argv=None):
    from benchmark import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fault", nargs="+", required=True,
                    choices=FAULTS + ("none", "int8_weights"))
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.cell_by_name(bench, args.workload)
    from deepspeed_tpu.utils.logging import route_logs_to_stderr
    route_logs_to_stderr()
    log = lambda msg: print(f"[control] {msg}", file=sys.stderr, flush=True)
    for fault in args.fault:
        out = (read_int8(bench, cell, args.seed) if fault == "int8_weights"
               else read_fault(bench, cell, args.seed, fault, log))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
