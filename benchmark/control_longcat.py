#!/usr/bin/env python3
"""The controls of ``serve_backlog_zero_experts``'s comparison for the
``longcat_flash`` family, at a cell's own size (the benchmark's own runs never
run this):

    python3 benchmark/control_longcat.py --workload <cell> --seed 1 2 --fault zero_dropped bias_in_weights

the PROGRAM, served as the cell serves it, with one mechanism of the double
block computed otherwise, through the runner's ``check`` itself, against the
sound reference (the weights are the program's own).  One JSON line a seed and a fault:

* ``zero_dropped``: the identity experts' part left out of every token's
  output (their pairs are still picked and counted);
* ``zero_reads_stream``: an identity expert returns the residual stream ``h``
  times its weight, not the normed input ``u`` the real experts read;
* ``shortcut_joins_early``: the expert layer's output ``m`` joins the stream
  after the FIRST dense FFN, before the second attention reads it;
* ``no_lora_scales``: ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` read as
  false (the normed latents not multiplied by 2 and 3.4641);
* ``bias_in_weights``: the selection bias enters the routing weights, not the
  pick alone.

``none`` plants nothing: the sound program through the same check without the
window before it, which is how the limits' first reading (the program's
largest over many seeds) is taken cheaply.

    python3 benchmark/control_longcat.py --workload <cell> --seed 1 --precision int8

the 8-bit control of the two logit limits: ``control_routed.py``'s reading
(the plain reference with every weight matrix rounded to int8 with a scale an
output channel, in the program's place, its routes its own picks) through this
cell's runner's ``compare``, which wants the sound reference sent to those
picks: the rounded tree is read first, and the sound one made again from the
seed (one tree on the device at a time).

A control is worth what it reads: the traffic file's notes say which of these
the comparison can tell from the program at the precision served;
``tests/test_longcat_flash.py`` holds every fault at float32 on the CPU.
"""

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):      # run as a script: find ``benchmark``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

FAULTS = ("zero_dropped", "zero_reads_stream", "shortcut_joins_early",
          "no_lora_scales", "bias_in_weights")


def plant(fault):
    """Put ``fault`` into the program's functions, which the model looks up
    at every call.  Returns a function that takes it out again."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import longcat_flash
    from deepspeed_tpu.moe import dropless
    Model = longcat_flash.LongcatFlash
    undo = []

    def patch(owner, name, value):
        undo.append((owner, name, getattr(owner, name)))
        setattr(owner, name, value)

    if fault == "zero_dropped":
        patch(dropless, "zero_experts",
              lambda x, *a: jnp.zeros(x.shape, jnp.float32))
    elif fault == "zero_reads_stream":
        # the model norms the stream right before its expert layer reads
        # the result: what that norm was given is ``h``
        rms, zero = longcat_flash._rms, dropless.zero_experts
        seen = []

        def remember(x, w, eps):
            seen[:] = [x]
            return rms(x, w, eps)
        patch(longcat_flash, "_rms", remember)
        patch(dropless, "zero_experts",
              lambda x, *a: zero(seen[0].reshape(x.shape), *a))
    elif fault == "shortcut_joins_early":
        # the expert layer hands the layer nothing to hold back; the dense
        # FFN that follows it (the first) takes ``m`` along
        moe, ffn = Model._moe, longcat_flash.swiglu
        held = []

        def handed_on(self, *a, **kw):
            m, counts, experts = moe(self, *a, **kw)
            held[:] = [m]
            return jnp.zeros_like(m), counts, experts
        patch(Model, "_moe", handed_on)
        patch(longcat_flash, "swiglu", lambda p, x: (
            ffn(p, x).astype(jnp.float32) + held.pop() if held
            else ffn(p, x)))
    elif fault == "no_lora_scales":
        init = Model.__init__

        def unscaled(self, *a, **kw):
            init(self, *a, **kw)
            self._mla.q_scale = self._mla.kv_scale = 1.0
        patch(Model, "__init__", unscaled)
    elif fault == "bias_in_weights":
        route = dropless.route

        def biased(logits, k, **kw):
            # the sound pick; the weights from the scores WITH the bias
            experts, _ = route(logits, k, **kw)
            scores = jax.nn.softmax(logits.astype(jnp.float32), -1) \
                + kw["bias"].astype(jnp.float32)
            return experts, jnp.take_along_axis(scores, experts, axis=-1) \
                * kw["routed_scaling_factor"]
        patch(dropless, "route", biased)
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


def read_precision(bench, cell, seed, precision):
    """``control_routed.read_precision`` for a comparison that forces the
    picks: the reference over the rounded tree, in the program's place, gives
    the logits and (its own picks) the routes; the reference over the sound
    tree, sent to those routes, what they are compared with."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import control_serial, harness, serving
    cfg = harness.load_config(bench, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    runner = harness.load_plugin("runners", traffic["kind"])
    family, reference = harness.family(cfg), harness.reference(cfg)
    model = family.build(cfg, jnp.bfloat16)
    items = runner.backlog(traffic, seed, family.dims(cfg)["vocab_size"])
    picks = serving.check_picks(items, traffic["check"]["slots"])
    padded, last = serving.padded_rows([it.prompt for it in picks])
    fn = jax.jit(lambda p, ids: reference.logits_and_scores_at(
        cfg, p, jnp.asarray(padded), jnp.asarray(last), forced=ids))
    got, coarse = (np.asarray(x, np.float32) for x in fn(
        control_serial.coarser_in_place(
            harness.seeded_weights(model, seed, jnp.bfloat16), precision),
        None))
    n, layers, W = coarse.shape
    routes = runner.route_ids(np.asarray(reference.picks(
        cfg, jnp.asarray(coarse.reshape(n * layers, W)))).reshape(
            n, layers, W))
    ref, scores = (np.asarray(x, np.float32) for x in fn(
        harness.seeded_weights(model, seed, jnp.bfloat16),
        jnp.asarray(np.moveaxis(routes, 0, 1))))
    ok, facts = runner.compare(traffic["check"], cfg, reference, got, ref,
                               routes, scores)
    return {"workload": cell["name"], "seed": seed, "precision": precision,
            "correct": ok, "facts": facts}


def main(argv=None):
    from benchmark import control, harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    what = ap.add_mutually_exclusive_group(required=True)
    what.add_argument("--precision", choices=control.PRECISIONS)
    what.add_argument("--fault", nargs="+", choices=FAULTS + ("none",))
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.cell_by_name(bench, args.workload)
    if args.precision:
        for seed in args.seed:
            print(json.dumps(read_precision(
                bench, cell, seed, args.precision)), flush=True)
        return 0
    from deepspeed_tpu.utils.logging import route_logs_to_stderr
    route_logs_to_stderr()
    log = lambda msg: print(f"[control] {msg}", file=sys.stderr, flush=True)
    for seed in args.seed:
        for fault in args.fault:
            print(json.dumps(read_fault(bench, cell, seed, fault, log)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
