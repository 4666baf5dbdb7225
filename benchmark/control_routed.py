#!/usr/bin/env python3
"""The controls of ``serve_backlog_routed``'s comparison, at a cell's own
size (``benchmark/runners/serve_backlog_routed.py``; the benchmark's own runs
never run this).  Each must come out NOT correct:

    python3 benchmark/control_routed.py --workload <cell> --seed 1 --precision int8

the plain reference with every weight matrix rounded to 8 bits in the
program's place (``control_serial.coarser_in_place``, one tree on the device
at a time), its routes its own picks, through the runner's ``compare``: the
control of the two logit limits, read over the rows the comparison judges;

    python3 benchmark/control_routed.py --workload <cell> --seed 1 --fault plain_top6

the PROGRAM, served as the cell serves it, with a routing fault planted in
``deepspeed_tpu.moe.dropless`` before anything is traced, through the
runner's ``check`` itself: ``plain_top6`` (plain greedy top-k where the
configuration says ``group_limited_greedy``), ``no_scaling_factor``
(``routed_scaling_factor`` left out) or ``held_dropped`` (the held experts'
part left out of every token's output).  One JSON line: ``correct`` and the
check's facts.
"""

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):      # run as a script: find ``benchmark``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

FAULTS = ("plain_top6", "no_scaling_factor", "held_dropped")


def plant(fault):
    """Put ``fault`` into ``moe/dropless.py``'s functions, which the model
    looks up at every call."""
    import jax.numpy as jnp
    from deepspeed_tpu.moe import dropless
    route, held_experts = dropless.route, dropless.held_experts
    if fault == "plain_top6":
        dropless.route = lambda logits, k, **kw: route(
            logits, k, **dict(kw, topk_method="greedy"))
    elif fault == "no_scaling_factor":
        dropless.route = lambda logits, k, **kw: route(
            logits, k, **dict(kw, routed_scaling_factor=1.0))
    elif fault == "held_dropped":
        dropless.held_experts = lambda x, *a, **kw: jnp.zeros_like(x)
    else:
        raise SystemExit(f"no fault {fault!r}: {FAULTS}")
    return route, held_experts


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
    plant(fault)
    model, eng, srv = serving.build(ctx)
    items = runner.backlog(ctx.traffic, ctx.seed, ctx.dims["vocab_size"])
    serving.warm_up(srv, serving.check_picks(
        items, ctx.traffic["check"]["slots"]))
    ok, facts = runner.check(ctx, model, eng, srv, items)
    eng.close()
    return {"workload": cell["name"], "seed": seed, "fault": fault,
            "correct": ok, "facts": facts}


def read_precision(bench, cell, seed, precision):
    """``control_serial.read_cell`` under the runner's ``compare``: the
    reference's logits and scores from the sound tree, then from the rounded
    one in the program's place."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import control_serial, harness, serving
    cfg = harness.load_config(bench, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    runner = harness.load_plugin("runners", traffic["kind"])
    family, reference = harness.family(cfg), harness.reference(cfg)
    model = family.build(cfg, jnp.bfloat16)
    params = harness.seeded_weights(model, seed, jnp.bfloat16)
    items = runner.backlog(traffic, seed, family.dims(cfg)["vocab_size"])
    picks = serving.check_picks(items, traffic["check"]["slots"])
    padded, last = serving.padded_rows([it.prompt for it in picks])
    fn = jax.jit(lambda p: reference.logits_and_scores_at(
        cfg, p, jnp.asarray(padded), jnp.asarray(last)))
    ref, scores = (np.asarray(x, np.float32) for x in fn(params))
    got, coarse = (np.asarray(x, np.float32) for x in fn(
        control_serial.coarser_in_place(params, precision)))
    n, layers, E = coarse.shape
    routes = runner.route_ids(np.asarray(reference.picks(
        cfg, jnp.asarray(coarse.reshape(n * layers, E)))).reshape(
            n, layers, E))
    ok, facts = runner.compare(traffic["check"], cfg, reference, got, ref,
                               routes, scores)
    return {"workload": cell["name"], "seed": seed, "precision": precision,
            "correct": ok, "facts": facts}


def main(argv=None):
    from benchmark import control, harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    what = ap.add_mutually_exclusive_group(required=True)
    what.add_argument("--precision", choices=control.PRECISIONS)
    what.add_argument("--fault", choices=FAULTS)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.cell_by_name(bench, args.workload)
    if args.fault:
        from deepspeed_tpu.utils.logging import route_logs_to_stderr
        route_logs_to_stderr()
        out = read_fault(bench, cell, args.seed, args.fault,
                         lambda msg: print(f"[control] {msg}",
                                           file=sys.stderr, flush=True))
    else:
        out = read_precision(bench, cell, args.seed, args.precision)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
