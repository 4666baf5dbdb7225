#!/usr/bin/env python3
"""The controls of the folded-cache comparison for the EvaByte family, at a
cell's own size (the benchmark's own runs never run this):

    python3 benchmark/control_evabyte.py --workload <cell> --seed 1 --fault mean_value

the PROGRAM, served as the cell serves it, with one part of the fold computed
otherwise, through the runner's ``check`` itself, against the sound reference
(the weights are the program's own):

* ``no_summaries``: a query reads its window's exact rows alone: the prompt's
  attention is handed no live summary, and decode attention walks the table
  from the window's first block (the kernel still runs);
* ``mean_value``: ``a = 1 / chunk_size``: ``b^`` is the chunk's mean value
  (what random weights with ``phi`` at 0.02 would make of the fold);
* ``no_mu``: ``k~`` is the chunk's mean key, without ``mu``;
* ``int8``: every K/V row is rounded to 8 bits (a scale a row a head) where
  it is written into the pool, prompt and decode alike; the summaries are
  folded from rounded rows.

``--fault none`` plants nothing: the sound program through the same check
without the window before it, which is how the limits' first reading (the
program's largest over many seeds) is taken cheaply.

    python3 benchmark/control_evabyte.py --workload <cell> --seed 1 --witness bf16_matmuls

is no control but the witness of the sound program's floor: the plain
REFERENCE in the program's place with every matmul at ``bfloat16`` (one pass
of the MXU, float32 accumulation: what the program's matmuls are) and
everything else as it is, through the runner's ``compare`` against the
reference at ``highest``; it must read correct.  No line of the program runs.
It means something on the chip alone: the CPU multiplies in float32 whatever
it is told.

One JSON line: ``correct`` and the check's facts.  ``benchmark/tests/
test_evabyte.py`` holds every fault at float32 on the CPU.
"""

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):      # run as a script: find ``benchmark``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

FAULTS = ("no_summaries", "mean_value", "no_mu", "int8")


def plant(fault):
    """Put ``fault`` into the program's functions, which the model looks up
    at every call.  Returns a function that takes it out again."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference import paged_kv as pk
    from deepspeed_tpu.models import evabyte
    undo = []

    def patch(owner, name, value):
        undo.append((owner, name, getattr(owner, name)))
        setattr(owner, name, value)

    if fault == "no_summaries":
        prompt, decode = (evabyte.eva_prompt_attention,
                          evabyte.eva_decode_attention)

        def window_only(q, pool, tables, lengths, layer, spec):
            # the table from the window's first block on, as a stream that
            # had folded nothing would hold it
            skip = spec.summary_blocks * (lengths // spec.window)
            cols = jnp.arange(tables.shape[1])[None, :] + skip[:, None]
            shifted = jnp.where(
                cols < tables.shape[1], jnp.take_along_axis(
                    tables, jnp.minimum(cols, tables.shape[1] - 1), axis=1),
                pk.SCRATCH_BLOCK)
            return decode(q, pool, shifted, lengths % spec.window, layer,
                          spec)
        patch(evabyte, "eva_prompt_attention",
              lambda q, k, v, k_sum, v_sum, n_sum, scale: prompt(
                  q, k, v, k_sum, v_sum, 0 * n_sum, scale))
        patch(evabyte, "eva_decode_attention", window_only)
    elif fault in ("mean_value", "no_mu"):
        fold = evabyte.eva_summarise

        def mean_value(k, v, phi, mu, chunk):
            return fold(k, v, jnp.zeros_like(phi), mu, chunk)
        patch(evabyte, "eva_summarise", mean_value if fault == "mean_value"
              else lambda k, v, phi, mu, chunk: fold(
                  k, v, phi, jnp.zeros_like(mu), chunk))
    elif fault == "int8":
        def rounded(x):
            """(..., H, hd) to 8 bits and back, a scale a row a head."""
            x32 = x.astype(jnp.float32)
            scale = jnp.maximum(jnp.abs(x32).max(-1, keepdims=True),
                                1e-30) / 127.0
            return (jnp.clip(jnp.round(x32 / scale), -127, 127)
                    * scale).astype(x.dtype)
        tokens, prefill = pk.write_tokens, pk.write_prefill
        patch(pk, "write_tokens",
              lambda pool, layer, tables, lengths, k, v, **kw: tokens(
                  pool, layer, tables, lengths, rounded(k), rounded(v), **kw))
        patch(pk, "write_prefill",
              lambda pool, blocks, k, v, layer=None: prefill(
                  pool, blocks, rounded(k), rounded(v), layer=layer))
    elif fault != "none":
        raise SystemExit(f"no fault {fault!r}: {FAULTS}")

    def unplant():
        for owner, name, old in reversed(undo):
            setattr(owner, name, old)
    return unplant


def read_fault(bench, cell, seed, fault, log, config=None, traffic=None):
    """The cell's server with ``fault`` planted, warmed for the check's
    prompts alone, through the runner's ``check`` (with no window before
    it: ``seconds`` 0 says so).  (The tests hand in a tiny configuration
    and mix of their own.)"""
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
        picks = runner.check_picks(items, ctx.config["window_size"],
                                   ctx.traffic["check"]["steps"])
        serving.warm_up(srv, list(picks.values()))
        ok, facts = runner.check(ctx, model, eng, srv, items)
        eng.close()
    finally:
        unplant()
    return {"workload": cell["name"], "seed": seed, "fault": fault,
            "correct": ok, "facts": facts}


def read_witness(bench, cell, seed, config=None, traffic=None):
    """The reference's logits of the check's prompts at ``highest``, then
    with every matmul at ``bfloat16``, through the runner's ``compare``."""
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
    picks = runner.check_picks(items, cfg["window_size"],
                               traffic["check"]["steps"])
    padded, last = serving.padded_rows([it.prompt for it in picks.values()])
    at = lambda precision: np.asarray(jax.jit(
        lambda p: reference.logits_at(cfg, p, jnp.asarray(padded),
                                      jnp.asarray(last), precision))(params),
        np.float32)
    ok, facts = runner.compare(traffic["check"], at("bfloat16"),
                               at("highest"))
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
