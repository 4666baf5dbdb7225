#!/usr/bin/env python3
"""The controls of ``serve_backlog_windowed``'s comparison for the AFMoE
family, at a cell's own size (the benchmark's own runs never run this):

    python3 benchmark/control_windowed.py --workload <cell> --seed 1 --fault window_plus_one

the PROGRAM, served as the cell serves it, with one mechanism of the layer
computed otherwise, through the runner's ``check`` itself, against the SOUND
reference (the sound configuration, whatever the program was built from; the
weights are the program's own):

* ``window_plus_one``: the window layers see ``sliding_window + 1`` keys;
* ``rope_on_global``: the global layers rotate q and k too;
* ``bias_in_weights``: ``expert_bias`` enters the routing weights, not the
  pick alone;
* ``no_gate``: the attention's output gate left out (``gate_proj`` read as
  zeros: a gate of one half everywhere, which the norm after ``o_proj``
  takes out);
* ``bf16_router``: the router's logits rounded to bfloat16.

One JSON line: ``correct`` and the check's facts.  A control is worth what it
reads: PERF.md section 6 (PR 39) says which of these the comparison can tell
from the program at the precision served, and why not the others.  The 8-bit
control of the two logit limits is ``control_routed.py --precision int8``.
"""

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):      # run as a script: find ``benchmark``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

FAULTS = ("window_plus_one", "rope_on_global", "bias_in_weights", "no_gate",
          "bf16_router")


def plant(fault, config):
    """The configuration to build the program from, with ``fault`` put into
    the program's functions where it is one."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import afmoe
    from deepspeed_tpu.moe import dropless
    if fault == "window_plus_one":
        return dict(config, sliding_window=config["sliding_window"] + 1)
    if fault == "no_gate":
        after = afmoe.Afmoe._after_attention
        afmoe.Afmoe._after_attention = lambda self, params, p, *rest: after(
            self, params, dict(p, gate_w=jnp.zeros_like(p["gate_w"])), *rest)
    elif fault == "rope_on_global":
        qkv = afmoe.Afmoe._qkv
        afmoe.Afmoe._qkv = lambda self, p, h, positions, sliding: qkv(
            self, p, h, positions, True)
    elif fault == "bias_in_weights":
        route = dropless.route

        def biased(logits, k, **kw):
            # the sound pick; the weights from the scores WITH the bias
            import jax
            experts, _ = route(logits, k, **kw)
            scores = jax.nn.sigmoid(logits.astype(jnp.float32)) + kw["bias"]
            w = jnp.take_along_axis(scores, experts, axis=-1)
            return experts, w / w.sum(-1, keepdims=True) \
                * kw["routed_scaling_factor"]
        dropless.route = biased
    elif fault == "bf16_router":
        route = dropless.route
        dropless.route = lambda logits, k, **kw: route(
            logits.astype(jnp.bfloat16).astype(jnp.float32), k, **kw)
    else:
        raise SystemExit(f"no fault {fault!r}: {FAULTS}")
    return config


class SoundReference:
    """The family's reference, always given the sound configuration."""

    def __init__(self, reference, config):
        self._reference, self._config = reference, config
        self.picks = reference.picks

    def logits_and_scores_at(self, cfg, params, tokens, positions):
        return self._reference.logits_and_scores_at(
            self._config, params, tokens, positions)


def read_fault(bench, cell, seed, fault, log, config=None, traffic=None):
    """The cell's server with ``fault`` planted, warmed for the check's
    prompts alone, through the runner's ``check``."""
    from benchmark import harness, serving
    from deepspeed_tpu.runtime import compile_cache
    compile_cache.use_persistent_cache()
    sound = config or harness.load_config(bench, cell["config"])
    faulty = plant(fault, sound)
    ctx = harness.RunContext(bench, cell, seed, 0.0, False, time.monotonic(),
                             log=log, config=faulty, traffic=traffic)
    runner = harness.load_plugin("runners", ctx.traffic["kind"])
    model, eng, srv = serving.build(ctx)
    reference = SoundReference(harness.reference(sound), sound)
    ctx.config = sound
    harness.reference = lambda cfg: reference
    items = runner.backlog(ctx.traffic, ctx.seed, ctx.dims["vocab_size"])
    serving.warm_up(srv, serving.check_picks(
        items, ctx.traffic["check"]["slots"]))
    ok, facts = runner.check(ctx, model, eng, srv, items)
    eng.close()
    return {"workload": cell["name"], "seed": seed, "fault": fault,
            "correct": ok, "facts": facts}


def main(argv=None):
    from benchmark import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fault", choices=FAULTS, required=True)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.cell_by_name(bench, args.workload)
    from deepspeed_tpu.utils.logging import route_logs_to_stderr
    route_logs_to_stderr()
    out = read_fault(bench, cell, args.seed, args.fault,
                     lambda msg: print(f"[control] {msg}", file=sys.stderr,
                                       flush=True))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
