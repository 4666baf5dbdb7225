#!/usr/bin/env python3
"""The controls of the routed comparison for the Nemotron-H family, at a
cell's own size (the benchmark's own runs never run this):

    python3 benchmark/control_nemotron.py --workload <cell> --seed 1 --fault state_not_carried

the PROGRAM, served as the cell serves it, with one mechanism of a layer
computed otherwise, through the runner's ``check`` itself, against the sound
reference (the weights are the program's own):

* ``rope_on_attention``: the attention layers rotate q and k (theta 10000,
  all of the head's dims), where the family's attention sees no position;
* ``norm_ungrouped``: the mixer's gated norm takes ONE mean square over all
  ``d_inner`` channels, not one a group;
* ``relu_not_squared``: an expert (routed and shared) is ``down(relu(up(x)))``;
* ``state_not_carried``: a prefill seats a ZERO recurrent state (the
  convolution's carry is kept), so decode starts from nothing;
* ``bias_in_weights``: ``e_score_correction_bias`` enters the routing
  weights, not the pick alone;
* ``state_bf16``: the recurrent state is rounded to bfloat16 wherever it is
  written (the prefill's seat, every decode step).

``--fault none`` plants nothing: the sound program through the same check
without the window before it, which is how the limits' first reading (the
program's largest over many seeds) is taken cheaply.

One JSON line: ``correct`` and the check's facts.  A control is worth what it
reads: the traffic file's notes say which of these the comparison can tell
from the program at the precision served; ``tests/test_nemotron_h.py`` holds
every one at float32 on the CPU.  The 8-bit control of the two logit limits
is ``control_routed.py --precision int8``.
"""

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):      # run as a script: find ``benchmark``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

FAULTS = ("rope_on_attention", "norm_ungrouped", "relu_not_squared",
          "state_not_carried", "bias_in_weights", "state_bf16")


def plant(fault):
    """Put ``fault`` into the program's functions, which the model looks up
    at every call.  Returns a function that takes it out again."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import nemotron_h
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops import mamba2
    Model = nemotron_h.NemotronH
    undo = []

    def patch(owner, name, value, item=False):
        old = owner[name] if item else getattr(owner, name)
        undo.append((owner, name, old, item))
        if item:
            owner[name] = value
        else:
            setattr(owner, name, value)

    if fault == "rope_on_attention":
        from deepspeed_tpu.models.rotary import (apply_rotary_pos_emb,
                                                 rotary_freqs)
        qkv, prefill, decode = (Model._qkv, Model.prefill_paged,
                                Model.decode_step_paged)
        where = {}

        def rotated(self, p, h):
            q, k, v = qkv(self, p, h)
            cos, sin = rotary_freqs(self.config.head_dim, self.config.max_seq)
            pos = where.get("positions")
            if pos is None:
                pos = jnp.arange(h.shape[1])[None]
            return (apply_rotary_pos_emb(q, cos, sin, pos),
                    apply_rotary_pos_emb(k, cos, sin, pos), v)

        def prefill_at(self, params, toks, *rest):
            where["positions"] = None
            return prefill(self, params, toks, *rest)

        def decode_at(self, params, toks, pool, tables, lengths, **kw):
            where["positions"] = lengths[:, None]
            return decode(self, params, toks, pool, tables, lengths, **kw)
        patch(Model, "_qkv", rotated)
        patch(Model, "prefill_paged", prefill_at)
        patch(Model, "decode_step_paged", decode_at)
    elif fault == "norm_ungrouped":
        norm = mamba2.gated_group_norm
        patch(mamba2, "gated_group_norm",
              lambda y, z, w, groups, eps: norm(y, z, w, 1, eps))
    elif fault == "relu_not_squared":
        patch(dropless.ACTIVATIONS, "relu2", jax.nn.relu, item=True)
    elif fault == "state_not_carried":
        prefill = Model.prefill_paged

        def unseated(self, params, toks, pool, blocks, slot, t_real):
            row, pool = prefill(self, params, toks, pool, blocks, slot,
                                t_real)
            return row, dict(pool, ssm=pool["ssm"].at[:, slot].set(0.0))
        patch(Model, "prefill_paged", unseated)
    elif fault == "bias_in_weights":
        route = dropless.route

        def biased(logits, k, **kw):
            # the sound pick; the weights from the scores WITH the bias
            experts, _ = route(logits, k, **kw)
            scores = jax.nn.sigmoid(logits.astype(jnp.float32)) + kw["bias"]
            w = jnp.take_along_axis(scores, experts, axis=-1)
            return experts, w / w.sum(-1, keepdims=True) \
                * kw["routed_scaling_factor"]
        patch(dropless, "route", biased)
    elif fault == "state_bf16":
        # NOT ``S.astype(bfloat16).astype(float32)``: on the chip XLA drops
        # that pair of converts (xla_allow_excess_precision) and nothing is
        # planted, which is what PR 42's first reading of this fault read
        coarse = lambda S: jax.lax.reduce_precision(
            S, exponent_bits=8, mantissa_bits=7)
        scan, step = mamba2.ssd_scan, mamba2.ssm_step

        def scan_coarse(*a, **kw):
            y, S = scan(*a, **kw)
            return y, coarse(S)

        def step_coarse(ssm, layer, *a, **kw):
            y, ssm = step(ssm, layer, *a, **kw)
            return y, ssm.at[layer].set(coarse(ssm[layer]))
        patch(mamba2, "ssd_scan", scan_coarse)
        patch(mamba2, "ssm_step", step_coarse)
    elif fault != "none":
        raise SystemExit(f"no fault {fault!r}: {FAULTS}")

    def unplant():
        for owner, name, old, item in reversed(undo):
            if item:
                owner[name] = old
            else:
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


def main(argv=None):
    from benchmark import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fault", choices=FAULTS + ("none",), required=True)
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
