#!/usr/bin/env python3
"""``control.py`` for a serving cell whose two parameter trees do not fit the
chip together: the same control (the plain reference with every weight matrix
rounded to 8 bits, in the program's place: ``control.coarser``, unchanged),
the same two numbers (``serving.logit_errors``), read with ONE tree on the
device at a time.  ``control.read_cell`` holds the sound tree and the rounded
tree at once and rounds a stacked leaf whole: at 3 B parameters that is 12 GB
and a 8 GB transient on a 16 GB chip.  Here the reference's logits are read
from the sound tree first; then each leaf is rounded a layer at a time and
takes the sound leaf's place.

    python3 benchmark/control_serial.py --workload <cell> --seeds 1 2 3 [--precision int8]

One JSON line a seed, as ``control.py`` prints them.
"""

import argparse
import json
import os
import sys

if __package__ in (None, ""):      # run as a script: find ``benchmark``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def coarser_in_place(params, precision):
    """``control.coarser(params, precision)``, leaf by leaf and, for a leaf
    stacked over layers (3 or more dimensions), layer by layer: the same
    numbers (a scale an output channel of each layer's matrix), a transient
    of one layer.  ``params`` (a dict of dicts) is emptied as it goes."""
    import jax.numpy as jnp
    from benchmark import control

    def rounded(x):
        if x.ndim < 3:
            return control.coarser({"x": x}, precision)["x"]
        return jnp.stack([control.coarser({"x": x[i]}, precision)["x"]
                          for i in range(x.shape[0])])

    def walk(tree):
        for key in list(tree):
            leaf = tree.pop(key)
            tree[key] = walk(leaf) if isinstance(leaf, dict) else rounded(leaf)
        return tree
    return walk(params)


def read_cell(bench, cell, seed, seconds, precision):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import harness, serving, traffic_gen
    cfg = harness.load_config(bench, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    family, reference = harness.family(cfg), harness.reference(cfg)
    vocab = family.dims(cfg)["vocab_size"]
    model = family.build(cfg, jnp.bfloat16)
    params = harness.seeded_weights(model, seed, jnp.bfloat16)
    if traffic["kind"] == "serve_open_loop":
        items = traffic_gen.open_loop_schedule(traffic, seconds, seed, vocab)
    else:
        items = traffic_gen.backlog(traffic, seed, vocab)
    picks = serving.check_picks(items, traffic["check"]["slots"])
    padded, last = serving.padded_rows([it.prompt for it in picks])
    fn = jax.jit(lambda p: reference.logits_at(cfg, p, jnp.asarray(padded),
                                               jnp.asarray(last)))
    ref = np.asarray(fn(params), np.float32)
    got = np.asarray(fn(coarser_in_place(params, precision)), np.float32)
    err, rms = serving.logit_errors(got, ref)
    return {"workload": cell["name"], "seed": seed, "precision": precision,
            "limit": traffic["check"]["logit_tol"],
            "rms_limit": traffic["check"].get("logit_rms_tol"),
            "control": err, "control_rms": rms}


def main(argv=None):
    from benchmark import control, harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", choices=control.PRECISIONS,
                    default="float8_e4m3fn")
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.cell_by_name(bench, args.workload)
    for seed in args.seeds:
        print(json.dumps(read_cell(bench, cell, seed, bench["run_seconds"],
                                   args.precision)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
