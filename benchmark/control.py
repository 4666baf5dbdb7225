#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the plain
reference put in the program's place, computed one step of precision below
the bfloat16 the cells state: every weight matrix rounded to 8 bits, the step
that would tempt a later PR.  Two roundings: ``float8_e4m3fn`` (3 bits of
mantissa for bfloat16's 7), and ``int8`` with one scale an output channel,
which keeps 7 bits and is the nearer of the two.  A limit is sound only while
the control comes out NOT correct under it, with room: PERF.md gives, for
each limit, the largest number sound runs read, the smallest the control
reads, and the limit.  On the chip at ``serve_chat_sat``'s size (my chip
runs, PR 27): the largest logit difference over the largest logit reads
0.9e-2 to 1.4e-2 for the program, 2.8e-2 to 3.0e-2 under ``int8`` and 0.39 to
0.45 under ``float8_e4m3fn``, so ``logit_tol`` 0.04 holds off the second
only; the root mean square of the differences over that of the logits reads
1.11e-2 to 1.20e-2, 2.93e-2 to 2.97e-2 and 0.40, steady enough that a limit
between the first two (``logit_rms_tol``, in the traffic files that state it)
holds off both.

The benchmark's own runs never run this.  ``benchmark/tests/test_control.py``
keeps it at a size a test can hold; on the chip, at a cell's own size:

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3 --precision int8

prints one JSON line a seed: the numbers the cell's check compares (serving:
``control``, the largest logit difference over the largest reference logit,
and ``control_rms``, at the last position of the prompts the check would
seat; training: relative difference of the first batch's loss), read with
the control in the program's place.
"""

import argparse
import json
import os
import sys

if __package__ in (None, ""):      # run as a script: find ``benchmark``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


PRECISIONS = ("float8_e4m3fn", "int8")


def coarser(params, precision="float8_e4m3fn"):
    """``params`` with every matrix (2 or more dimensions) rounded to 8 bits
    and brought back to its own type; vectors (biases, norm scales) stay, as
    they do in 8-bit serving.  ``float8_e4m3fn``: a plain cast.  ``int8``:
    round-to-nearest onto 255 levels with one scale an output channel (the
    largest magnitude along the second-to-last axis over 127)."""
    import jax
    import jax.numpy as jnp

    def rounded(x):
        if x.ndim < 2:
            return x
        if precision == "float8_e4m3fn":
            return x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        wide = x.astype(jnp.float32)
        scale = jnp.maximum(jnp.abs(wide).max(axis=-2, keepdims=True),
                            1e-30) / 127.0
        return (jnp.round(wide / scale).astype(jnp.int8).astype(jnp.float32)
                * scale).astype(x.dtype)
    if precision not in PRECISIONS:
        raise SystemExit(f"no control precision {precision!r}: {PRECISIONS}")
    # eagerly: inside one jitted program XLA folds the two casts away
    return jax.tree_util.tree_map(rounded, params)


def logit_error(cfg, reference, params, control_params, tokens, positions):
    """What ``serving.check`` computes, ``(largest, root mean square)``,
    with the control's logits where the program's would be."""
    import jax
    import numpy as np
    from benchmark import serving
    fn = jax.jit(lambda p: reference.logits_at(cfg, p, tokens, positions))
    ref = np.asarray(fn(params), np.float32)
    got = np.asarray(fn(control_params), np.float32)
    return serving.logit_errors(got, ref)


def loss_error(cfg, reference, params, control_params, batch):
    """What the train runner's check computes: the relative difference of
    the batch's mean loss, a row at a time."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    fn = jax.jit(lambda p, row: reference.loss(cfg, p, row))

    def mean_loss(p):
        return float(np.mean([float(fn(p, jnp.asarray(row[None])))
                              for row in batch]))
    ref = mean_loss(params)
    return abs(mean_loss(control_params) - ref) / abs(ref)


def read_cell(bench, cell, seed, seconds, precision="float8_e4m3fn"):
    """The control's number for one cell and one seed."""
    import jax.numpy as jnp
    from benchmark import harness, serving, traffic_gen
    cfg = harness.load_config(bench, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    family, reference = harness.family(cfg), harness.reference(cfg)
    vocab = family.dims(cfg)["vocab_size"]
    out = {"workload": cell["name"], "seed": seed, "precision": precision}
    if traffic["kind"] == "train":
        model = family.build(cfg, jnp.bfloat16, max_positions=traffic["seq"],
                             **traffic["model"])
        params = harness.seeded_weights(model, seed, jnp.bfloat16)
        batch = traffic_gen.token_batches(
            traffic, seed, vocab, traffic["micro_batch"])[0]
        out["limit"] = traffic["check"]["loss_rtol"]
        out["control"] = loss_error(cfg, reference, params,
                                    coarser(params, precision), batch)
        return out
    model = family.build(cfg, jnp.bfloat16)
    params = harness.seeded_weights(model, seed, jnp.bfloat16)
    if traffic["kind"] == "serve_open_loop":
        items = traffic_gen.open_loop_schedule(traffic, seconds, seed, vocab)
    else:
        items = traffic_gen.backlog(traffic, seed, vocab)
    picks = serving.check_picks(items, traffic["check"]["slots"])
    padded, last = serving.padded_rows([it.prompt for it in picks])
    out["limit"] = traffic["check"]["logit_tol"]
    out["rms_limit"] = traffic["check"].get("logit_rms_tol")
    out["control"], out["control_rms"] = logit_error(
        cfg, reference, params, coarser(params, precision),
        jnp.asarray(padded), jnp.asarray(last))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", choices=PRECISIONS,
                    default="float8_e4m3fn")
    args = ap.parse_args(argv)
    from benchmark import harness
    bench = harness.load_benchmark()
    cell = harness.cell_by_name(bench, args.workload)
    for seed in args.seeds:
        print(json.dumps(read_cell(bench, cell, seed, bench["run_seconds"],
                                   args.precision)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
