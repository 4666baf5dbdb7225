#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips.  It refuses anything but a TPU and
never falls back.  Logs go to stderr; the last line of stdout is the one JSON
object ``BENCHMARK.json``'s contract fixes.  With ``--trace 0`` the metrics
are the cell's end-to-end metrics; with ``--trace 1`` a profiler trace covers
the last seconds of the window and the metrics are the cell's per-layer
metrics, each computed by its own reader (``benchmark/layer_metrics/*.json``
names it).

``setup_s`` runs from the process's start to the window's start, LESS the one
call in which the TPU runtime starts (``jax.devices()`` below, logged as
``chip reach``): 7.6 to 12.5 s on one machine by what ran on the chip before,
more on four chips, and none of it this repo's (PERF.md, section 2).  The
interpreter, every import and all the program does stay in.
"""

import time
_T_PROCESS_START = time.monotonic()     # before anything heavy is imported

import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(bench, cell, *, seed, seconds, trace, t_process_start=None,
             chip_reach_s=0.0, log=log, trace_dir=None, config=None,
             traffic=None):
    """Run one cell and return the result object.  ``main`` alone refuses a
    machine without a TPU; the tests call this at a tiny size on the CPU.
    ``chip_reach_s`` is what the TPU runtime took to start: the set-up
    clock and ``setup.before_program_s`` both leave it out."""
    from benchmark import harness
    if t_process_start is None:
        t_process_start = time.monotonic()
    ctx = harness.RunContext(
        bench, cell, seed, seconds, trace, t_process_start + chip_reach_s,
        log=log, trace_dir=trace_dir, config=config, traffic=traffic)
    runner = harness.load_plugin("runners", ctx.traffic["kind"])
    if trace:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    out = runner.run(ctx)
    device = out["device"]
    values = {"setup_s": out["setup_s"], **out["end_to_end"]}
    breakdown = None
    if trace:
        from benchmark import trace_reduce
        summary = trace_reduce.reduce_file(
            out["trace_path"], n_devices=cell["chips"])
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = summary["breakdown"]
        view = {"trace": summary, "spans": ctx.spans, "counters":
                out["counters"], "facts": out["facts"], "config": ctx.config,
                "family": ctx.family,
                "peaks": harness.peaks_for(device["kind"])
                if device["platform"] == "tpu" else None,
                "trace_span": out["trace_span"],
                "chip_reach_s": chip_reach_s}
        values = {}
        for metric in harness.cell_metrics(bench, "per_layer", cell["name"]):
            spec = harness.read_json("layer_metrics",
                                     f"{metric['name']}.json")
            reader = harness.load_plugin("readers", spec["reader"])
            value = reader.read(view, **spec.get("params", {}))
            if value is not None:       # nothing to read: left out
                values[metric["name"]] = value
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    group = "per_layer" if trace else "end_to_end"
    names = [m["name"] for m in harness.cell_metrics(bench, group,
                                                     cell["name"])]
    result = {
        "correct": out["correct"], "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": {n: {"value": values[n], "unit": units[n]}
                    for n in names if n in values},
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["details"] = {"workload": cell["name"], "seed": int(seed),
                         "seconds": seconds, "chip_reach_s": chip_reach_s,
                         "counters": out["counters"],
                         "facts": {k: v for k, v in out["facts"].items()
                                   if k != "live_tokens"}}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="offered rate instead of the traffic file's: for "
                         "the knee sweep that defines a cell, never for a "
                         "measurement")
    args = ap.parse_args(argv)

    from benchmark import harness
    bench = harness.load_benchmark()
    cell = harness.cell_by_name(bench, args.workload)

    import jax
    t_reach = time.monotonic()
    devices = jax.devices()             # the TPU runtime starts here
    chip_reach_s = time.monotonic() - t_reach
    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}; chip reach {chip_reach_s:.3f} s, left out of "
        f"setup_s")
    if jax.default_backend() != "tpu":
        log("no TPU: the benchmark measures the chip and does not fall back")
        return 1
    if len(devices) < cell["chips"]:
        log(f"{cell['name']} needs {cell['chips']} chips; this machine has "
            f"{len(devices)}")
        return 1
    harness.peaks_for(devices[0].device_kind)   # an unknown chip is an error

    from deepspeed_tpu.runtime import compile_cache
    from deepspeed_tpu.utils.logging import route_logs_to_stderr
    route_logs_to_stderr()
    log(f"compile caches under {compile_cache.use_persistent_cache()}")

    traffic = None
    if args.rate is not None:
        traffic = harness.load_traffic(cell["traffic"])
        traffic["arrivals"]["rate"] = args.rate
    result = run_cell(bench, cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), traffic=traffic,
                      t_process_start=_T_PROCESS_START,
                      chip_reach_s=chip_reach_s)
    log(f"whole run {time.monotonic() - _T_PROCESS_START:.1f} s")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
