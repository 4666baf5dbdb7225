"""Traffic kind ``serve_open_loop``: requests arrive on a schedule drawn from
the seed, whether or not earlier ones have finished, and each is timed from
when it was DUE.

Which end-to-end metric the runner prints is the one ``BENCHMARK.json`` binds
the cell to, and no other.  Below the knee that is the tail of the time per
output token (the wait for the first token is reported beside it, unjudged:
its spread between identical runs is wider than any bound, PERF.md).  Above
the knee the queue grows all through the window and every tail swings with
the smallest change: the cell is then bound to the tokens of the requests
completed inside the window, per second (``serve_tokens_per_s``, as the
offline runner defines it), and the tails are facts.
"""

import time

from benchmark import harness, serving, traffic_gen


def run(ctx):
    model, eng, srv = serving.build(ctx)
    items = traffic_gen.open_loop_schedule(
        ctx.traffic, ctx.seconds, ctx.seed, ctx.dims["vocab_size"])
    n_buckets = serving.warm_up(srv, items)
    ctx.log(f"warmed {n_buckets} prefill buckets and the decode step; "
            f"{len(items)} requests due in {ctx.seconds:g} s")
    setup_s = time.monotonic() - ctx.t_process_start

    out = serving.run_window(ctx, srv, eng, serving.OpenLoopFeeder(items))
    rows = out.pop("rows")
    ok, check = serving.check(ctx, model, eng, srv, items)
    eng.close()

    def tail(key, q):
        return harness.percentile([r[key] for r in rows], q)

    def backlog(t):
        return sum(1 for r in rows if r["due_s"] <= t < r["done_s"])

    rate, completed = serving.tokens_per_s(rows, ctx.seconds)
    measured = {"tpot_ms_p95": tail("tpot_ms", 95),
                "serve_tokens_per_s": rate}
    bound_to = [m["name"] for m in harness.cell_metrics(
        ctx.bench, "end_to_end", ctx.cell["name"])]
    out["facts"].update(
        measured, check=check, ttft_ms_p50=tail("ttft_ms", 50),
        ttft_ms_p95=tail("ttft_ms", 95), tpot_ms_p50=tail("tpot_ms", 50),
        completed_in_window=completed,
        # requests due and not finished at the middle and at the end of the
        # window: the rate sweep's test for a growing queue
        backlog_mid_end=[backlog(ctx.seconds / 2), backlog(ctx.seconds)])
    return {**out, "setup_s": setup_s,
            "end_to_end": {name: measured[name] for name in bound_to
                           if name in measured},
            "attempted": len(rows),
            "failed": sum(not r["ok"] for r in rows),
            "correct": bool(ok and out["in_window_compiles"] == 0)}
