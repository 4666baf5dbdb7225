"""Traffic kind ``serve_open_loop``: requests arrive on a schedule drawn from
the seed, whether or not earlier ones have finished, and each is timed from
when it was DUE.  The tail of the time per output token is what is judged;
the wait for the first token is reported beside it, unjudged (its spread
between identical runs is wider than any bound: PERF.md).
"""

import time

from benchmark import harness, serving, traffic_gen


def run(ctx):
    model, eng, srv = serving.build(ctx)
    items = traffic_gen.open_loop_schedule(
        ctx.traffic, ctx.seconds, ctx.seed, ctx.config["vocab_size"])
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

    out["facts"].update(
        check=check, ttft_ms_p50=tail("ttft_ms", 50),
        ttft_ms_p95=tail("ttft_ms", 95), tpot_ms_p50=tail("tpot_ms", 50),
        # requests due and not finished at the middle and at the end of the
        # window: the rate sweep's test for a growing queue
        backlog_mid_end=[backlog(ctx.seconds / 2), backlog(ctx.seconds)])
    return {**out, "setup_s": setup_s,
            "end_to_end": {"tpot_ms_p95": tail("tpot_ms", 95)},
            "attempted": len(rows),
            "failed": sum(not r["ok"] for r in rows),
            "correct": bool(ok and out["in_window_compiles"] == 0)}
