"""Traffic kind ``serve_offline``: a closed backlog.  Requests are submitted
at the start and the queue is topped up so it never empties; what counts is
the tokens (prompt tokens prefilled plus tokens generated) of the requests
COMPLETED inside the window, per second.
"""

import time

from benchmark import serving, traffic_gen


def run(ctx):
    model, eng, srv = serving.build(ctx)
    pool = traffic_gen.backlog(ctx.traffic, ctx.seed,
                               ctx.dims["vocab_size"])
    n_buckets = serving.warm_up(srv, pool)
    ctx.log(f"warmed {n_buckets} prefill buckets and the decode step; a "
            f"backlog of {len(pool)} requests, round and round")
    setup_s = time.monotonic() - ctx.t_process_start

    out = serving.run_window(ctx, srv, eng, serving.BacklogFeeder(
        pool, int(ctx.traffic["queue_depth"]), ctx.seconds))
    rows = out.pop("rows")
    ok, check = serving.check(ctx, model, eng, srv, pool)
    eng.close()

    rate, completed = serving.tokens_per_s(rows, ctx.seconds)
    out["facts"].update(check=check, completed_in_window=completed)
    return {**out, "setup_s": setup_s,
            "end_to_end": {"serve_tokens_per_s": rate},
            "attempted": len(rows),
            "failed": sum(not r["ok"] for r in rows),
            "correct": bool(ok and out["in_window_compiles"] == 0)}
