"""Traffic kind ``serve_backlog``: ``serve_offline``'s closed backlog (the
same feeder, window, rows and ``serve_tokens_per_s``: prompt tokens prefilled
plus tokens generated of the requests COMPLETED inside the window, per
second) for a model whose K/V pool takes half the chip and whose window holds
some fifty requests.  Two things differ, and they are why this is a runner of
its own and not ``serve_offline`` (PERF.md section 6, PR 32):

* **The file fixes the backlog's order** (``order_seed``, as the open-loop
  files do): ``--seed`` draws the token ids, the sampling seeds and the
  weights, and every seed offers the same queue.  With the order left to the
  seed, which requests land inside the window moved the number by 16.5 %
  between the quartiles of eight seeds while the step moved 0.65 %.
* **The check donates the pool to the decode step it compares** and hands the
  written pool back to the server.  ``serving.check`` calls the step under a
  jit that donates nothing, so a second pool has to stand beside the first:
  the weights and two pools of 8.05 GB do not fit a 16 GB chip.  The
  comparison itself is ``serving.check``'s, number for number.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.serving import OK

from benchmark import harness, serving, traffic_gen


def backlog(traffic, seed, vocab_size):
    """The ``pool_requests`` requests, all due at 0, in the order the FILE
    fixes (``order_seed``); their token ids come from ``seed``."""
    return traffic_gen.make_items(
        traffic, int(traffic["pool_requests"]), seed, vocab_size,
        order_seed=traffic["order_seed"])


def check(ctx, model, eng, srv, items):
    """``serving.check``, with the pool donated: a live decode step's logits
    through the paged kernel against the plain float32 reference's full
    forward over the same slots' tokens; all requests ``ok`` with their
    tokens; every block recycled; the Mosaic kernel in the decode executable.
    The step writes the seated streams' next K/V rows into the pool it was
    given and the server goes on with that pool (its own next step writes
    the same rows again).  The reference runs after the server has given the
    pool back."""
    spec = ctx.traffic["check"]
    log = ctx.log
    uids = [srv.submit(serving.to_request(dataclasses.replace(
        it, new_tokens=spec["steps"] + 4, do_sample=False)))
        for it in serving.check_picks(items, spec["slots"])]
    for _ in range(spec["steps"]):
        srv.step()

    params, pool, tables, lengths, toks = srv._decode_args()[:5]
    with jax.set_mesh(eng.mesh):
        step = jax.jit(lambda p, t, pl, tb, ln: srv.model.decode_step_paged(
            srv._deq(p), t, pl, tb, ln), donate_argnums=(2,))
        srv.pool = None              # the server's reference: donated below
        logits, srv.pool = step(params, toks, pool, tables, lengths)
        del pool
        kernel = np.asarray(logits, np.float32)
    live = [i for i, s in enumerate(srv._slots) if s is not None]
    histories = [np.concatenate([np.asarray(srv._slots[i].req.tokens),
                                 np.asarray(srv._slots[i].out_tokens)])
                 for i in live]
    n_mosaic = srv._decode.executable(*srv._decode_args()).as_text().count(
        "tpu_custom_call")
    impl = srv.model.paged_attention_impl()

    while srv.step():
        pass
    results = [srv.results[u] for u in uids]
    served = all(r["outcome"] == OK and len(r["tokens"]) == spec["steps"] + 4
                 for r in results)
    recycled = srv.allocator.free_blocks == srv.num_blocks - 1
    srv.close()

    padded, last = serving.padded_rows(histories)
    reference = harness.reference(ctx.config)
    ref = np.asarray(jax.jit(
        lambda p, t, pos: reference.logits_at(ctx.config, p, t, pos))(
        eng.params, jnp.asarray(padded), jnp.asarray(last)), np.float32)
    got = kernel[live]
    err, rms = serving.logit_errors(got, ref)
    agree = int((got.argmax(-1) == ref.argmax(-1)).sum())
    on_tpu = jax.default_backend() == "tpu"
    facts = {"logit_err": err, "logit_tol": spec["logit_tol"],
             "logit_rms_err": rms, "logit_rms_tol": spec["logit_rms_tol"],
             "argmax_equal": f"{agree}/{len(live)}", "served": served,
             "blocks_recycled": recycled, "mosaic_calls": n_mosaic,
             "paged_impl": impl, "reference_rows": [len(h) for h in histories]}
    ok = (np.isfinite(got).all() and err <= spec["logit_tol"]
          and rms <= spec["logit_rms_tol"] and served and recycled
          and impl == "kernel" and (n_mosaic > 0 or not on_tpu))
    log(f"check: {facts} -> {'ok' if ok else 'FAILED'}")
    return bool(ok), facts


def run(ctx):
    model, eng, srv = serving.build(ctx)
    pool = backlog(ctx.traffic, ctx.seed, ctx.dims["vocab_size"])
    n_buckets = serving.warm_up(srv, pool)
    ctx.log(f"warmed {n_buckets} prefill buckets and the decode step; a "
            f"backlog of {len(pool)} requests in the file's order, round "
            "and round")
    setup_s = time.monotonic() - ctx.t_process_start

    out = serving.run_window(ctx, srv, eng, serving.BacklogFeeder(
        pool, int(ctx.traffic["queue_depth"]), ctx.seconds))
    rows = out.pop("rows")
    ok, facts = check(ctx, model, eng, srv, pool)
    eng.close()

    rate, completed = serving.tokens_per_s(rows, ctx.seconds)
    out["facts"].update(check=facts, completed_in_window=completed)
    return {**out, "setup_s": setup_s,
            "end_to_end": {"serve_tokens_per_s": rate},
            "attempted": len(rows),
            "failed": sum(not r["ok"] for r in rows),
            "correct": bool(ok and out["in_window_compiles"] == 0)}
