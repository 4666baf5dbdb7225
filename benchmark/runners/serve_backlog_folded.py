"""Traffic kind ``serve_backlog_folded``: ``serve_backlog`` (its backlog in
the file's order, its feeder, its window, its rows and its
``serve_tokens_per_s``) for a model whose cache FOLDS itself
(``docs/serving.md#folded-cache``): a stream's table is summary blocks and a
window of exact rows, a prompt is prefilled a window at a time, and a window
that ends in decoding is folded by an executable the host launches between
two steps.  Two things differ from ``serve_backlog``:

**The warm-up.**  ``serving.warm_up`` seats one throw-away request for every
distinct prompt length in blocks.  A folded prompt's executables are the
whole window's and one a bucket of the TAIL after its last whole window,
whatever its length: one request a tail bucket (the shortest the backlog has)
builds every one of them, and the rest of the lengths would only run
(:func:`warm_picks`).

**The check.**  ``serve_backlog``'s seats prompts spread over the lengths'
quantiles and compares a decode step three steps later.  Whether a compared
row FOLDED in those steps is then left to the lengths.  This check picks its
prompts for what they cover, and says so in its facts:

* ``ends_a_window``, ``ends_a_window_too``: the longest and the shortest
  prompt whose length is 1 to ``steps`` bytes short of a window's end, so
  that a fold made IN DECODING stands in two of the compared rows, after
  many windows and after few (the compared step reads the summaries those
  folds wrote);
* ``just_past_a_window``: the prompt with the shortest tail after its last
  whole window (a table that is nearly all summaries);
* ``longest``: the longest prompt (the most summaries, the longest bucket);
* ``median``: the prompt of median length.

and asks, beside ``serve_backlog``'s comparison (``serving.logit_errors``,
number for number, the pool donated to the compared step): that a window was
folded in decoding for each ``ends_a_window`` row between the seat and the
compared step, that the timed window (where there was one: a control runs
the check alone) folded some and gave their blocks back
(``blocks_released_by_fold`` of the server's own count), and that every
block came home at the drain.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.serving import OK

from benchmark import harness, serving

_base = harness.load_plugin("runners", "serve_backlog")
backlog = _base.backlog


def tail_bucket(n, window, block):
    """Tokens of the prefill bucket that holds the tail of a prompt of ``n``
    after its last whole window (0: it has none)."""
    return -(-(n % window) // block) * block


def warm_picks(items, window, block):
    """The shortest request of every tail bucket the backlog has: each runs
    the whole window's executable (where it has a whole window) and its
    bucket's, the first decode step and, with its first upload, the fold's."""
    seen = {}
    for it in sorted(items, key=lambda it: len(it.prompt)):
        seen.setdefault(tail_bucket(len(it.prompt), window, block), it)
    return list(seen.values())


def check_picks(items, window, steps):
    """``{what it covers: item}``, as the module docstring sets out; a
    traffic mix that holds no prompt 1 to ``steps`` short of a window's end
    gives no ``ends_a_window``, and the check then fails by name."""
    by_len = sorted(items, key=lambda it: len(it.prompt))
    short = [it for it in by_len if 1 <= -len(it.prompt) % window <= steps]
    tails = [it for it in by_len if len(it.prompt) >= window
             and len(it.prompt) % window]
    picks = {"ends_a_window": short[-1] if short else None,
             "ends_a_window_too": short[0] if len(
                 {len(it.prompt) for it in short}) > 1 else None,
             "just_past_a_window": min(
                 tails, key=lambda it: len(it.prompt) % window,
                 default=None),
             "longest": by_len[-1], "median": by_len[len(by_len) // 2]}
    return {k: v for k, v in picks.items() if v is not None}


def compare(spec, got, ref):
    """The comparison that decides ``correct``, on logits alone: ``(ok,
    facts)`` of ``serving.logit_errors`` against the file's two limits."""
    err, rms = serving.logit_errors(got, ref)
    agree = int((got.argmax(-1) == ref.argmax(-1)).sum())
    facts = {"logit_err": err, "logit_tol": spec["logit_tol"],
             "logit_rms_err": rms, "logit_rms_tol": spec["logit_rms_tol"],
             "argmax_equal": f"{agree}/{len(got)}"}
    ok = (np.isfinite(got).all() and err <= spec["logit_tol"]
          and rms <= spec["logit_rms_tol"])
    return bool(ok), facts


def check(ctx, model, eng, srv, items):
    """``serve_backlog.check`` over prompts picked for what they cover, with
    a decode-time fold among the compared rows."""
    spec = ctx.traffic["check"]
    log = ctx.log
    window = ctx.config["window_size"]
    before = srv.stats()
    timed = ctx.seconds > 0          # a control runs the check alone
    picks = check_picks(items, window, spec["steps"])
    uids = [srv.submit(serving.to_request(dataclasses.replace(
        it, new_tokens=spec["steps"] + 4, do_sample=False)))
        for it in picks.values()]
    for _ in range(spec["steps"]):
        srv.step()

    params, pool, tables, lengths, toks = srv._decode_args()[:5]
    folded_in_check = (srv.stats()["windows_folded_total"]
                       - before["windows_folded_total"])
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
    ok, facts = compare(spec, kernel[live], ref)
    on_tpu = jax.default_backend() == "tpu"
    facts.update(
        served=served, blocks_recycled=recycled, mosaic_calls=n_mosaic,
        paged_impl=impl, reference_rows=[len(h) for h in histories],
        covers={k: len(it.prompt) for k, it in picks.items()},
        folded_in_check=folded_in_check,
        windows_folded_in_window=before["windows_folded_total"],
        blocks_released_by_fold=before["blocks_released_by_fold_total"])
    ok = (ok and served and recycled and impl == "kernel"
          and (n_mosaic > 0 or not on_tpu) and "ends_a_window" in picks
          and folded_in_check >= sum(k.startswith("ends") for k in picks)
          and (before["blocks_released_by_fold_total"] > 0 or not timed))
    log(f"check (folded cache): {facts} -> {'ok' if ok else 'FAILED'}")
    return bool(ok), facts


def run(ctx):
    """``serve_backlog.run`` with this module's warm-up and check."""
    model, eng, srv = serving.build(ctx)
    pool = backlog(ctx.traffic, ctx.seed, ctx.dims["vocab_size"])
    warm = warm_picks(pool, ctx.config["window_size"],
                      srv.config.block_size)
    serving.warm_up(srv, warm)
    ctx.log(f"warmed the whole window's prefill, {len(warm)} tail buckets, "
            f"the decode step and the fold; a backlog of {len(pool)} "
            "requests in the file's order, round and round")
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
