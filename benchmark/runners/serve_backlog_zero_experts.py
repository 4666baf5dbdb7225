"""Traffic kind ``serve_backlog_zero_experts``: ``serve_backlog`` (its
backlog, its window, its rows and its ``serve_tokens_per_s``, number for
number: this module runs ITS ``run``) with ``serve_backlog_routed``'s check
(its live decode step, its reference call, its tie test) for a model whose
router is WIDER than its experts (``models/longcat_flash.py``: 512 experts
with matrices and 256 identity experts, ids 512..767, whose output is the
token's own input times the routing weight), and with NO row set aside.

Two things differ from ``serve_backlog_routed``'s comparison:

* WHICH PICKS COUNT.  The parent asks whether the HELD experts the program
  picked are the reference's, because an absent expert adds nothing on this
  chip in either.  Here an IDENTITY expert's pick does change this chip's
  output, whichever chip holds what: every chip computes the identity part
  whole.  So the experts that count are the held ids AND the ids past the
  real experts (``zero_expert_num`` of them): a tie between an identity
  expert and an absent one moves this chip's logits, a tie between two
  absent ones does not.
* A TIED ROW STILL DECIDES.  Top-12 of 768 ties in more than half of the
  compared rows, and the parent sets a tied row aside, logits and all: a fault
  that moves only those rows' logits would go unseen, and a handful of rows
  would decide.  Here the reference is given the program's picks of the
  compared token (``logits_and_scores_at(..., forced=routes)``): the picks
  come from the program, everything else (the scores that weigh them, the
  identity part, the experts, both attentions) from the reference, and EVERY
  row is held to the two logit limits.  Each layer's scores then come from a
  stream that took the program's experts in the layers above, so the tie
  test is asked in every layer and not in a row's first differing one alone:
  where the counted picks differ from what the reference's ``x = s +
  router_bias`` rank first, ``x`` with the program's picks raised and the
  others lowered by ``route_tie_margin`` must pick the program's set, else
  the row was ROUTED WRONG and the check fails.  At most
  ``route_tied_rows_max`` rows may differ at all.

``benchmark/control_longcat.py`` reads the controls.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.serving import OK

from benchmark import harness, serving

_routed = harness.load_plugin("runners", "serve_backlog_routed")
backlog = _routed.backlog
route_ids = _routed.route_ids
margin_needed = _routed.margin_needed


def counted_here(cfg, width):
    """``ids -> the ids among them that move THIS chip's output``: the held
    real experts and every identity expert, for a router ``width`` wide."""
    real = width - cfg["zero_expert_num"]
    first, count = cfg.get("experts_held") or (0, real)
    return lambda ids: {e for e in ids
                        if first <= e < first + count or e >= real}


def compare(spec, cfg, reference, got, ref, routes, scores):
    """The comparison that decides ``correct``, as the module docstring sets
    out.  ``got`` (n, V): the program's logits; ``routes`` (expert layers,
    n, k): its picks; ``ref`` (n, V) and ``scores`` (n, expert layers, E +
    Z): the reference's logits and ``x`` WITH THOSE PICKS FORCED.  Returns
    ``(ok, facts)``."""
    n, layers, W = scores.shape
    here = counted_here(cfg, W)
    real = W - cfg["zero_expert_num"]
    want = np.asarray(reference.picks(
        cfg, jnp.asarray(scores.reshape(n * layers, W)))).reshape(n, layers, W)
    same, tied, wrong, needed, at_layer = [], [], [], [], []
    differ = differ_here = differ_zero = 0
    for b in range(n):
        state = same
        for i in range(layers):
            mine = set(np.asarray(routes[i, b]).tolist())
            theirs = set(np.nonzero(want[b, i])[0].tolist())
            differ += mine != theirs
            differ_zero += ({e for e in mine if e >= real}
                            != {e for e in theirs if e >= real})
            if here(mine) == here(theirs):
                continue
            differ_here += 1
            m = margin_needed(cfg, reference, scores[b, i], mine)
            needed.append(m)
            at_layer.append(i)
            if m is None or m > spec["route_tie_margin"]:
                state = wrong
            elif state is same:
                state = tied
        state.append(b)
    err, rms = serving.logit_errors(got, ref)
    facts = {
        "logit_err": err, "logit_tol": spec["logit_tol"],
        "logit_rms_err": rms, "logit_rms_tol": spec["logit_rms_tol"],
        "rows_same_route": len(same), "rows_tied": len(tied),
        "rows_routed_wrong": len(wrong),
        "route_tied_rows_max": spec["route_tied_rows_max"],
        "route_tie_margin": spec["route_tie_margin"],
        "tie_margins_needed": needed, "tie_layers": at_layer,
        "row_layers": n * layers, "expert_set_differs": int(differ),
        "held_set_differs": int(differ_here),
        "zero_set_differs": int(differ_zero),
        "logit_err_tied_rows": serving.logit_errors(got[tied], ref[tied])
        if tied else None,
        "logit_err_by_row": [
            round(float(np.abs(g - r).max() / np.abs(ref).max()), 4)
            for g, r in zip(got.astype(np.float64), ref.astype(np.float64))],
        "rows_differing": tied + wrong,
        "argmax_equal": f"{int((got.argmax(-1) == ref.argmax(-1)).sum())}"
                        f"/{n}"}
    ok = (not wrong and len(tied) <= spec["route_tied_rows_max"]
          and bool(np.isfinite(got).all()) and err <= spec["logit_tol"]
          and rms <= spec["logit_rms_tol"])
    return ok, facts


def check(ctx, model, eng, srv, items):
    """``serve_backlog_routed.check`` (a live decode step through the paged
    kernel, its pool donated, handing back the experts each seated token was
    routed to; all requests ``ok``; every block recycled; the Mosaic kernel
    in the decode executable) against the plain float32 reference's full
    forward over the same slots' tokens WITH THOSE EXPERTS FORCED on the
    compared token, through :func:`compare`; at least 16 seated slots."""
    spec = ctx.traffic["check"]
    assert spec["slots"] >= 16 or \
        ctx.traffic["serving"]["batch_slots"] < 16, spec
    uids = [srv.submit(serving.to_request(dataclasses.replace(
        it, new_tokens=spec["steps"] + 4, do_sample=False)))
        for it in serving.check_picks(items, spec["slots"])]
    for _ in range(spec["steps"]):
        srv.step()

    params, pool, tables, lengths, toks = srv._decode_args()[:5]
    with jax.set_mesh(eng.mesh):
        step = jax.jit(lambda p, t, pl, tb, ln: srv.model.decode_step_paged(
            srv._deq(p), t, pl, tb, ln, with_routes=True),
            donate_argnums=(2,))
        srv.pool = None              # the server's reference: donated below
        logits, srv.pool, routes = step(params, toks, pool, tables, lengths)
        del pool
        kernel = np.asarray(logits, np.float32)
        routes = np.asarray(routes)
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
    routes = routes[:, live]
    ref, scores = jax.jit(
        lambda p, t, pos, ids: reference.logits_and_scores_at(
            ctx.config, p, t, pos, forced=ids))(
        eng.params, jnp.asarray(padded), jnp.asarray(last),
        jnp.asarray(np.moveaxis(routes, 0, 1)))
    same_route, facts = compare(
        spec, ctx.config, reference, kernel[live], np.asarray(ref, np.float32),
        routes, np.asarray(scores, np.float32))
    on_tpu = jax.default_backend() == "tpu"
    facts.update(served=served, blocks_recycled=recycled,
                 mosaic_calls=n_mosaic, paged_impl=impl,
                 reference_rows=[len(h) for h in histories])
    ok = (same_route and served and recycled and impl == "kernel"
          and (n_mosaic > 0 or not on_tpu))
    ctx.log(f"check: {facts} -> {'ok' if ok else 'FAILED'}")
    return bool(ok), facts


def run(ctx):
    """``serve_backlog.run``, with this module's check."""
    _routed._base.check = check
    return _routed._base.run(ctx)
