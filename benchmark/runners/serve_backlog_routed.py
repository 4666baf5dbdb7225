"""Traffic kind ``serve_backlog_routed``: ``serve_backlog`` (its backlog, its
window, its rows and its ``serve_tokens_per_s``, number for number: this
module runs ITS ``run``) for a model with ROUTED EXPERTS, whose check has to
tell a tie from a fault (PERF.md section 6, PR 34).

A dense model's logits move with the precision; a routed model's also JUMP:
a token whose sixth and seventh router scores lie within bfloat16's rounding
of each other (or whose third and fourth routing groups do) picks another
expert in the served bfloat16 than in the float32 reference, and its logits
move by a whole expert's output.  That is no fault (either pick is the
published forward, within the precision served) and it is not rare: at
DeepSeek-V2's widths about one compared row in six, whatever the router's
scale.  ``serve_backlog``'s check, a largest difference over ALL rows, then
has two ways out and both are wrong: a limit above the jump (past the 8-bit
control, and past any routing fault), or experts drawn so small that the jump
vanishes (and every routing fault with it).  So this check compares routes
before it compares logits:

* the decode step it compares returns the experts each seated token was
  routed to (``decode_step_paged(..., with_routes=True)``: the same
  executable's, not a recomputation), and the reference the router's float32
  scores of the same tokens (``logits_and_scores_at``);
* row by row and layer by layer, the HELD experts picked are compared with
  the reference's picks (an absent expert adds nothing here, in either).
  Where they differ, the reference's picks are taken again from its own
  scores with the program's experts raised and all others lowered by
  ``route_tie_margin``: if that gives the program's set, the scores TIED
  within the margin, and the row is set aside (its later layers saw another
  stream and cannot be compared).  If it does not, the row was ROUTED WRONG
  and the check fails, whatever the logits say;
* the two logit limits are ``serve_backlog``'s (``serving.logit_errors``),
  over the rows that took the reference's route; at most
  ``route_tied_rows_max`` rows may be set aside.

``benchmark/control_routed.py`` reads this comparison's controls at the cell's
own size: the 8-bit reference in the program's place, and a program with a
routing fault planted.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.serving import OK

from benchmark import harness, serving

_base = harness.load_plugin("runners", "serve_backlog")   # a copy of our own
backlog = _base.backlog

# the margins tried, smallest first, to say how close a differing pick was
_LADDER = (0.002, 0.005, 0.01, 0.02, 0.03, 0.05, 0.07, 0.1, 0.15, 0.2, 0.3,
           0.5)


def margin_needed(cfg, reference, scores, chosen):
    """The smallest margin of the ladder at which the reference's own
    ``scores`` (E,), the ``chosen`` experts raised by it and the others
    lowered, pick exactly ``chosen``; None if none does."""
    mask = np.zeros(scores.shape, bool)
    mask[list(chosen)] = True
    for m in _LADDER:
        moved = scores * np.where(mask, 1.0 + m, 1.0 - m)
        if set(np.nonzero(np.asarray(reference.picks(
                cfg, jnp.asarray(moved[None])))[0])[0].tolist()) == chosen:
            return m
    return None


def compare(spec, cfg, reference, got, ref, routes, scores):
    """The comparison that decides ``correct``, as the module docstring sets
    out.  ``got`` / ``ref`` (n, V): the program's and the reference's
    logits; ``routes`` (expert layers, n, k): the program's picks;
    ``scores`` (n, expert layers, E): the reference's router scores.
    Returns ``(ok, facts)``."""
    n, layers, E = scores.shape
    first, count = cfg.get("experts_held") or (0, E)
    here = lambda ids: {e for e in ids if first <= e < first + count}
    want = np.asarray(reference.picks(
        cfg, jnp.asarray(scores.reshape(n * layers, E)))).reshape(n, layers, E)
    same, tied, wrong, needed = [], [], [], []
    differ = differ_held = 0
    for b in range(n):
        state = "same"
        for i in range(layers):
            mine = set(np.asarray(routes[i, b]).tolist())
            theirs = set(np.nonzero(want[b, i])[0].tolist())
            differ += mine != theirs
            differ_held += here(mine) != here(theirs)
            if state == "same" and here(mine) != here(theirs):
                m = margin_needed(cfg, reference, scores[b, i], mine)
                needed.append(m)
                state = "tied" if m is not None and \
                    m <= spec["route_tie_margin"] else "wrong"
        {"same": same, "tied": tied, "wrong": wrong}[state].append(b)
    err, rms = serving.logit_errors(got[same], ref[same]) if same \
        else (None, None)
    err_all, rms_all = serving.logit_errors(got, ref)
    facts = {
        "logit_err": err, "logit_tol": spec["logit_tol"],
        "logit_rms_err": rms, "logit_rms_tol": spec["logit_rms_tol"],
        "rows_same_route": len(same), "rows_tied": len(tied),
        "rows_routed_wrong": len(wrong),
        "route_tied_rows_max": spec["route_tied_rows_max"],
        "route_tie_margin": spec["route_tie_margin"],
        "tie_margins_needed": needed,
        "row_layers": n * layers, "expert_set_differs": int(differ),
        "held_set_differs": int(differ_held),
        "logit_err_all_rows": err_all, "logit_rms_err_all_rows": rms_all,
        "logit_err_by_row": [
            round(float(np.abs(g - r).max() / np.abs(ref).max()), 4)
            for g, r in zip(got.astype(np.float64), ref.astype(np.float64))],
        "rows_set_aside": tied + wrong,
        "argmax_equal": f"{int((got.argmax(-1) == ref.argmax(-1)).sum())}"
                        f"/{n}"}
    ok = (bool(same) and not wrong
          and len(tied) <= spec["route_tied_rows_max"]
          and bool(np.isfinite(got).all()) and err <= spec["logit_tol"]
          and rms <= spec["logit_rms_tol"])
    return ok, facts


def route_ids(picked):
    """(n, layers, E) bool with ``k`` picks a row -> (layers, n, k) ids."""
    n, layers, E = picked.shape
    ids = np.nonzero(picked.reshape(n * layers, E))[1]
    return np.moveaxis(ids.reshape(n, layers, -1), 0, 1)


def check(ctx, model, eng, srv, items):
    """``serve_backlog.check`` (a live decode step through the paged kernel,
    its pool donated, against the plain float32 reference's full forward over
    the same slots' tokens; all requests ``ok``; every block recycled; the
    Mosaic kernel in the decode executable) with :func:`compare` in the place
    of the two bare limits."""
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
    ref, scores = jax.jit(
        lambda p, t, pos: reference.logits_and_scores_at(
            ctx.config, p, t, pos))(
        eng.params, jnp.asarray(padded), jnp.asarray(last))
    same_route, facts = compare(
        spec, ctx.config, reference, kernel[live], np.asarray(ref, np.float32),
        routes[:, live], np.asarray(scores, np.float32))
    on_tpu = jax.default_backend() == "tpu"
    facts.update(served=served, blocks_recycled=recycled,
                 mosaic_calls=n_mosaic, paged_impl=impl,
                 reference_rows=[len(h) for h in histories])
    ok = (same_route and served and recycled and impl == "kernel"
          and (n_mosaic > 0 or not on_tpu))
    log(f"check: {facts} -> {'ok' if ok else 'FAILED'}")
    return bool(ok), facts


def run(ctx):
    """``serve_backlog.run``, with this module's check."""
    _base.check = check
    return _base.run(ctx)
