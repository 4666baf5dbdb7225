"""Traffic kind ``serve_backlog_hybrid``: ``serve_backlog`` (its backlog, its
window, its rows and its ``serve_tokens_per_s``: this module runs ITS ``run``)
for a model whose serving state holds THREE kinds at once
(``docs/serving.md#a-cache-that-several-layers-read``): the blocks of one
growing table, a ring of window blocks under an allocator of its own, and
recurrent rows at a precision the configuration states.  The accepted runners
are loaded as plugins and none is edited.  The check is this module's own:

* **Logits, no routes** (the model routes nothing): a live decode step
  through both pools and the rows, donated as ``serve_backlog.check`` donates
  it, against the plain float32 reference's full forward over the same
  streams' tokens; the two numbers are ``serving.logit_errors``'.
* **The prefill's row too** (``prefill_logits``): the cross-decoder, and the
  full layer past its K and V, write no cache, so what a prefill computes
  there at its one position reaches a decode step only as the answer's first
  token, which the histories take as given.  The row ``prefill_paged``
  returns for each seated prompt is therefore compared as well, under the
  same two limits, with the same reference pass read at position
  ``len(prompt) - 1``.
* **The prompts are chosen to cross what is new** (``check_prompts``): the
  schedule's prompts at ``check.prompt_quantiles`` of its lengths, and one
  more cut to ``check.short_prompt`` tokens, shorter than the window (its
  ring has not wrapped and holds scratch entries); at least
  ``rows_past_window_min`` compared rows are longer than ``sliding_window``
  and ``rows_past_min`` are longer than ``rows_past`` tokens, so a key that
  should have slid out, or a long walk of the shared cache, shows in the
  logits.
* ``serve_backlog_windowed``'s demand that BOTH allocators' blocks came home.
* ``serve_backlog_recurrent``'s ``check.state`` on the recurrent rows
  (``state_precision``, unchanged), BEFORE the logits are compared.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference import paged_kv
from deepspeed_tpu.inference.serving import OK

from benchmark import harness, serving

_base = harness.load_plugin("runners", "serve_backlog")
_recurrent = harness.load_plugin("runners", "serve_backlog_recurrent")
backlog = _base.backlog


def check_prompts(spec, items):
    """The requests the check seats: one cut to ``short_prompt`` tokens, then
    the schedule's prompts at ``prompt_quantiles`` of its lengths."""
    by_len = sorted(items, key=lambda it: len(it.prompt))
    picks = [by_len[int(q * (len(by_len) - 1))]
             for q in spec["prompt_quantiles"]]
    short = dataclasses.replace(
        by_len[0], prompt=by_len[0].prompt[:int(spec["short_prompt"])])
    return [short] + picks


def prefill_logits(eng, srv, prompts):
    """The row ``prefill_paged`` returns (the logits at each prompt's last
    position) for ``prompts``, after the server has drained: ONE executable,
    every prompt padded to the longest one's bucket; the tables name the
    scratch block alone and slot 0 takes the recurrent rows, so no live block
    is written; the server's state is donated and handed back as
    ``serve_backlog.check`` does it."""
    bs = srv.config.block_size
    nb = paged_kv.blocks_needed(max(len(p) for p in prompts), bs)
    blocks = jnp.full((nb + srv.ring,), paged_kv.SCRATCH_BLOCK, jnp.int32)
    fn = jax.jit(lambda p, t, pool, n: srv.model.prefill_paged(
        srv._deq(p), t, pool, blocks, jnp.int32(0), n), donate_argnums=(2,))
    rows = []
    with jax.set_mesh(eng.mesh):
        for prompt in prompts:
            toks = np.zeros((1, nb * bs), np.int32)
            toks[0, :len(prompt)] = prompt
            pool, srv.pool = srv.pool, None
            row, srv.pool = fn(eng.params, jnp.asarray(toks), pool,
                               jnp.int32(len(prompt)))
            del pool
            rows.append(np.asarray(row[0], np.float32))
    return np.stack(rows)


def check(ctx, model, eng, srv, items):
    """As the module docstring sets out.  Returns ``(ok, facts)``; the
    reference runs after the server has given its pool back."""
    spec = ctx.traffic["check"]
    log = ctx.log
    uids = [srv.submit(serving.to_request(dataclasses.replace(
        it, new_tokens=spec["steps"] + 4, do_sample=False)))
        for it in check_prompts(spec, items)]
    for _ in range(spec["steps"]):
        srv.step()

    params, pool, tables, lengths, toks = srv._decode_args()[:5]
    with jax.set_mesh(eng.mesh):
        kept, state = _recurrent.state_precision(spec["state"], pool)
        step = jax.jit(lambda p, t, pl, tb, ln: srv.model.decode_step_paged(
            srv._deq(p), t, pl, tb, ln), donate_argnums=(2,))
        srv.pool = None              # the server's reference: donated below
        logits, srv.pool = step(params, toks, pool, tables, lengths)
        del pool
        kernel = np.asarray(logits, np.float32)
    live = [i for i, s in enumerate(srv._slots) if s is not None]
    prompts = [np.asarray(srv._slots[i].req.tokens) for i in live]
    histories = [np.concatenate([p, np.asarray(srv._slots[i].out_tokens)])
                 for p, i in zip(prompts, live)]
    n_mosaic = srv._decode.executable(*srv._decode_args()).as_text().count(
        "tpu_custom_call")
    impl = srv.model.paged_attention_impl()

    while srv.step():
        pass
    results = [srv.results[u] for u in uids]
    served = all(r["outcome"] == OK and len(r["tokens"]) == spec["steps"] + 4
                 for r in results)
    recycled = srv.allocator.free_blocks == srv.num_blocks - 1
    window_recycled = (srv.window_allocator.free_blocks
                       == srv.window_num_blocks - 1)
    prefilled = prefill_logits(eng, srv, prompts)
    srv.close()

    # one reference pass a row, read at two positions: the prompt's last (the
    # prefill's row) and the history's last (the decode step's)
    padded, last = serving.padded_rows(histories)
    at = np.stack([[len(p) - 1 for p in prompts], last], axis=1)
    reference = harness.reference(ctx.config)
    ref = np.asarray(jax.jit(
        lambda p, t, pos: reference.logits_at(ctx.config, p, t, pos))(
        eng.params, jnp.asarray(padded), jnp.asarray(at)), np.float32)
    pre_err, pre_rms = serving.logit_errors(prefilled, ref[:, 0])
    ref = ref[:, 1]
    got = kernel[live]
    err, rms = serving.logit_errors(got, ref)
    agree = int((got.argmax(-1) == ref.argmax(-1)).sum())
    on_tpu = jax.default_backend() == "tpu"
    rows = [len(h) for h in histories]
    window = ctx.config["sliding_window"]
    crossed = (sum(n < window for n in rows) >= 1
               and sum(n > window for n in rows)
               >= spec["rows_past_window_min"]
               and sum(n > spec["rows_past"] for n in rows)
               >= spec["rows_past_min"])
    facts = {"logit_err": err, "logit_tol": spec["logit_tol"],
             "logit_rms_err": rms, "logit_rms_tol": spec["logit_rms_tol"],
             "prefill_logit_err": pre_err, "prefill_logit_rms_err": pre_rms,
             "argmax_equal": f"{agree}/{len(live)}", "served": served,
             "blocks_recycled": recycled,
             "window_blocks_recycled": window_recycled,
             "mosaic_calls": n_mosaic, "paged_impl": impl,
             "reference_rows": rows, "rows_cross_what_is_new": crossed,
             "state_precision": state, "state_kept_as_stated": kept}
    ok = (np.isfinite(got).all() and np.isfinite(prefilled).all()
          and max(err, pre_err) <= spec["logit_tol"]
          and max(rms, pre_rms) <= spec["logit_rms_tol"] and served
          and recycled and window_recycled and crossed and kept
          and impl == "kernel"
          and (n_mosaic > 0 or not on_tpu))
    log(f"check: {facts} -> {'ok' if ok else 'FAILED'}")
    return bool(ok), facts


def run(ctx):
    """``serve_backlog.run``, with this module's check."""
    _base.check = check
    return _base.run(ctx)
