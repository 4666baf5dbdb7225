"""What the two serving runners share: building the server through the
user's entry points, warming the shapes the schedule will use, reducing the
per-request stamps, and the correctness check against the plain reference.

Copied in shape from ``chip_smoke.py``'s ``build_server`` / ``serve_phase``
(proved on the chip in PR 21) so that later PRs may change the smoke and not
the yardstick.
"""

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu as ds
from deepspeed_tpu.inference import Request, ServingEngine
from deepspeed_tpu.inference.serving import OK
from deepspeed_tpu.runtime import compile_cache

from benchmark import harness


def build(ctx):
    """Model, weights from the seed in one dispatch, ``ds.init_inference``,
    ``ServingEngine`` with the traffic file's ``serving`` block and every
    other field at its default."""
    dtype = getattr(jnp, ctx.traffic.get("dtype", "bfloat16"))
    model = harness.build_model(ctx.config, dtype)
    params = harness.seeded_weights(model, ctx.seed, dtype)
    eng = ds.init_inference(model, params=params, dtype=dtype,
                            compile_cache=compile_cache.aot_dir())
    srv = ServingEngine(engine=eng, config=dict(ctx.traffic["serving"]))
    return model, eng, srv


def to_request(item):
    return Request(tokens=item.prompt, max_new_tokens=item.new_tokens,
                   do_sample=item.do_sample, temperature=item.temperature,
                   seed=item.seed)


def warm_up(srv, items):
    """One throw-away request per distinct prefill bucket of the schedule
    (two new tokens each, so the decode step runs too), through ``submit``
    and ``step`` like any request; then the stats are reset."""
    block = srv.config.block_size
    seen = {}
    for it in items:
        seen.setdefault(-(-len(it.prompt) // block), it)
    for it in seen.values():
        srv.submit(to_request(dataclasses.replace(it, new_tokens=2)))
    while srv.step():
        pass
    # a sampled and a greedy request share one decode executable (the flag
    # is an operand), so nothing else is left to compile
    srv.reset_stats()
    settle_heap()
    return len(seen)


def settle_heap():
    """Collect what set-up left behind and move the survivors out of the
    collector's reach (``gc.freeze``), as a long-running server does once
    it is warm.  The collector stays on: it goes on collecting what the
    window allocates.  Without this, the one full collection that falls
    into a 40 s window walks the 415,000 objects of imports and traced
    programs and stops the host for 141 to 144 ms, once, at a moment that
    differs from run to run: the 16 requests in flight carry it into
    ``tpot_ms_p95`` (10.01 to 10.27 ms with it, 9.63 to 9.70 without, three
    runs each, alternating; my chip runs, PR 27)."""
    gc.collect()
    gc.freeze()


class OpenLoopFeeder:
    """Hands over each request when it is due, whatever the server does."""

    def __init__(self, items):
        self.items = items
        self.i = 0

    def take(self, now, srv):
        start = self.i
        while self.i < len(self.items) and self.items[self.i].due <= now:
            self.i += 1
        return self.items[start:self.i]

    def next_due(self):
        return self.items[self.i].due if self.i < len(self.items) else None

    def unsent(self):
        return self.items[self.i:]


class BacklogFeeder:
    """A closed backlog: tops the queue up to ``depth`` from a pool of
    requests, round and round, until the window is over."""

    def __init__(self, pool, depth, seconds):
        self.pool, self.depth, self.seconds = pool, depth, seconds
        self.n = 0

    def take(self, now, srv):
        if now >= self.seconds:
            return []
        out = []
        for _ in range(max(0, self.depth - len(srv.queue))):
            out.append(dataclasses.replace(
                self.pool[self.n % len(self.pool)], due=now))
            self.n += 1
        return out

    def next_due(self):
        return None

    def unsent(self):
        return []


def run_window(ctx, srv, eng, feeder):
    """The measured window and the bounded drain after it, on one thread:
    submit what the feeder hands over, take one scheduler step, repeat.
    With ``--trace 1`` the profiler covers the last seconds of the window
    and is stopped when the window is over."""
    spans, log = ctx.spans, ctx.log
    compiled_before = ctx.compile_count(eng.compile_report())
    trace = ctx.trace_window()
    issued, lateness, live_tokens = [], [], []
    limit = ctx.seconds + float(ctx.traffic["drain_limit_s"])
    t0 = time.monotonic()
    while True:
        now = time.monotonic() - t0
        trace.poll(now)
        if now >= ctx.seconds:
            # the stall of writing the trace falls after the window, and is
            # the benchmark's own: the backlog gets its whole time to drain
            limit += trace.stop()
        batch = feeder.take(now, srv)
        if batch:
            with spans.span("submit"):
                for item in batch:
                    issued.append((srv.submit(to_request(item)), item))
                    lateness.append(now - item.due)
        tokens_live = int(srv._lengths.sum())
        t_step = time.monotonic()
        with spans.span("step"):
            more = srv.step()
        if more:
            live_tokens.append((t_step, tokens_live))
        else:
            spans.rows.pop()             # an idle poll is not a step
            nxt = feeder.next_due()
            if nxt is None:
                if now >= ctx.seconds or not batch:
                    break
                continue
            with spans.span("wait_for_arrival"):
                time.sleep(max(0.0, nxt - (time.monotonic() - t0)))
        if now > limit:
            log(f"drain limit reached with {srv.stats()['pending']} pending")
            break
    t_end = time.monotonic()
    trace.stop()
    in_window = ctx.compile_count(eng.compile_report()) - compiled_before
    late = np.asarray(lateness or [0.0])
    drain_s = t_end - t0 - ctx.seconds
    log(f"generator lateness: median {np.median(late) * 1e3:.2f} ms, max "
        f"{late.max() * 1e3:.2f} ms; drained {drain_s:.2f} s after the "
        f"window; {in_window} compilation(s) inside it")
    rows = request_rows(
        srv, issued + [(None, it) for it in feeder.unsent()], t0, t_end)
    return {
        "rows": rows, "in_window_compiles": in_window,
        "device": harness.device_block(ctx.cell["chips"]),
        "counters": {**counters(srv), "in_window_compiles": in_window,
                     **harness.cache_counters(ctx.compiles,
                                              eng.compile_report())},
        "facts": {**shape_facts(ctx, srv), "live_tokens": live_tokens,
                  "window": (t0, t0 + ctx.seconds), "drain_s": drain_s,
                  "lateness_ms": {"median": float(np.median(late) * 1e3),
                                  "max": float(late.max() * 1e3)}},
        "trace_path": trace.path,
        "trace_span": (trace.t_start, trace.t_stop),
    }


COUNTERS = ("completed", "decode_steps", "generated_tokens",
            "state_reused_steps", "state_uploads")


def counters(srv):
    """The engine's own counts since the warm-up's ``reset_stats()``; one a
    program does not keep is left out, and its metric with it."""
    st = srv.stats()
    return {name: st[name] for name in COUNTERS if name in st}


def request_rows(srv, issued, t0, t_end):
    """One row per issued request from ``ServingEngine.results``: whether it
    finished ``ok`` with all its tokens, and its stamps relative to when it
    was DUE.  ``issued``: ``(uid, item)`` pairs."""
    rows = []
    for uid, item in issued:
        rec = srv.results.get(uid)
        done = (rec is not None and rec["outcome"] == OK
                and rec["tokens"] is not None
                and len(rec["tokens"]) == item.new_tokens)
        due = t0 + item.due
        row = {"ok": done, "prompt": len(item.prompt), "due_s": item.due,
               "generated": item.new_tokens if done else 0}
        if done:
            n = len(rec["tokens"])
            row["ttft_ms"] = (rec["t_first"] - due) * 1e3
            row["tpot_ms"] = ((rec["t_done"] - rec["t_first"]) * 1e3
                              / max(1, n - 1))
            row["done_s"] = rec["t_done"] - t0
        else:
            # a request that failed or was cut misses every latency: it
            # enters the tails at the time it had waited when we gave up
            row["ttft_ms"] = row["tpot_ms"] = (t_end - due) * 1e3
            row["done_s"] = float("inf")
        rows.append(row)
    return rows


def tokens_per_s(rows, seconds):
    """``(rate, requests)``: prompt tokens prefilled plus tokens generated
    of the requests COMPLETED inside the window, per second of the window,
    and how many those were."""
    in_time = [r for r in rows if r["done_s"] <= seconds]
    return (sum(r["prompt"] + r["generated"] for r in in_time) / seconds,
            len(in_time))


def check_picks(items, n):
    """The ``n`` requests whose prompts the check seats: spread over the
    schedule's prompt lengths from the 10th to the 95th percentile."""
    by_len = sorted(items, key=lambda it: len(it.prompt))
    return [by_len[int(q * (len(by_len) - 1))]
            for q in np.linspace(0.1, 0.95, n)]


def padded_rows(rows):
    """Token rows of unequal length as one ``(n, longest)`` int32 array,
    padded on the right, and each row's last position."""
    padded = np.zeros((len(rows), max(len(r) for r in rows)), np.int32)
    for i, r in enumerate(rows):
        padded[i, :len(r)] = r
    return padded, np.array([len(r) - 1 for r in rows], np.int32)


def logit_errors(got, ref):
    """The two numbers the check compares: the largest difference over the
    largest reference logit, and the root mean square of the differences
    over that of the reference logits (every row and every vocabulary
    entry: far steadier from seed to seed than a maximum)."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return (float(np.abs(got - ref).max() / np.abs(ref).max()),
            float(np.sqrt(np.mean((got - ref) ** 2) / np.mean(ref ** 2))))


def check(ctx, model, eng, srv, items):
    """Correctness, after the window.  A live decode step's logits through
    the paged kernel against the plain float32 reference's full forward over
    the same slots' tokens; all requests ``ok`` with their tokens; every
    block recycled; the Mosaic kernel in the decode executable.  Returns
    ``(ok, facts)``; the reference runs after the server has given its pool
    back, so the two never share the chip's memory."""
    spec = ctx.traffic["check"]
    log = ctx.log
    uids = [srv.submit(to_request(dataclasses.replace(
        it, new_tokens=spec["steps"] + 4, do_sample=False)))
        for it in check_picks(items, spec["slots"])]
    for _ in range(spec["steps"]):
        srv.step()

    # the operands of the NEXT decode step, as the engine would send them
    params, pool, tables, lengths, toks = srv._decode_args()[:5]
    with jax.set_mesh(eng.mesh):
        step = jax.jit(lambda p, t, pl, tb, ln: srv.model.decode_step_paged(
            srv._deq(p), t, pl, tb, ln)[0])
        kernel = np.asarray(step(params, toks, pool, tables, lengths),
                            np.float32)
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

    # the reference: float32, full forward, rows padded on the right
    padded, last = padded_rows(histories)
    reference = harness.reference(ctx.config)
    ref = np.asarray(jax.jit(
        lambda p, t, pos: reference.logits_at(ctx.config, p, t, pos))(
        eng.params, jnp.asarray(padded), jnp.asarray(last)), np.float32)
    got = kernel[live]
    err, rms = logit_errors(got, ref)
    agree = int((got.argmax(-1) == ref.argmax(-1)).sum())
    on_tpu = jax.default_backend() == "tpu"
    facts = {"logit_err": err, "logit_tol": spec["logit_tol"],
             "logit_rms_err": rms, "logit_rms_tol": spec.get("logit_rms_tol"),
             "argmax_equal": f"{agree}/{len(live)}", "served": served,
             "blocks_recycled": recycled, "mosaic_calls": n_mosaic,
             "paged_impl": impl, "reference_rows": [len(h) for h in histories]}
    ok = (np.isfinite(got).all() and err <= spec["logit_tol"]
          and rms <= spec.get("logit_rms_tol", float("inf")) and served
          and recycled and impl == "kernel" and (n_mosaic > 0 or not on_tpu))
    log(f"check: {facts} -> {'ok' if ok else 'FAILED'}")
    return bool(ok), facts


def shape_facts(ctx, srv):
    """Sizes the per-layer readers price kernels with: the family's
    ``dims`` and the pool's element size."""
    return {**ctx.dims,
            "kv_bytes_per_element": 2 if srv.config.kv_bits == 16 else 1}
