"""Traffic kind ``train``: whole optimizer steps through ``ds.initialize`` /
``train_batch`` for the length of the window, on the mesh the traffic file
states.  Copied in shape from ``chip_smoke.py``'s ``train_phase``.

What counts is tokens trained per second: global batch x sequence x whole
steps, over the time those steps took.  The loop reads every step's loss,
``loss_read_lag`` steps after it dispatched that step (the traffic file's; 0
reads each loss before the next dispatch).  At a lag of 1 the host prepares
and dispatches step k+1 while the device runs step k, as ``train_batch`` is
built to be driven (it syncs only on the steps it prints), so the rate is the
device's and does not move with the load on the host's cores.  The window
opens with nothing in flight and closes when the last loss has been read.
"""

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu as ds
from deepspeed_tpu.monitor import gauges
from deepspeed_tpu.parallel import mesh as M
from deepspeed_tpu.runtime import compile_cache

from benchmark import harness, traffic_gen


def build(ctx, devices):
    t = ctx.traffic
    mesh = M.make_mesh(dict(t["mesh"]), devices=devices)
    model = harness.build_model(ctx.config, jnp.bfloat16,
                                max_positions=t["seq"], **t["model"])
    config = {
        "train_micro_batch_size_per_gpu": t["micro_batch"],
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        "gradient_clipping": t["gradient_clipping"],
        "bf16": {"enabled": True},
        "optimizer": t["optimizer"],
        "zero_optimization": {"stage": t["zero_stage"]},
        "compile_cache": {"dir": compile_cache.aot_dir()},
    }
    engine, _, _, _ = ds.initialize(config=config, model=model, mesh=mesh,
                                    rng_seed=harness.key_seed(ctx.seed))
    return model, engine, mesh, t["micro_batch"] * M.dp_world_size(mesh)


def state_share_by_device(state):
    """Share of params + master + optimizer state resident on each device,
    read from the arrays' addressable shards (not from their specs)."""
    leaves = jax.tree_util.tree_leaves(
        (state.params, state.master, state.opt_state))
    per = {}
    for leaf in leaves:
        for sh in leaf.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    total = sum(int(x.nbytes) for x in leaves)
    return {d: b / total for d, b in sorted(per.items())}, total


def run(ctx):
    spans, log, t = ctx.spans, ctx.log, ctx.traffic
    devices = jax.devices()[:ctx.cell["chips"]]
    model, engine, mesh, global_batch = build(ctx, devices)
    pool = traffic_gen.token_batches(t, ctx.seed, ctx.dims["vocab_size"],
                                     global_batch)
    n_batch = [0]

    def batches():
        while True:
            with spans.span("make_batch"):
                batch = pool[n_batch[0] % len(pool)]
                n_batch[0] += 1
            yield batch
    data = batches()

    lag = int(t["loss_read_lag"])
    in_flight, losses = collections.deque(), []

    def step(name, lag=lag):
        """Dispatch one step, then read losses down to ``lag`` in flight."""
        with spans.span(name):
            in_flight.append(engine.train_batch(data))
            while len(in_flight) > lag:
                losses.append(float(in_flight.popleft()))

    for _ in range(int(t["warmup_steps"])):
        step("warmup_step", lag=0)
    compiled_before = ctx.compile_count(engine.compile_report())
    setup_s = time.monotonic() - ctx.t_process_start

    trace = ctx.trace_window()
    t0 = time.monotonic()
    while True:
        now = time.monotonic() - t0
        if now >= ctx.seconds:
            break
        trace.poll(now)
        step("train_step")
    with spans.span("train_drain"):
        while in_flight:
            losses.append(float(in_flight.popleft()))
    t_end = time.monotonic()
    trace.stop()
    steps = len(losses) - int(t["warmup_steps"])
    in_window = ctx.compile_count(engine.compile_report()) - compiled_before
    device = harness.device_block(ctx.cell["chips"])
    caches = harness.cache_counters(ctx.compiles, engine.compile_report())
    tokens_per_step = global_batch * t["seq"]
    span_ms = [1e3 * d for d in spans.durations("train_step", t0)] or [0.0]
    log(f"{steps} steps of {global_batch} x {t['seq']} tokens in "
        f"{t_end - t0:.3f} s; each loss read {lag} step(s) behind; step spans "
        f"p50 {harness.percentile(span_ms, 50):.2f} p95 "
        f"{harness.percentile(span_ms, 95):.2f} max {max(span_ms):.2f} ms; "
        f"{in_window} compilation(s) inside the window")

    # ---- correctness, after the window
    n_mosaic = gauges.latest_executable(
        engine._jit_train_step).as_text().count("tpu_custom_call")
    shares, state_bytes = state_share_by_device(engine.state)
    engine.close()
    ref_loss = reference_loss(ctx, model, pool[0])
    rel = abs(losses[0] - ref_loss) / abs(ref_loss)
    on_tpu = jax.default_backend() == "tpu"
    want = 1.0 / mesh.size
    sharded = (t["zero_stage"] != 3
               or all(want <= s <= want * 1.2 for s in shares.values()))
    check = {"first_loss": losses[0], "reference_loss": ref_loss,
             "loss_rel_err": rel, "loss_rtol": t["check"]["loss_rtol"],
             "losses_finite": bool(np.isfinite(losses).all()),
             "mosaic_calls": n_mosaic, "state_bytes": state_bytes,
             "state_share_by_device": [round(s, 4) for s in shares.values()]}
    ok = (check["losses_finite"] and rel <= t["check"]["loss_rtol"]
          and len(shares) == mesh.size and sharded
          and (n_mosaic > 0 or not on_tpu))
    log(f"check: {check} -> {'ok' if ok else 'FAILED'}")

    return {
        "setup_s": setup_s,
        "end_to_end": {"train_tokens_per_s":
                       steps * tokens_per_step / (t_end - t0)},
        "attempted": steps, "failed": 0,
        "correct": bool(ok and in_window == 0 and steps > 0),
        "in_window_compiles": in_window,
        "counters": {"steps": steps, "in_window_compiles": in_window,
                     **caches},
        "facts": {"check": check, "window": (t0, t_end),
                  "tokens_per_step": tokens_per_step, "seq": t["seq"],
                  "global_batch": global_batch, "chips": mesh.size,
                  **ctx.dims, "matmul_params_per_token":
                  ctx.family.matmul_params_per_token(ctx.config),
                  "tokens_per_s": steps * tokens_per_step / (t_end - t0)},
        "device": device, "trace_path": trace.path,
        "trace_span": (trace.t_start, trace.t_stop),
    }


def reference_loss(ctx, model, batch):
    """The plain float32 loss of the first batch under the weights the
    engine started from (the same seed through the same ``model.init``,
    rounded to bfloat16 as the engine's compute copy is), a row at a time."""
    params = harness.seeded_weights(model, ctx.seed, jnp.bfloat16)
    reference = harness.reference(ctx.config)
    fn = jax.jit(lambda p, row: reference.loss(ctx.config, p, row))
    return float(np.mean([float(fn(params, jnp.asarray(row[None])))
                          for row in batch]))
