"""``ds_mem``: the predictive memory capacity model.

ZeRO's memory layout is a *closed-form* function of (shape, stage,
dtypes, mesh) — arXiv 1910.02054 tabulates it, and ZeRO-Infinity's whole
thesis (arXiv 2104.07857) is engineering against a modeled memory wall.
This module puts that model in the runtime instead of hand arithmetic
over MAXPARAMS.json:

- **closed-form per-subsystem byte formulas** (:func:`train_device_plan`
  for on-device ZeRO state, :func:`host_offload_plan` for the host
  offload tier, :func:`serving_plan` for the paged-KV serving side),
  keyed by the same subsystem names the runtime memory ledger
  (``monitor/memory_ledger.py``) attributes measured bytes to — model
  and measurement cannot drift apart in vocabulary;
- **a fitted host residual**: the MAXPARAMS campaign proved the host RSS
  carries a client term the formulas do not cover (runtime transfer
  buffering + allocator slack, ~linear in model size — the 6.7B
  post-mortem's "~23 GB client term").  :func:`fit_host_residual`
  least-squares fits ``residual_gb ≈ c0 + c1·params_b`` from the
  committed rungs, so :func:`replay_maxparams` reproduces the recorded
  HWMs (acceptance: 1.3B within ±10%) and :func:`max_params_b` answers
  ROADMAP #4's capacity question *before* anything allocates — the model
  must bracket the measured ceiling (2.65B fits, 6.7B does not);
- **serving capacity** (:func:`max_streams`): how many concurrent
  streams a given HBM budget admits at a serving configuration —
  the same math ``ServingEngine`` admission enforces, answerable
  offline;
- **the OOM verdict** (:func:`verdict_from_snapshot`): given a ledger
  snapshot, which subsystem blew the budget and which knob buys the
  needed headroom — what the RESOURCE_EXHAUSTED forensic dumps embed.

CLI (``bin/ds_mem``): ``ds_mem <run_dir>`` renders a monitor stream's
``mem`` events; ``--replay MAXPARAMS.json`` runs the acceptance replay;
``--max-params`` / ``--max-streams`` answer the capacity questions.
"""

import argparse
import json
import os
import sys

GIB = float(2 ** 30)

# bytes per parameter by subsystem (the MAXPARAMS.json
# ram_arithmetic_bytes_per_param table, made executable)
FP32_BYTES = 4
BF16_BYTES = 2
ADAM_MOMENTS_PER_PARAM = 2 * FP32_BYTES      # exp_avg + exp_avg_sq

# moments stay on host RAM up to this size; the MAXPARAMS criterion
# moved them to the NVMe tier above it (and the 16-bit payload image
# with them — the r5a fix)
CPU_MOMENTS_MAX_PARAMS_B = 2.7

# which knob buys headroom, per over-budget subsystem (the OOM verdict's
# advice column; names match monitor/memory_ledger.py)
KNOB_ADVICE = {
    "params": "raise zero_optimization.stage (shard params over fsdp), "
              "stream them (offload_param), or quantize the weights "
              "(int8 serving)",
    "master_fp32": "zero stage >= 1 shards the master; offload_optimizer "
                   "moves it to host RAM",
    "opt_moments": "offload_optimizer.device=cpu|nvme moves the moments "
                   "off-device; nvme tier frees host RAM too",
    "ef_state": "comms_compression off (or hierarchical:false) drops the "
                "qgZ error-feedback state",
    "compiled_programs": "fewer live signatures: pin batch shapes / lower "
                         "prefill bucket count (smaller max_seq)",
    "paged_kv_pool": "kv_bits=8 halves pool bytes; shrink num_blocks / "
                     "batch_slots / block_size; serving.prefix_cache "
                     "shares common-prefix blocks (admission then "
                     "charges unique blocks only)",
    "host_master_fp32": "move the fp32 master to the NVMe swapper tier "
                        "(ROADMAP #4; runtime/swap_tensor/)",
    "host_grad_landing_fp32": "data_types.grad_accum_dtype=bf16 halves "
                              "the gradient landing buffer",
    "host_payload_image_16bit": "offload_param.device=nvme drops the RAM "
                                "image (drop_payload)",
    "host_adam_moments": "offload_optimizer.device=nvme moves the moments "
                         "to disk",
    "h2d_staging": "lower micro batch (bench.plan_micro_backoff) or the "
                   "uploader chunk_bytes",
    "nvme_swap_buffers": "smaller aio buffer_count/buffer_numel",
    "compile_cache": "compile_cache.max_entries LRU bound",
    "residual": "the fitted client term scales with model bytes: smaller "
                "model per host, or more hosts (ds_mem --max-params "
                "prices it)",
}


def _ceil_div(a, b):
    return -(-int(a) // int(b))


# ------------------------------------------------------------ device formulas

def train_device_plan(num_params, *, zero_stage, n_devices=1, fsdp=1,
                      compute_bytes=2, needs_master=True,
                      grad_accum_bytes=None) -> dict:
    """Per-subsystem **device** bytes for one ZeRO training state, summed
    over this process's devices — the same view
    ``memory_ledger.tree_device_bytes`` measures, so the test can assert
    plan == ledger leaf-for-leaf.

    Layout rules (``zero/partition.py``, arXiv 1910.02054): a subsystem
    sharded over the fsdp extent lives ``n_devices / fsdp`` times across
    the process (once per fsdp shard, replicated over the other axes); a
    replicated one lives ``n_devices`` times.  Params shard at stage
    >= 3, master + moments at stage >= 1; gradients are transient
    (inside-step temps, priced by ``preflight_memory``'s temp term, not
    resident state)."""
    P = int(num_params)
    n = max(1, int(n_devices))
    fsdp = max(1, min(int(fsdp), n))
    sharded = n // fsdp            # copies of an fsdp-sharded subsystem
    params_copies = sharded if zero_stage >= 3 else n
    opt_copies = sharded if zero_stage >= 1 else n
    plan = {
        "params": P * compute_bytes * params_copies,
        "master_fp32": (P * FP32_BYTES * opt_copies) if needs_master
        else 0,
        "opt_moments": P * ADAM_MOMENTS_PER_PARAM * opt_copies,
    }
    plan["grads_transient"] = P * (grad_accum_bytes or compute_bytes) \
        * (sharded if zero_stage >= 2 else n)
    plan["resident_bytes"] = (plan["params"] + plan["master_fp32"]
                              + plan["opt_moments"])
    return plan


def host_offload_plan(params_b, *, moments_tier="cpu",
                      param_tier=None, grad_accum_bytes=FP32_BYTES) -> dict:
    """Per-subsystem **host RSS** bytes of the offload tier for a model
    of ``params_b`` billion parameters — the executable form of
    MAXPARAMS.json's ``ram_arithmetic_bytes_per_param`` table.
    ``param_tier`` defaults to the campaign's rule: the 16-bit payload
    image rides host RAM while the moments do (both moved to NVMe
    together at the 6.7B rung, the r5a fix)."""
    if param_tier is None:
        param_tier = moments_tier
    P = params_b * 1e9
    plan = {
        "host_master_fp32": P * FP32_BYTES,
        "host_grad_landing_fp32": P * grad_accum_bytes,
        "host_payload_image_16bit": (P * BF16_BYTES
                                     if param_tier == "cpu" else 0.0),
        "host_adam_moments": (P * ADAM_MOMENTS_PER_PARAM
                              if moments_tier == "cpu" else 0.0),
    }
    plan["plan_bytes"] = sum(plan.values())
    plan["plan_gb"] = plan["plan_bytes"] / GIB
    plan["moments_tier"] = moments_tier
    plan["param_tier"] = param_tier
    return plan


# ------------------------------------------------------- fitted host residual

def fit_host_residual(samples):
    """Least-squares fit of the UNEXPLAINED host term.

    ``samples``: ``[(params_b, measured_rss_gb, plan_gb), ...]`` —
    returns ``{"c0_gb", "c1_gb_per_b", "points"}`` with
    ``residual_gb(params_b) ≈ c0 + c1·params_b``.  The residual is the
    runtime client's transfer buffering + allocator slack — measured to
    scale with model bytes and insensitive to streaming discipline
    (MAXPARAMS ``analysis_6p7b_attempts``), which is exactly what makes
    it fittable."""
    pts = [(float(x), float(m) - float(p)) for x, m, p in samples]
    n = len(pts)
    if n == 0:
        return {"c0_gb": 0.0, "c1_gb_per_b": 0.0, "points": []}
    if n == 1:
        return {"c0_gb": pts[0][1], "c1_gb_per_b": 0.0, "points": pts}
    sx = sum(x for x, _ in pts)
    sy = sum(y for _, y in pts)
    sxx = sum(x * x for x, _ in pts)
    sxy = sum(x * y for x, y in pts)
    denom = n * sxx - sx * sx
    if abs(denom) < 1e-12:
        return {"c0_gb": sy / n, "c1_gb_per_b": 0.0, "points": pts}
    c1 = (n * sxy - sx * sy) / denom
    c0 = (sy - c1 * sx) / n
    return {"c0_gb": c0, "c1_gb_per_b": c1, "points": pts}


def predicted_rss_gb(params_b, fit, *, moments_tier=None,
                     grad_accum_bytes=FP32_BYTES) -> float:
    """Plan + fitted residual for one rung (``moments_tier=None`` →
    the campaign's tier rule: cpu up to 2.7B, nvme above)."""
    if moments_tier is None:
        moments_tier = ("cpu" if params_b <= CPU_MOMENTS_MAX_PARAMS_B
                        else "nvme")
    plan = host_offload_plan(params_b, moments_tier=moments_tier,
                             grad_accum_bytes=grad_accum_bytes)
    return (plan["plan_gb"] + fit["c0_gb"]
            + fit["c1_gb_per_b"] * params_b)


def max_params_b(fit, host_ram_gb, *, grad_accum_bytes=FP32_BYTES,
                 step_b=0.01) -> float:
    """Largest ``params_b`` whose predicted host RSS fits ``host_ram_gb``
    under the tier rule — the ROADMAP #4 question, answered by the model
    instead of by OOM.  Scanned at ``step_b`` granularity (the predicted
    curve has one tier discontinuity; a closed-form solve per tier works
    too, the scan is simply immune to tier-boundary edge cases)."""
    x, best = step_b, 0.0
    while x <= 1000.0:
        if predicted_rss_gb(x, fit,
                            grad_accum_bytes=grad_accum_bytes) \
                <= host_ram_gb:
            best = x
        elif best and x > CPU_MOMENTS_MAX_PARAMS_B:
            break          # past the tier switch and over budget: done
        x = round(x + step_b, 10)
    return round(best, 3)


# ------------------------------------------------------------ MAXPARAMS replay

# acceptance tolerance for the replay (ISSUE 13): predicted vs recorded
# host-RSS HWM per rung
REPLAY_TOLERANCE = 0.10


def _rung_samples(doc):
    """(name, params_b, measured_rss_gb, moments_tier) per recorded rung
    — including the FAILED rung: its parent-observed HWM at the kill is
    a real (params, rss) sample (the process reached it), and the fit
    needs the large-model end of the curve."""
    out = []
    for name, entry in (doc.get("per_size") or {}).items():
        params_b = entry.get("params_b")
        if params_b is None:
            try:
                params_b = float(name.rstrip("bB"))
            except ValueError:
                continue
        measured = entry.get("rss_hwm_gb",
                             entry.get("parent_observed_rss_hwm_gb"))
        if measured is None:
            continue
        tier = entry.get("moments_tier")
        if tier is None:
            prog = entry.get("progress_before_failure") or []
            tier = (prog[0].get("moments") if prog else None) or "nvme"
        out.append((name, float(params_b), float(measured), tier))
    return sorted(out, key=lambda r: r[1])


def replay_maxparams(doc, *, tolerance=REPLAY_TOLERANCE) -> dict:
    """Fit the residual from a MAXPARAMS document's rungs, then replay:
    per-rung predicted vs recorded HWM (±``tolerance``), per-rung
    fits-the-host verdicts, and the model's own max-params answer.  The
    acceptance contract (tests/test_memory.py): the 1.3B rung reproduces
    within ±10% and the model brackets the measured ceiling — the
    largest committed rung fits, the recorded OOM rung does not."""
    host_ram_gb = float(doc.get("host_ram_gb", 0)) or None
    rungs = _rung_samples(doc)
    samples = []
    for name, params_b, measured, tier in rungs:
        plan = host_offload_plan(params_b, moments_tier=tier)
        samples.append((params_b, measured, plan["plan_gb"]))
    fit = fit_host_residual(samples)
    rows = []
    for (name, params_b, measured, tier), (_, _, plan_gb) in zip(rungs,
                                                                 samples):
        pred = (plan_gb + fit["c0_gb"] + fit["c1_gb_per_b"] * params_b)
        err = (pred - measured) / measured if measured else 0.0
        rows.append({
            "rung": name, "params_b": params_b, "moments_tier": tier,
            "plan_gb": round(plan_gb, 2),
            "predicted_rss_gb": round(pred, 2),
            "measured_rss_gb": measured,
            "err_pct": round(100.0 * err, 1),
            "within_tolerance": abs(err) <= tolerance,
            "fits_host": (pred <= host_ram_gb) if host_ram_gb else None,
        })
    out = {
        "fit": {"c0_gb": round(fit["c0_gb"], 3),
                "c1_gb_per_b": round(fit["c1_gb_per_b"], 3)},
        "host_ram_gb": host_ram_gb,
        "rungs": rows,
        "tolerance": tolerance,
        "all_within_tolerance": all(r["within_tolerance"] for r in rows),
    }
    if host_ram_gb:
        out["max_params_b"] = max_params_b(fit, host_ram_gb)
        out["max_params_b_bf16_grad_accum"] = max_params_b(
            fit, host_ram_gb, grad_accum_bytes=BF16_BYTES)
    return out


# ------------------------------------------------------------ serving capacity

def request_unique_blocks(*, prompt_tokens, max_new_tokens, block_size,
                          max_seq=None, shared_prefix_tokens=0) -> dict:
    """THE per-request block math: a stream's cost AT ITS END, as
    ``ServingEngine.capacity``, ``ds_mem --max-streams`` and the memory
    ledger's shared/unique split all call it, so the three can never
    disagree (regression-pinned in tests/test_prefix_cache.py).  Serving
    admission charges the same total over a stream's life
    (``ServingEngine._plan``: ``blocks_needed`` of prompt + generation less
    the shared blocks) and takes it at once only where a seat is the whole
    life; elsewhere a stream holds what it has written.

    ``total_blocks`` is the classic cost (``paged_kv.blocks_needed`` of
    prompt+generation).  ``shared_blocks`` is how many leading blocks a
    prefix-cache hit of ``shared_prefix_tokens`` covers, clamped to
    ``(prompt_tokens - 1) // block_size`` — the final prompt token (and
    every position the decode step will WRITE) must land in a PRIVATE
    block, the same clamp ``ServingEngine._prefix_match`` applies.
    ``unique_blocks`` is what admission actually charges."""
    bs = max(1, int(block_size))
    prompt = max(1, int(prompt_tokens))
    total_tokens = prompt + int(max_new_tokens)
    if max_seq:
        total_tokens = min(total_tokens, int(max_seq))
    total = max(1, _ceil_div(total_tokens, bs))   # = pk.blocks_needed
    shared = max(0, min(int(shared_prefix_tokens) // bs,
                        (prompt - 1) // bs, total))
    return {"total_blocks": total, "shared_blocks": shared,
            "unique_blocks": total - shared}


def serving_plan(*, n_layer, n_head, head_dim, max_seq, block_size=16,
                 kv_bits=16, quant_block=64, batch_slots=8, num_blocks=0,
                 max_new_tokens=64, weight_bytes=0, prompt_tokens=None,
                 shared_prefix_tokens=0, kv_layers=None,
                 kv_row_bytes=None) -> dict:
    """Closed-form serving memory plan mirroring ``paged_kv.init_pool``'s
    arithmetic exactly (tested equal to ``pool_bytes`` of a real pool):
    per-block bytes, total pool bytes for the configuration's block
    count, and the per-request block cost at the default generation
    length (the ``ServingEngine.capacity()`` admission math).

    ``kv_layers`` is the model config's: the layer-applications that keep
    K/V for a token, where that is not ``n_layer`` (a hybrid's attention
    layers alone; a looped model's loops x layers).  ``kv_row_bytes`` is the
    bytes a token keeps in ONE of them as the pool stores it, where that is
    not K and V of ``n_head x head_dim`` cells (a latent row,
    ``paged_kv.latent_row_bytes``: 1,280 for 512 + 64 values in whole
    128-lane tiles)."""
    if kv_layers is None:
        kv_layers = n_layer
    nb_max = _ceil_div(max_seq, block_size)
    if not num_blocks:
        num_blocks = 1 + batch_slots * nb_max
    cell = n_head * head_dim
    if kv_row_bytes is not None:
        per_tok = int(kv_row_bytes)
    elif kv_bits == 8:
        # the quantizer's pick_block rule (runtime/comm/quantized.py):
        # LARGEST DIVISOR of head_dim <= quant_block — re-stated here
        # (not a halving loop: head_dim=96, qb=64 picks 48, not 32) so
        # the plan mirrors init_pool byte-for-byte on non-power-of-2
        # head dims too (tested against the real pool)
        qb = min(int(quant_block), int(head_dim))
        while qb > 1 and head_dim % qb:
            qb -= 1
        per_tok = 2 * (cell * 1 + (cell // qb) * FP32_BYTES)   # k+v, +scales
    else:
        per_tok = 2 * cell * BF16_BYTES
    per_block = kv_layers * block_size * per_tok
    # the unified per-request math (request_unique_blocks): the default
    # prompt (one block) reproduces the classic
    # ceil(min(max_seq, block_size + max_new) / block_size) exactly
    ub = request_unique_blocks(
        prompt_tokens=(block_size if prompt_tokens is None
                       else prompt_tokens),
        max_new_tokens=max_new_tokens, block_size=block_size,
        max_seq=max_seq, shared_prefix_tokens=shared_prefix_tokens)
    return {
        "paged_kv_pool": per_block * num_blocks,
        "per_block_bytes": per_block,
        "num_blocks": num_blocks,
        "nb_max": nb_max,
        "blocks_per_request": ub["total_blocks"],
        "shared_prefix_blocks": ub["shared_blocks"],
        "unique_blocks_per_request": ub["unique_blocks"],
        "weight_bytes": int(weight_bytes),
    }


def max_streams(plan: dict, budget_bytes, *, safety=0.92,
                workspace_bytes=0) -> dict:
    """Concurrent-stream bound for an HBM budget: blocks the budget can
    hold after weights + workspace, divided by the per-request block
    cost, answerable before anything allocates (the serving twin of
    :func:`max_params_b`).  A LOWER bound on what ``ServingEngine``
    seats: the cost is a stream's at its END, and admission by the pool's
    timeline (docs/serving.md#capacity-math--admission-control) charges a
    stream the blocks it has written, so streams at different points of
    their answers fit where as many at their ends would not.  It is the
    number where a seat is still the whole life (the prefix cache, a
    snapshot cadence or a transfer queue armed)."""
    usable = budget_bytes * safety - plan["weight_bytes"] - workspace_bytes
    blocks = max(0, int(usable // plan["per_block_bytes"]) - 1)  # scratch
    # prefix sharing amortizes the shared head ONCE across every stream;
    # each stream then costs its UNIQUE blocks (the same
    # request_unique_blocks split serving admission charges).  With no
    # sharing, unique == blocks_per_request and this is the classic bound.
    shared = int(plan.get("shared_prefix_blocks", 0))
    unique = int(plan.get("unique_blocks_per_request",
                          plan["blocks_per_request"]))
    streams = max(0, blocks - shared) // max(1, unique)
    return {"budget_bytes": int(budget_bytes), "safety": safety,
            "usable_pool_bytes": max(0, int(usable)),
            "allocatable_blocks": blocks,
            "blocks_per_request": plan["blocks_per_request"],
            "shared_prefix_blocks": shared,
            "unique_blocks_per_request": unique,
            "max_streams": streams}


# ---------------------------------------------------------------- OOM verdict

def verdict_from_snapshot(snapshot: dict, budget_bytes=None,
                          space=None) -> dict:
    """Which subsystem blew the budget, and which knob buys headroom.

    ``space`` names the exhausted space when the caller knows it (an
    allocator RESOURCE_EXHAUSTED / serving preflight is ``"hbm"``, a
    SIGKILL-by-oom-killer is ``"host"``); unset, the verdict picks the
    space with the larger attributed total.  Within the space it names
    the LARGEST subsystem, falling back to the residual itself when it
    out-weighs every named term — the honest answer the 6.7B campaign
    needed four runs to reach."""
    spaces = {}
    for sp in ("hbm", "host"):
        entries = dict(snapshot.get(sp) or {})
        resid = snapshot.get(f"{sp}_residual_bytes")
        if resid and resid > 0:
            entries["residual"] = resid
        if entries:
            spaces[sp] = entries
    if space is not None and space not in spaces:
        space = None
    if not spaces:
        return {"over_budget_subsystem": "unknown", "space": None,
                "advice": "no ledger attribution available"}
    if space is None:
        space = max(spaces, key=lambda s: sum(spaces[s].values()))
    sub = max(spaces[space], key=spaces[space].get)
    nbytes = spaces[space][sub]
    out = {
        "over_budget_subsystem": sub,
        "space": space,
        "bytes": int(nbytes),
        "gb": round(nbytes / GIB, 2),
        "advice": KNOB_ADVICE.get(sub, "see docs/monitoring.md"
                                       "#memory-explainability"),
    }
    if budget_bytes:
        out["budget_bytes"] = int(budget_bytes)
        total = sum(spaces[space].values())
        out["space_attributed_bytes"] = int(total)
        out["over_budget_bytes"] = int(max(0, total - budget_bytes))
    return out


# --------------------------------------------------------------- stream + CLI

def fold_mem_stream(events) -> dict:
    """Newest ``mem`` event per role from a parsed monitor stream (plus
    how many were seen) — what ``ds_mem <run_dir>`` renders."""
    latest = {}
    count = 0
    for e in events:
        if e.kind == "mem":
            count += 1
            latest[e.fields.get("role", e.name)] = dict(e.fields,
                                                        step=e.step)
    return {"latest": latest, "count": count}


def _fmt_gb(nbytes):
    return f"{nbytes / GIB:.2f} GB"


def render_ledger(folded: dict, source: str) -> str:
    lines = [f"ds_mem — memory ledger over {source}", ""]
    if not folded["count"]:
        lines.append(
            "no `mem` events in the stream — run with the monitor "
            "enabled on a build that emits the memory ledger "
            "(docs/monitoring.md#memory-explainability)")
        return "\n".join(lines)
    for role, snap in sorted(folded["latest"].items()):
        lines.append(f"[{role}] step {snap.get('step')}")
        for space in ("hbm", "host", "disk"):
            entries = snap.get(space) or {}
            if not entries:
                continue
            total = sum(entries.values())
            parts = ", ".join(
                f"{k} {_fmt_gb(v)}" for k, v in
                sorted(entries.items(), key=lambda kv: -kv[1]))
            lines.append(f"  {space}: {_fmt_gb(total)} attributed "
                         f"({parts})")
            for k, det in sorted(((snap.get("detail") or {})
                                  .get(space) or {}).items()):
                lines.append("    " + k + ": " + ", ".join(
                    f"{dk}={dv}" for dk, dv in sorted(det.items())))
        if snap.get("host_residual_bytes") is not None:
            lines.append(
                f"  host residual: "
                f"{_fmt_gb(snap['host_residual_bytes'])} "
                f"(RSS {_fmt_gb(snap.get('host_rss_bytes', 0))} − "
                f"attributed "
                f"{_fmt_gb(snap.get('host_attributed_bytes', 0))})")
        lines.append(f"  host RSS HWM: {snap.get('rss_hwm_gb')} GB")
        for ph in snap.get("phases") or ():
            lines.append(
                f"    phase {ph['phase']:>13}: HWM "
                f"{_fmt_gb(ph['rss_hwm_bytes'])} "
                f"(+{_fmt_gb(ph['delta_bytes'])})")
        v = verdict_from_snapshot(snap)
        lines.append(f"  largest term: {v['over_budget_subsystem']} "
                     f"[{v['space']}] {v.get('gb')} GB — knob: "
                     f"{v['advice']}")
        lines.append("")
    return "\n".join(lines)


def render_replay(rep: dict) -> str:
    lines = ["ds_mem — MAXPARAMS replay (predictive host-RSS model)", ""]
    f = rep["fit"]
    lines.append(f"fitted residual: {f['c0_gb']:+.2f} GB "
                 f"{f['c1_gb_per_b']:+.2f} GB per B params "
                 "(the runtime client term the formulas do not cover)")
    lines.append(f"{'rung':>8} {'tier':>6} {'plan':>8} {'predicted':>10} "
                 f"{'measured':>9} {'err':>7}  fits host?")
    for r in rep["rungs"]:
        fits = {True: "yes", False: "NO", None: "-"}[r["fits_host"]]
        lines.append(
            f"{r['rung']:>8} {r['moments_tier']:>6} "
            f"{r['plan_gb']:>7.1f}G {r['predicted_rss_gb']:>9.1f}G "
            f"{r['measured_rss_gb']:>8.1f}G {r['err_pct']:>+6.1f}%  "
            f"{fits}")
    tol = int(rep["tolerance"] * 100)
    lines.append(
        f"replay: {'ALL rungs' if rep['all_within_tolerance'] else 'NOT all'}"
        f" within ±{tol}% of the recorded HWM")
    if rep.get("max_params_b"):
        lines.append(
            f"predicted ceiling on the {rep['host_ram_gb']:.0f} GB host: "
            f"{rep['max_params_b']} B params "
            f"({rep['max_params_b_bf16_grad_accum']} B with "
            "grad_accum_dtype=bf16)")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="ds_mem",
        description="memory explainability: render a run's memory "
                    "ledger, replay MAXPARAMS.json through the capacity "
                    "model, or answer max-params / max-streams "
                    "(docs/monitoring.md#memory-explainability)")
    ap.add_argument("run", nargs="?", default=None,
                    help="monitor run dir (or an events.jsonl path) "
                         "whose `mem` events to render")
    ap.add_argument("--replay", metavar="RUNGS_JSON", default=None,
                    help="fit + replay a recorded max-params document "
                         "(tests/data/maxparams_rungs.json holds the "
                         "rungs recorded so far)")
    ap.add_argument("--max-params", action="store_true",
                    help="predict the largest trainable params for "
                         "--host-ram-gb (fit from --replay)")
    ap.add_argument("--host-ram-gb", type=float, default=None)
    ap.add_argument("--max-streams", action="store_true",
                    help="serving capacity: concurrent streams an HBM "
                         "budget admits at the given model/config dims")
    ap.add_argument("--budget-gb", type=float, default=16.0,
                    help="HBM budget for --max-streams (default 16, "
                         "v5e-class)")
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--kv-layers", type=int, default=None,
                    help="layer-applications that keep K/V for a token, "
                         "where not --layers (the model config's "
                         "kv_layers: a looped model's loops x layers)")
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--max-seq", type=int, default=1024)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--kv-bits", type=int, default=16, choices=(8, 16))
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--prompt-tokens", type=int, default=None,
                    help="per-request prompt length for --max-streams "
                         "(default: one block)")
    ap.add_argument("--shared-prefix-tokens", type=int, default=0,
                    help="tokens of common prompt prefix served from the "
                         "radix cache (serving.prefix_cache): the shared "
                         "head is charged ONCE, each stream pays only "
                         "its unique blocks")
    ap.add_argument("--weight-gb", type=float, default=0.0,
                    help="resident weight bytes to subtract from the "
                         "--max-streams budget")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.max_params and not args.replay:
        ap.error("--max-params fits from recorded rungs: pass --replay "
                 "<rungs.json>")
    if args.replay:
        path = args.replay
        try:
            with open(path, encoding="utf-8") as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"ds_mem: cannot load {path}: {e}", file=sys.stderr)
            return 2
        rep = replay_maxparams(doc)
        if args.host_ram_gb:
            fit = {"c0_gb": rep["fit"]["c0_gb"],
                   "c1_gb_per_b": rep["fit"]["c1_gb_per_b"]}
            rep["max_params_b"] = max_params_b(fit, args.host_ram_gb)
            rep["host_ram_gb"] = args.host_ram_gb
        print(json.dumps(rep, indent=2) if args.json
              else render_replay(rep))
        return 0 if rep["all_within_tolerance"] else 1

    if args.max_streams:
        plan = serving_plan(
            n_layer=args.layers, kv_layers=args.kv_layers,
            n_head=args.heads, head_dim=args.head_dim,
            max_seq=args.max_seq, block_size=args.block_size,
            kv_bits=args.kv_bits, max_new_tokens=args.max_new,
            weight_bytes=int(args.weight_gb * GIB),
            prompt_tokens=args.prompt_tokens,
            shared_prefix_tokens=args.shared_prefix_tokens)
        ms = max_streams(plan, args.budget_gb * GIB)
        out = {"plan": plan, **ms}
        if args.json:
            print(json.dumps(out, indent=2))
        else:
            shared_note = ""
            if ms["shared_prefix_blocks"]:
                shared_note = (
                    f" ({ms['shared_prefix_blocks']} shared prefix "
                    f"block(s) charged once, "
                    f"{ms['unique_blocks_per_request']} unique/stream)")
            print(f"ds_mem — serving capacity at {args.budget_gb:.1f} GB "
                  f"HBM:\n  per-block {plan['per_block_bytes']} B, "
                  f"{ms['blocks_per_request']} block(s)/request"
                  f"{shared_note}\n"
                  f"  max concurrent streams: {ms['max_streams']}")
        return 0

    if not args.run:
        ap.error("give a monitor run dir, --replay, --max-params, or "
                 "--max-streams")
    from ..monitor.__main__ import StreamFollower, resolve_stream
    stream = resolve_stream(args.run)
    if not os.path.exists(stream):
        print(f"ds_mem: no event stream at {stream}", file=sys.stderr)
        return 1
    folded = fold_mem_stream(StreamFollower(stream).poll())
    if args.json:
        print(json.dumps(folded, indent=2, sort_keys=True))
    else:
        print(render_ledger(folded, stream))
    return 0


if __name__ == "__main__":
    sys.exit(main())
