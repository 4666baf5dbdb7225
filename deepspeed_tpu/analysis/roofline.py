"""Roofline attribution: explain measured step time against the chip.

``ds_explain`` (``python -m deepspeed_tpu.analysis.roofline <run_dir>``,
or ``bin/ds_explain``) turns a monitor event stream into a per-executable
*verdict*: which roofline the step is actually hitting — **compute**
(peak FLOPS), **HBM** (memory bandwidth), or **wire** (interconnect) —
what fraction of that binding roofline the measured wall achieves, and a
decomposition of the gap:

- modeled device time  = max(flops/peak, hbm_bytes/bw, wire_bytes/ici)
  (the roofline model: terms overlap; the largest one binds);
- host/scheduling time = measured wall − modeled device time (dispatch
  gaps, host work, Python — everything the chip was NOT the reason for);
- gather-materialization bytes: the paged decode path materializes each
  slot's gathered K/V blocks before attending (``paged_kv.gather_kv``'s
  honest cost note) — those bytes are named explicitly as a slice of
  the HBM term, because they are the exact traffic the ROADMAP-1
  in-place Pallas kernel deletes.

Inputs, all already on the bus (docs/monitoring.md#ds_explain):

- ``exe_cost`` gauge events — one per priced executable: XLA
  cost-analysis FLOPs + ``bytes accessed``, the HLO wire census bytes
  (``analysis/comms.py``), the producing device kind and chip count;
- ``step`` events (``fields.wall_s``) and/or the ``step_wall_ms`` hist
  event — the measured wall-time stream;
- the shared :data:`monitor.gauges.CHIP_TABLE` (peak FLOPS + HBM +
  ICI bandwidth per generation; ``--chip``/``--hbm-gb-s``/... override).

This makes a hand-argued "decode at such a fraction of the HBM bound" a
regenerable report: the acceptance test replays a recorded step (cost
analysis + wall time, ``tests/data/bench_diff_fixture_inference.json``)
through this module and reproduces the fraction (tests/test_roofline.py).
"""

import argparse
import json
import os
import sys

from ..monitor.gauges import CHIP_TABLE, chip_specs

BOUNDS = ("compute", "hbm", "wire")

# warmup steps excluded from the wall-time series (compile/deserialize)
DEFAULT_WARMUP_STEPS = 2


def gather_materialization_bytes(*, n_layer, batch_slots, nb_max,
                                 block_size, n_head, head_dim,
                                 itemsize, paged_impl="gather") -> int:
    """HBM traffic of the paged decode's gather materialization, per
    decode step — FOR THE LIVE IMPLEMENTATION.

    The legacy/fallback ``paged_impl="gather"`` path
    (``paged_kv.gather_kv``) gathers every slot's K AND V block lists
    into dense ``(B, nb_max·block_size, H, hd)`` copies per layer,
    written once and read once — 4x the slot's KV bytes of traffic.
    The in-place Pallas kernel (``paged_impl="kernel"``,
    ``ops/transformer/paged_attention.py``) DMAs blocks straight from
    the pool: the term is **0**, and ``ds_explain`` proves the bytes
    are gone rather than keeping a modeled cost the implementation no
    longer pays."""
    if paged_impl == "kernel":
        return 0
    assert paged_impl == "gather", f"unknown paged_impl {paged_impl!r}"
    copy = 2 * n_layer * batch_slots * nb_max * block_size \
        * n_head * head_dim * itemsize       # K + V materialized copies
    return 2 * copy                          # written, then read


def attribute(*, wall_s, flops=0, hbm_bytes=0, wire_bytes=0,
              chip=None, n_chips=1, gather_bytes=0,
              paged_impl=None) -> dict:
    """One executable's roofline verdict (module docstring).

    ``chip`` is a :func:`monitor.gauges.chip_specs` row (default: the
    local device's).  Returns bound / achieved_frac / per-term modeled
    times / the gap decomposition; ``achieved_frac`` is modeled-bound
    time over measured wall, i.e. 1.0 = running AT the binding roofline.
    """
    if wall_s is None or wall_s <= 0:
        raise ValueError(f"wall_s must be > 0, got {wall_s}")
    chip = dict(chip) if chip else chip_specs()
    n_chips = max(1, int(n_chips))
    t_compute = flops / (chip["peak_bf16_flops"] * n_chips) if flops else 0.0
    t_hbm = (hbm_bytes / (chip["hbm_gb_s"] * 1e9 * n_chips)
             if hbm_bytes else 0.0)
    t_wire = (wire_bytes / (chip["ici_gb_s"] * 1e9 * n_chips)
              if wire_bytes else 0.0)
    terms = {"compute": t_compute, "hbm": t_hbm, "wire": t_wire}
    bound = max(terms, key=terms.get)
    t_bound = terms[bound]
    if t_bound <= 0:
        bound = "unknown"
    achieved = (t_bound / wall_s) if t_bound > 0 else None
    gap_s = max(0.0, wall_s - t_bound)
    out = {
        "bound": bound,
        "achieved_frac": round(achieved, 4) if achieved is not None
        else None,
        "wall_s": wall_s,
        "modeled": {k: round(v, 12) for k, v in terms.items()},
        "modeled_device_s": round(t_bound, 12),
        "gap": {
            "host_scheduling_s": round(gap_s, 12),
            "host_pct": round(100.0 * gap_s / wall_s, 2),
        },
        "inputs": {"flops": int(flops), "hbm_bytes": int(hbm_bytes),
                   "wire_bytes": int(wire_bytes), "n_chips": n_chips},
        "chip": {k: chip.get(k) for k in
                 ("device_kind", "matched", "peak_bf16_flops",
                  "hbm_gb_s", "ici_gb_s", "nominal") if k in chip},
    }
    if paged_impl is not None:
        # which paged-attention impl produced this stream: the verdict
        # names it so "the gather bytes are gone" is a reported fact,
        # not an inference (kernel → the term below is exactly 0)
        out["paged_attention_impl"] = str(paged_impl)
    if gather_bytes or paged_impl is not None:
        # named explicitly: the slice of the HBM term the in-place
        # paged-attention kernel recovers (0 when the kernel IS the
        # live impl — the ROADMAP-1 acceptance evidence)
        g_s = gather_bytes / (chip["hbm_gb_s"] * 1e9 * n_chips)
        out["gap"]["gather_materialization_bytes"] = int(gather_bytes)
        out["gap"]["gather_materialization_s"] = round(g_s, 12)
        if hbm_bytes:
            out["gap"]["gather_pct_of_hbm_bytes"] = round(
                100.0 * gather_bytes / hbm_bytes, 2)
    return out


# --------------------------------------------------------------- the stream

def _median(vals):
    if not vals:
        return None
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def fold_stream(events, warmup=DEFAULT_WARMUP_STEPS) -> dict:
    """Collect what the verdicts need from a parsed event stream:
    per-step-name wall series (warmup-trimmed), the newest ``exe_cost``
    record per executable, and the newest ``step_wall_ms`` histogram."""
    walls = {}                   # step name -> [wall_s, ...]
    costs = {}                   # exe name  -> exe_cost fields
    step_hist = None
    for e in events:
        if e.kind == "step" and e.fields.get("wall_s"):
            walls.setdefault(e.name, []).append(float(e.fields["wall_s"]))
        elif e.kind == "gauge" and e.name == "exe_cost":
            exe = e.fields.get("exe")
            if exe:
                costs[str(exe)] = dict(e.fields)
        elif e.kind == "hist" and e.name == "step_wall_ms":
            step_hist = e.fields
    walls = {k: (v[warmup:] if len(v) > warmup else v)
             for k, v in walls.items()}
    return {"walls": walls, "costs": costs, "step_wall_hist": step_hist}


def explain(folded, *, chip=None) -> dict:
    """Per-executable verdicts from a :func:`fold_stream` result (which
    already applied the warmup trim).  The wall estimate is the p50 of
    the ``step_wall_ms`` histogram when the stream carries one
    (whole-run, exact-count), else the median of the step events'
    ``wall_s`` series (interval-thinned)."""
    verdicts = {}
    for exe, cost in folded["costs"].items():
        wall_s = None
        wall_src = None
        if exe == "serving_step" and folded["step_wall_hist"]:
            from ..monitor.histogram import LogHistogram
            try:
                h = LogHistogram.from_dict(folded["step_wall_hist"])
                if h:
                    wall_s = h.quantile(0.5) / 1e3
                    wall_src = f"step_wall_ms hist p50 (n={h.count})"
            except (KeyError, TypeError, ValueError):
                pass
        if wall_s is None:
            series = folded["walls"].get(exe) or []
            wall_s = _median(series)
            wall_src = f"median of {len(series)} step wall_s samples"
        if not wall_s:
            verdicts[exe] = {"error": "no measured wall time in the "
                             "stream for this executable"}
            continue
        row = chip or (chip_specs(cost.get("device_kind"))
                       if cost.get("device_kind") else None)
        v = attribute(
            wall_s=wall_s,
            flops=cost.get("flops") or 0,
            hbm_bytes=cost.get("hbm_bytes") or 0,
            wire_bytes=cost.get("wire_bytes") or 0,
            chip=row, n_chips=cost.get("n_chips") or 1,
            gather_bytes=cost.get("gather_bytes") or 0,
            paged_impl=cost.get("paged_impl"))
        v["wall_source"] = wall_src
        if cost.get("tokens_per_step"):
            v["tokens_per_step"] = cost["tokens_per_step"]
        verdicts[exe] = v
    return verdicts


# ----------------------------------------------------------------- the CLI

def _fmt_ms(s):
    return f"{s * 1e3:.3f} ms"


def render(verdicts: dict, source: str) -> str:
    lines = [f"ds_explain — roofline attribution over {source}", ""]
    if not verdicts:
        lines.append(
            "no priced executables in the stream (no `exe_cost` events) "
            "— run with the monitor enabled on a build that emits them "
            "(docs/monitoring.md#ds_explain)")
        return "\n".join(lines)
    for exe, v in sorted(verdicts.items()):
        if "error" in v:
            lines.append(f"[{exe}] {v['error']}")
            continue
        c = v["chip"]
        nom = " (NOMINAL table row — non-TPU backend)" if c.get("nominal") \
            else ""
        lines += [
            f"[{exe}]  wall {_fmt_ms(v['wall_s'])} "
            f"({v['wall_source']})",
            f"  chip: {c.get('device_kind')} -> {c.get('matched')}{nom}: "
            f"{c['peak_bf16_flops'] / 1e12:.0f} TFLOPs, "
            f"HBM {c['hbm_gb_s']:.0f} GB/s, ICI {c['ici_gb_s']:.0f} GB/s "
            f"x{v['inputs']['n_chips']} chip(s)",
            f"  modeled: compute {_fmt_ms(v['modeled']['compute'])} | "
            f"HBM {_fmt_ms(v['modeled']['hbm'])} | "
            f"wire {_fmt_ms(v['modeled']['wire'])}",
        ]
        if v["achieved_frac"] is not None:
            lines.append(
                f"  verdict: {v['bound'].upper()}-BOUND — achieved "
                f"{v['achieved_frac']:.2f} of the {v['bound']} roofline")
        else:
            lines.append("  verdict: UNKNOWN — no cost inputs priced")
        g = v["gap"]
        lines.append(
            f"  gap: host/scheduling {_fmt_ms(g['host_scheduling_s'])} "
            f"({g['host_pct']:.0f}% of wall)")
        if "gather_materialization_bytes" in g:
            impl = v.get("paged_attention_impl")
            if impl == "kernel" and not g["gather_materialization_bytes"]:
                lines.append(
                    "    paged attention: in-place Pallas kernel — "
                    "gather materialization 0 B/step (the copy the "
                    "gather fallback would pay is deleted)")
            else:
                tag = f" [impl: {impl}]" if impl else ""
                lines.append(
                    f"    gather materialization (paged decode{tag}): "
                    f"{g['gather_materialization_bytes'] / 1e6:.1f} MB/step "
                    f"= {_fmt_ms(g['gather_materialization_s'])} of the HBM "
                    f"term ({g.get('gather_pct_of_hbm_bytes', 0):.1f}% of "
                    f"HBM bytes) — the in-place kernel "
                    f"(paged_attention_impl=kernel) deletes it")
        lines.append("")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="ds_explain",
        description="roofline attribution over a monitor event stream "
                    "(docs/monitoring.md#ds_explain)")
    ap.add_argument("run", help="monitor run dir (or an events.jsonl path)")
    ap.add_argument("--chip", default=None,
                    help=f"chip table row to price against (default: the "
                         f"stream's device_kind); one of "
                         f"{sorted(CHIP_TABLE)}")
    ap.add_argument("--peak-tflops", type=float, default=None,
                    help="override peak bf16 TFLOPs per chip")
    ap.add_argument("--hbm-gb-s", type=float, default=None,
                    help="override HBM GB/s per chip")
    ap.add_argument("--ici-gb-s", type=float, default=None,
                    help="override interconnect GB/s per chip")
    ap.add_argument("--warmup", type=int, default=DEFAULT_WARMUP_STEPS,
                    help="leading steps to drop from the wall series "
                         f"(default {DEFAULT_WARMUP_STEPS})")
    ap.add_argument("--json", action="store_true",
                    help="emit the verdicts as JSON instead of the report")
    args = ap.parse_args(argv)

    from ..monitor.__main__ import StreamFollower, resolve_stream
    stream = resolve_stream(args.run)
    if not os.path.exists(stream):
        print(f"ds_explain: no event stream at {stream}", file=sys.stderr)
        return 1
    events = StreamFollower(stream).poll()
    folded = fold_stream(events, warmup=args.warmup)

    chip = None
    if args.chip:
        if args.chip not in CHIP_TABLE:
            print(f"ds_explain: unknown --chip {args.chip!r}; known: "
                  f"{sorted(CHIP_TABLE)}", file=sys.stderr)
            return 2
        chip = dict(CHIP_TABLE[args.chip], device_kind=args.chip,
                    matched=args.chip)
    if args.peak_tflops or args.hbm_gb_s or args.ici_gb_s:
        chip = dict(chip or chip_specs())
        if args.peak_tflops:
            chip["peak_bf16_flops"] = args.peak_tflops * 1e12
        if args.hbm_gb_s:
            chip["hbm_gb_s"] = args.hbm_gb_s
        if args.ici_gb_s:
            chip["ici_gb_s"] = args.ici_gb_s

    verdicts = explain(folded, chip=chip)
    if args.json:
        print(json.dumps(verdicts, indent=2, sort_keys=True))
    else:
        print(render(verdicts, stream))
    return 0


if __name__ == "__main__":
    sys.exit(main())
