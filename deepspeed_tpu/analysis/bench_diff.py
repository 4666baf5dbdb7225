"""``ds_bench_diff``: the perf-regression gate over bench artifacts.

Compares two JSON documents of metrics — a ``benchmark/run.py`` result
line, a stdout capture whose last line is one, or a recorded document
(the tests' ``tests/data/bench_diff_fixture_*.json``) — metric by metric, with per-metric **noise bands**, and exits non-zero
on a regression beyond the band.  The record of speed is
``PERF_LEDGER.jsonl``, which the driver writes; this tool compares two
documents by hand (ROADMAP D1b).

Metric classification (by key name, innermost key of the JSON path):

- **higher-better** (throughput family): ``tokens_per_sec``, ``tok_s``,
  ``mfu`` (and ``projected_mfu*``), ``samples_per_sec``,
  ``fraction_of_bound``, ``achieved_frac``, ``reduction_x``,
  ``bound_tokens_per_sec``, ``decode_tokens_per_sec``, and the
  migration wins ``migrated_streams`` / ``recompute_tokens_saved``
  (restore-first handoffs and the decode work they avoided);
- **lower-better** (latency/cost family): keys ending in ``_ms``/``_s``
  (``p50_ms``, ``p99_ms``, ``ttft_*``, ``prefill_ms``, compile times),
  ``ms_per_token*``, ``*_bytes``/``*_bytes_per_step`` (wire/pool cost),
  ``host_pct``/``overhead_pct``, the memory family
  (``rss_hwm_gb``, ``pool_bytes``, ``peak_bytes`` — capacity costs),
  and the slo family (``*burn_rate*``, ``slo_breaches`` — error-budget
  costs), and the router family (``lost_requests``,
  ``duplicate_answers``, ``handoff_requeue_ms`` — zero-loss serving
  costs: any growth is a robustness regression), and the migration
  family (``migration_fallbacks`` — each one is a stream that paid
  full recompute because its image was unusable; ``restore_ms`` gates
  through the ``_ms`` suffix rule);
- everything else numeric is **informational** — reported when it moved,
  never gated (counts, shapes, config echoes).

Band defaults (docs/monitoring.md#ds_bench_diff): ``--band 0.2`` —
±20%, this container's measured fast-tier run-to-run swing (CHANGES.md
PR-6/PR-9 notes); TPU runs are steadier, ``--band 0.05`` is apt there.
Per-metric overrides: ``--band-for p99_ms=0.5`` (tail latencies are
noisier than medians).  A metric present on one side only is reported
as added/removed, never gated; so is one whose baseline is zero (a
relative band cannot price an infinite delta).

Exit codes: 0 = no regression beyond band, 1 = regression(s), 2 = usage.
"""

import argparse
import json
import sys

DEFAULT_BAND = 0.2         # ±20%: this container's measured CPU-tier noise

HIGHER_BETTER = ("tokens_per_sec", "tok_s", "samples_per_sec", "mfu",
                 "fraction_of_bound", "achieved_frac", "reduction_x",
                 "bound_tokens_per_sec", "decode_tokens_per_sec",
                 "migrated_streams", "recompute_tokens_saved",
                 "prefix_hit_rate", "max_streams")
LOWER_BETTER_SUFFIX = ("_ms", "_s")
LOWER_BETTER = ("ms_per_token", "overhead_pct", "host_pct")
LOWER_BETTER_BYTES = ("wire_bytes", "bytes_per_step")
# memory family (docs/monitoring.md#memory-explainability): host-RSS
# high-water marks, KV-pool residency and projected/measured peaks are
# capacity costs — growth beyond band is a regression
LOWER_BETTER_MEM = ("rss_hwm_gb", "pool_bytes", "peak_bytes")
# slo family (docs/monitoring.md#slo-tracking): burn rates and breach
# counts are budget costs — growth beyond band is a regression
LOWER_BETTER_SLO = ("burn_rate", "slo_breaches")
# router family (docs/serving.md#replica-router): lost requests and
# duplicate answers must be exactly zero (the zero-loss contract), and
# handoff requeue latency is the fail-over cost — growth is a
# robustness regression
LOWER_BETTER_ROUTER = ("lost_requests", "duplicate_answers",
                       "handoff_requeue_ms")
# sanitizer family (docs/static-analysis.md#sanitizer): a clean run
# must report zero lifecycle findings — any growth is a serving bug,
# not noise
LOWER_BETTER_SANITIZE = ("sanitizer_findings",)
# migration family (docs/serving.md#kv-migration): every fallback is a
# stream that paid full recompute because its KV image was torn,
# corrupt, or unplaceable — growth is a robustness regression
# (restore_ms gates via the _ms suffix rule)
LOWER_BETTER_MIGRATION = ("migration_fallbacks",)
# prefix-sharing family (docs/serving.md#prefix-sharing):
# unique_block_frac is physical-over-logical block residency — a rise
# means the radix cache is deduplicating LESS of the co-tenant KV
# (prefix_hit_rate gates the other direction via HIGHER_BETTER)
LOWER_BETTER_PREFIX = ("unique_block_frac",)
# disaggregation family (docs/serving.md#disaggregation): the per-stream
# handoff cost (publish + seat + restore) and the decode-side
# inter-token p99 the role split exists to flatten — both explicit here
# even though the _ms suffix rule would catch them: the headline
# metrics must never silently drop to informational under a rename
LOWER_BETTER_DISAGG = ("handoff_ms", "decode_cadence_p99_ms")
# exact count contracts where ZERO is the baseline by design: any
# growth regresses even though a relative band cannot gate it (the
# zero-baseline report-never-regress policy below is for
# rounded-to-0.0 gauges, not for these)
ZERO_CONTRACT = ("sanitizer_findings", "lost_requests",
                 "duplicate_answers", "slo_breaches")


def classify(key: str):
    """'higher' | 'lower' | None (informational) for one metric key."""
    k = key.lower()
    for name in HIGHER_BETTER:
        if name in k:
            return "higher"
    for name in (LOWER_BETTER + LOWER_BETTER_BYTES + LOWER_BETTER_MEM
                 + LOWER_BETTER_SLO + LOWER_BETTER_ROUTER
                 + LOWER_BETTER_SANITIZE + LOWER_BETTER_MIGRATION
                 + LOWER_BETTER_PREFIX + LOWER_BETTER_DISAGG):
        if name in k:
            return "lower"
    if k.endswith(LOWER_BETTER_SUFFIX):
        return "lower"
    return None


def _numeric_leaves(doc, prefix=""):
    """Flatten a bench JSON into {path: float} over its numeric leaves
    (bools excluded — `breaker_open: false` is a flag, not a metric)."""
    out = {}
    if isinstance(doc, dict):
        for k, v in doc.items():
            out.update(_numeric_leaves(v, f"{prefix}.{k}" if prefix else k))
    elif isinstance(doc, (int, float)) and not isinstance(doc, bool):
        out[prefix] = float(doc)
    return out


def compare(base: dict, new: dict, band: float = DEFAULT_BAND,
            bands: dict = None) -> dict:
    """Per-metric comparison.  Returns ``{"rows": [...], "regressions":
    [...], "added": [...], "removed": [...]}`` — a row per shared
    numeric leaf that moved, each with the applied band and verdict."""
    bands = bands or {}
    a, b = _numeric_leaves(base), _numeric_leaves(new)
    rows, regressions = [], []
    for path in sorted(set(a) & set(b)):
        key = path.rsplit(".", 1)[-1]
        direction = classify(key)
        va, vb = a[path], b[path]
        if va == vb:
            continue
        if not va and not any(name in key.lower()
                              for name in ZERO_CONTRACT):
            # zero baseline: no relative band can gate this (delta is
            # infinite for ANY change) — report, never regress.  A
            # rounded-to-0.0 gap_host_pct moving to 0.3 is noise, not
            # a perf cliff; absolute gating needs a real baseline.
            # Exact zero-contract counts (ZERO_CONTRACT) stay gated:
            # there, zero IS the contract and any growth is a bug.
            direction = None
        delta = (vb - va) / abs(va) if va else float("inf")
        this_band = bands.get(key, bands.get(path, band))
        verdict = "info"
        if direction is not None and abs(delta) > this_band:
            bad = (delta < 0) if direction == "higher" else (delta > 0)
            verdict = "REGRESSION" if bad else "improved"
        row = {"path": path, "base": va, "new": vb,
               "delta_pct": round(100.0 * delta, 2),
               "direction": direction, "band_pct": round(100 * this_band, 1),
               "verdict": verdict}
        rows.append(row)
        if verdict == "REGRESSION":
            regressions.append(row)
    return {"rows": rows, "regressions": regressions,
            "added": sorted(set(b) - set(a)),
            "removed": sorted(set(a) - set(b))}


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        text = fh.read()
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        # a bench stdout capture: the headline is the strict final line
        for line in reversed(text.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
        raise


def render(result: dict, base_path: str, new_path: str) -> str:
    lines = [f"ds_bench_diff: {base_path} -> {new_path}"]
    shown = [r for r in result["rows"] if r["verdict"] != "info"] or \
        result["rows"][:20]
    for r in shown:
        arrow = {"higher": "↑ better", "lower": "↓ better",
                 None: ""}[r["direction"]]
        lines.append(
            f"  [{r['verdict']:>10}] {r['path']}: {r['base']:g} -> "
            f"{r['new']:g} ({r['delta_pct']:+.1f}%, band "
            f"±{r['band_pct']:.0f}%) {arrow}")
    if result["added"]:
        lines.append(f"  added: {len(result['added'])} metric(s) "
                     f"(e.g. {result['added'][0]})")
    if result["removed"]:
        lines.append(f"  removed: {len(result['removed'])} metric(s) "
                     f"(e.g. {result['removed'][0]})")
    n = len(result["regressions"])
    lines.append(f"verdict: {n} regression(s) beyond the noise band"
                 if n else "verdict: no regression beyond the noise band")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="ds_bench_diff",
        description="compare two bench JSONs with per-metric noise "
                    "bands; exit 1 on regression beyond the band "
                    "(docs/monitoring.md#ds_bench_diff)")
    ap.add_argument("base", help="baseline JSON (headline or committed "
                                 "BENCH_*.json artifact)")
    ap.add_argument("new", help="candidate JSON")
    ap.add_argument("--band", type=float, default=DEFAULT_BAND,
                    help=f"relative noise band (default {DEFAULT_BAND} "
                         "= ±20%%, the measured CPU-tier swing)")
    ap.add_argument("--band-for", action="append", default=[],
                    metavar="METRIC=BAND",
                    help="per-metric override, e.g. p99_ms=0.5 "
                         "(repeatable; matches the key or the full path)")
    ap.add_argument("--json", action="store_true",
                    help="emit the comparison as JSON")
    args = ap.parse_args(argv)

    bands = {}
    for spec in args.band_for:
        if "=" not in spec:
            ap.error(f"--band-for wants METRIC=BAND, got {spec!r}")
        key, val = spec.rsplit("=", 1)
        bands[key] = float(val)
    try:
        base, new = _load(args.base), _load(args.new)
    except (OSError, json.JSONDecodeError) as e:
        print(f"ds_bench_diff: cannot load inputs: {e}", file=sys.stderr)
        return 2
    result = compare(base, new, band=args.band, bands=bands)
    if args.json:
        print(json.dumps(result, indent=2))
    else:
        print(render(result, args.base, args.new))
    return 1 if result["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
