"""``ds_fleet``: merge N per-replica monitor streams into one fleet view.

ROADMAP #3's replica router spreads requests over N ``ServingEngine``
replicas; its load-balancing/autoscale signal is exactly what this
module computes, shipped BEFORE the router so the router lands on
proven plumbing:

- **merged distributions** — each replica's ``hist`` events are
  cumulative whole-run snapshots of a mergeable log-bucketed histogram
  (``monitor/histogram.py``); the fleet takes the NEWEST snapshot per
  (replica, name) and merges them with the PR-12 *exact* merge
  primitive, so the fleet p50/p99 equals (within the proven ε bound)
  the quantile over every replica's completions — no central sample
  store, no approximation on top of an approximation;
- **summed counters** — cumulative counters (completions, shed/
  deadline/poisoned totals, wire bytes) take the newest value per
  replica and sum exactly;
- **attributed gauges** — instantaneous gauges (tokens/s, queue depth,
  MFU) stay per replica: averaging them away is how stragglers hide;
- **straggler / imbalance detection** — Frontier (arXiv 2501.04266):
  fleet behavior is dominated by the slowest participant, so the
  slowest replica must be a first-class observable.  Per replica the
  fleet computes the median *observed step cadence* (wall-clock gap
  between consecutive step events — catches slowdowns wherever they
  happen, host or device), the median in-step wall, and the mean queue
  depth, then z-scores each replica against the OTHER replicas
  (leave-one-out: with 2-4 replicas a plain fleet z-score saturates at
  (N-1)/√N and can never cross a sane threshold).  A replica is named
  straggler when its z exceeds ``zmax`` AND its relative excess over
  the others' mean exceeds ``min_excess`` (pure jitter on a tight
  fleet must not page);
- **fleet SLO** — with ``--slo objectives.json`` the merged stream
  replays through the SAME ``SLOEvaluator`` the live engines run
  (``monitor/slo.py``), so an offline fleet verdict and the live
  per-replica verdicts cannot drift.

Streams are read segment-aware (``sinks.stream_segments`` — rotation-
safe) and torn-tail-safe via the incremental
:class:`..__main__.StreamFollower`; replicas are labeled by the
``run`` stamp their events carry (``monitor.run_id``), falling back to
the directory name.

CLI: ``bin/ds_fleet dir1 dir2 ... [--once] [--json] [--slo cfg.json]``
or ``python -m deepspeed_tpu.monitor --fleet dir1 dir2 ...``.
"""

import argparse
import json
import os
import statistics
import sys
import time
from typing import Dict, List, Optional

from .events import Event
from .histogram import LogHistogram
from .sinks import resolve_stream

# straggler verdict knobs (module docstring has the rationale)
STRAGGLER_ZMAX = 3.0
STRAGGLER_MIN_EXCESS = 0.2      # >= 20% above the others' mean
# series the straggler scan walks: (key, verdict label, minimum ABSOLUTE
# excess over the others' mean).  The absolute floor keeps tiny-valued
# series honest: queue depth 1 vs 2 is scheduler jitter (100% relative!),
# queue depth 2 vs 9 is a replica falling behind; the timing series are
# already mean-relative so 0 suffices.
_STRAGGLER_SERIES = (("step_cadence_ms", "step cadence", 0.0),
                     ("step_wall_ms", "step wall", 0.0),
                     ("queue_depth", "queue depth", 4.0))


class ReplicaView:
    """Folded state of ONE replica's stream (fed incrementally)."""

    def __init__(self, source: str):
        self.source = source                  # run dir / stream path
        self.run_id: Optional[str] = None     # from the events' run stamp
        self.events = 0
        self.bad_lines = 0
        self.last_step: Optional[int] = None
        self.last_t: Optional[float] = None
        self.first_t: Optional[float] = None
        self.step_name: Optional[str] = None
        self.counters: Dict[str, float] = {}  # newest value per name
        self.gauges: Dict[str, float] = {}
        self.hists: Dict[str, dict] = {}      # newest hist payload per name
        self.alerts: List[Event] = []
        self.slo: Dict[str, dict] = {}        # newest slo verdict per obj
        self.step_walls_ms: List[float] = []
        self.step_ts: List[float] = []        # step-event wall-clock stamps
        self.queue_depths: List[float] = []

    def feed(self, events: List[Event]):
        for e in events:
            self.events += 1
            self.last_t = e.t
            if self.first_t is None:
                self.first_t = e.t
            if e.run:
                self.run_id = e.run
            if e.kind == "step":
                self.last_step = e.step
                self.step_name = e.name
                self.step_ts.append(e.t)
                wall = e.fields.get("wall_s")
                if wall is not None:
                    self.step_walls_ms.append(float(wall) * 1e3)
                q = e.fields.get("queued")
                if q is not None:
                    self.queue_depths.append(float(q))
            elif e.kind == "counter" and e.value is not None:
                self.counters[e.name] = e.value
            elif e.kind == "gauge" and e.value is not None:
                self.gauges[e.name] = e.value
            elif e.kind == "hist":
                self.hists[e.name] = dict(e.fields)
            elif e.kind == "alert":
                self.alerts.append(e)
            elif e.kind == "slo":
                self.slo[e.name] = dict(e.fields)

    @property
    def label(self) -> str:
        return self.run_id or os.path.basename(
            os.path.normpath(self.source)) or self.source

    # ------------------------------------------------- straggler signals
    def step_cadence_ms(self) -> Optional[float]:
        """Median wall-clock gap between consecutive step events (ms) —
        the consumer-side step-wall: it includes EVERYTHING between
        steps (journal IO, host scheduling, injected throttles), which
        the in-step ``wall_s`` bracket can miss."""
        if len(self.step_ts) < 2:
            return None
        gaps = [(b - a) * 1e3 for a, b in
                zip(self.step_ts, self.step_ts[1:]) if b >= a]
        return statistics.median(gaps) if gaps else None

    def signal(self, key: str) -> Optional[float]:
        if key == "step_cadence_ms":
            return self.step_cadence_ms()
        if key == "step_wall_ms":
            return (statistics.median(self.step_walls_ms)
                    if self.step_walls_ms else None)
        if key == "queue_depth":
            return (statistics.fmean(self.queue_depths)
                    if self.queue_depths else None)
        raise KeyError(key)


def _leave_one_out_z(values: List[float], i: int) -> float:
    """z-score of ``values[i]`` against the OTHER replicas.  The std
    floor (5% of the others' mean, or an epsilon) keeps a razor-tight
    fleet from producing infinite z on the first microsecond of jitter."""
    others = values[:i] + values[i + 1:]
    mean = statistics.fmean(others)
    std = statistics.pstdev(others) if len(others) > 1 else 0.0
    floor = max(abs(mean) * 0.05, 1e-9)
    return (values[i] - mean) / max(std, floor)


class FleetView:
    """The merged cross-replica view (module docstring)."""

    def __init__(self, replicas: List[ReplicaView]):
        self.replicas = replicas

    # ---------------------------------------------------------- merging
    def merged_hists(self) -> Dict[str, LogHistogram]:
        """Newest snapshot per (replica, name), merged EXACTLY across
        replicas (``LogHistogram.merge`` — bucket counts add)."""
        out: Dict[str, LogHistogram] = {}
        for r in self.replicas:
            for name, payload in r.hists.items():
                try:
                    h = LogHistogram.from_dict(payload)
                except (KeyError, TypeError, ValueError):
                    continue
                if name in out:
                    out[name].merge(h)
                else:
                    out[name] = h
        return out

    def summed_counters(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for r in self.replicas:
            for name, v in r.counters.items():
                out[name] = out.get(name, 0) + v
        return out

    def fleet_tokens_per_sec(self) -> Optional[float]:
        vals = [r.gauges.get("tokens_per_sec") for r in self.replicas]
        vals = [v for v in vals if v is not None]
        return sum(vals) if vals else None

    # ------------------------------------------------------- stragglers
    def straggler(self, zmax: float = STRAGGLER_ZMAX,
                  min_excess: float = STRAGGLER_MIN_EXCESS) -> dict:
        """Name the outlier replica (or none).  Walks the straggler
        series in order; the first series where some replica exceeds
        BOTH the leave-one-out z bound and the relative-excess floor
        names the straggler."""
        verdict = {"straggler": None, "series": None, "signals": {}}
        if len(self.replicas) < 2:
            return verdict
        for key, label, min_abs in _STRAGGLER_SERIES:
            vals = [r.signal(key) for r in self.replicas]
            if any(v is None for v in vals):
                continue
            sig = {r.label: round(v, 3)
                   for r, v in zip(self.replicas, vals)}
            verdict["signals"][key] = sig
            if verdict["straggler"] is not None:
                continue              # keep collecting signals for display
            worst_i = max(range(len(vals)), key=lambda i: vals[i])
            others = vals[:worst_i] + vals[worst_i + 1:]
            mean_others = statistics.fmean(others)
            if mean_others <= 0:
                continue
            excess = vals[worst_i] / mean_others - 1.0
            z = _leave_one_out_z(vals, worst_i)
            if (z >= zmax and excess >= min_excess
                    and vals[worst_i] - mean_others >= min_abs):
                verdict.update({
                    "straggler": self.replicas[worst_i].label,
                    "series": key, "series_label": label,
                    "value": round(vals[worst_i], 3),
                    "fleet_mean_others": round(mean_others, 3),
                    "excess_frac": round(excess, 4),
                    "zscore": round(z, 2)})
        return verdict

    # ---------------------------------------------------------- verdict
    def verdict(self) -> dict:
        """The full machine-readable fleet verdict (``ds_fleet
        --json``)."""
        hists = self.merged_hists()
        out = {
            "replicas": [
                {"label": r.label, "source": r.source, "events": r.events,
                 "bad_lines": r.bad_lines, "last_step": r.last_step,
                 "step_cadence_ms": r.step_cadence_ms(),
                 "step_wall_ms": r.signal("step_wall_ms"),
                 "queue_depth": r.signal("queue_depth"),
                 "tokens_per_sec": r.gauges.get("tokens_per_sec"),
                 "counters": dict(r.counters),
                 "alerts": len(r.alerts)}
                for r in self.replicas],
            "counters": self.summed_counters(),
            "hists": {name: {"count": h.count, **{
                k: (round(v, 3) if v is not None else None)
                for k, v in h.percentiles().items()}}
                for name, h in sorted(hists.items())},
            "tokens_per_sec": self.fleet_tokens_per_sec(),
            "straggler": self.straggler(),
            "alerts": sum(len(r.alerts) for r in self.replicas),
        }
        per_replica_slo = self.replica_slo()
        if per_replica_slo["objectives"]:
            out["slo"] = per_replica_slo
        return out

    def replica_slo(self) -> dict:
        """Roll-up of the NEWEST per-replica ``slo`` verdicts found in
        the streams (the replicas' own live SLO engines).  The
        fleet-WIDE replay over merged raw events is
        :func:`fleet_evaluate_slo` (``ds_fleet --slo``)."""
        agg = {"objectives": []}
        for r in self.replicas:
            for name, fields in r.slo.items():
                agg["objectives"].append({"replica": r.label, **fields})
        if agg["objectives"]:
            agg["objectives_met"] = sum(
                1 for o in agg["objectives"] if o.get("met"))
            agg["objectives_total"] = len(agg["objectives"])
            burns = [max(o.get("burn_fast", 0), o.get("burn_slow", 0))
                     for o in agg["objectives"]]
            agg["worst_burn_rate"] = max(burns) if burns else 0.0
        return agg


def fleet_evaluate_slo(events_by_replica: Dict[str, List[Event]],
                       slo_cfg) -> dict:
    """One-shot offline fleet SLO: replay every replica's raw events,
    in global time order, through ONE evaluator.  The live ``ds_fleet
    --slo`` loop does the same thing incrementally (a persistent
    evaluator fed each poll's ``FleetFollower.new_events``)."""
    from .slo import SLOConfig, SLOEvaluator
    ev = SLOEvaluator(SLOConfig.from_value(slo_cfg))
    merged = []
    for events in events_by_replica.values():
        merged.extend(events)
    merged.sort(key=lambda e: e.t)
    ev.feed_many(merged)
    return ev.verdict()


class FleetFollower:
    """N incremental stream followers + their replica views (the live
    ``ds_fleet`` loop; ``--once`` polls once).  Each poll's NEW events,
    merged across replicas in time order, land in :attr:`new_events` —
    the incremental feed for a persistent fleet-wide
    :class:`~.slo.SLOEvaluator`; nothing is retained across polls, so a
    long watch of a busy fleet stays bounded."""

    def __init__(self, sources: List[str], max_version=None):
        from .__main__ import StreamFollower
        self.views = [ReplicaView(src) for src in sources]
        self._followers = [StreamFollower(resolve_stream(src),
                                          max_version=max_version)
                           for src in sources]
        self.new_events: List[Event] = []

    def poll(self) -> FleetView:
        fresh: List[Event] = []
        for view, follower in zip(self.views, self._followers):
            events = follower.poll()
            view.feed(events)
            view.bad_lines = follower.bad_lines
            fresh.extend(events)
        fresh.sort(key=lambda e: e.t)
        self.new_events = fresh
        return FleetView(self.views)


def _fmt(v, nd=1):
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}f}"
    return str(v)


def render_fleet(view: FleetView, slo_verdict=None,
                 clock=time.time) -> str:
    """One fleet table frame as a string (pure: unit-testable)."""
    lines = [f"ds_fleet — {len(view.replicas)} replica(s)", "-" * 78,
             f"{'replica':>16} {'step':>7} {'cadence':>9} {'wall':>8} "
             f"{'queued':>7} {'tok/s':>8} {'done':>6} {'alerts':>6}"]
    for r in view.replicas:
        done = r.counters.get("completed_total")
        if done is None:
            # serving carries completed_total in step fields; training
            # runs have no completion counter — show steps seen instead
            done = len(r.step_ts) or None
        lines.append(
            f"{r.label[-16:]:>16} {_fmt(r.last_step, 0):>7} "
            f"{_fmt(r.step_cadence_ms()):>9} "
            f"{_fmt(r.signal('step_wall_ms')):>8} "
            f"{_fmt(r.signal('queue_depth')):>7} "
            f"{_fmt(r.gauges.get('tokens_per_sec')):>8} "
            f"{_fmt(done, 0):>6} {len(r.alerts):>6}")
    lines.append("-" * 78)
    counters = view.summed_counters()
    if counters:
        # router handoff counters (inference/router.py) roll up beside
        # the per-replica outcome counters: a fleet view that hides
        # requeues/suppressed duplicates hides the fail-overs
        keys = ("shed_total", "deadline_total", "poisoned_total",
                "requeued_total", "router_requeued_total",
                "router_duplicates_suppressed_total")
        parts = [f"{k.replace('_total', '')} {int(counters[k])}"
                 for k in keys if k in counters]
        extra = [f"{k} {int(v)}" for k, v in sorted(counters.items())
                 if k not in keys and not k.startswith("breaker")]
        lines.append("fleet counters: " + "  ".join(parts + extra[:4]))
    tps = view.fleet_tokens_per_sec()
    if tps is not None:
        lines.append(f"fleet tokens/s (sum of live gauges): {tps:.1f}")
    hists = view.merged_hists()
    if hists:
        parts = []
        for name, h in sorted(hists.items()):
            p = h.percentiles()
            if p["p50"] is None:
                continue
            parts.append(f"{name} p50 {_fmt(p['p50'])} "
                         f"p99 {_fmt(p['p99'])} (n={h.count})")
        if parts:
            lines.append("merged hist: " + "  |  ".join(parts))
    strag = view.straggler()
    if strag["straggler"] is not None:
        lines.append(
            f"STRAGGLER: {strag['straggler']} — {strag['series_label']} "
            f"{_fmt(strag['value'])} vs fleet "
            f"{_fmt(strag['fleet_mean_others'])} "
            f"(+{strag['excess_frac'] * 100:.0f}%, z={strag['zscore']})")
    elif strag["signals"]:
        lines.append("straggler: none (fleet balanced)")
    if slo_verdict and slo_verdict.get("objectives_total"):
        lines.append(
            f"fleet slo: {slo_verdict['objectives_met']}/"
            f"{slo_verdict['objectives_total']} objective(s) met, "
            f"worst burn {slo_verdict['worst_burn_rate']:.1f}, "
            f"breaches {slo_verdict.get('slo_breaches', 0)}")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="ds_fleet",
        description="merge N per-replica monitor streams into one fleet "
                    "view (docs/monitoring.md#fleet-view)")
    ap.add_argument("runs", nargs="+",
                    help="monitor run dirs (or events.jsonl paths), one "
                         "per replica")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="refresh period in seconds (default 2)")
    ap.add_argument("--once", action="store_true",
                    help="render one frame and exit")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable fleet verdict on stdout "
                         "(implies --once)")
    ap.add_argument("--slo", default=None, metavar="CFG.json",
                    help="evaluate a monitor.slo config block over the "
                         "merged stream (fleet-wide objectives)")
    args = ap.parse_args(argv)

    missing = [r for r in args.runs
               if not os.path.exists(resolve_stream(r))]
    if missing and (args.once or args.as_json):
        if args.as_json:
            # the --json contract is one parseable object on stdout,
            # success or failure
            print(json.dumps({"error": "no event stream",
                              "missing": missing}))
        else:
            print(f"ds_fleet: no event stream under {missing}")
        return 1
    evaluator = None
    if args.slo:
        from .slo import SLOConfig, SLOEvaluator
        with open(args.slo) as fh:
            evaluator = SLOEvaluator(SLOConfig.from_value(json.load(fh)))
    follower = FleetFollower(args.runs)
    try:
        while True:
            view = follower.poll()
            slo_verdict = None
            if evaluator is not None:
                # incremental: only this poll's new events replay — a
                # long watch never re-feeds (or retains) the history
                evaluator.feed_many(follower.new_events)
                slo_verdict = evaluator.verdict()
            if args.as_json:
                v = view.verdict()
                if slo_verdict is not None:
                    v["slo_fleet"] = slo_verdict
                print(json.dumps(v, sort_keys=True, default=str))
                return 0
            frame = render_fleet(view, slo_verdict=slo_verdict)
            if args.once:
                print(frame)
                return 0
            sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
            sys.stdout.flush()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
