"""``ds_top``: tail a monitor run's JSONL stream into a live terminal table.

Usage::

    python -m deepspeed_tpu.monitor <run_dir | events.jsonl> \
        [--interval 2] [--once] [--tail N]
    python -m deepspeed_tpu.monitor <run_dir> --export-trace [--out X.json]
    python -m deepspeed_tpu.monitor --fleet dir1 dir2 ...   # -> ds_fleet

Reads ``events.jsonl`` incrementally (only bytes appended since the last
poll), folds the events into one aggregate view (latest step scalars,
latest gauges/counters by name, the last step's span breakdown, artifact
announcements), and redraws the table every ``--interval`` seconds.
``--once`` renders a single frame and exits (scripting/tests).

Malformed or future-schema lines are counted and skipped — a live tail
must survive a writer mid-line or a newer producer.
"""

import argparse
import os
import sys
import time

from .events import parse_line
from .sinks import EVENTS_FILE, resolve_stream  # noqa: F401 (re-export:
# bench/tests resolve run dirs through this module's historical name)


class StreamFollower:
    """Incremental JSONL reader: remembers the byte offset, returns only
    complete new lines each poll (a partial trailing line is carried).

    Segment-aware (docs/monitoring.md#stream-rotation): when the sink
    rotates the active file to ``events.jsonl.<n>``, the follower
    finishes the rotated segment from its remembered offset (matched by
    inode — the rename preserves it) before moving to the fresh active
    file, so no event is ever skipped or double-read across a rotation.
    Unread older segments found on first poll are read in order, which
    is also how ``ds_fleet`` reads a whole rotated stream."""

    def __init__(self, path, max_version=None):
        self.path = path
        self.offset = 0
        self._carry = ""
        self._ino = None              # inode of the file `offset` is into
        self._done = set()            # fully-consumed rotated segments
        self.bad_lines = 0
        self.max_version = max_version   # None -> this build's ceiling

    def _read_from(self, path, start):
        """Complete new lines of one file from byte ``start``; returns
        (events, end_offset)."""
        try:
            with open(path, "r", encoding="utf-8") as f:
                f.seek(start)
                chunk = f.read()
                end = f.tell()
        except OSError:
            return [], start
        data = self._carry + chunk
        lines = data.split("\n")
        self._carry = lines.pop()     # "" on a complete final line
        events = []
        for line in lines:
            if not line.strip():
                continue
            try:
                if self.max_version is None:
                    events.append(parse_line(line))
                else:
                    events.append(parse_line(
                        line, max_version=self.max_version))
            except Exception:
                self.bad_lines += 1
        return events, end

    @staticmethod
    def _ino_of(path):
        try:
            return os.stat(path).st_ino
        except OSError:
            return None

    def poll(self):
        from .sinks import stream_segments
        events = []
        # rotated segments first (oldest → newest): the one our offset
        # was into — identified by inode — resumes from that offset, any
        # other unread segment reads from the top
        for seg in stream_segments(self.path):
            if seg in self._done:
                continue
            ino = self._ino_of(seg)
            start = self.offset if (self._ino is not None
                                    and ino == self._ino) else 0
            got, _ = self._read_from(seg, start)
            events.extend(got)
            if self._carry:
                # rotated segments are immutable: a torn trailing line
                # can only be a crash mid-write — count it, drop it
                self.bad_lines += 1
                self._carry = ""
            self._done.add(seg)
            if self._ino is not None and ino == self._ino:
                self._ino, self.offset = None, 0
        # then the active file
        ino = self._ino_of(self.path)
        if ino is None:
            return events
        if ino != self._ino and self._ino is not None:
            # the active file was rotated AFTER the segment scan above:
            # drain the renamed file (matched by inode) before switching,
            # so the boundary is never skipped or double-read
            for seg in stream_segments(self.path):
                if seg not in self._done and self._ino_of(seg) == self._ino:
                    got, _ = self._read_from(seg, self.offset)
                    events.extend(got)
                    if self._carry:
                        self.bad_lines += 1
                        self._carry = ""
                    self._done.add(seg)
                    break
            else:
                # rename not visible in the listing yet: leave the
                # offset alone and resolve on the next poll
                return events
            self._ino, self.offset = None, 0
        if ino != self._ino:
            # fresh active file (first poll, or a rotation we just
            # drained above): start from the top
            self._ino, self.offset, self._carry = ino, 0, ""
        try:
            size = os.path.getsize(self.path)
        except OSError:
            return events
        if size < self.offset:        # truncated in place: restart
            self.offset, self._carry = 0, ""
        if size == self.offset:
            return events
        got, self.offset = self._read_from(self.path, self.offset)
        events.extend(got)
        return events


class Aggregate:
    """Folds the event stream into the state one table frame renders."""

    def __init__(self):
        self.step = None              # latest step event
        self.gauges = {}              # name -> (step, value)
        self.counters = {}
        self.spans = {}               # spans of the newest span-step
        self._span_step = None
        self.artifacts = []           # newest-last (path, name)
        self.hists = {}               # name -> latest hist event fields
        self.traces = 0               # request traces seen
        self.last_trace = None        # newest trace event fields
        self.mem = None               # newest memory-ledger event fields
        self.slo = {}                 # objective name -> newest slo fields
        self.alerts = []              # newest-last alert events (bounded)
        self.alerts_total = 0
        self.events = 0
        self.skips_total = 0
        self.last_t = None

    def feed(self, events):
        for e in events:
            self.events += 1
            self.last_t = e.t
            if e.kind == "step":
                self.step = e
                if e.fields.get("skip"):
                    self.skips_total += 1
            elif e.kind == "gauge":
                self.gauges[e.name] = (e.step, e.value)
            elif e.kind == "counter":
                self.counters[e.name] = (e.step, e.value)
            elif e.kind == "span":
                if e.step != self._span_step:
                    self._span_step = e.step
                    self.spans = {}
                self.spans[e.name] = e
            elif e.kind == "artifact":
                self.artifacts.append((e.name, e.path))
                del self.artifacts[:-4]
            elif e.kind == "hist":
                self.hists[e.name] = e.fields
            elif e.kind == "trace":
                self.traces += 1
                self.last_trace = e.fields
            elif e.kind == "mem":
                self.mem = e.fields
            elif e.kind == "slo":
                self.slo[e.name] = e.fields
            elif e.kind == "alert":
                self.alerts_total += 1
                self.alerts.append(e)
                del self.alerts[:-4]


def _fmt(v, unit=""):
    if v is None:
        return "-"
    if isinstance(v, bool):
        return str(v)
    try:
        v = float(v)
    except (TypeError, ValueError):
        return str(v)
    if unit == "B":
        for u in ("B", "KB", "MB", "GB", "TB"):
            if abs(v) < 1024 or u == "TB":
                return f"{v:.1f}{u}" if u != "B" else f"{v:.0f}B"
            v /= 1024
    if abs(v) >= 1e5 or 0 < abs(v) < 1e-3:
        return f"{v:.3e}"
    if abs(v) >= 10:
        return f"{v:.1f}"
    return f"{v:.4f}"


def render(agg: Aggregate, source: str, clock=time.time) -> str:
    """One table frame as a string (pure: unit-testable)."""
    g = lambda name: agg.gauges.get(name, (None, None))[1]
    c = lambda name: agg.counters.get(name, (None, None))[1]
    step = agg.step
    fields = step.fields if step is not None else {}
    age = (f"{clock() - agg.last_t:5.1f}s ago" if agg.last_t is not None
           else "never")
    lines = [
        f"ds_top — {source}",
        f"events: {agg.events}   last event: {age}",
        "-" * 78,
        f"{'step':>8} {'loss':>10} {'lr':>10} {'tokens/s':>10} "
        f"{'MFU':>7} {'HBM':>9} {'wire/step':>10} {'skips':>6}",
        f"{_fmt(step.step if step else None):>8} "
        f"{_fmt(fields.get('loss')):>10} "
        f"{_fmt(fields.get('lr')):>10} "
        f"{_fmt(g('tokens_per_sec') or g('samples_per_sec')):>10} "
        f"{_fmt(g('mfu')):>7} "
        f"{_fmt(g('device_mem_in_use') or g('hbm_peak_projected'), 'B'):>9} "
        f"{_fmt(c('wire_bytes_per_step'), 'B'):>10} "
        f"{_fmt(fields.get('skipped_steps', agg.skips_total)):>6}",
    ]
    # serving resilience line (docs/serving.md#resilience): rendered when
    # the stream carries serving decode steps or any resilience counter
    srv = {k: c(k) for k in ("shed_total", "deadline_total",
                             "poisoned_total", "requeued_total",
                             "breaker_open")}
    if (any(v is not None for v in srv.values())
            or (step is not None and step.name == "serving_step")):
        lines += [
            "-" * 78,
            f"serving: active {_fmt(fields.get('active_slots'))}  "
            f"queued {_fmt(fields.get('queued'))}  "
            f"shed {_fmt(srv['shed_total'] or 0)}  "
            f"deadline {_fmt(srv['deadline_total'] or 0)}  "
            f"poisoned {_fmt(srv['poisoned_total'] or 0)}  "
            f"requeued {_fmt(srv['requeued_total'] or 0)}  "
            f"breaker {'OPEN' if srv['breaker_open'] else 'closed'}"]
    if agg.hists:
        # whole-run latency percentiles from the mergeable histograms
        # (docs/monitoring.md#histograms) — not a truncated window
        from .histogram import LogHistogram
        parts = []
        for name, payload in sorted(agg.hists.items()):
            try:
                h = LogHistogram.from_dict(payload)
            except (KeyError, TypeError, ValueError):
                continue
            p = h.percentiles()
            if p["p50"] is None:
                continue
            parts.append(
                f"{name} p50 {_fmt(p['p50'])} p99 {_fmt(p['p99'])} "
                f"p999 {_fmt(p['p999'])} (n={h.count})")
        if parts:
            lines += ["-" * 78, "hist: " + "  |  ".join(parts)]
    if agg.mem:
        # memory-ledger line (docs/monitoring.md#memory-explainability):
        # top attributed subsystems per space + the explicit residual
        m = agg.mem
        parts = []
        for space in ("hbm", "host"):
            entries = m.get(space) or {}
            if not entries:
                continue
            top = sorted(entries.items(), key=lambda kv: -kv[1])[:3]
            inner = " ".join(f"{k}={_fmt(v, 'B')}" for k, v in top)
            parts.append(f"{space} {_fmt(sum(entries.values()), 'B')} "
                         f"({inner})")
        resid = m.get("host_residual_bytes")
        if resid is not None:
            parts.append(f"residual {_fmt(resid, 'B')}")
        parts.append(f"rss hwm {_fmt(m.get('rss_hwm_gb'))}GB")
        lines += ["-" * 78, "mem: " + "  |  ".join(parts)]
    if agg.slo or agg.alerts_total:
        # SLO line (docs/monitoring.md#slo-tracking): per-objective
        # verdict — met/BURNING, budget remaining, fast/slow burn rates
        parts = []
        for name, f in sorted(agg.slo.items()):
            bound = (f"<={_fmt(f.get('max'))}" if f.get("max") is not None
                     else f">={_fmt(f.get('min'))}")
            state = "BURNING" if f.get("alerting") else (
                "ok" if f.get("met") else "breached")
            budget_pct = (f.get("budget_remaining_frac") or 0) * 100
            parts.append(
                f"{name} [{f.get('series', '?')}{bound}] {state} "
                f"budget {_fmt(budget_pct)}% "
                f"burn {_fmt(f.get('burn_fast'))}/"
                f"{_fmt(f.get('burn_slow'))}")
        line = "slo: " + ("  |  ".join(parts) if parts else "-")
        if agg.alerts_total:
            last = agg.alerts[-1]
            detail = last.fields.get("state")
            if not detail:
                rel = (last.fields.get("rel_change") or 0) * 100
                detail = f"+{_fmt(rel)}%"
            line += (f"   alerts: {agg.alerts_total} "
                     f"(last {last.name}: "
                     f"{last.fields.get('series', '?')} {detail})")
        lines += ["-" * 78, line]
    if agg.traces:
        lt = agg.last_trace or {}
        lines.append(
            f"traces: {agg.traces} request(s)  last uid "
            f"{_fmt(lt.get('uid'))} [{lt.get('outcome', '?')}] "
            f"ttft {_fmt(lt.get('ttft_ms'))}ms  (--export-trace)")
    if agg.spans:
        root = agg.spans.get("step")
        parts = [f"step {root.dur_s * 1e3:.1f}ms"] if root is not None \
            else []
        parts += [f"{n} {e.dur_s * 1e3:.1f}" for n, e in
                  sorted(((n, e) for n, e in agg.spans.items()
                          if n != "step"), key=lambda kv: -kv[1].dur_s)]
        lines += ["-" * 78, "spans (ms): " + " | ".join(parts)]
    extra = {k: v for k, (_, v) in sorted(agg.gauges.items())
             if k not in ("tokens_per_sec", "samples_per_sec", "mfu",
                          "device_mem_in_use", "hbm_peak_projected")}
    if extra:
        lines.append("gauges: " + "  ".join(
            f"{k}={_fmt(v)}" for k, v in extra.items()))
    if agg.artifacts:
        lines += ["artifacts:"] + [f"  [{n}] {p}" for n, p in
                                   agg.artifacts]
    return "\n".join(lines)


def main(argv=None):
    # fleet mode hands the whole argv to ds_fleet (monitor/fleet.py):
    # N run dirs, merged view, straggler verdict
    argv = sys.argv[1:] if argv is None else list(argv)
    if "--fleet" in argv:
        from .fleet import main as fleet_main
        return fleet_main([a for a in argv if a != "--fleet"])
    ap = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.monitor",
        description="ds_top: live terminal view of a monitor event stream")
    ap.add_argument("run", help="monitor run dir (or an events.jsonl path)")
    ap.add_argument("--fleet", action="store_true",
                    help="merge MULTIPLE run dirs into the ds_fleet view "
                         "(accepts many dirs; see bin/ds_fleet)")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="refresh period in seconds (default 2)")
    ap.add_argument("--once", action="store_true",
                    help="render one frame and exit")
    ap.add_argument("--tail", type=int, default=0,
                    help="with --once: also print the last N raw events")
    ap.add_argument("--export-trace", action="store_true",
                    help="convert the stream's request traces to Chrome "
                         "trace-event JSON (Perfetto-loadable) and exit")
    ap.add_argument("--out", default=None,
                    help="with --export-trace: output path "
                         "(default <run_dir>/trace.json)")
    args = ap.parse_args(argv)

    stream = resolve_stream(args.run)
    if args.export_trace:
        from .trace_export import export_chrome_trace
        if not os.path.exists(stream):
            print(f"ds_top: no event stream at {stream}")
            return 1
        follower = StreamFollower(stream)
        events = follower.poll()
        out = args.out or os.path.join(os.path.dirname(stream),
                                       "trace.json")
        doc = export_chrome_trace(events, out)
        n_req = doc["otherData"]["requests"]
        print(f"exported {n_req} request trace(s) "
              f"({len(doc['traceEvents'])} trace events) -> {out}")
        if n_req == 0:
            print("no `trace` events in the stream — was the run's "
                  "serving.trace_sample_rate > 0 with the monitor on? "
                  "(docs/monitoring.md#request-tracing)")
        return 0
    follower = StreamFollower(stream)
    agg = Aggregate()
    if not os.path.exists(stream) and args.once:
        print(f"ds_top: no event stream at {stream}")
        return 1
    try:
        while True:
            events = follower.poll()
            agg.feed(events)
            frame = render(agg, stream)
            if args.once:
                print(frame)
                if args.tail:
                    for e in (events or [])[-args.tail:]:
                        print(e.to_json())
                return 0
            # full-screen redraw (clear + home); plain prints would scroll
            sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
            sys.stdout.flush()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
