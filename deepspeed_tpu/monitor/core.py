"""The :class:`Monitor`: spans + gauges + counters + trace windows over
one bus, with the engine-facing lifecycle.

Hot-path discipline (the <2% overhead guarantee, docs/monitoring.md):

- **No forced syncs.**  Device scalars (loss, grad norm...) are queued as
  references and synced ONE STEP LATE — the same lag trick the health
  guardian uses (``runtime/health.py``): by the time step *t*'s scalars
  are read, step *t+1* has already been dispatched, so the read blocks
  only on work the device has finished.
- **Nothing in the traced program.**  Spans are host brackets; gauges
  read host state.  ``--audit-step monitor`` asserts zero DSTPU201 host
  callbacks and the jaxpr-equality test pins monitor-on == monitor-off.
- **Interval thinning.**  ``monitor.interval`` emits every Nth step;
  off-interval steps pay only what every step pays, armed or not: the
  span brackets of the process-wide recorder (``monitor/spans.py``).

Disabled monitoring is a :class:`NullMonitor` — shared no-op context
managers, no bus, nothing allocated per step.
"""

import os
import time
from contextlib import contextmanager

from ..utils.logging import logger
from .bus import MonitorBus
from .events import _scalar
from .sinks import RingBufferSink, SinkUnavailable, make_sink
from . import spans as _spans
from .trace import TraceWindow

DEFAULT_RUN_DIR = "ds_monitor"
ENV_ENABLED = "DSTPU_MONITOR"
ENV_DIR = "DSTPU_MONITOR_DIR"
ENV_RUN_ID = "DSTPU_RUN_ID"

# scalar-sync lag in steps (mirrors health_check.check_interval's default):
# reading step t's device scalars after step t+1 dispatched blocks only on
# already-finished work, preserving the engine's async-dispatch overlap
_SCALAR_LAG = 1


def _is_rank0() -> bool:
    try:
        import jax
        return jax.process_index() == 0
    except Exception:
        return True


class _NullCtx:
    """Reusable nothing-context (cheaper than contextlib.nullcontext()
    per call — one shared instance, no allocation on the hot path)."""

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()


class NullMonitor:
    """API-compatible disabled monitor: every method is a no-op."""

    armed = False
    bus = None
    ring = None
    run_dir = None
    run_id = None
    memory_interval = None
    slo = None

    def slo_verdict(self):
        return None

    def span(self, name):
        return _NULL_CTX

    def standalone_span(self, name):
        return _NULL_CTX

    def begin_step(self, root=None):
        pass

    def abort_step(self):
        pass

    def end_step(self, step_no, scalars=None, gauges=None, counters=None,
                 name="train_step"):
        return []

    def should_emit(self, step_no) -> bool:
        return False

    def set_rates(self, **kw):
        pass

    def gauge(self, *a, **kw):
        pass

    def counter(self, *a, **kw):
        pass

    def artifact(self, *a, **kw):
        pass

    def hist(self, *a, **kw):
        pass

    def trace(self, *a, **kw):
        pass

    def mem(self, *a, **kw):
        pass

    def trace_before_step(self, step_no):
        pass

    def flush(self):
        pass

    def close(self):
        pass

    def report(self) -> dict:
        return {"enabled": False}


class _SLOBridge:
    """Pseudo-sink: feeds every bus emission through the SLO evaluator
    (``monitor/slo.py``) and re-emits the due ``slo``/``alert`` events.
    Reentrant ``bus.emit`` is safe — the evaluator ignores the kinds it
    produces — and the bus's failure isolation applies: an evaluator
    bug detaches telemetry, never the step."""

    name = "slo"

    def __init__(self, evaluator, bus):
        self.evaluator = evaluator
        self._bus = bus

    def write(self, event):
        for e in self.evaluator.feed(event):
            self._bus.emit(e)

    def flush(self):
        pass

    def close(self):
        pass


class Monitor:
    """Armed runtime telemetry for one process (see module docstring)."""

    armed = True

    def __init__(self, *, run_dir=None, sinks=("jsonl", "ring"),
                 interval=1, trace_steps=None, ring_size=1024, retry=None,
                 role="train", clock=time.time, memory_interval=None,
                 run_id=None, slo=None, rotate_mb=0):
        self.run_dir = run_dir
        self.role = role
        self.interval = max(1, int(interval))
        # replica stamp for fleet merges (monitor/fleet.py): explicit >
        # env DSTPU_RUN_ID > host-pid.  Stamped on every event by the bus.
        self.run_id = str(run_id or os.environ.get(ENV_RUN_ID, "").strip()
                          or _default_run_id())
        # memory-ledger cadence carried WITH the monitor so consumers
        # that never see the config block (ServingEngine takes a Monitor
        # object) still honor `monitor.memory_interval` — None means
        # "use the consumer's role default", 0 disables the ledger
        self.memory_interval = (None if memory_interval is None
                                else int(memory_interval))
        self.spans = _spans.recorder()   # the process-wide one
        self.ring = None
        built = []
        rank0 = _is_rank0()
        for kind in sinks:
            if kind != "ring" and not rank0:
                continue              # file/export sinks are rank-0 only
            if kind != "ring" and not run_dir:
                logger.warning(f"monitor: sink {kind!r} needs a run dir; "
                               "skipped")
                continue
            try:
                sink = make_sink(kind, run_dir, retry=retry,
                                 ring_size=ring_size,
                                 rotate_bytes=int(rotate_mb or 0) << 20)
            except SinkUnavailable as e:
                logger.warning(f"monitor: sink {kind!r} unavailable ({e}); "
                               "continuing without it")
                continue
            if isinstance(sink, RingBufferSink):
                self.ring = sink.ring
            built.append(sink)
        self.bus = MonitorBus(built, clock=clock, run_id=self.run_id)
        # SLO engine (monitor/slo.py): a bridge sink feeds every bus
        # emission through the evaluator and re-emits the due slo/alert
        # events — live and offline replay share one code path
        from .slo import SLOConfig
        self.slo = None
        slo_cfg = SLOConfig.from_value(slo)
        if slo_cfg is not None:
            from .slo import SLOEvaluator
            self.slo = SLOEvaluator(slo_cfg)
            self.bus.attach(_SLOBridge(self.slo, self.bus))
        self._trace = None
        if trace_steps:
            start, stop = trace_steps
            self._trace = TraceWindow(
                os.path.join(run_dir or DEFAULT_RUN_DIR, "traces"),
                start, stop)
        self._rates = {}              # tokens_per_step/flops_per_step/peak
        self._root = None             # the open step's root span
        self._owns_root = False       # opened here, not by an engine
        self._pending = []            # lagged step-event queue
        self._tail = None             # newest interval-thinned step (the
        #                               flush-at-close fix: a 7-step run
        #                               at interval=5 must not lose steps
        #                               6-7's gauges from the stream)
        self._last_step = None
        self.steps_seen = 0

    # ---------------------------------------------------------------- spans
    def span(self, name):
        """Nested span context; records only inside an open step (so
        preflight/audit calls through instrumented helpers stay silent)."""
        if self._root is None:
            return _NULL_CTX
        return self.spans.span(name)

    @contextmanager
    def standalone_span(self, name):
        """Span outside any step (checkpoint commit, eval): recorded like
        any other, emitted immediately."""
        rec = self.spans.open(name, step=self._last_step)
        try:
            yield
        finally:
            self.bus.span(name, self.spans.close(rec), step=self._last_step)

    # ---------------------------------------------------------------- steps
    def begin_step(self, root=None):
        """Start a step.  An engine hands in the root span it has opened
        in the recorder (it records with or without a monitor, and closes
        the root itself); without one the monitor opens and owns a root
        called ``step``."""
        if self._root is not None and self._owns_root:
            # a step aborted mid-flight (exception between begin and
            # end): its partial spans must not fold into this step
            self.spans.discard(self._root)
        self._owns_root = root is None
        self._root = self.spans.open("step") if root is None else root

    def abort_step(self):
        """Forget the open step WITHOUT emitting — for idle or aborted
        iterations (a serving scheduler poll with no active slots would
        otherwise overwrite the last real step's breakdown under a reused
        step number).  A root the monitor opened is discarded with its
        spans; an engine discards its own."""
        if self._root is not None and self._owns_root:
            self.spans.discard(self._root)
        self._root = None

    def should_emit(self, step_no) -> bool:
        """True when this step's events would actually land somewhere:
        on the interval AND with at least one live sink.  The bus-less
        monitor `wall_clock_breakdown` arms (and a run whose sinks all
        died) then skips gauge computation, the lagged scalar sync, and
        — engine-side — the one-time executable pricing entirely; spans
        are still measured for the breakdown log."""
        return step_no % self.interval == 0 and bool(self.bus.sinks)

    def set_rates(self, **kw):
        """Per-step denominators for the rate gauges: ``tokens_per_step``,
        ``samples_per_step``, ``flops_per_step``, ``peak_flops`` (set
        lazily by the engine once each is known)."""
        for k, v in kw.items():
            if v is not None:
                self._rates[k] = v

    def end_step(self, step_no, scalars=None, gauges=None, counters=None,
                 name="train_step"):
        """End the step and emit (span events + rate gauges now; the
        scalar ``step`` event one step late).  The step's wall time runs
        from its root's start to this call: a root the monitor opened is
        closed here, an engine's stays open for the engine to close.
        Returns the step's completed spans as ``(name, parent, dur_s)``,
        root last, names without the root's layer prefix (the
        ``wall_clock_breakdown`` feed and the ``span`` events)."""
        root = self._root
        if root is None:
            return []
        self._root = None
        rows = self.spans.since(root)
        wall = (self.spans.close(root) if self._owns_root
                else self.spans.now() - root.t0)
        done = [(_spans.leaf(r.name, root.name),
                 _spans.leaf(r.parent, root.name), r.t_end - r.t_start)
                for r in rows if r.parent is not None]
        done.append((_spans.leaf(root.name, root.name), None, wall))
        self._last_step = step_no
        self.steps_seen += 1
        if not self.should_emit(step_no):
            # off-interval: stash the newest step so a terminal flush
            # (drain/close) can still land it — interval thinning must
            # not drop the run's FINAL steps from the stream
            if bool(self.bus.sinks):
                self._tail = (step_no, name, dict(scalars or {}), wall,
                              dict(gauges or {}), dict(counters or {}))
            if self._trace is not None:
                self._trace_after(step_no)
            return done
        self._tail = None
        for sname, parent, dur_s in done:
            self.bus.span(sname, dur_s, step=step_no, parent=parent)
        self._emit_rate_gauges(step_no, wall)
        for gname, gval in (gauges or {}).items():
            self.bus.gauge(gname, gval, step=step_no)
        for cname, cval in (counters or {}).items():
            self.bus.counter(cname, cval, step=step_no)
        self._pending.append((step_no, name, dict(scalars or {}),
                              wall))
        while len(self._pending) > _SCALAR_LAG:
            self._emit_step(self._pending.pop(0))
        # one buffered write per emitted step: ds_top's tail stays at
        # most `interval` steps behind while the hot path pays a single
        # append syscall
        self.bus.flush()
        if self._trace is not None:
            self._trace_after(step_no)
        return done

    def _emit_rate_gauges(self, step_no, wall_s):
        if wall_s <= 0:
            return
        r = self._rates
        if r.get("tokens_per_step"):
            self.bus.gauge("tokens_per_sec", r["tokens_per_step"] / wall_s,
                           step=step_no)
        if r.get("samples_per_step"):
            self.bus.gauge("samples_per_sec",
                           r["samples_per_step"] / wall_s, step=step_no)
        if r.get("flops_per_step") and r.get("peak_flops"):
            self.bus.gauge(
                "mfu", r["flops_per_step"] / wall_s / r["peak_flops"],
                step=step_no)

    def _emit_step(self, entry):
        step_no, name, scalars, wall = entry
        fields = {}
        for k, v in scalars.items():
            try:
                fields[k] = _scalar(v)    # device ref -> host (lagged sync)
            except Exception:
                continue
        fields["wall_s"] = wall
        self.bus.step(name, step_no, value=fields.get("loss"), **fields)

    # ---------------------------------------------------- one-off emissions
    def gauge(self, name, value, step=None, **fields):
        self.bus.gauge(name, value, step=step if step is not None
                       else self._last_step, **fields)

    def counter(self, name, value, step=None, **fields):
        self.bus.counter(name, value, step=step if step is not None
                         else self._last_step, **fields)

    def artifact(self, name, path, step=None, **fields):
        self.bus.artifact(name, path, step=step if step is not None
                          else self._last_step, **fields)

    def hist(self, name, hist, step=None, **fields):
        self.bus.hist(name, hist, step=step if step is not None
                      else self._last_step, **fields)

    def trace(self, name, step=None, **fields):
        self.bus.trace(name, step=step if step is not None
                       else self._last_step, **fields)

    def mem(self, name, step=None, **fields):
        self.bus.mem(name, step=step if step is not None
                     else self._last_step, **fields)

    # ----------------------------------------------------------------- trace
    def trace_before_step(self, step_no):
        if self._trace is not None:
            self._trace.before_step(step_no)

    def _trace_after(self, step_no):
        path = self._trace.after_step(step_no)
        if path is not None:
            self.bus.artifact("profiler_trace", path, step=step_no,
                              start_step=self._trace.start_step,
                              stop_step=self._trace.stop_step)
            self.bus.flush()

    # ----------------------------------------------------------------- slo
    def slo_verdict(self):
        """The SLO engine's roll-up verdict (None when ``monitor.slo``
        is not configured) — what ``ServingEngine.slo_report()`` and the
        bench/autotuner consume (docs/monitoring.md#slo-tracking)."""
        return self.slo.verdict() if self.slo is not None else None

    # ------------------------------------------------------------- lifecycle
    def flush(self):
        if self._tail is not None:
            # terminal flush of the newest interval-thinned step: its
            # step event, rate gauges and host gauges/counters land now,
            # so short runs and ds_fleet merges see complete streams
            step_no, name, scalars, wall, gauges, counters = self._tail
            self._tail = None
            self._emit_rate_gauges(step_no, wall)
            for gname, gval in gauges.items():
                self.bus.gauge(gname, gval, step=step_no)
            for cname, cval in counters.items():
                self.bus.counter(cname, cval, step=step_no)
            self._pending.append((step_no, name, scalars, wall))
        while self._pending:
            self._emit_step(self._pending.pop(0))
        self.bus.flush()

    def close(self):
        if self._trace is not None:
            self._trace.abort()
        self.flush()
        if self.slo is not None:
            # whole-run SLO verdict, one terminal `slo` event per
            # objective (short runs may never hit the emit cadence)
            for e in self.slo.final_events(step=self._last_step,
                                           t=time.time()):
                self.bus.emit(e)
            self.bus.flush()
        self.bus.close()

    def report(self) -> dict:
        return {"enabled": True, "dir": self.run_dir, "role": self.role,
                "interval": self.interval, "run_id": self.run_id,
                "sinks": [getattr(s, "name", "?") for s in self.bus.sinks],
                "dead_sinks": dict(self.bus.dead_sinks),
                "events_emitted": self.bus.emitted,
                "slo": (self.slo.cfg.describe() if self.slo is not None
                        else None),
                "steps_seen": self.steps_seen}


def _default_run_id() -> str:
    """host-pid replica stamp: unique enough to tell fleet replicas
    apart without coordination (explicit ``monitor.run_id`` / env
    ``DSTPU_RUN_ID`` wins for stable names)."""
    import socket
    try:
        host = socket.gethostname().split(".")[0]
    except OSError:
        host = "host"
    return f"{host}-{os.getpid()}"


def env_enabled(default=None):
    """The DSTPU_MONITOR env override, parsed ONCE here for every
    consumer (config block, serving engine): True/False when the var is
    set, ``default`` when unset."""
    v = os.environ.get(ENV_ENABLED, "").strip().lower()
    if not v:
        return default
    return v in ("1", "true", "yes", "on")


def resolve_run_dir(cfg_dir=None) -> str:
    """Monitor output dir: config ``monitor.dir`` > env ``DSTPU_MONITOR_DIR``
    (set by ``deepspeed --monitor-dir``) > ``./ds_monitor``."""
    return (cfg_dir or os.environ.get(ENV_DIR, "").strip()
            or os.path.join(os.getcwd(), DEFAULT_RUN_DIR))


def from_config(cfg, *, override_enabled=None, retry=None, role="train"):
    """Build the engine's monitor from its parsed ``monitor`` config
    block, honoring the kwarg > env > config precedence (the env is
    already folded into ``cfg.enabled`` at parse time; the kwarg arrives
    here as ``override_enabled``)."""
    enabled = cfg.enabled if override_enabled is None else override_enabled
    if not enabled:
        return NullMonitor()
    return Monitor(run_dir=resolve_run_dir(cfg.dir), sinks=cfg.sinks,
                   interval=cfg.interval, trace_steps=cfg.trace_steps,
                   ring_size=cfg.ring_size, retry=retry, role=role,
                   memory_interval=getattr(cfg, "memory_interval", None),
                   run_id=getattr(cfg, "run_id", None),
                   slo=getattr(cfg, "slo", None),
                   rotate_mb=getattr(cfg, "rotate_mb", 0))
