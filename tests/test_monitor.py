"""Unified runtime telemetry (``deepspeed_tpu/monitor``; docs/monitoring.md):
event schema round-trip, sink failure isolation, ring bounds, the engine's
span/gauge/counter stream, the compiled-step purity guarantee (jaxpr
equality monitor-on vs monitor-off), the overhead bound, trace capture,
``ds_top``, the DSTPU104 lint rule, and the timer satellite fixes.
"""

import json
import os
import sys

import numpy as np
import pytest
import jax

import deepspeed_tpu as ds
from deepspeed_tpu.monitor import (Event, parse_line, RingBuffer,
                                   MonitorBus, SpanRecorder, JSONLSink,
                                   CSVSink, RingBufferSink, Monitor,
                                   NullMonitor, EVENTS_FILE)
from deepspeed_tpu.monitor.events import SCHEMA_VERSION

from simple_model import SimpleModel, random_dataset, base_config


def _events(run_dir):
    path = os.path.join(str(run_dir), EVENTS_FILE)
    with open(path) as f:
        return [parse_line(ln) for ln in f if ln.strip()]


def _by_kind(events):
    out = {}
    for e in events:
        out.setdefault(e.kind, []).append(e)
    return out


# ---------------------------------------------------------------------------
# schema
# ---------------------------------------------------------------------------

def test_event_schema_roundtrip():
    """JSONL -> parse -> the same event, for every kind."""
    samples = [
        Event(kind="step", name="train_step", t=123.5, step=7, value=2.25,
              fields={"loss": 2.25, "lr": 1e-3, "skip": False}),
        Event(kind="span", name="dispatch", t=1.0, step=7, dur_s=0.012,
              parent="step"),
        Event(kind="gauge", name="mfu", t=2.0, step=7, value=0.41),
        Event(kind="counter", name="wire_bytes_per_step", t=3.0, step=7,
              value=4096),
        Event(kind="artifact", name="profiler_trace", t=4.0,
              path="/tmp/x.xplane.pb", fields={"start_step": 2}),
    ]
    for e in samples:
        line = e.to_json()
        assert "\n" not in line
        assert parse_line(line) == e
    # version is on the wire, stamped PER KIND (v1 kinds stay v1 under a
    # v2 producer — the forward-compat contract), and gates parsing
    d = samples[0].to_dict()
    assert d["v"] == 1                    # "step" is a v1 kind
    d["v"] = SCHEMA_VERSION + 1
    with pytest.raises(ValueError):
        Event.from_dict(d)


def test_schema_v1_to_v2_forward_compat():
    """v2 adds `hist`/`trace` kinds stamped v:2.  A v1 reader
    (max_version=1) must parse every v1 event from a mixed v2 stream and
    reject EXACTLY the new kinds — which stream followers count-and-skip
    — while the v2 reader round-trips everything."""
    from deepspeed_tpu.monitor.histogram import LogHistogram
    h = LogHistogram()
    h.add_many([1.0, 5.0, 250.0])
    mixed = [
        Event(kind="step", name="serving_step", t=1.0, step=4,
              fields={"wall_s": 0.01}),
        Event(kind="hist", name="latency_ms", t=2.0, step=4,
              fields=h.to_dict()),
        Event(kind="trace", name="request", t=3.0, step=4,
              fields={"uid": 7, "outcome": "ok",
                      "spans": [{"name": "queue_wait", "start_ms": 0.0,
                                 "dur_ms": 1.5}]}),
        Event(kind="gauge", name="mfu", t=4.0, step=4, value=0.4),
    ]
    assert [e.v for e in mixed] == [1, 2, 2, 1]
    lines = [e.to_json() for e in mixed]
    # v2 reader: full round-trip, nested payloads intact
    parsed = [parse_line(ln) for ln in lines]
    assert parsed == mixed
    assert parsed[2].fields["spans"][0]["name"] == "queue_wait"
    # v1 reader: the v1 kinds parse, the new kinds raise (skippable)
    ok, skipped = [], 0
    for ln in lines:
        try:
            ok.append(parse_line(ln, max_version=1))
        except ValueError:
            skipped += 1
    assert [e.kind for e in ok] == ["step", "gauge"]
    assert skipped == 2


def test_event_rejects_unknown_kind_and_sanitizes():
    with pytest.raises(ValueError):
        Event(kind="metricish", name="x", t=0.0)
    # numpy scalars become plain python; non-finite floats stay parseable
    e = Event(kind="gauge", name="g", t=0.0, value=np.float32(2.5),
              fields={"z": float("nan")})
    assert isinstance(e.value, float) and e.value == 2.5
    parsed = json.loads(e.to_json())      # strict JSON (allow_nan=False)
    assert parsed["fields"]["z"] == "nan"


def test_ring_buffer_bounds():
    ring = RingBuffer(8)
    for i in range(20):
        ring.append(i)
    assert len(ring) == 8
    assert ring.to_list() == list(range(12, 20))
    assert ring[0] == 12 and ring[-1] == 19
    with pytest.raises(ValueError):
        RingBuffer(0)


# ---------------------------------------------------------------------------
# bus + sinks
# ---------------------------------------------------------------------------

class _BoomSink:
    name = "boom"
    writes = 0

    def write(self, event):
        _BoomSink.writes += 1
        raise RuntimeError("sink exploded")

    def flush(self):
        pass

    def close(self):
        pass


def test_sink_failure_isolation():
    """A raising sink detaches after ONE write and never kills emission;
    the surviving sinks keep receiving."""
    _BoomSink.writes = 0
    ring = RingBufferSink(maxlen=16)
    bus = MonitorBus([_BoomSink(), ring])
    bus.gauge("a", 1.0)
    bus.gauge("b", 2.0)
    bus.gauge("c", 3.0)
    assert _BoomSink.writes == 1          # detached after the first raise
    assert "boom" in bus.dead_sinks
    assert [e.name for e in ring.ring] == ["a", "b", "c"]


def test_jsonl_and_csv_sinks(tmp_path):
    jpath = tmp_path / "events.jsonl"
    cpath = tmp_path / "events.csv"
    js = JSONLSink(str(jpath))
    cs = CSVSink(str(cpath))
    bus = MonitorBus([js, cs])
    bus.step("train_step", 1, value=0.5, loss=0.5)
    bus.span("dispatch", 0.01, step=1, parent="step")
    bus.flush()
    evs = [parse_line(ln) for ln in jpath.read_text().splitlines()]
    assert [e.kind for e in evs] == ["step", "span"]
    rows = cpath.read_text().splitlines()
    assert rows[0].startswith("v,kind,name")
    assert len(rows) == 3


def test_span_recorder_nesting():
    """Rows carry start and end on one clock, the enclosing span's name,
    and the step and uid of the enclosing span unless they give their own."""
    rec = SpanRecorder()
    root = rec.open("step", step=7)
    with rec.span("data_fetch"):
        pass
    with rec.span("dispatch", uid=3, attrs={"bucket": 64}):
        with rec.span("inner"):
            pass
    rec.close(root)
    rows = rec.rows()
    assert [r.name for r in rows] == ["data_fetch", "inner", "dispatch",
                                      "step"]             # completion order
    done = {r.name: r for r in rows}
    assert done["data_fetch"].parent == "step"
    assert done["inner"].parent == "dispatch"
    assert done["step"].parent is None
    for r in rows:
        assert r.t_start <= r.t_end
        assert r.step == 7                                # inherited
        if r.parent is not None:                          # enclosed
            up = done[r.parent]
            assert up.t_start <= r.t_start and r.t_end <= up.t_end
    assert done["inner"].uid == done["dispatch"].uid == 3
    assert done["step"].uid is done["data_fetch"].uid is None
    assert done["dispatch"].attrs == {"bucket": 64}
    assert rec.depth == 0
    assert rec.close(root) >= 0 and len(rec.rows()) == 4  # closed once
    assert rec.since(root) == rows


def test_span_recorder_close_after_skipped_inner_closes():
    rec = SpanRecorder()
    root = rec.open("step")
    rec.open("left_open")           # an exception skipped its close
    rec.close(root)
    assert [r.name for r in rec.rows()] == ["left_open", "step"]
    assert rec.depth == 0


def test_span_ring_drops_oldest_and_counts():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]
    rec = SpanRecorder(capacity=4, clock=clock)
    assert rec.dropped == 0 and rec.dropped_until is None
    for i in range(6):
        with rec.span(f"s{i}"):
            pass
    assert [r.name for r in rec.rows()] == ["s2", "s3", "s4", "s5"]
    assert rec.dropped == 2
    # the newest dropped row is s1: a reader whose range starts after its
    # end lost nothing
    s2 = rec.rows()[0]
    assert rec.dropped_until == s2.t_start - 1.0 < s2.t_start
    rec.reset()
    assert rec.rows() == [] and rec.dropped == 0


def test_span_discard_drops_the_bracket_and_keeps_recorded_events():
    rec = SpanRecorder()
    with rec.span("kept"):
        pass
    root = rec.open("poll", step=1)
    with rec.span("admit"):
        # a request refused during an idle poll is an event of its own
        rec.record("request", 0.25, 0.5, uid=9, attrs={"outcome": "shed"})
    rec.discard(root)
    assert [r.name for r in rec.rows()] == ["kept", "request"]
    assert rec.rows("request")[0].uid == 9
    assert rec.depth == 0 and rec.dropped == 0
    rec.close(root)                                       # nothing more
    assert len(rec.rows()) == 2


def test_spans_are_trace_annotations_on_the_profilers_clock(tmp_path):
    """Every span is a ``ds.<name>`` TraceAnnotation: a profiler capture
    holds it on a host line, with step and uid as metadata, nested as
    recorded; never under the benchmark's ``bench.`` prefix."""
    from jax.profiler import ProfileData
    from deepspeed_tpu.monitor.trace import newest_trace_artifact
    rec = SpanRecorder()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with rec.span("serving.step", step=3):
            with rec.span("serving.admit", uid=9):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = {}
    for plane in ProfileData.from_file(
            newest_trace_artifact(str(tmp_path))).planes:
        for line in plane.lines:
            for ev in line.events:
                assert not ev.name.startswith("bench.")
                if ev.name.startswith("ds."):
                    assert plane.name.startswith("/host:")
                    events[ev.name] = (ev.start_ns,
                                       ev.start_ns + ev.duration_ns,
                                       dict(ev.stats))
    assert set(events) == {"ds.serving.step", "ds.serving.admit"}
    s0, s1, smeta = events["ds.serving.step"]
    a0, a1, ameta = events["ds.serving.admit"]
    assert s0 <= a0 <= a1 <= s1
    assert smeta["step"] == 3 and ameta == {"step": 3, "uid": 9}


def test_monitor_step_is_a_view_of_the_recorder():
    """``begin_step`` / ``end_step`` read the process-wide recorder: an
    engine's root is adopted and left for the engine to close, and the
    ``span`` events keep their short names."""
    from deepspeed_tpu.monitor import spans as monspans
    mon = Monitor(run_dir=None, sinks=("ring",))
    rec = monspans.recorder()
    assert mon.spans is rec
    root = rec.open("serving.step", step=5)
    mon.begin_step(root)
    with rec.span("serving.admit"):
        with rec.span("serving.prefill", uid=1):
            with rec.span("serving.prefill.dispatch"):
                pass
    rec.record("serving.request", 0.0, 1.0, uid=1)    # not a span event
    done = mon.end_step(5, name="serving_step")
    assert [(n, p) for n, p, _ in done] == [
        ("prefill.dispatch", "prefill"), ("prefill", "admit"),
        ("admit", "step"), ("step", None)]
    assert not root.closed                             # the engine's
    rec.close(root)
    evs = [e for e in mon.ring.to_list() if e.kind == "span"]
    assert [e.name for e in evs] == ["prefill.dispatch", "prefill", "admit",
                                     "step"]
    assert all(e.step == 5 and e.dur_s >= 0 for e in evs)
    # a root of the monitor's own is opened and closed by it
    mon.begin_step()
    assert [n for n, _, _ in mon.end_step(6)] == ["step"]
    assert rec.rows()[-1].name == "step" and rec.depth == 0
    # an aborted own step is discarded, not recorded
    n = len(rec.rows())
    mon.begin_step()
    mon.abort_step()
    assert len(rec.rows()) == n and rec.depth == 0
    mon.close()


# ---------------------------------------------------------------------------
# engine end-to-end (the acceptance scenario)
#
# The engine-building integration tests are compile-heavy and live in the
# slow tier (--runslow / RUN_SLOW=1), like every other engine suite here
# — the default fast tier keeps one cheap armed-engine smoke plus the
# pure-unit coverage above.
# ---------------------------------------------------------------------------

def test_monitor_smoke_fast(tmp_path, mesh8):
    """Fast-tier smoke: an armed engine streams parseable step/span/gauge
    events (the deep assertions live in the slow-tier twins below)."""
    cfg = base_config(over={
        "monitor": {"enabled": True, "dir": str(tmp_path)}})
    e, _, _, _ = ds.initialize(config=cfg, model=SimpleModel(),
                               training_data=random_dataset(64), mesh=mesh8)
    e.train_batch()
    e.train_batch()
    e.monitor.flush()
    kinds = _by_kind(_events(tmp_path))
    assert {"step", "span", "gauge"} <= set(kinds)
    assert "loss" in kinds["step"][-1].fields
    e.close()


@pytest.fixture
def z3_monitored(tmp_path, mesh_2x4):
    cfg = base_config(over={
        "zero_optimization": {"stage": 3},
        "monitor": {"enabled": True, "dir": str(tmp_path), "interval": 1}})
    engine, _, _, _ = ds.initialize(config=cfg, model=SimpleModel(),
                                    training_data=random_dataset(64),
                                    mesh=mesh_2x4)
    yield engine, tmp_path
    engine.close()


@pytest.mark.slow
def test_zero3_monitor_stream(z3_monitored):
    """ZeRO-3 + armed monitor emits a parseable JSONL stream with spans
    (breakdown summing to ~step wall), MFU/HBM gauges, and per-step
    wire-byte counters — the acceptance scenario."""
    engine, run_dir = z3_monitored
    for _ in range(4):
        engine.train_batch()
    engine.monitor.flush()
    kinds = _by_kind(_events(run_dir))
    # step events carry the training scalars (one step of lag -> >= 3)
    steps = kinds["step"]
    assert len(steps) >= 3
    assert {"loss", "lr", "grad_norm", "wall_s"} <= set(steps[-1].fields)
    assert steps[-1].value == steps[-1].fields["loss"]
    # spans: a root "step" with the dispatch-path children, and the
    # children sum to ~the root (nothing large is unaccounted)
    last = max(e.step for e in kinds["span"])
    spans = {e.name: e for e in kinds["span"] if e.step == last}
    assert {"step", "data_fetch", "h2d_upload", "dispatch"} <= set(spans)
    root = spans["step"].dur_s
    kids = sum(e.dur_s for e in spans.values() if e.parent == "step")
    assert 0 < kids <= root * 1.05
    assert root > 0.5 * sum(e.dur_s for e in spans.values()
                            if e.parent == "step")
    # gauges: MFU (XLA cost analysis / measured wall) and an HBM reading
    # (live stats, or the memory_analysis projection on this backend)
    gauges = {e.name for e in kinds["gauge"]}
    assert "mfu" in gauges
    assert "device_mem_in_use" in gauges or "hbm_peak_projected" in gauges
    assert "samples_per_sec" in gauges
    mfu = [e for e in kinds["gauge"] if e.name == "mfu"][-1]
    assert mfu.value > 0
    # counters: the compiled step's collective census priced per step
    counters = {e.name: e for e in kinds["counter"]}
    assert counters["wire_bytes_per_step"].value > 0
    assert counters["wire_logical_bytes_per_step"].value >= \
        counters["wire_quantized_bytes_per_step"].value


@pytest.mark.slow
def test_monitor_off_is_null_and_jaxpr_identical(tmp_path, mesh8):
    """The armed monitor must not change the traced program: jaxpr text
    of the compiled step is byte-identical monitor-on vs monitor-off
    (the PR-3 equality gate applied to telemetry)."""
    def build(mon):
        over = {"zero_optimization": {"stage": 2}}
        if mon:
            over["monitor"] = {"enabled": True, "dir": str(tmp_path)}
        e, _, _, _ = ds.initialize(config=base_config(over=over),
                                   model=SimpleModel(),
                                   training_data=random_dataset(64),
                                   mesh=mesh8)
        return e

    # the ONE normalized-jaxpr helper the audit stage also uses — the
    # gate and the test cannot drift
    from deepspeed_tpu.analysis.jaxpr_audit import train_step_jaxpr_text \
        as jaxpr_text

    off = build(False)
    on = build(True)
    assert isinstance(off.monitor, NullMonitor)
    assert not off.monitor.armed and on.monitor.armed
    try:
        assert jaxpr_text(off) == jaxpr_text(on)
        assert "callback" not in jaxpr_text(on)
    finally:
        off.close()
        on.close()


@pytest.mark.slow
def test_monitor_overhead_within_noise(tmp_path, mesh8):
    """Armed-vs-off step-time delta stays within noise on the fast tier
    (the <2% production guarantee is asserted loosely here: tiny CPU
    steps are ~ms, so the bound is a generous multiple, not 2%)."""
    import time as _time

    def run(mon):
        # no compile cache for EITHER twin: a warm-started engine pays
        # the CPU copy-on-donate dispatch path (compile_cache.py) that a
        # freshly-compiled one does not — with the session cache on, the
        # second engine built would warm-start and the comparison would
        # measure cache dispatch asymmetry, not monitor overhead
        over = {"zero_optimization": {"stage": 1},
                "compile_cache": {"enabled": False}}
        if mon:
            over["monitor"] = {"enabled": True, "dir": str(tmp_path)}
        e, _, _, _ = ds.initialize(config=base_config(over=over),
                                   model=SimpleModel(),
                                   training_data=random_dataset(128),
                                   mesh=mesh8)
        for _ in range(3):
            e.train_batch()          # warmup/compile
        times = []
        for _ in range(15):
            t0 = _time.perf_counter()
            e.train_batch()
            times.append(_time.perf_counter() - t0)
        e.close()
        return float(np.median(times))

    t_off = run(False)
    t_on = run(True)
    assert t_on <= t_off * 1.75 + 0.005, \
        f"monitor overhead out of bounds: off={t_off:.5f}s on={t_on:.5f}s"


@pytest.mark.slow
def test_monitor_interval_thins_emission(tmp_path, mesh8):
    cfg = base_config(over={
        "monitor": {"enabled": True, "dir": str(tmp_path), "interval": 3}})
    e, _, _, _ = ds.initialize(config=cfg, model=SimpleModel(),
                               training_data=random_dataset(64), mesh=mesh8)
    for _ in range(6):
        e.train_batch()
    e.monitor.flush()
    kinds = _by_kind(_events(tmp_path))
    assert {ev.step for ev in kinds["step"]} == {3, 6}
    assert {ev.step for ev in kinds["span"]} == {3, 6}
    e.close()


@pytest.mark.slow
def test_trace_capture_window(tmp_path, mesh8):
    """monitor.trace_steps brackets jax.profiler around the step range
    and announces the xplane artifact on the bus."""
    cfg = base_config(over={
        "monitor": {"enabled": True, "dir": str(tmp_path),
                    "trace_steps": [2, 2]}})
    e, _, _, _ = ds.initialize(config=cfg, model=SimpleModel(),
                               training_data=random_dataset(64), mesh=mesh8)
    for _ in range(3):
        e.train_batch()
    e.monitor.flush()
    arts = [ev for ev in _events(tmp_path) if ev.kind == "artifact"
            and ev.name == "profiler_trace"]
    e.close()
    assert arts, "no profiler_trace artifact event emitted"
    assert os.path.exists(arts[-1].path)
    assert arts[-1].fields["start_step"] == 2


@pytest.mark.slow
def test_checkpoint_artifact_and_commit_span(tmp_path, mesh8):
    mon_dir = tmp_path / "mon"
    cfg = base_config(over={
        "monitor": {"enabled": True, "dir": str(mon_dir)}})
    e, _, _, _ = ds.initialize(config=cfg, model=SimpleModel(),
                               training_data=random_dataset(64), mesh=mesh8)
    e.train_batch()
    e.save_checkpoint(str(tmp_path / "ckpt"))
    e.monitor.flush()
    evs = _events(mon_dir)
    arts = [ev for ev in evs if ev.kind == "artifact"
            and ev.name == "checkpoint"]
    spans = [ev for ev in evs if ev.kind == "span"
             and ev.name == "checkpoint_commit"]
    e.close()
    assert arts and os.path.isdir(arts[-1].path)
    assert spans and spans[-1].dur_s > 0


@pytest.mark.slow
def test_tensorboard_routes_through_bus_without_torch(tmp_path, mesh8):
    """tensorboard.enabled attaches a NON-torch sink to the bus; the old
    torch.utils.tensorboard import must never happen."""
    before = "torch.utils.tensorboard" in sys.modules
    cfg = base_config(over={
        "tensorboard": {"enabled": True, "output_path": str(tmp_path),
                        "job_name": "tbrun"}})
    e, _, _, _ = ds.initialize(config=cfg, model=SimpleModel(),
                               training_data=random_dataset(64), mesh=mesh8)
    assert not before and "torch.utils.tensorboard" not in sys.modules
    # in this container tensorboardX is importable -> the sink attached
    # and armed a bus-only monitor; elsewhere it degrades to a warning
    names = [getattr(s, "name", "") for s in
             (e.monitor.bus.sinks if e.monitor.armed else ())]
    if e.monitor.armed:
        assert "tensorboard" in names
        e.train_batch()
    e.close()


@pytest.mark.slow
def test_wall_clock_breakdown_feeds_named_timers(mesh8):
    """wall_clock_breakdown (previously parsed and dead) now records the
    measured spans into the SynchronizedWallClockTimer registry."""
    cfg = base_config(over={"wall_clock_breakdown": True})
    e, _, _, _ = ds.initialize(config=cfg, model=SimpleModel(),
                               training_data=random_dataset(64), mesh=mesh8)
    assert e.monitor.armed            # bus-less monitor armed for spans
    assert e.monitor.bus.sinks == ()  # ...but nothing is written anywhere
    for _ in range(2):
        e.train_batch()
    assert e.timers.has_timer("dispatch")
    assert e.timers("dispatch").elapsed_ > 0
    assert e.timers.has_timer("step")
    e.close()


# ---------------------------------------------------------------------------
# health guardian integration (ring absorption + bus events)
# ---------------------------------------------------------------------------

def test_health_history_is_monitor_ring():
    from deepspeed_tpu.runtime.config import DeepSpeedHealthCheckConfig
    from deepspeed_tpu.runtime.health import HealthMonitor
    mon = HealthMonitor(DeepSpeedHealthCheckConfig(
        {"health_check": {"history": 16}}))
    assert isinstance(mon.history, RingBuffer)
    assert mon.history.maxlen == 16


def test_health_events_reach_bus(tmp_path):
    from deepspeed_tpu.runtime.config import DeepSpeedHealthCheckConfig
    from deepspeed_tpu.runtime.health import HealthMonitor
    ring = RingBufferSink(maxlen=32)
    bus = MonitorBus([ring])
    mon = HealthMonitor(DeepSpeedHealthCheckConfig({}), bus=bus)
    mon.record_rewind(tag="global_step5")
    path = mon.forensic_dump(str(tmp_path), "test-abort")
    names = [e.name for e in ring.ring]
    assert "health_rewind" in names
    assert "health_forensics" in names
    art = [e for e in ring.ring if e.name == "health_forensics"][-1]
    assert art.path == path and os.path.isfile(path)


# ---------------------------------------------------------------------------
# timers (satellite: avg_step_time + span feed)
# ---------------------------------------------------------------------------

def test_throughput_timer_avg_step_time():
    from deepspeed_tpu.utils.timer import ThroughputTimer
    t = ThroughputTimer(batch_size=8, start_step=0,
                        steps_per_output=10 ** 9)
    assert t.avg_step_time() == 0.0       # nothing counted yet
    for _ in range(3):
        t.start()
        t.stop(global_step=True)
    assert t.global_step_count == 3
    expected = t.total_elapsed_time / 3
    assert t.avg_step_time() == pytest.approx(expected)
    # the flops profiler consumes this directly (no hasattr guessing)
    assert t.avg_samples_per_sec() == pytest.approx(
        8 / t.avg_step_time())


def test_wallclock_timer_record_span():
    from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer
    timers = SynchronizedWallClockTimer()
    timers.record_span("dispatch", 0.010)
    timers.record_span("dispatch", 0.030)
    assert timers.has_timer("dispatch")
    assert timers("dispatch").elapsed_ == pytest.approx(0.040)
    assert timers.get_mean(["dispatch"])["dispatch"] == pytest.approx(20.0)


def test_async_swapper_dead_timers_param_removed():
    import inspect
    from deepspeed_tpu.runtime.swap_tensor.async_swapper import \
        AsyncTensorSwapper
    assert "timers" not in inspect.signature(
        AsyncTensorSwapper.__init__).parameters


# ---------------------------------------------------------------------------
# config / env / launcher
# ---------------------------------------------------------------------------

def test_monitor_config_defaults_and_validation():
    from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                              DeepSpeedMonitorConfig)
    cfg = DeepSpeedMonitorConfig({})
    assert not cfg.enabled
    assert cfg.sinks == ("jsonl", "ring") and cfg.interval == 1
    assert cfg.trace_steps is None
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedMonitorConfig({"monitor": {"sinks": ["prometheus"]}})
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedMonitorConfig({"monitor": {"interval": 0}})
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedMonitorConfig({"monitor": {"trace_steps": [5, 2]}})
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedMonitorConfig({"monitor": {"trace_steps": [0, 2]}})
    ok = DeepSpeedMonitorConfig({"monitor": {"trace_steps": [2, 5]}})
    assert ok.trace_steps == (2, 5)


def test_monitor_env_override(monkeypatch):
    from deepspeed_tpu.runtime.config import DeepSpeedMonitorConfig
    monkeypatch.setenv("DSTPU_MONITOR", "1")
    assert DeepSpeedMonitorConfig({}).enabled
    monkeypatch.setenv("DSTPU_MONITOR", "0")
    assert not DeepSpeedMonitorConfig(
        {"monitor": {"enabled": True}}).enabled


@pytest.mark.slow
def test_initialize_kwarg_outranks_config(tmp_path, mesh8):
    cfg = base_config(over={
        "monitor": {"enabled": True, "dir": str(tmp_path)}})
    e, _, _, _ = ds.initialize(config=cfg, model=SimpleModel(),
                               training_data=random_dataset(64),
                               mesh=mesh8, monitor=False)
    assert not e.monitor.armed
    e.close()


def test_launcher_monitor_flags():
    from deepspeed_tpu.launcher.runner import parse_args
    args = parse_args(["--monitor", "--monitor-dir", "/tmp/m", "t.py"])
    assert args.monitor is True and args.monitor_dir == "/tmp/m"
    args = parse_args(["--no-monitor", "t.py"])
    assert args.monitor is False
    args = parse_args(["t.py"])
    assert args.monitor is None


# ---------------------------------------------------------------------------
# ds_top
# ---------------------------------------------------------------------------

def test_ds_top_renders_stream(tmp_path, capsys):
    from deepspeed_tpu.monitor.__main__ import main as ds_top
    bus = MonitorBus([JSONLSink(str(tmp_path / EVENTS_FILE))])
    bus.span("step", 0.020, step=5)
    bus.span("dispatch", 0.015, step=5, parent="step")
    bus.gauge("mfu", 0.4321, step=5)
    bus.counter("wire_bytes_per_step", 4096, step=5)
    bus.step("train_step", 5, value=1.25, loss=1.25, lr=1e-3, skip=False)
    bus.flush()
    assert ds_top([str(tmp_path), "--once"]) == 0
    out = capsys.readouterr().out
    assert "ds_top" in out and "1.25" in out and "0.4321" in out
    assert "4.0KB" in out                 # wire column humanized
    assert "dispatch 15.0" in out         # span breakdown in ms


def test_ds_top_renders_serving_resilience_line(tmp_path, capsys):
    """A serving stream's resilience counters (docs/serving.md#resilience)
    render as the dedicated serving line; a training stream shows none."""
    from deepspeed_tpu.monitor.__main__ import main as ds_top
    bus = MonitorBus([JSONLSink(str(tmp_path / EVENTS_FILE))])
    bus.step("serving_step", 9, active_slots=3, queued=7)
    bus.counter("shed_total", 4, step=9)
    bus.counter("poisoned_total", 1, step=9)
    bus.counter("breaker_open", 1, step=9)
    bus.flush()
    assert ds_top([str(tmp_path), "--once"]) == 0
    out = capsys.readouterr().out
    assert "serving: active 3" in out and "queued 7" in out
    assert "shed 4" in out and "poisoned 1" in out
    assert "breaker OPEN" in out


def test_ds_top_renders_hist_and_trace_lines(tmp_path, capsys):
    """Schema-v2 hist events render whole-run p50/p99/p999; trace events
    render the request-trace summary with the export pointer."""
    from deepspeed_tpu.monitor.__main__ import main as ds_top
    from deepspeed_tpu.monitor.histogram import LogHistogram
    h = LogHistogram()
    h.add_many([10.0] * 98 + [500.0, 900.0])
    bus = MonitorBus([JSONLSink(str(tmp_path / EVENTS_FILE))])
    bus.step("serving_step", 9, active_slots=2, queued=0)
    bus.hist("latency_ms", h, step=9, unit="ms")
    bus.trace("request", step=9, uid=42, outcome="ok", ttft_ms=12.5,
              spans=[{"name": "queue_wait", "start_ms": 0.0,
                      "dur_ms": 2.0}])
    bus.flush()
    assert ds_top([str(tmp_path), "--once"]) == 0
    out = capsys.readouterr().out
    assert "latency_ms p50" in out and "p999" in out and "n=100" in out
    assert "traces: 1 request(s)" in out and "42" in out
    assert "--export-trace" in out


def test_ds_top_follower_incremental(tmp_path):
    from deepspeed_tpu.monitor.__main__ import StreamFollower
    path = tmp_path / EVENTS_FILE
    f = StreamFollower(str(path))
    assert f.poll() == []                 # file not there yet
    sink = JSONLSink(str(path))
    bus = MonitorBus([sink])
    bus.gauge("a", 1, step=1)
    bus.flush()
    assert [e.name for e in f.poll()] == ["a"]
    # a torn trailing line is carried, not mis-parsed
    with open(path, "a") as fh:
        fh.write('{"v":1,"kind":"gauge","name":"b","t":1.0,')
    assert f.poll() == []
    with open(path, "a") as fh:
        fh.write('"value":2}\n')
    assert [e.name for e in f.poll()] == ["b"]
    assert f.bad_lines == 0


# ---------------------------------------------------------------------------
# lint: DSTPU104
# ---------------------------------------------------------------------------

def test_dstpu104_flags_adhoc_emission():
    from deepspeed_tpu.analysis import lint_file, select_rules
    rules = select_rules(["DSTPU104"])
    src = ("import json\n"
           "def emit(m):\n"
           "    print(m)\n"
           "    json.dump(m, open('x.json', 'w'))\n")
    found = lint_file("deepspeed_tpu/runtime/foo.py", rules=rules, src=src)
    assert sorted(f.line for f in found) == [3, 4]
    # out-of-scope files (utils, analysis, monitor itself) are exempt
    assert lint_file("deepspeed_tpu/utils/foo.py", rules=rules,
                     src=src) == []
    assert lint_file("deepspeed_tpu/monitor/__main__.py", rules=rules,
                     src=src) == []
    # a per-site suppression is honored
    sup = ("def emit(m):\n"
           "    print(m)  # dstpu: disable=DSTPU104\n")
    assert lint_file("deepspeed_tpu/runtime/x.py", rules=rules,
                     src=sup) == []
    assert len(lint_file("deepspeed_tpu/runtime/x.py", rules=rules,
                         src=sup.replace("  # dstpu: disable=DSTPU104",
                                         ""))) == 1


def test_package_lint_clean_with_dstpu104():
    """The shipped runtime/inference trees carry no unsuppressed ad-hoc
    metric emission (the tier-1 gate runs exactly this)."""
    import deepspeed_tpu
    from deepspeed_tpu.analysis import lint_paths, select_rules
    root = os.path.dirname(os.path.abspath(deepspeed_tpu.__file__))
    found = lint_paths([root], rules=select_rules(["DSTPU104"]))
    assert found == [], [str(f) for f in found]


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serving_monitor_stream(tmp_path):
    """The serving scheduler rides the same bus/schema: decode-step
    events, admit/prefill/dispatch spans, latency gauges."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
    from deepspeed_tpu.inference import ServingEngine, ServingConfig, Request

    cfg = GPT2Config(vocab_size=64, max_seq=32, n_embd=32, n_layer=2,
                     n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    model = GPT2(cfg, dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0))
    mon = Monitor(run_dir=str(tmp_path), sinks=("jsonl",), role="serving")
    srv = ServingEngine(model=model, params=params, monitor=mon,
                        config=ServingConfig(batch_slots=2, block_size=8,
                                             max_new_tokens=4,
                                             preflight=False))
    srv.run([Request(tokens=np.arange(5), max_new_tokens=4, seed=1),
             Request(tokens=np.arange(7), max_new_tokens=4, seed=2)])
    mon.close()
    kinds = _by_kind(_events(tmp_path))
    assert any(e.name == "serving_step" for e in kinds["step"])
    span_names = {e.name for e in kinds["span"]}
    assert {"step", "admit", "dispatch"} <= span_names
    assert "prefill" in span_names
    last = [e for e in kinds["step"] if e.name == "serving_step"][-1]
    assert "completed_total" in last.fields
    srv.close()
