"""Unified runtime telemetry: one event bus, one schema, many consumers.

The reference ships a monitoring stack (``deepspeed/monitor/``, the
``wall_clock_breakdown`` timers of ``utils/timer.py``, the FLOPS profiler,
a tensorboard writer); this reproduction's equivalents were scattered
one-off emitters — a torch-importing tensorboard path that could never
run here, ``wall_clock_breakdown`` parsed but driving nothing, and health
forensics / serving stats / wire census each inventing a format.  This
package replaces them with a process-local **event bus** over a typed,
versioned event schema (``events.Event``: ``step`` | ``span`` | ``gauge``
| ``counter`` | ``artifact``, plus the v2 kinds ``hist`` — mergeable
log-bucketed histograms, ``histogram.LogHistogram`` — and ``trace`` —
per-request serving traces, Chrome-trace-exportable) and pluggable
sinks:

- :class:`sinks.JSONLSink` — the default stream (rank-0, one event per
  line, O_APPEND-atomic writes through the PR-1 retry IO);
- :class:`sinks.CSVSink` — the same events as a flat table;
- :class:`sinks.RingBufferSink` — bounded in-memory history (the class
  behind the health guardian's forensic ring);
- :class:`sinks.TensorboardSink` — scalar export through a NON-torch
  writer when one is importable (degrades to a one-line warning).

Instrumentation is **monitor-side only**: spans are host wall-clock
brackets around the dispatch path, gauges/counters are host reads of
already-computed values — nothing here is traced into a jitted step, so
an armed monitor leaves the compiled program byte-identical (gated by
the jaxpr-equality test and the ``--audit-step monitor`` stage).

Consumption: ``python -m deepspeed_tpu.monitor <run_dir>`` (``ds_top``)
tails the JSONL stream into a refreshing terminal table; ``ds_fleet``
(``monitor/fleet.py``, or ``--fleet dir1 dir2 ...``) merges N
per-replica streams into one fleet view with exact histogram merges and
a straggler verdict.  The v4 kinds — ``slo`` (rolling error-budget
verdicts) and ``alert`` (burn-rate trips + the live regression
sentinel) — come from the declarative SLO engine (``monitor/slo.py``,
config block ``monitor.slo``).

See docs/monitoring.md for the schema, span taxonomy, configuration
(config ``monitor`` block > env ``DSTPU_MONITOR`` > ``deepspeed
--monitor``), and the overhead guarantees.
"""

from .events import SCHEMA_VERSION, EVENT_KINDS, Event, parse_line
from .histogram import LogHistogram
from .ring import RingBuffer
from .bus import MonitorBus
from .spans import SpanRecorder
from .sinks import (Sink, JSONLSink, CSVSink, RingBufferSink,
                    TensorboardSink, SinkUnavailable, EVENTS_FILE,
                    stream_segments)
from .core import Monitor, NullMonitor, from_config
from .scope_maps import device_scopes
from .slo import (Objective, SentinelConfig, SLOConfig, SLOEvaluator,
                  RegressionSentinel)

__all__ = [
    "SCHEMA_VERSION", "EVENT_KINDS", "Event", "parse_line",
    "LogHistogram", "RingBuffer", "MonitorBus", "SpanRecorder",
    "Sink", "JSONLSink", "CSVSink", "RingBufferSink", "TensorboardSink",
    "SinkUnavailable", "EVENTS_FILE", "stream_segments",
    "Monitor", "NullMonitor", "from_config",
    "Objective", "SentinelConfig", "SLOConfig", "SLOEvaluator",
    "RegressionSentinel", "device_scopes",
]
