"""What every cell of the benchmark shares: finding a cell's data files by
the names in ``BENCHMARK.json``, finding the configuration's model family
and plain reference by its ``model_type``, weights from the seed,
compile-cache counting, host spans, the device block of the result line.

Nothing here imports JAX at module level: ``run.py`` must be able to refuse
a machine without a TPU before anything heavy is loaded, and the tests import
these functions on the CPU.
"""

import contextlib
import importlib.util
import json
import os
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def read_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_by_name(bench, name):
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                     f"{[c['name'] for c in bench['workloads']]}")


def load_config(bench, name):
    """The configuration file a cell names, found through ``configs``."""
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            with open(os.path.join(ROOT, cfg["file"])) as f:
                return json.load(f)
    raise SystemExit(f"no config {name!r} in BENCHMARK.json")


def load_traffic(name):
    return read_json("traffic", f"{name}.json")


def load_plugin(folder, name):
    """``benchmark/<folder>/<name>.py`` as a module: runners, readers,
    model families and their references are found by the name a data file
    gives, never listed in code."""
    path = os.path.join(BENCH_DIR, folder, f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {name!r} among the benchmark's {folder}: "
                         f"{path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{folder}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench, group, cell_name):
    """The metrics of ``group`` (``end_to_end`` / ``per_layer``) that this
    cell reports: those with no ``workloads`` key, or that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


# ------------------------------------------------------------------ the model
def family(cfg):
    """The configuration's model family,
    ``benchmark/families/<model_type>.py``: ``build``, ``dims``, ``matmul_params_per_token`` and, optionally,
    ``costs`` (PERF.md, "Adding to the benchmark")."""
    return load_plugin("families", cfg["model_type"])


def reference(cfg):
    """The family's plain reference, ``benchmark/reference/<model_type>.py``:
    ``logits_at(cfg, params, tokens, positions)`` and ``loss(cfg, params,
    batch)``, which decide ``correct``."""
    return load_plugin("reference", cfg["model_type"])


def build_model(cfg, dtype, **extra):
    """The model through the program's normal path, as the family builds
    it; a file the program cannot run is refused there."""
    return family(cfg).build(cfg, dtype, **extra)


def key_seed(seed):
    """``--seed`` may exceed 32 signed bits; JAX keys and ``Request.seed``
    want an int32.  A fixed hash of the seed, 31 bits."""
    import numpy as np
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


def seeded_weights(model, seed, dtype=None):
    """Weights on the device from the seed in ONE jitted dispatch, cast to
    ``dtype`` inside it when given (the type they are served in)."""
    import jax

    def make(key):
        params = model.init(key)
        if dtype is None:
            return params
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
    return jax.jit(make)(jax.random.PRNGKey(key_seed(seed)))


# ------------------------------------------------------------ compile counter
class CompileCounter:
    """Counts JAX's compilation-cache hits and misses and every backend
    compile, from ``jax.monitoring`` events.  ``mark()`` returns the counts
    so far; a window is the difference of two marks."""

    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.hits = self.misses = self.compiles = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        self.hits += event == self.HIT
        self.misses += event == self.MISS

    def _duration(self, event, _secs, **_):
        self.compiles += event == self.COMPILE

    def mark(self):
        return {"hits": self.hits, "misses": self.misses,
                "compiles": self.compiles}


def aot_events(report):
    """How many executables the repo's AOT store has served (hits) or had
    compiled for it (misses) so far."""
    return report["hits"] + report["misses"] if report.get("enabled") else 0


def cache_counters(compiles, report):
    """Hits and misses of both compile caches so far: JAX's persistent
    cache (events) and the repo's AOT executable store (its report)."""
    mark = compiles.mark()
    on = report.get("enabled")
    return {"cache_hits": mark["hits"] + (report["hits"] if on else 0),
            "cache_misses": mark["misses"] + (report["misses"] if on else 0)}


# ---------------------------------------------------------------------- spans
class Spans:
    """The benchmark's own host spans: kept in memory, and written into the
    profiler's trace as ``TraceAnnotation`` so idle gaps on the device can
    be named by what the host was doing."""

    PREFIX = "bench."

    def __init__(self):
        self.rows = []          # (name, start_s, seconds), monotonic clock

    @contextlib.contextmanager
    def span(self, name):
        import jax
        with jax.profiler.TraceAnnotation(self.PREFIX + name):
            t = time.monotonic()
            try:
                yield
            finally:
                self.rows.append((name, t, time.monotonic() - t))

    def durations(self, name, since=0.0, until=float("inf")):
        return [d for n, t, d in self.rows
                if n == name and since <= t < until]


# -------------------------------------------------------------------- tracing
class TraceWindow:
    """A profiler capture over the LAST ``seconds`` of a window of
    ``window_s``: it starts when ``poll`` first sees the time has come and
    is stopped by the runner once the window is over, so the stall of
    writing the trace falls after the measured work."""

    def __init__(self, enabled, out_dir, window_s, seconds):
        self.enabled = enabled
        self.dir = out_dir
        self.start_at = max(0.0, window_s - seconds)
        self.t_start = self.t_stop = self.path = None

    def poll(self, elapsed):
        if self.enabled and self.t_start is None and elapsed >= self.start_at:
            import jax
            os.makedirs(self.dir, exist_ok=True)
            # the Python tracer would log every function call of the host
            # loop: a far larger trace and a slower host.  The benchmark's
            # own spans are TraceAnnotations and need only the host tracer.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.t_start = time.monotonic()

    def stop(self):
        """Stop the capture if one is running; ``path`` is then the
        ``.xplane.pb`` file.  Calling it again does nothing.  Returns the
        seconds the host stood still while the trace was written."""
        if self.t_start is None or self.t_stop is not None:
            return 0.0
        import jax
        from deepspeed_tpu.monitor.trace import newest_trace_artifact
        self.t_stop = time.monotonic()
        jax.profiler.stop_trace()
        self.path = newest_trace_artifact(self.dir)
        return time.monotonic() - self.t_stop


# --------------------------------------------------------------------- device
def device_block(n_chips):
    """The ``device`` object of the result line, as JAX reports it."""
    import jax
    devs = jax.devices()[:n_chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def peaks_for(kind):
    table = read_json("peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json; "
                       "add its published peaks, never a default")
    return table["devices"][kind]


def percentile(values, q):
    """The q-th percentile by linear interpolation (numpy's default)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


# ---------------------------------------------------------------- run context
class RunContext:
    """What a runner is handed: the cell, its data files, the arguments of
    the command, and the shared instruments."""

    def __init__(self, bench, cell, seed, seconds, trace, t_process_start,
                 log=None, trace_dir=None, config=None, traffic=None):
        self.bench = bench
        self.cell = cell
        # the tests hand in a tiny configuration and mix of their own
        self.config = config or load_config(bench, cell["config"])
        self.traffic = traffic or load_traffic(cell["traffic"])
        self.family = family(self.config)
        self.dims = self.family.dims(self.config)
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_process_start = t_process_start
        self.log = log or (lambda msg: None)
        self.spans = Spans()
        self.compiles = CompileCounter()
        self.trace_dir = trace_dir or os.path.join(
            ROOT, ".bench_out", "trace", cell["name"])

    def compile_count(self, report):
        """Backend compiles plus executables the AOT store served or had
        compiled, so far; a window is clean when this does not move."""
        return self.compiles.mark()["compiles"] + aot_events(report)

    def trace_window(self):
        return TraceWindow(self.trace, self.trace_dir, self.seconds,
                           float(self.traffic.get("trace_seconds", 3)))
