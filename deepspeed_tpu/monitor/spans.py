"""The one span recorder of the package, on the profiler's clock.

A span is a host bracket around a region of the dispatch path (admission,
prefill, the decode dispatch, the wait for the device, the train step's data
fetch and upload, executable acquisition...).  Both engines record into ONE
process-wide :func:`recorder` whether or not a ``Monitor`` is armed: the
monitor's ``span`` events, the serving engine's sampled request trace and the
compile cache's timing counters are views of its rows, and so are the
benchmark's ``program_span`` metrics, which read it after the engines have
been closed.

A completed span is a :class:`Span` row ``(name, t_start, t_end, parent,
step, uid, attrs)`` on ``time.monotonic()``, the clock ``ServingEngine.results``
stamps with.  ``parent`` is the enclosing span's name; ``step`` (the
scheduler's or trainer's step number) and ``uid`` (the request's) are
inherited from the enclosing span unless given; ``attrs`` is ``None`` or a
small dict.  Rows live in a bounded ring, oldest dropped, with a ``dropped``
count and the end time of the newest dropped row (``dropped_until``) so a
reader can refuse a range the ring no longer covers.

Start-up is the exception: its rows are few, the oldest, and read after
everything else has been written.  A span opened with :meth:`setup_span`
(the layers ``setup.*`` and ``compile.*``) goes into the ring like any other
AND into a small store of its own that step rows never displace; the root of
a step in which such a row was recorded (the warm-up's ``serving.step`` /
``train.step``) is kept with it.  JAX's own ``jax.monitoring`` durations
(tracing, lowering to MLIR, the backend compile) are rows ``jax.trace`` /
``jax.lower`` / ``jax.compile`` of that store, which is how a ``jax.jit``
that no ``CachedStep`` wraps is seen at all.  :meth:`setup_rows` returns the
store and whether it is whole, ``t_process_start`` is when the process began
on this clock, and :func:`innermost_seconds` divides an interval among rows
that nest (``monitor/startup.py`` names the phases).  The hot path pays
nothing for any of it: ``open`` / ``close`` / ``_append`` are as they were.

Every span is also a ``jax.profiler.TraceAnnotation("ds.<name>")``: a
profiler capture holds the program's spans on the device trace's own clock,
beside the device's operations (Perfetto / XProf), with step and uid as
metadata.  Outside a capture no annotation is made (one made there would
record nothing).  Nothing here enters a traced function, so the compiled programs
are byte-identical whatever is recorded (the jaxpr-equality tests and
``--audit-step monitor`` prove it).

One thread drives each engine and nothing in the package starts another, so
the recorder takes no lock: a ``deque.append`` is atomic under the GIL.
"""

import collections
import functools
import heapq
import os
import time

import jax.monitoring
from jax.profiler import TraceAnnotation

ANNOTATION_PREFIX = "ds."        # never "bench.": that is the benchmark's
DEFAULT_CAPACITY = 65536         # rows; serve_chat's 40 s are about 46 k
SETUP_CAPACITY = 4096            # set-up rows; a process writes hundreds
# a model's trace emits a sub-millisecond duration for every jitted jnp
# call inside it, thousands an executable; rows that short move no metric
# read in seconds, and the rows that hold them are kept
JAX_ROW_MIN_S = 1e-3
JAX_ROWS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
# fires inside a backend compile that JAX's persistent cache answered
JAX_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

Span = collections.namedtuple(
    "Span", "name t_start t_end parent step uid attrs")


class _Open:
    """A span that has started.  ``attrs`` may be set until it is closed;
    as a context manager it closes itself; ``t1`` is its end once it has
    been closed and ``None`` before (and for a discarded one)."""

    __slots__ = ("name", "parent", "t0", "t1", "step", "uid", "attrs",
                 "mark", "closed", "_recorder", "_annotation")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._recorder.close(self)
        return False


class _OpenSetup(_Open):
    """A span of start-up: closed into the ring and the set-up store."""

    __slots__ = ()

    def __exit__(self, *exc):
        self._recorder.close_setup(self)
        return False


class SpanRecorder:
    def __init__(self, capacity=DEFAULT_CAPACITY, clock=time.monotonic,
                 setup_capacity=SETUP_CAPACITY):
        self.capacity = int(capacity)
        self._clock = clock
        self._stack = []
        self._rows = collections.deque()
        self._appended = 0           # rows ever appended (a span's ``mark``)
        self.dropped = 0             # rows the ring has pushed out
        self.dropped_until = None    # t_end of the newest of them
        # when the process began, on this clock (a clock of the caller's
        # own has no such moment: the recorder's creation stands in)
        self.t_process_start = (_PROCESS_START if clock is time.monotonic
                                else clock())
        self.setup_capacity = int(setup_capacity)
        self._setup = []             # set-up rows, in the order they ended
        self._setup_roots = []       # step roots a set-up row ended inside
        self.setup_dropped = 0       # set-up rows refused at the cap
        self._jax_cache_hit = False  # a retrieval since the last compile

    @property
    def depth(self) -> int:
        return len(self._stack)

    def now(self) -> float:
        return self._clock()

    # ------------------------------------------------------------- recording
    def open(self, name, step=None, uid=None, attrs=None) -> _Open:
        """Start a span under whatever span is open; close it with
        :meth:`close` or by using the returned object in a ``with``."""
        rec = _Open()
        rec.name = name
        rec.attrs = attrs
        rec.closed = False
        rec.t1 = None
        rec._recorder = self
        rec.mark = self._appended
        stack = self._stack
        if stack:
            top = stack[-1]
            rec.parent = top.name
            rec.step = top.step if step is None else step
            rec.uid = top.uid if uid is None else uid
        else:
            rec.parent, rec.step, rec.uid = None, step, uid
        if TraceAnnotation.is_enabled():      # a capture is running
            meta = {k: v for k, v in (("step", rec.step), ("uid", rec.uid))
                    if v is not None}
            ann = TraceAnnotation(ANNOTATION_PREFIX + name, **meta)
            ann.__enter__()
            rec._annotation = ann
        else:
            # an annotation made outside a capture records nothing even if
            # one starts before it ends: none is made
            rec._annotation = None
        stack.append(rec)
        rec.t0 = self._clock()
        return rec

    span = open

    def setup_span(self, name, attrs=None) -> _Open:
        """Start a span of start-up (``setup.*``, ``compile.*``): one
        :meth:`open` makes, kept in the set-up store too when it closes.
        The step rows' own entry points know nothing of that store."""
        rec = self.open(name, attrs=attrs)
        rec.__class__ = _OpenSetup
        return rec

    def close_setup(self, rec: _Open) -> float:
        """:meth:`close` for a span of :meth:`setup_span`."""
        was_open = not rec.closed
        seconds = self.close(rec)
        if was_open:
            # the ring's row and this one share ``attrs``: what is added
            # to the dict after the close is read from both
            self._keep(Span(rec.name, rec.t0, rec.t1, rec.parent, rec.step,
                            rec.uid, rec.attrs))
        return seconds

    def setup_record(self, name, t_start, t_end, attrs=None):
        """A set-up row whose times the caller holds (the package's import;
        a duration JAX reports when it is over).  Kept in the store alone:
        no reader of the ring knows these names."""
        self._keep(Span(name, t_start, t_end, None, None, None, attrs))

    def _keep(self, row):
        if len(self._setup) >= self.setup_capacity:
            self.setup_dropped += 1
            return
        self._setup.append(row)
        # the step that stood still for this row is start-up too: its root
        # is open now and has no row yet, so the object is kept and read
        # once it has closed.  Nothing is asked of a step that acquires
        # nothing.
        root = self._stack[0] if self._stack else None
        if root.__class__ is _Open and (
                not self._setup_roots or self._setup_roots[-1] is not root):
            self._setup_roots.append(root)

    def _jax_duration(self, event, seconds):
        """One of JAX's compile durations, as it ends (the listener)."""
        if event == JAX_CACHE_RETRIEVAL:
            self._jax_cache_hit = True
            return
        name = JAX_ROWS.get(event)
        if name is None:
            return
        attrs = None
        if name == "jax.compile":
            if self._jax_cache_hit:
                attrs = {"cached": True}
            self._jax_cache_hit = False
        if seconds < JAX_ROW_MIN_S:
            return
        now = self._clock()
        start = now - seconds
        # a jit traced inside another reports before it: the row that
        # holds those of its own name replaces them
        kept = self._setup
        while kept and kept[-1].name == name and kept[-1].t_start >= start:
            kept.pop()
        self._keep(Span(name, start, now, None, None, None, attrs))

    def close(self, rec: _Open) -> float:
        """Close ``rec`` and anything left open inside it (an exception may
        have skipped inner closes).  Returns its duration; closing a span
        twice records nothing."""
        now = self._clock()
        if not rec.closed:
            stack = self._stack
            while stack:
                top = stack.pop()
                self._finish(top, now)
                if top is rec:
                    break
        return now - rec.t0

    def discard(self, rec: _Open):
        """Close ``rec`` and drop it with every span recorded under it (an
        idle scheduler poll, a cache lookup that found nothing).  Rows
        handed to :meth:`record` meanwhile stay: they are events of their
        own, not parts of ``rec``."""
        if rec.closed:
            return
        while self._stack:
            top = self._stack.pop()
            self._leave(top)
            if top is rec:
                break
        kept = []
        for _ in range(min(self._appended - rec.mark, len(self._rows))):
            row = self._rows.pop()
            if row.parent is None:
                kept.append(row)
        self._appended = rec.mark
        for row in reversed(kept):
            self._append(row)

    def record(self, name, t_start, t_end, step=None, uid=None, attrs=None):
        """A row whose times the caller already holds (a request's whole
        life, known only at its end).  No annotation, no parent."""
        self._append(Span(name, t_start, t_end, None, step, uid, attrs))

    @staticmethod
    def _leave(rec):
        rec.closed = True
        if rec._annotation is not None:
            rec._annotation.__exit__(None, None, None)

    def _finish(self, rec, now):
        self._leave(rec)
        rec.t1 = now
        # tuple.__new__ skips the namedtuple's Python-level constructor
        self._append(tuple.__new__(Span, (rec.name, rec.t0, now, rec.parent,
                                          rec.step, rec.uid, rec.attrs)))

    def _append(self, row):
        rows = self._rows
        if len(rows) >= self.capacity:
            self.dropped += 1
            self.dropped_until = rows.popleft().t_end
        rows.append(row)
        self._appended += 1

    # --------------------------------------------------------------- reading
    def rows(self, name=None) -> list:
        """The rows the ring holds, oldest first (those called ``name``)."""
        if name is None:
            return list(self._rows)
        return [r for r in self._rows if r.name == name]

    def since(self, rec: _Open) -> list:
        """Rows completed since ``rec`` was opened, oldest first."""
        n = min(self._appended - rec.mark, len(self._rows))
        return [self._rows[-i] for i in range(n, 0, -1)]

    def newest_first(self):
        """Iterate the ring from its newest row backwards."""
        return reversed(self._rows)

    def setup_rows(self):
        """``(rows, whole)``: the set-up store in the order its rows ended,
        with the closed roots of the steps they ended inside, and whether
        the store has kept every row it was handed."""
        roots = [Span(r.name, r.t0, r.t1, None, r.step, r.uid, r.attrs)
                 for r in self._setup_roots if r.t1 is not None]
        return self._setup + roots, self.setup_dropped == 0

    def reset(self):
        """Forget everything: rows, open spans and the dropped counts."""
        for rec in self._stack:
            self._leave(rec)
        self._stack = []
        self._rows.clear()
        self._appended = 0
        self.dropped = 0
        self.dropped_until = None
        self._setup = []
        self._setup_roots = []
        self.setup_dropped = 0


def leaf(name, root_name):
    """``name`` as the monitor's ``span`` events and the named timers carry
    it: without the layer prefix it shares with its step's root
    (``serving.prefill.dispatch`` under ``serving.step`` ->
    ``prefill.dispatch``; ``compile.lower`` stays)."""
    prefix = root_name[:root_name.rfind(".") + 1]
    if name is not None and prefix and name.startswith(prefix):
        return name[len(prefix):]
    return name


def innermost_seconds(rows, t_from, t_to, label):
    """``[t_from, t_to]`` divided among ``rows``: each instant goes to the
    innermost row that covers it (the one that started last) and is booked
    under ``label(row)``; an instant no row covers is booked under ``None``.
    ``{label: seconds}``; the values add up to ``t_to - t_from``."""
    out = collections.defaultdict(float)
    if t_to <= t_from:
        return dict(out)
    rows = sorted((r for r in rows if r.t_end > t_from and r.t_start < t_to
                   and r.t_end > r.t_start),
                  key=lambda r: (r.t_start, -r.t_end))
    cuts = sorted({t_from, t_to}.union(
        t for r in rows for t in (r.t_start, r.t_end) if t_from < t < t_to))
    open_rows, i = [], 0       # heap of (-t_start, order, row)
    for lo, hi in zip(cuts, cuts[1:]):
        while i < len(rows) and rows[i].t_start <= lo:
            heapq.heappush(open_rows, (-rows[i].t_start, -i, rows[i]))
            i += 1
        while open_rows and open_rows[0][2].t_end <= lo:
            # ended: an outer row that ended before it is still in the
            # heap below and is dropped when it comes to the top
            heapq.heappop(open_rows)
        out[label(open_rows[0][2]) if open_rows else None] += hi - lo
    return dict(out)


def _process_start():
    """When this process began, on ``time.monotonic()``: its start time in
    ``/proc/self/stat`` (clock ticks since boot) against ``CLOCK_BOOTTIME``.
    The program cannot see a harness's own first clock read, and everything
    before the first import of this module would otherwise have no start.
    Falls back to now, the module's import."""
    now = time.monotonic()
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - int(fields[19]) / os.sysconf("SC_CLK_TCK"))
    except (OSError, AttributeError, ValueError, IndexError):
        return now
    return now - age if age >= 0.0 else now


_PROCESS_START = _process_start()
_RECORDER = SpanRecorder()


def _on_jax_duration(event, seconds, **_):
    _RECORDER._jax_duration(event, seconds)


# one listener for the process: it runs when something compiles, so never
# inside a clean window
jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def recorder() -> SpanRecorder:
    """The process-wide recorder both engines write into."""
    return _RECORDER


def in_setup_span(name, **attrs):
    """Decorator: each call of the function is one span of start-up in the
    process-wide recorder (an engine's constructor)."""
    def wrap(fn):
        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with _RECORDER.setup_span(name, attrs=dict(attrs)):
                return fn(*args, **kwargs)
        return spanned
    return wrap
