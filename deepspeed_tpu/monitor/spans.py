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
import time

from jax.profiler import TraceAnnotation

ANNOTATION_PREFIX = "ds."        # never "bench.": that is the benchmark's
DEFAULT_CAPACITY = 65536         # rows; serve_chat's 40 s are about 30 k

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


class SpanRecorder:
    def __init__(self, capacity=DEFAULT_CAPACITY, clock=time.monotonic):
        self.capacity = int(capacity)
        self._clock = clock
        self._stack = []
        self._rows = collections.deque()
        self._appended = 0           # rows ever appended (a span's ``mark``)
        self.dropped = 0             # rows the ring has pushed out
        self.dropped_until = None    # t_end of the newest of them

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

    def reset(self):
        """Forget everything: rows, open spans and the dropped count."""
        for rec in self._stack:
            self._leave(rec)
        self._stack = []
        self._rows.clear()
        self._appended = 0
        self.dropped = 0
        self.dropped_until = None


def leaf(name, root_name):
    """``name`` as the monitor's ``span`` events and the named timers carry
    it: without the layer prefix it shares with its step's root
    (``serving.prefill.dispatch`` under ``serving.step`` ->
    ``prefill.dispatch``; ``compile.lower`` stays)."""
    prefix = root_name[:root_name.rfind(".") + 1]
    if name is not None and prefix and name.startswith(prefix):
        return name[len(prefix):]
    return name


_RECORDER = SpanRecorder()


def recorder() -> SpanRecorder:
    """The process-wide recorder both engines write into."""
    return _RECORDER
