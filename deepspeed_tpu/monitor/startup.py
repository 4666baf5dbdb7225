"""Where a process's start-up went: the seconds from its start to a moment
of the caller's choosing, divided among eight phases that add up.

It is what ``compile_report()`` is for the caches, read from ROWS and not
from counters: the set-up store of the span recorder (``monitor/spans.py``)
holds a row for the package's import, for each engine's construction and
the placements inside it, for every acquisition of a ``CachedStep``
(``compile.lower`` / ``.key`` / ``.load`` / ``.build``), for every duration
JAX reports of a ``jax.jit`` (``jax.trace`` / ``.lower`` / ``.compile``) and
for the root of each step that stood still for one of those.  Each instant
falls to the innermost row that covers it:

========================  ==================================================
``before_program``        process start to the start of the first row: the
                          interpreter, ``import jax``, reaching the chip
``import``                ``setup.import``
``engine_init``           ``setup.engine_init`` and its ``setup.*`` children
``trace_lower``           ``compile.lower``, ``jax.trace``, ``jax.lower``
``cache_load``            ``compile.key``, ``compile.load`` and a
                          ``jax.compile`` JAX's persistent cache answered
``build``                 ``compile.build`` and every other ``jax.compile``
``warmup_run``            what is left of the ``serving.step`` /
                          ``train.step`` roots: executing and reading back
``unattributed``          after the first row, under no row at all: the
                          caller's own work
========================  ==================================================

:func:`partition` is the one implementation; the benchmark's
``setup_seconds`` reader calls it with the rows and its window's start, and
:func:`report` / :func:`line` with this process's rows and now.
"""

from . import spans

PHASES = ("before_program", "import", "engine_init", "trace_lower",
          "cache_load", "build", "warmup_run", "unattributed")
STEP_ROOTS = ("serving.step", "train.step")
_BY_NAME = {"setup.import": "import",
            "compile.lower": "trace_lower", "jax.trace": "trace_lower",
            "jax.lower": "trace_lower",
            "compile.key": "cache_load", "compile.load": "cache_load",
            "compile.build": "build"}


def phase_of(row):
    """The phase a row's own seconds belong to (``None``: not a row of
    start-up)."""
    name = row.name
    if name in _BY_NAME:
        return _BY_NAME[name]
    if name == "jax.compile":
        cached = row.attrs is not None and row.attrs.get("cached")
        return "cache_load" if cached else "build"
    if name.startswith("setup."):
        return "engine_init"
    if name in STEP_ROOTS and row.parent is None:
        return "warmup_run"
    return None


def partition(rows, t_process_start, t_until):
    """``{phase: seconds}`` over ``[t_process_start, t_until]``, every
    phase of :data:`PHASES` present; the values add up to the interval."""
    rows = [r for r in rows if phase_of(r) is not None]
    first = min((r.t_start for r in rows), default=t_until)
    first = min(max(first, t_process_start), t_until)
    parts = spans.innermost_seconds(rows, first, t_until, phase_of)
    out = dict.fromkeys(PHASES, 0.0)
    out["before_program"] = first - t_process_start
    out["unattributed"] = parts.pop(None, 0.0)
    out.update(parts)
    return out


def report(until=None):
    """This process's start-up so far (or up to ``until`` on the
    recorder's clock): ``{"total_s", "whole", "phases": {phase: s}}``.
    ``whole`` is false once the set-up store has refused a row."""
    rec = spans.recorder()
    until = rec.now() if until is None else until
    rows, whole = rec.setup_rows()
    return {"total_s": until - rec.t_process_start, "whole": whole,
            "phases": partition(rows, rec.t_process_start, until)}


_WORDS = {"before_program": "before the program", "trace_lower":
          "trace+lower", "cache_load": "cache load", "engine_init": "engine",
          "warmup_run": "warm-up", "unattributed": "other"}


def line(until=None):
    """``start-up 38.2 s: before the program 14.9, import 2.1, ...``"""
    got = report(until)
    parts = ", ".join(f"{_WORDS.get(p, p)} {got['phases'][p]:.1f}"
                      for p in PHASES)
    return (f"start-up {got['total_s']:.1f} s: {parts}"
            + ("" if got["whole"] else " (set-up rows were dropped)"))
