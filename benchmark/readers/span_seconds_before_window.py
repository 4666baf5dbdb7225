"""Seconds the program spent in spans whose name starts with ``prefix``
(``compile.``: lowering, loading and building executables) and that ended
before the measured window began: their share of set-up.  Read from the
oldest rows of the program's span recorder, so nothing is returned once the
ring has dropped any."""

from benchmark import program_spans


def read(view, prefix):
    t0, _ = view["facts"]["window"]
    rows = program_spans.rows_from(view, None)
    if rows is None:
        return None
    seconds = [r.t_end - r.t_start for r in rows
               if r.name.startswith(prefix) and r.t_end <= t0]
    return sum(seconds) if seconds else None
