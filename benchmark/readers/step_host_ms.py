"""A percentile, in milliseconds, of the HOST's part of a step: the duration
of each ``root`` span of the program's span recorder (``serving.step``,
``train.step``) that began inside the measured window, less the spans named
in ``minus`` that lie inside it (the reads that wait for the device).  What
is left is what the host does while the device has nothing new queued."""

from benchmark import harness, program_spans


def read(view, root, q, minus=()):
    t0, t1 = view["facts"]["window"]
    rows = program_spans.rows_from(view, t0)
    if rows is None:
        return None
    values, waits = [], []       # a span's children close before it does
    for r in rows:
        if r.name in minus:
            waits.append(r)
        elif r.name == root:
            if t0 <= r.t_start < t1:
                values.append((r.t_end - r.t_start) - sum(
                    w.t_end - w.t_start for w in waits
                    if w.t_start >= r.t_start))
            waits = []
    if not values:
        return None
    return harness.percentile(values, q) * 1e3
