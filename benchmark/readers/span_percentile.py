"""A percentile of the durations of one of the benchmark's host spans, over
the measured window.  Host clock; in milliseconds by default."""

from benchmark import harness


def read(view, span, q, scale=1e3):
    t0, t1 = view["facts"]["window"]
    durations = view["spans"].durations(span, since=t0, until=t1)
    if not durations:
        return None
    return harness.percentile(durations, q) * scale
