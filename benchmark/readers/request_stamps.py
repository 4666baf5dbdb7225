"""A percentile, in milliseconds, of what the serving engine stamped on each
request (``serving.request`` rows of the program's span recorder), over the
requests SUBMITTED inside the measured window: ``queue_wait`` is ``t_admit -
t_submit`` (a request never seated waited until it was refused),
``prefill`` is ``t_first - t_admit``, ``inter_token`` is every gap between
consecutive token stamps of those requests, pooled: the true inter-token
gap.  A request submitted inside the window counts whenever it ended."""

from benchmark import harness, program_spans


def read(view, what, q):
    t0, t1 = view["facts"]["window"]
    rows = program_spans.rows_from(view, t0)
    if rows is None:
        return None
    values = []
    for r in rows:
        if r.name != "serving.request" or not t0 <= r.t_start < t1:
            continue
        a = r.attrs
        if what == "queue_wait":
            values.append(a["t_admit"] - r.t_start)
        elif what == "prefill":
            if a["t_first"] is not None:
                values.append(a["t_first"] - a["t_admit"])
        elif what == "inter_token":
            stamps = a["t_tokens"] or ()
            values.extend(b - c for b, c in zip(stamps[1:], stamps))
        else:
            raise ValueError(f"unknown stamp difference {what!r}")
    if not values:
        return None
    return harness.percentile(values, q) * 1e3
