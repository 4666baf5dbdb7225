"""The mean, in percent, over the rows of one program span (``span``, in the
program's span recorder) that began inside the measured window, of the
attribute ``num`` over the sum of the attributes ``den``.  A program that
records the span without those attributes (any commit before they came)
gives nothing to read."""

from benchmark import program_spans


def read(view, span, num, den):
    t0, t1 = view["facts"]["window"]
    rows = program_spans.rows_from(view, t0)
    if rows is None:
        return None
    names = [num, *den]
    ratios = []
    for r in rows:
        if r.name != span or not t0 <= r.t_start < t1:
            continue
        a = r.attrs or {}
        if all(n in a for n in names) and sum(a[d] for d in den):
            ratios.append(a[num] / sum(a[d] for d in den))
    if not ratios:
        return None
    return 100.0 * sum(ratios) / len(ratios)
