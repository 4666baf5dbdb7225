"""The ratio of two sums of the run's counters (``num`` and ``den`` are
lists of counter names), times ``scale``."""


def read(view, num, den, scale=1.0):
    counters = view["counters"]
    if any(name not in counters for name in num + den):
        return None
    bottom = sum(counters[name] for name in den)
    if bottom == 0:
        return None
    return scale * sum(counters[name] for name in num) / bottom
