"""A number the runner already worked out over the whole window (one of its
``facts``), such as a percentile of the per-request stamps."""


def read(view, key):
    return view["facts"].get(key)
