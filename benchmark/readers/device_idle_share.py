"""Share of the traced window, in percent, in which no operation ran on the
device: 1 - the union of operation intervals over the window, on the device
that was idle longest."""


def read(view):
    return 100.0 * view["trace"]["idle_share_worst"]
