"""Share of the traced window, in percent, spent in collective operations
while no compute ran on that device (the device where that is longest)."""


def read(view):
    trace = view["trace"]
    if trace["collective_s"] == 0:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
