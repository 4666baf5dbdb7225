"""Share of the traced window, in percent, in which the device ran XLA
modules whose name contains ``match`` (device trace, averaged over the
devices used)."""


def read(view, match):
    trace = view["trace"]
    if not trace["module_s"]:
        return None
    seconds = sum(s for name, s in trace["module_s"].items() if match in name)
    return 100.0 * seconds / trace["window_s"]
