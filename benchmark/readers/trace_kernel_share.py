"""Share of the traced window, in percent, that the device spent in the
custom calls whose name, without its number, is one of ``kernels`` (device
trace, self time, averaged over the devices used): how much of the window a
kernel is, where ``kernel_roofline`` says how well it runs."""


def read(view, kernels):
    trace = view["trace"]
    seconds = sum(trace["kernel_s"].get(k, 0.0) for k in kernels)
    if seconds == 0:
        return None
    return 100.0 * seconds / trace["window_s"]
