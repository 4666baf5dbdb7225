"""A kernel's share of its roofline, in percent: the least time the chip
could take for what the kernel's calls in the traced window NEED (the larger
of FLOPs over peak FLOP/s and bytes over peak bytes/s) over the device time
of the custom calls whose name, without its number, is one of ``kernels``.
``cost`` names the function that prices the calls: one of
``benchmark/costs.py``'s ``KERNEL_NEEDS``, or else one of the family's
``costs`` (a new architecture's kernel arrives priced in its family's file);
the metric file's other parameters are handed to it."""

from benchmark import costs


def read(view, kernels, cost, **params):
    trace, peaks = view["trace"], view["peaks"]
    seconds = sum(trace["kernel_s"].get(k, 0.0) for k in kernels)
    if peaks is None or seconds == 0:
        return None
    need = costs.KERNEL_NEEDS.get(cost) or getattr(
        view.get("family"), "costs", {}).get(cost)
    if need is None:
        raise ValueError(f"unknown cost {cost!r}: neither in costs.py nor "
                         "in the family's ``costs``")
    flops, nbytes = need(view, **params)
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
