"""An XLA module's share of its roofline, in percent: the least time the
chip could take for what the module's executions in the traced window NEED
(the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s) over the
device time of the modules whose name contains ``match``.  ``cost`` names
the function that prices them, found as ``kernel_roofline`` finds its own
(``benchmark/costs.py``'s ``KERNEL_NEEDS``, then the family's ``costs``);
it is handed ``module_match`` = ``match`` and the metric file's other
parameters.  For a step that is one stream of weights and cache, where
``kernel_roofline`` prices one custom call inside it."""

from benchmark import costs


def read(view, match, cost, **params):
    trace, peaks = view["trace"], view["peaks"]
    seconds = sum(s for name, s in trace["module_s"].items() if match in name)
    if peaks is None or seconds == 0:
        return None
    need = costs.KERNEL_NEEDS.get(cost) or getattr(
        view.get("family"), "costs", {}).get(cost)
    if need is None:
        raise ValueError(f"unknown cost {cost!r}: neither in costs.py nor "
                         "in the family's ``costs``")
    flops, nbytes = need(view, module_match=match, **params)
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
