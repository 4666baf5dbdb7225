"""A kernel's share of its roofline, in percent: the least time the chip
could take for what the kernel's calls in the traced window NEED (the larger
of FLOPs over peak FLOP/s and bytes over peak bytes/s, from
``benchmark/costs.py``) over the device time of the custom calls whose
name, without its number, is one of ``kernels``.  ``cost`` names the
pricing below."""

from benchmark import costs


def traced_steps(view, module_match):
    """How many executions of the step module the trace holds, counting a
    cut one by the part that is there: module time over the median call."""
    calls = [(n, s) for n, s in view["trace"]["module_calls"].items()
             if module_match in n]
    return sum(s / med for n, (s, med) in calls if med > 0) if calls else 0.0


def need_paged_attention(view):
    f = view["facts"]
    t0, t1 = view["trace_span"]
    live = sum(n for t, n in f["live_tokens"] if t0 <= t < t1)
    return (costs.paged_attention_flops(live, f["n_layer"], f["n_embd"]),
            costs.paged_attention_bytes(live, f["n_layer"], f["n_embd"],
                                        f["kv_bytes_per_element"]))


def need_flash_attention(view, module_match):
    f = view["facts"]
    steps = traced_steps(view, module_match)
    per_device_batch = f["global_batch"] // f["chips"]
    flops = steps * costs.flash_attention_flops(
        per_device_batch, f["n_head"], f["head_dim"], f["seq"], f["n_layer"])
    # bytes: q, k, v, o and their gradients once each, far under the FLOPs
    return flops, 0.0


def read(view, kernels, cost, module_match=""):
    trace, peaks = view["trace"], view["peaks"]
    seconds = sum(trace["kernel_s"].get(k, 0.0) for k in kernels)
    if peaks is None or seconds == 0:
        return None
    if cost == "paged_attention":
        flops, nbytes = need_paged_attention(view)
    elif cost == "flash_attention":
        flops, nbytes = need_flash_attention(view, module_match)
    else:
        raise ValueError(f"unknown cost {cost!r}")
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
