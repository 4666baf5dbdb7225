"""Each per-layer reader on a view worked by hand: the arithmetic from spans,
counters and the reduced trace to the number, and nothing returned where
there is nothing to read."""

import pytest

from benchmark import costs, harness

PEAKS = harness.peaks_for("TPU v5 lite")


def reader(name):
    return harness.load_plugin("readers", name).read


def serving_view():
    spans = harness.Spans()
    spans.rows = [("step", 10.0 + i, 0.01 * (i + 1)) for i in range(5)] \
        + [("step", 99.0, 5.0)]                       # outside the window
    trace = {"window_s": 2.0, "idle_share_worst": 0.4,
             "module_s": {"jit_step": 1.0, "jit_prefill": 0.1},
             "module_calls": {"jit_step": (1.0, 0.005)},
             "op_self_s": {"paged_attention.13": 0.004, "fusion.1": 0.9},
             "kernel_s": {"paged_attention": 0.004},
             "collective_s": 0.0, "collective_exposed_s": 0.0}
    facts = {"window": (10.0, 50.0), "n_layer": 24, "d_model": 2048,
             "kv_width": 2048, "n_head": 16, "n_kv_head": 16,
             "head_dim": 128, "kv_bytes_per_element": 2,
             "live_tokens": [(20.0, 4000), (20.5, 4166), (60.0, 9999)]}
    return {"spans": spans, "trace": trace, "facts": facts, "peaks": PEAKS,
            "counters": {"generated_tokens": 900, "decode_steps": 100,
                         "cache_hits": 30, "cache_misses": 10},
            "trace_span": (19.0, 21.0)}


def test_span_percentile_reads_the_window_only():
    got = reader("span_percentile")(serving_view(), span="step", q=50)
    assert got == pytest.approx(30.0)                 # ms
    assert reader("span_percentile")(serving_view(), span="nope",
                                     q=50) is None


def test_counter_ratio():
    v = serving_view()
    assert reader("counter_ratio")(v, num=["generated_tokens"],
                                   den=["decode_steps"]) == 9.0
    assert reader("counter_ratio")(
        v, num=["cache_hits"], den=["cache_hits", "cache_misses"],
        scale=100.0) == 75.0
    assert reader("counter_ratio")(v, num=["absent"], den=["x"]) is None


def test_trace_shares():
    v = serving_view()
    assert reader("trace_module_share")(v, match="prefill") \
        == pytest.approx(5.0)
    assert reader("device_idle_share")(v) == pytest.approx(40.0)
    assert reader("collective_exposed_share")(v) is None


def test_paged_attention_roofline():
    v = serving_view()
    # 8,166 live tokens in the traced span x 196,608 B, over 819 GB/s
    least = 8166 * 196_608 / 819e9
    got = reader("kernel_roofline")(v, kernels=["paged_attention"],
                                    cost="paged_attention")
    assert got == pytest.approx(100 * least / 0.004)
    assert 0 < got < 100
    assert reader("kernel_roofline")(v, kernels=["absent"],
                                     cost="paged_attention") is None


def test_flash_roofline_and_mfu():
    facts = {"n_layer": 36, "d_model": 1280, "n_head": 20, "head_dim": 64,
             "vocab_size": 50257, "seq": 1024, "global_batch": 4, "chips": 1,
             "tokens_per_step": 4096, "tokens_per_s": 9000.0,
             "matmul_params_per_token": costs.matmul_params(1280, 36, 50257)}
    trace = {"kernel_s": {"attention": 0.15},
             "module_calls": {"jit_train_step": (0.9, 0.3)}}
    # the capture runs from 100 to 103 s.  A step that began before it, one
    # that only refills the device (short: it sets the clock), three whole
    # steps at 14,000 tokens/s, one cut by the capture's end; the window's
    # own rate (``tokens_per_s``: a stall outside the capture) is not read
    step = 4096 / 14000.0
    spans = harness.Spans()
    spans.rows = [("train_step", 99.9, 0.2), ("train_step", 100.1, 0.01)] \
        + [("train_step", 100.11 + i * step, step) for i in range(3)] \
        + [("train_step", 100.11 + 3 * step, 5.0), ("other", 100.5, 0.1)]
    v = {"facts": facts, "trace": trace, "peaks": PEAKS, "spans": spans,
         "trace_span": (100.0, 103.0)}
    need = 3 * costs.flash_attention_flops(4, 20, 64, 1024, 36)  # 3 steps
    got = reader("kernel_roofline")(v, kernels=["attention", "shard_map"],
                                    cost="flash_attention",
                                    module_match="train_step")
    assert got == pytest.approx(100 * need / 197e12 / 0.15)
    assert reader("mfu")(v, span="train_step") == pytest.approx(
        100 * 4_916_098_560 * 14000 / 197e12)
    # a CPU run has no peaks: nothing is reported under a device's name;
    # nor is anything with no capture, or fewer than two steps inside it
    assert reader("mfu")({**v, "peaks": None}, span="train_step") is None
    assert reader("mfu")({**v, "trace_span": (None, None)},
                         span="train_step") is None
    assert reader("mfu")({**v, "trace_span": (100.0, 100.2)},
                         span="train_step") is None


def test_a_kernel_is_priced_by_costs_py_first_and_the_family_second():
    """``kernel_roofline``'s ``cost`` is looked up in ``costs.KERNEL_NEEDS``
    and then in the family's ``costs``; the metric file's other parameters
    go to the function."""
    import types
    v = serving_view()
    family = types.SimpleNamespace(costs={
        "latent_attention": lambda view, scale: (0.0, scale * 819e9),
        "paged_attention": lambda view: (0.0, 0.0)})      # never reached
    v["family"] = family
    got = reader("kernel_roofline")(v, kernels=["paged_attention"],
                                    cost="latent_attention", scale=0.002)
    assert got == pytest.approx(50.0)        # 2 ms needed of 4 ms taken
    assert reader("kernel_roofline")(
        v, kernels=["paged_attention"], cost="paged_attention") \
        == pytest.approx(100 * 8166 * 196_608 / 819e9 / 0.004)
    with pytest.raises(ValueError, match="unknown cost"):
        reader("kernel_roofline")(v, kernels=["paged_attention"],
                                  cost="absent")
