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
    facts = {"window": (10.0, 50.0), "n_layer": 24, "n_embd": 2048,
             "n_head": 16, "head_dim": 128, "kv_bytes_per_element": 2,
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
    facts = {"n_layer": 36, "n_embd": 1280, "n_head": 20, "head_dim": 64,
             "vocab_size": 50257, "seq": 1024, "global_batch": 4, "chips": 1,
             "tokens_per_s": 14000.0}
    trace = {"kernel_s": {"attention": 0.15},
             "module_calls": {"jit_train_step": (0.9, 0.3)}}
    v = {"facts": facts, "trace": trace, "peaks": PEAKS}
    need = 3 * costs.flash_attention_flops(4, 20, 64, 1024, 36)  # 3 steps
    got = reader("kernel_roofline")(v, kernels=["attention", "shard_map"],
                                    cost="flash_attention",
                                    module_match="train_step")
    assert got == pytest.approx(100 * need / 197e12 / 0.15)
    assert reader("mfu")(v) == pytest.approx(
        100 * 4_916_098_560 * 14000 / 197e12)
    # a CPU run has no peaks: nothing is reported under a device's name
    assert reader("mfu")({**v, "peaks": None}) is None
