"""The reduction from trace rows to numbers, on rows worked by hand and on a
small trace recorded on the chip (``data/``, kept as rows)."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6    # rows are in nanoseconds


def test_interval_arithmetic():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [[0, 3], [5, 8]] and tr.total(u) == 6
    assert tr.subtract([[0, 10]], [[2, 3], [5, 7]]) \
        == [[0, 2], [3, 5], [7, 10]]
    assert tr.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert tr.subtract([[0, 4]], []) == [[0, 4]]


def test_self_time_of_nested_operations():
    rows = [("while.1", 0, 100, ""), ("fusion.2", 10, 30, ""),
            ("custom-call.3", 50, 40, ""), ("fusion.2", 120, 5, "")]
    s = tr.self_times(rows)
    assert s["while.1"] == 30 and s["fusion.2"] == 35
    assert s["custom-call.3"] == 40


def hand_trace():
    """Two devices, a 10 ms window.  Device 0: compute 0-3 ms, an all-gather
    in flight 2-6 ms (started under the compute, waited for 3-6 ms with
    nothing to cover it), compute 8-10 ms.  Device 1: one
    operation 0-10 ms.  The host was in ``step`` when the gap at 6 ms
    opened."""
    ops0 = [("fusion.1", 0, 3 * MS, "jit_step"),
            ("all-gather-done.2", 3 * MS, 3 * MS, "jit_step"),
            ("fusion.3", 8 * MS, 2 * MS, "jit_step")]
    mods0 = [("jit_step(123)", 0, 6 * MS, ""),
             ("jit_step(123)", 8 * MS, 2 * MS, "")]
    ops1 = [("fusion.1", 0, 10 * MS, "jit_step")]
    return {"devices": {
        "/device:TPU:0": {tr.OPS_LINE: ops0, tr.MODULES_LINE: mods0,
                          tr.ASYNC_LINE: [("all-gather-start.2", 2 * MS,
                                           4 * MS, "")]},
        "/device:TPU:1": {tr.OPS_LINE: ops1,
                          tr.MODULES_LINE: [("jit_step(123)", 0, 10 * MS,
                                             "")]}},
        "spans": [("step", 5 * MS, 2 * MS), ("submit", 7 * MS, 0.5 * MS)]}


def test_reduce_by_hand():
    s = tr.reduce_rows(hand_trace())
    assert s["window_s"] == pytest.approx(10e-3)
    assert s["busy_s_by_device"] == pytest.approx([8e-3, 10e-3])
    assert s["busy_s"] == pytest.approx(9e-3)
    assert s["idle_share_worst"] == pytest.approx(0.2)
    assert s["collective_s"] == pytest.approx(2e-3)       # mean of 4 and 0
    assert s["collective_exposed_s"] == pytest.approx(3e-3)
    assert s["module_s"]["jit_step"] == pytest.approx(9e-3)
    assert s["op_self_s"]["fusion.1"] == pytest.approx(6.5e-3)
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["fusion"] == pytest.approx(7.5e-3)
    assert dict(s["breakdown"]["idle_gaps"]) == pytest.approx(
        {"step": 2e-3})
    assert len(s["breakdown"]["device_ops"]) <= 10


def test_one_device_of_several():
    s = tr.reduce_rows(hand_trace(), n_devices=1)
    assert s["busy_s"] == pytest.approx(8e-3)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce_rows({"devices": {}, "spans": []})


def test_names():
    assert tr.base_name("fusion.123") == "fusion"
    assert tr.base_name("paged_attention") == "paged_attention"
    assert tr.base_module("jit_prefill(98765)") == "jit_prefill"
    assert tr.op_name("%fusion.180 = bf16[16,1,2048]{2,0,1} fusion(bf16[1] "
                      "%get-tuple-element.7), kind=kOutput") == "fusion.180"
    assert tr.op_name("paged_attention.13") == "paged_attention.13"
    assert tr.is_collective("all-gather.7") and tr.is_collective(
        "reduce-scatter.1")
    assert not tr.is_collective("fusion.3")


def test_recorded_chip_trace():
    """Two decode steps of ``serve_chat`` recorded on a TPU v5e (my chip
    run, PR 24), kept as rows: 24 layers, so 48 calls of the paged kernel,
    found by the name the kernel gives itself."""
    with open(os.path.join(DATA, "serve_chat_v5e_2steps.json")) as f:
        trace = json.load(f)
    ops = trace["devices"]["/device:TPU:0"][tr.OPS_LINE]
    assert sum(n.startswith("paged_attention") for n, *_ in ops) == 48
    s = tr.reduce_rows(trace)
    assert s["window_s"] == pytest.approx(15.104333e-3)
    assert s["busy_s"] == pytest.approx(11.356442e-3)
    assert s["idle_share_worst"] == pytest.approx(0.2481335, rel=1e-5)
    assert s["module_s"] == pytest.approx({"jit_step": 11.357111e-3})
    assert s["module_calls"]["jit_step"][1] == pytest.approx(5.6785555e-3)
    assert s["kernel_s"] == pytest.approx({"paged_attention": 3.539469e-3})
    top = s["breakdown"]["device_ops"]
    assert [k for k, _ in top[:2]] == ["fusion", "paged_attention"]
    # the whole of the device's idle time fell inside the host's step span
    assert dict(s["breakdown"]["idle_gaps"]) == pytest.approx(
        {"step": 3.747891e-3})
    # self times add up to the busy time: nothing is counted twice
    assert sum(s["op_self_s"].values()) == pytest.approx(s["busy_s"],
                                                         rel=1e-3)
