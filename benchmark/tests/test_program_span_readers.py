"""The readers of the PROGRAM's own spans, on rows made by hand (the window
filter, a request that straddles the window's edge, one never seated, a ring
that has dropped rows) and the division of the device's idle time among the
program's spans, by hand and on a small trace recorded on the chip."""

import collections
import json
import os

import pytest

from benchmark import harness, program_spans as ps, trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6    # trace rows are in nanoseconds

Span = collections.namedtuple(
    "Span", "name t_start t_end parent step uid attrs")


def reader(name):
    return harness.load_plugin("readers", name).read


def request(uid, t_submit, t_admit, tokens, outcome="ok"):
    """A ``serving.request`` row: ``tokens`` are the token stamps."""
    return Span("serving.request", t_submit, tokens[-1] if tokens
                else t_admit, None, 1, uid,
                {"outcome": outcome, "prompt_len": 8, "t_admit": t_admit,
                 "t_first": tokens[0] if tokens else None,
                 "t_tokens": tokens or None})


def view(rows, dropped_until=None, window=(10.0, 50.0)):
    return {"facts": {"window": window},
            "program_spans": {"rows": rows, "dropped_until": dropped_until}}


def request_rows():
    return [
        request(1, 5.0, 5.5, [6.0, 6.1]),               # before the window
        request(2, 10.0, 10.004, [10.010, 10.020, 10.050]),
        request(3, 20.0, 20.100, [20.110, 20.120]),
        # submitted inside the window, finished after it: counts
        request(4, 49.0, 49.002, [49.020, 55.0]),
        # never seated (shed at admission): t_admit is its terminal time
        request(5, 30.0, 30.5, [], outcome="shed"),
        request(6, 50.0, 50.1, [50.2, 50.3]),            # after the window
    ]


@pytest.mark.parametrize("what,q,want_ms", [
    # waits of 2, 3, 4 and 5: 4, 100, 2, 500 ms
    ("queue_wait", 50, 52.0), ("queue_wait", 100, 500.0),
    # prefills of 2, 3 and 4 (5 had none): 6, 10, 18 ms
    ("prefill", 50, 10.0),
    # gaps of 2, 3 and 4, pooled: 10, 30, 10, 5980 ms
    ("inter_token", 50, 20.0), ("inter_token", 100, 5980.0)])
def test_request_stamps_reads_requests_submitted_in_the_window(what, q,
                                                               want_ms):
    got = reader("request_stamps")(view(request_rows()), what=what, q=q)
    assert got == pytest.approx(want_ms)


def test_request_stamps_refuses_a_ring_that_dropped_inside_its_range():
    read = reader("request_stamps")
    rows = request_rows()
    # rows dropped before the window began cost nothing
    assert read(view(rows, dropped_until=9.0), what="queue_wait", q=50) \
        == pytest.approx(52.0)
    assert read(view(rows, dropped_until=10.5), what="queue_wait",
                q=50) is None
    assert read(view([]), what="queue_wait", q=50) is None
    assert read(view([request(5, 30.0, 30.5, [])]), what="prefill",
                q=50) is None


def step_rows():
    def step(n, t, dur, waits):
        rows = [Span("serving.admit", t, t + 0.001, "serving.step", n, None,
                     None)]
        at = t + 0.001
        for name, d in waits:
            rows.append(Span(name, at, at + d, "serving.step", n, None, None))
            at += d
        return rows + [Span("serving.step", t, t + dur, None, n, None, None)]
    return (step(1, 9.0, 0.5, [("serving.readback", 0.4)])      # before
            + step(2, 10.0, 0.010, [("serving.readback", 0.004)])
            + step(3, 11.0, 0.030, [("serving.prefill.readback", 0.010),
                                    ("serving.readback", 0.005)])
            + step(4, 12.0, 0.020, [])
            + step(5, 60.0, 0.9, [("serving.readback", 0.1)]))   # after


def test_step_host_ms_takes_the_waits_for_the_device_out():
    read = reader("step_host_ms")
    minus = ["serving.readback", "serving.prefill.readback"]
    # host parts of steps 2, 3, 4: 6, 15, 20 ms
    assert read(view(step_rows()), root="serving.step", q=50,
                minus=minus) == pytest.approx(15.0)
    assert read(view(step_rows()), root="serving.step", q=0,
                minus=minus) == pytest.approx(6.0)
    # nothing taken out: the spans' durations
    assert read(view(step_rows()), root="serving.step",
                q=50) == pytest.approx(20.0)
    assert read(view(step_rows()), root="train.step", q=50) is None
    assert read(view(step_rows(), dropped_until=10.2), root="serving.step",
                q=50) is None


def test_readers_return_nothing_for_a_program_without_a_recorder(
        monkeypatch):
    """The parent of the PR that brought the recorder: ``spans.recorder``
    does not exist there, and no reader may raise."""
    from deepspeed_tpu.monitor import spans
    monkeypatch.delattr(spans, "recorder")
    v = {"facts": {"window": (0.0, 1.0)}}
    assert reader("request_stamps")(v, what="queue_wait", q=95) is None
    assert reader("step_host_ms")(v, root="serving.step", q=50) is None
    assert reader("idle_in_span")(
        v, span="serving.admit", root="serving.step",
        trace_root="benchmark/tests/data/no_such_dir") is None


# ----------------------------------------------------- the idle-time division
def hand_capture():
    """One device, a 20 ms window.  Busy 0-4, 9-12 and 18-20 ms: idle 4-9
    and 12-18 ms.  One step 2-16 ms with children admit 2-5, dispatch 5-6
    (holding a nested compile 5.2-5.8) and readback 7-15; the rest of the
    step (6-7, 15-16) is the root alone; 16-18 ms no span is open."""
    ops = [("fusion.1", 0, 4 * MS, ""), ("fusion.2", 9 * MS, 3 * MS, ""),
           ("fusion.3", 18 * MS, 2 * MS, "")]
    ann = [("serving.step", 2 * MS, 16 * MS),
           ("serving.admit", 2 * MS, 5 * MS),
           ("serving.dispatch", 5 * MS, 6 * MS),
           ("compile.lower", 5.2 * MS, 5.8 * MS),
           ("serving.readback", 7 * MS, 15 * MS)]
    return {"devices": {"/device:TPU:0": {tr.OPS_LINE: ops}},
            "spans": []}, ann


def test_idle_is_divided_among_the_spans_it_overlaps():
    trace, ann = hand_capture()
    table = ps.gaps_table(trace, ann, "serving.step")
    by = {k: v * 1e3 for k, v in table["by_span"].items()}
    assert table["window_s"] == pytest.approx(20e-3)
    assert table["idle_s"] == pytest.approx(11e-3)
    # the gap 4-9 ms: 1 ms admit, 1 dispatch, 1 root alone, 2 readback (the
    # span open when it began would have taken all five); 12-18 ms: 3
    # readback, 1 root alone, 2 with no span
    assert by["serving.admit"] == pytest.approx(1.0)
    assert by["serving.dispatch"] == pytest.approx(1.0)
    assert by["serving.readback"] == pytest.approx(5.0)
    assert by[ps.ROOT_ONLY] == pytest.approx(2.0)
    assert by[ps.NO_SPAN] == pytest.approx(2.0)
    assert "compile.lower" not in by          # a grandchild, not a child
    assert sum(by.values()) == pytest.approx(11.0, abs=1e-9)
    # no root annotation (a program that writes none): nothing to read
    assert ps.gaps_table(trace, [], "serving.step") is None
    assert ps.gaps_table({"devices": {}, "spans": []}, ann,
                         "serving.step") is None


def test_idle_division_adds_up_to_the_reducers_idle_time_on_a_recording():
    """Two decode steps of ``serve_chat`` recorded on a TPU v5e, kept as
    rows: the device's operations and the program's ``ds.*`` annotations of
    the same capture.  The parts add up to ``trace_reduce``'s idle time."""
    with open(os.path.join(DATA, "serve_chat_v5e_ds_spans.json")) as f:
        rec = json.load(f)
    trace = {"devices": {p: {ln: [tuple(r) for r in rows]
                             for ln, rows in lines.items()}
                         for p, lines in rec["devices"].items()},
             "spans": []}
    ann = [tuple(r) for r in rec["annotations"]]
    table = ps.gaps_table(trace, ann, "serving.step")
    summary = tr.reduce_rows(trace)
    assert table["window_s"] == pytest.approx(summary["window_s"], abs=1e-12)
    idle_s = summary["idle_share_worst"] * summary["window_s"]
    assert table["idle_s"] == pytest.approx(idle_s, abs=1e-9)
    assert sum(table["by_span"].values()) == pytest.approx(idle_s, abs=1e-9)
    # the recording holds real steps: the wait for the device and the
    # upload both own some of the idle time
    assert table["by_span"]["serving.readback"] > 0
    assert {"serving.upload", "serving.dispatch", "serving.bookkeeping"} \
        <= set(table["by_span"])
