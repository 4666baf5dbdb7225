"""The ``setup_seconds`` reader on views made by hand: a program without the
set-up store gives nothing, a whole store gives eight phases that add up to
the window's start less the process's start, a store that has refused a row
gives nothing; and every ``setup.*_s`` metric of ``BENCHMARK.json`` names a
phase the program knows."""

import collections

import pytest

from benchmark import harness

Span = collections.namedtuple(
    "Span", "name t_start t_end parent step uid attrs")
PHASES = ("before_program", "import", "engine_init", "trace_lower",
          "cache_load", "build", "warmup_run", "unattributed")


def read(view, phase):
    return harness.load_plugin("readers", "setup_seconds").read(view, phase)


def row(name, t0, t1, parent=None, attrs=None):
    return Span(name, t0, t1, parent, None, None, attrs)


def hand_view(whole=True, window=(140.0, 180.0)):
    """A process that began at 100 and whose window opens at 140: 15 s to
    reach the program, 2 s of import, an engine built in 4 s of which 1 s
    compiled, the harness's weights (a ``jax.jit`` of its own, 3 s traced
    and lowered, 2 s from JAX's cache), one warm-up step of 12 s that
    lowered for 5, hashed and loaded for 2 and ran for 5."""
    rows = [
        row("setup.import", 115, 117, attrs={"jax_preloaded": True}),
        row("jax.trace", 118, 120), row("jax.lower", 120, 121),
        row("jax.compile", 121, 123, attrs={"cached": True}),
        row("setup.engine_init", 123, 127, attrs={"engine": "ServingEngine"}),
        row("setup.pool_alloc", 124, 126, "setup.engine_init",
            {"bytes": 1 << 30}),
        row("jax.compile", 125, 126),
        row("compile.lower", 128, 133, "serving.prefill.dispatch",
            {"fn": "prefill", "trace_s": 3.0, "mlir_s": 2.0}),
        row("jax.trace", 128, 131), row("jax.lower", 131, 133),
        row("compile.key", 133, 134, "serving.prefill.dispatch"),
        row("compile.load", 134, 135, "serving.prefill.dispatch"),
        row("serving.step", 127.5, 139.5),
        # the window's own rows are not set-up
        row("serving.step", 141, 142),
        row("compile.build", 141.2, 141.8, "serving.dispatch"),
    ]
    return {"facts": {"window": window},
            "program_setup": {"rows": rows, "whole": whole,
                              "t_process_start": 100.0}}


WANT = {"before_program": 15.0, "import": 2.0, "engine_init": 3.0,
        "trace_lower": 8.0, "cache_load": 4.0, "build": 1.0,
        "warmup_run": 5.0, "unattributed": 2.0}


@pytest.mark.parametrize("phase", PHASES)
def test_each_phase_of_a_hand_made_view(phase):
    assert read(hand_view(), phase) == pytest.approx(WANT[phase])


def test_the_eight_phases_add_up_to_the_window_start_less_process_start():
    view = hand_view()
    assert sum(read(view, p) for p in PHASES) == pytest.approx(40.0)
    assert sum(WANT.values()) == 40.0
    # an earlier window: the rows after it are clipped, the sum follows
    early = hand_view(window=(130.0, 170.0))
    assert sum(read(early, p) for p in PHASES) == pytest.approx(30.0)
    assert read(early, "trace_lower") == pytest.approx(3.0 + 2.0)


@pytest.mark.parametrize("phase", PHASES)
def test_the_call_that_started_the_tpu_runtime_is_left_out(phase):
    """``run.py`` hands the seconds of its ``jax.devices()`` in: the clock
    starts that much later, so ``before_program`` alone is shorter and the
    eight still add up to what ``setup_s`` counts."""
    view = dict(hand_view(), chip_reach_s=9.0)
    want = dict(WANT, before_program=15.0 - 9.0)
    assert read(view, phase) == pytest.approx(want[phase])
    assert sum(read(view, p) for p in PHASES) == pytest.approx(40.0 - 9.0)


@pytest.mark.parametrize("phase", PHASES)
def test_nothing_once_the_store_has_refused_a_row(phase):
    assert read(hand_view(whole=False), phase) is None


def test_nothing_for_a_program_without_the_set_up_store(monkeypatch):
    """The parent of the PR that brought the store: its recorder has no
    ``setup_rows``, and the reader may not raise."""
    from deepspeed_tpu.monitor import spans
    view = {"facts": {"window": (1.0, 2.0)}}
    assert read(view, "import") is not None       # this program has it
    monkeypatch.delattr(spans.SpanRecorder, "setup_rows")
    assert all(read(view, p) is None for p in PHASES)
    monkeypatch.delattr(spans, "recorder")        # ... or no recorder
    assert all(read(view, p) is None for p in PHASES)


def test_the_live_recorder_reads_from_process_start_to_the_window():
    from deepspeed_tpu.monitor import spans
    rec = spans.recorder()
    t0 = rec.now()
    view = {"facts": {"window": (t0, t0 + 1.0)}}
    got = {p: read(view, p) for p in PHASES}
    if not rec.setup_rows()[1]:
        pytest.skip("an earlier test overfilled this process's store")
    assert all(v >= 0.0 for v in got.values())
    assert sum(got.values()) == pytest.approx(t0 - rec.t_process_start)
    assert got["import"] > 0.0


def test_every_setup_metric_names_a_phase_and_none_is_missing():
    bench = harness.load_benchmark()
    metrics = [m for m in bench["per_layer"]
               if m["name"].startswith("setup.")]
    assert [m["name"] for m in metrics] == [f"setup.{p}_s" for p in PHASES]
    from deepspeed_tpu.monitor import startup
    assert startup.PHASES == PHASES
    for m in metrics:
        spec = harness.read_json("layer_metrics", f"{m['name']}.json")
        assert spec["reader"] == "setup_seconds"
        assert m["name"] == f"setup.{spec['params']['phase']}_s"
        # every cell reports setup_s: no list, as cache.hit_share has none
        assert "workloads" not in m and m["moves"] == "setup_s"
        assert (m["unit"], m["better"], m["source"]) == (
            "s", "lower", "program_span")
