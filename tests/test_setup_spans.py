"""Start-up traced from inside the program (``monitor/spans.py``'s set-up
store, ``monitor/startup.py``; docs/monitoring.md#start-up): the store
outlives the step ring, the partition adds up and books each instant once,
both engines leave their rows and log the start-up line once."""

import contextlib
import json
import logging
import os
import subprocess
import sys
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.inference import Request, ServingConfig, ServingEngine
from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
from deepspeed_tpu.monitor import spans as monspans, startup
from deepspeed_tpu.monitor.spans import Span, SpanRecorder
from deepspeed_tpu.runtime import compile_cache as cc
from deepspeed_tpu.utils.logging import logger

from simple_model import SimpleModel, random_dataset, base_config


def ticking(step=1.0):
    t = [0.0]

    def clock():
        t[0] += step
        return t[0]
    return clock


def row(name, t0, t1, parent=None, attrs=None):
    return Span(name, t0, t1, parent, None, None, attrs)


@contextlib.contextmanager
def logged():
    """The package logger's messages (it does not propagate to caplog)."""
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    logger.addHandler(handler)
    try:
        yield lines
    finally:
        logger.removeHandler(handler)


# ------------------------------------------------------------------ the store
def test_setup_store_survives_a_ring_that_drops_step_rows():
    rec = SpanRecorder(capacity=8, clock=ticking())
    with rec.setup_span("setup.engine_init", attrs={"engine": "x"}):
        with rec.setup_span("setup.pool_alloc"):
            pass
    for i in range(40):
        with rec.span("serving.step", step=i):
            with rec.span("serving.dispatch"):
                pass
    assert rec.dropped > 0 and rec.dropped_until is not None
    assert not [r for r in rec.rows() if r.name.startswith("setup.")]
    rows, whole = rec.setup_rows()
    assert whole
    assert [r.name for r in rows] == ["setup.pool_alloc", "setup.engine_init"]
    assert rows[0].parent == "setup.engine_init"
    assert rows[1].attrs == {"engine": "x"}


def test_setup_rows_ride_the_ring_too_until_it_drops_them():
    """``rows()`` keeps its meaning: a reader of the ring (a recompile
    inside the window, named by the step it fell in) finds the
    ``compile.*`` rows where they were."""
    rec = SpanRecorder(clock=ticking())
    root = rec.open("serving.step", step=1)
    with rec.setup_span("compile.lower", attrs={"fn": "f"}):
        pass
    rec.close(root)
    assert [r.name for r in rec.rows()] == ["compile.lower", "serving.step"]
    assert rec.rows()[0].parent == "serving.step"
    assert rec.since(root) == rec.rows()
    # the two rows share their attributes: what is learned after the close
    rec.setup_rows()[0][0].attrs["trace_s"] = 0.5
    assert rec.rows("compile.lower")[0].attrs == {"fn": "f", "trace_s": 0.5}


def test_setup_store_has_a_cap_and_says_when_it_is_not_whole():
    rec = SpanRecorder(clock=ticking(), setup_capacity=3)
    for i in range(3):
        rec.setup_record("jax.compile", float(i), i + 0.5)
    assert rec.setup_rows()[1] and rec.setup_dropped == 0
    with rec.setup_span("compile.build"):
        pass
    rows, whole = rec.setup_rows()
    assert not whole and rec.setup_dropped == 1 and len(rows) == 3
    assert rec.rows("compile.build")          # the ring took it all the same
    rec.reset()
    assert rec.setup_rows() == ([], True)


def test_a_discarded_setup_span_is_kept_nowhere():
    """``compile.load`` on a store that holds nothing: a lookup, not a
    load."""
    rec = SpanRecorder(clock=ticking())
    with rec.setup_span("compile.load") as span:
        rec.discard(span)
    assert rec.rows() == [] and rec.setup_rows() == ([], True)
    assert rec.depth == 0


def test_the_root_of_a_step_that_acquired_something_is_kept_with_it():
    rec = SpanRecorder(capacity=4, clock=ticking())
    idle = rec.open("serving.step", step=0)
    rec.setup_record("jax.compile", 0.25, 0.5)
    rec.discard(idle)                       # an idle poll has no row
    first = rec.open("serving.step", step=1)
    with rec.span("serving.dispatch"):
        with rec.setup_span("compile.lower"):
            pass
        with rec.setup_span("compile.build"):
            pass
    assert [r.name for r in rec.setup_rows()[0]] == [
        "jax.compile", "compile.lower", "compile.build"]     # still open
    rec.close(first)
    for i in range(2, 12):                  # steps that acquire nothing
        with rec.span("serving.step", step=i):
            pass
    rows, whole = rec.setup_rows()
    assert whole and rec.dropped > 0
    assert [(r.name, r.step) for r in rows] == [
        ("jax.compile", None), ("compile.lower", 1), ("compile.build", 1),
        ("serving.step", 1)]
    assert rows[3].t_start == first.t0 and rows[3].t_end == first.t1


def test_process_start_is_on_the_recorders_clock():
    rec = monspans.recorder()
    now = time.monotonic()
    # pytest's own start-up lies between the two; an hour is a broken clock
    assert 0.0 < now - rec.t_process_start < 3600.0
    assert SpanRecorder(clock=ticking(5.0)).t_process_start == 5.0


# --------------------------------------------------------------- jax's events
TRACE, LOWER, COMPILE = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration")


def test_jax_durations_become_rows_that_end_now():
    now = [100.0]
    rec = SpanRecorder(clock=lambda: now[0])
    rec._jax_duration(TRACE, 0.25)
    rec._jax_duration("/jax/some/other_duration", 3.0)
    rec._jax_duration(LOWER, 0.0005)          # under a millisecond: no row
    now[0] = 101.0
    rec._jax_duration(monspans.JAX_CACHE_RETRIEVAL, 0.125)
    rec._jax_duration(COMPILE, 0.5)
    now[0] = 103.0
    rec._jax_duration(COMPILE, 1.5)
    assert rec.setup_rows()[0] == [
        row("jax.trace", 99.75, 100.0),
        row("jax.compile", 100.5, 101.0, attrs={"cached": True}),
        row("jax.compile", 101.5, 103.0)]
    assert rec.rows() == []                   # no reader of the ring


def test_a_jit_traced_inside_another_leaves_the_outer_row():
    now = [10.0]
    rec = SpanRecorder(clock=lambda: now[0])
    for end, seconds in ((10.0, 0.5), (11.0, 0.25), (12.0, 3.0)):
        now[0] = end
        rec._jax_duration(TRACE, seconds)
    now[0] = 13.0
    rec._jax_duration(LOWER, 0.75)
    now[0] = 14.0
    rec._jax_duration(TRACE, 0.5)
    assert rec.setup_rows()[0] == [
        row("jax.trace", 9.0, 12.0), row("jax.lower", 12.25, 13.0),
        row("jax.trace", 13.5, 14.0)]


def test_a_jit_outside_cachedstep_is_seen_and_a_lowering_is_split(tmp_path):
    rec = fresh()
    t0 = rec.now()

    def heavy(x):
        for _ in range(300):
            x = jnp.sin(x) * 2.0 + 1.0
        return x
    jax.jit(heavy)(jnp.ones((7,)))            # no CachedStep round this one
    names = {r.name for r in rec.setup_rows()[0] if r.t_start >= t0}
    assert {"jax.trace", "jax.lower", "jax.compile"} <= names

    t1 = rec.now()
    step = cc.wrap_step("heavy", heavy, cache=cc.CompileCache(str(tmp_path)))
    step(jnp.ones((9,)))
    lower, key = [r for r in rec.setup_rows()[0] if r.t_start >= t1
                  and r.name in ("compile.lower", "compile.key")]
    assert lower.attrs["fn"] == key.attrs["fn"] == "heavy"
    assert lower.attrs["trace_s"] > 0 and lower.attrs["mlir_s"] > 0
    assert lower.attrs["trace_s"] + lower.attrs["mlir_s"] \
        <= lower.t_end - lower.t_start
    assert lower.t_end <= key.t_start


def test_a_lowering_says_which_grouped_products_it_traced(tmp_path):
    """An executable that holds an expert layer: its ``compile.lower`` row
    names each grouped product by kernel, tiles and shapes
    (``moe/dropless.products_traced``); one that holds none has no such
    attribute."""
    from deepspeed_tpu.moe import dropless
    rec = fresh()
    t0 = rec.now()
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(key[0], (10, 16))
    experts, weights = dropless.route(jax.random.normal(key[1], (10, 4)), 2)
    gate, up = (jax.random.normal(k, (4, 16, 24)) for k in key[2:4])
    down = jax.random.normal(key[4], (4, 24, 16))
    cache = cc.CompileCache(str(tmp_path))
    cc.wrap_step("layer", lambda x, e, w: dropless.held_experts(
        x, e, w, gate, up, down, 0), cache=cache)(x, experts, weights)
    cc.wrap_step("plain", lambda x: x * 2.0, cache=cache)(x)
    layer, plain = [r for r in rec.setup_rows()[0]
                    if r.t_start >= t0 and r.name == "compile.lower"]
    assert layer.attrs["grouped_products"] == {
        "ragged_dot of 20x16x24/4": 2, "ragged_dot of 20x24x16/4": 1}
    assert "grouped_products" not in plain.attrs


# -------------------------------------------------------------- the partition
def parts_of(rows, t_start, t_until):
    parts = startup.partition(rows, t_start, t_until)
    assert tuple(parts) == startup.PHASES
    assert sum(parts.values()) == t_until - t_start
    return {k: v for k, v in parts.items() if v}


@pytest.mark.parametrize("rows,want", [
    # a lowering inside a step inside nothing; the window opens at 16
    ([row("setup.import", 2, 4),
      row("serving.step", 6, 12),
      row("compile.lower", 7, 9, "serving.prefill.dispatch"),
      row("serving.step", 20, 21)],
     {"before_program": 2, "import": 2, "trace_lower": 2, "warmup_run": 4,
      "unattributed": 6}),
    # a trace inside a lowering and one outside any
    ([row("compile.lower", 1, 5, attrs={"fn": "f"}),
      row("jax.trace", 1.5, 3), row("jax.lower", 3, 4.5),
      row("jax.trace", 8, 10), row("jax.lower", 10, 11),
      row("jax.compile", 11, 13),
      row("jax.compile", 13, 13.5, attrs={"cached": True})],
     {"before_program": 1, "trace_lower": 7, "build": 2, "cache_load": 0.5,
      "unattributed": 5.5}),
    # an engine's constructor holding a placement and a build
    ([row("setup.engine_init", 1, 9, attrs={"engine": "train"}),
      row("setup.state_place", 2, 7, "setup.engine_init"),
      row("jax.compile", 3, 5),
      row("train.step", 10, 15),
      row("compile.lower", 10.5, 11, "train.dispatch"),
      row("compile.key", 11, 11.5, "train.dispatch"),
      row("compile.load", 11.5, 12, "train.dispatch"),
      row("compile.build", 12, 14, "train.dispatch"),
      row("jax.compile", 12, 13.5, attrs={"cached": True})],
     {"before_program": 1, "engine_init": 6, "build": 2.5,
      "trace_lower": 0.5, "cache_load": 2.5, "warmup_run": 1.5,
      "unattributed": 2}),
    # rows of the window, and rows no phase knows, change nothing
    ([row("setup.import", 0, 1), row("serving.request", 1, 30),
      row("serving.dispatch", 2, 3, "serving.step"),
      row("compile.build", 17, 19, "serving.step")],
     {"import": 1, "unattributed": 15}),
    # nothing recorded: all of it is before the program
    ([], {"before_program": 16}),
])
def test_partition_adds_up_and_books_each_instant_once(rows, want):
    assert parts_of(rows, 0.0, 16.0) == want


def test_innermost_row_wins_whatever_order_the_rows_come_in():
    rows = [row("b", 2, 6), row("a", 0, 10), row("c", 3, 4), row("d", 8, 12)]
    for order in (rows, rows[::-1]):
        got = monspans.innermost_seconds(order, 1.0, 11.0, lambda r: r.name)
        assert got == {"a": 1 + 2, "b": 1 + 2, "c": 1, "d": 3}
    assert monspans.innermost_seconds(rows, 20.0, 21.0, len) == {None: 1.0}
    assert monspans.innermost_seconds(rows, 5.0, 5.0, len) == {}


# ---------------------------------------------------------------- the engines
def since(t0, prefix="setup."):
    return [r for r in monspans.recorder().setup_rows()[0]
            if r.t_start >= t0 and r.name.startswith(prefix)]


def fresh():
    """The process-wide recorder with room in its set-up store: a pytest
    worker that has compiled for minutes has filled it (the store keeps a
    process's FIRST rows, by design)."""
    rec = monspans.recorder()
    rec.reset()
    return rec


def test_the_packages_import_is_the_programs_first_row():
    """In a process of its own: pytest's has long since reset the store."""
    code = (
        "import sys, json, time\n"
        "import deepspeed_tpu\n"
        "from deepspeed_tpu.monitor import spans, startup\n"
        "rec = spans.recorder()\n"
        "rows, whole = rec.setup_rows()\n"
        "print(json.dumps({'rows': [[r.name, r.t_start, r.t_end, r.attrs]"
        " for r in rows], 'whole': whole, 'start': rec.t_process_start,"
        " 'now': time.monotonic(), 'report': startup.report()}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [os.path.dirname(os.path.dirname(ds.__file__))]
        + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=300).stdout
    got = json.loads(out.strip().splitlines()[-1])
    assert got["whole"]
    (name, t0, t1, attrs), = [r for r in got["rows"]
                              if r[0] == "setup.import"]
    assert attrs == {"jax_preloaded": False}
    assert got["start"] < t0 < t1 <= got["now"]
    assert t0 == min(r[1] for r in got["rows"])
    # the interpreter's own start lies before the import: tenths of a
    # second, not the seconds the import itself takes
    phases = got["report"]["phases"]
    assert 0.0 < phases["before_program"] < t1 - t0
    assert phases["import"] == pytest.approx(t1 - t0, abs=0.05)
    assert sum(phases.values()) == pytest.approx(got["report"]["total_s"])


def test_initialize_leaves_its_rows_and_logs_the_line_once(mesh8):
    t0 = fresh().now()
    with logged() as lines:
        engine, _, _, _ = ds.initialize(
            config=base_config(over={"zero_optimization": {"stage": 1},
                                     "bf16": {"enabled": True}}),
            model=SimpleModel(), training_data=random_dataset(64), mesh=mesh8)
        rows = {r.name: r for r in since(t0)}
        assert set(rows) == {"setup.engine_init", "setup.params_init",
                             "setup.state_place"}
        root = rows["setup.engine_init"]
        assert root.parent is None and root.attrs == {"engine": "train"}
        for child in ("setup.params_init", "setup.state_place"):
            assert rows[child].parent == "setup.engine_init"
            assert root.t_start <= rows[child].t_start
            assert rows[child].t_end <= root.t_end
        state = jax.tree_util.tree_leaves(engine.state)
        assert rows["setup.state_place"].attrs == {
            "bytes": sum(int(leaf.nbytes) for leaf in state)}
        assert not [ln for ln in lines if "start-up" in ln]
        for _ in range(3):
            engine.train_batch()
    said = [ln for ln in lines if "start-up" in ln]
    assert len(said) == 1 and "DeepSpeedEngine start-up " in said[0]
    for word in ("before the program", "import", "engine", "trace+lower",
                 "cache load", "build", "warm-up", "other"):
        assert f"{word} " in said[0]
    # the first step stood still for its executable: its root is start-up
    steps = [r for r in since(t0, "train.step")]
    assert steps and steps[0].parent is None
    got = startup.report()
    assert got["whole"] and sum(got["phases"].values()) == pytest.approx(
        got["total_s"])
    assert got["phases"]["warmup_run"] > 0
    engine.close()


def test_serving_engine_leaves_its_rows_and_logs_the_line_once():
    cfg = GPT2Config(vocab_size=128, max_seq=64, n_embd=32, n_layer=2,
                     n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    model = GPT2(cfg, dtype=jnp.float32)
    t0 = fresh().now()
    with logged() as lines:
        srv = ServingEngine(model=model, params=None,
                            config=ServingConfig(batch_slots=2, block_size=8))
        rows = since(t0)
        assert [(r.name, r.parent) for r in rows] == [
            ("setup.engine_init", "setup.engine_init"),   # the engine built
            ("setup.pool_alloc", "setup.engine_init"),    # inside the server
            ("setup.engine_init", None)]
        assert rows[0].attrs == {"engine": "InferenceEngine"}
        assert rows[2].attrs == {"engine": "ServingEngine"}
        from deepspeed_tpu.inference import paged_kv as pk
        assert rows[1].attrs == {"bytes": pk.pool_bytes(srv.pool)}
        assert srv.step() is False                # an idle poll: no line
        assert not [ln for ln in lines if "start-up" in ln]
        srv.run([Request(tokens=np.arange(5), max_new_tokens=3),
                 Request(tokens=np.arange(9), max_new_tokens=2)])
    said = [ln for ln in lines if "start-up" in ln]
    assert len(said) == 1 and "ServingEngine start-up " in said[0]
    acquired = {r.name for r in since(t0, "compile.")}
    assert {"compile.lower", "compile.key"} <= acquired
    steps = since(t0, "serving.step")
    assert steps and all(r.parent is None for r in steps)
    srv.close()
