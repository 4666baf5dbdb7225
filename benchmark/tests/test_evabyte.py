"""The EvaByte family's files (``configs/evabyte-6.5b.json``, ``families/
evabyte.py``, ``reference/evabyte.py``, ``control_evabyte.py``) and its cell
(``traffic/serve_bulk_bytes_evabyte.json``, ``runners/
serve_backlog_folded.py``, the ``*.evabyte`` metric files): the file held to
the catalog row key by key but the depth, the parameter count against its
closed form and the program's own shapes, the family's costs against numbers
worked by hand (by the ROWS the tables hold), the cell through its runner at
a tiny size on the CPU with a fold made in decoding among the compared rows,
every control of the fold reading not-correct through that runner's own
comparison, and the decode step, the whole-window prefill and the fold
compiled for a described v5e at the published widths with the file's pool.

(The tiny preset stands here and not in ``benchmark/tests/tiny.py``: that
file is the benchmark's own, and a model_config PR edits none of them.)
"""

import collections
import copy
import importlib
import json

import pytest

from benchmark import harness, run
from benchmark.tests.cell_metrics import own_and_shared
from benchmark.tests.test_runners_cpu import SEED

# chunk 4, window 16: a stream of 64 tokens folds three times
TINY = {"model_type": "evabyte", "vocab_size": 320, "hidden_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 256,
        "rms_norm_eps": 1e-5, "rope_theta": 100000, "rope_scaling": None,
        "max_position_embeddings": 128, "chunk_size": 4, "window_size": 16,
        "num_pred_heads": 8, "norm_add_unit_offset": True}
ROW_BYTES = 2 * 32 * 128 * 2                # K and V of one row, one layer
BLOCKS = 1152                               # ISSUE 56's pool: 9.66 GB
BENCH = harness.load_benchmark()
CELL = harness.cell_by_name(BENCH, "serve_bulk_bytes_evabyte")
TRAFFIC = harness.load_traffic(CELL["traffic"])
with open("/opt/skills/guides/model-configs/architectures.jsonl") as _f:
    CATALOG = next(row for row in map(json.loads, _f)
                   if row["name"] == "EvaByte")


@pytest.fixture(scope="module")
def config():
    return harness.read_json("configs", "evabyte-6.5b.json")


@pytest.fixture(scope="module")
def family(config):
    return harness.family(config)


# ------------------------------------------------------------ the configuration
@pytest.mark.parametrize("key", sorted(CATALOG["config"]))
def test_the_file_is_the_catalog_row_key_by_key_but_the_depth(config, key):
    if key == "num_hidden_layers":
        assert (config[key], CATALOG["config"][key]) == (8, 32)
        assert config["published"] == {key: 32}
        assert config["reduced"] == [key]
    else:
        assert key in config and config[key] == CATALOG["config"][key]


def test_the_file_states_its_source_its_departures_and_its_deployment(config):
    assert config["source"] == CATALOG["source_url"]
    assert config["name"] == "evabyte-6.5b"
    for key in ("rotary_before_pooling", "scale_inside_a", "current_window",
                "phi_mu_init", "head_order", "fp32_flags", "weights",
                "multi_byte_decoding", "typed_without_a_network"):
        assert key in config["assumed"], key
    said = config["deployment"]
    assert "FOUR PIPELINE STAGES" in said and "no layer is divided" in said
    assert "about four times a deployment's" in said
    assert f"{BLOCKS:,} blocks" in said and "9.66 GB" in said


def test_parameters_match_the_closed_form_and_the_programs_shapes(config,
                                                                  family):
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert layer == family.layer_params(config) == 202_391_552
    rest = 320 * 4096 + 8 * 320 * 4096 + 4096
    assert rest == 11_800_576
    assert 8 * layer + rest == 1_630_932_992 == config["parameters"] \
        == family.parameters(config)
    assert family.parameters({**config, "num_hidden_layers": 32}) \
        == 6_488_330_240
    import jax
    import jax.numpy as jnp
    model = family.build(config, jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == model.num_params() == config["parameters"]
    assert shapes["head"].shape == (8 * 320, 4096)
    c = model.config
    assert (c.n_layer, c.kv_layers, c.n_head, c.n_kv_head, c.head_dim,
            c.max_seq, c.window_size, c.chunk_size) == (
                8, 8, 32, 32, 128, 32768, 2048, 16)
    fold = model.cache_fold(64)
    assert (fold.summary_blocks, fold.window_blocks,
            fold.table_blocks(c.max_seq), model.summary_table_blocks(64)) \
        == (2, 32, 62, 30)


def test_dims_and_what_the_family_refuses(config, family):
    assert family.dims(config) == {
        "n_layer": 8, "n_head": 32, "n_kv_head": 32, "head_dim": 128,
        "d_model": 4096, "kv_width": 4096, "vocab_size": 320,
        "max_positions": 32768}
    # the layers' matrices and head 0 of the eight
    assert family.matmul_params_per_token(config) == \
        8 * (4 * 4096 ** 2 + 3 * 4096 * 11008) + 320 * 4096
    for key, value in [("attention_class", "softmax"), ("hidden_act", "gelu"),
                       ("fp32_skip_add", False), ("attention_bias", True),
                       ("tie_word_embeddings", True), ("max_seq_length", 4096)]:
        with pytest.raises(ValueError, match=key):
            family.build({**config, key: value}, None)
    with pytest.raises(ValueError, match="rope_scaling"):
        family.build({**config, "rope_scaling": {"factor": 2.0}}, None)


# -------------------------------------------------------------------- costs
def view_with(family, rows=()):
    Row = collections.namedtuple("Row", "name t_start t_end attrs")
    cfg = harness.read_json("configs", "evabyte-6.5b.json")
    facts = {**family.dims(cfg), "kv_bytes_per_element": 2,
             "window": (0.0, 40.0),
             "live_tokens": [(20.0, 400_000), (20.5, 400_046)]}
    return {"facts": facts, "trace_span": (19.0, 21.0), "family": family,
            "config": cfg, "peaks": harness.peaks_for("TPU v5 lite"),
            "program_spans": {"rows": [Row(*r) for r in rows],
                              "dropped_until": None}}


def step_rows():
    """Three ``serving.step`` rows in the capture, one before it, one of a
    program that records no rows."""
    attrs = lambda rows, summary, window: {
        "n_active": 46, "kv_tokens": rows,
        "summary_blocks": summary, "window_blocks": window,
        "blocks_in_use": summary + window,
        "blocks_free": 1151 - summary - window}
    return [("serving.step", 5.0, 5.1, attrs(99_999, 10, 10)),
            ("serving.step", 19.5, 19.6, attrs(70_000, 300, 700)),
            ("serving.step", 20.0, 20.1, attrs(70_046, 300, 702)),
            ("serving.step", 20.5, 20.6, attrs(68_000, 310, 640)),
            ("serving.step", 20.7, 20.8, {"n_active": 46})]


def test_costs_count_the_rows_the_tables_hold_not_the_streams_lengths(
        family, config):
    v = view_with(family, step_rows())
    rows = 70_000 + 70_046 + 68_000
    assert family.table_rows_in_capture(v) == rows
    flops, nbytes = family.costs["evabyte_paged_attention"](v)
    assert nbytes == rows * 8 * ROW_BYTES == rows * 131_072
    assert flops == rows * 8 * 2 * 2 * 4096
    # nothing of the 800,046 stream tokens the harness counted
    assert nbytes < 0.3 * 800_046 * 8 * ROW_BYTES
    weights = 2 * (8 * 202_391_552 + 4096 + 320 * 4096)
    assert family.decode_step_weight_bytes(config) == weights \
        == 3_240_894_464
    v["trace"] = {"module_calls": {"jit_step": (0.06, 0.02),
                                   "jit_prefill_window": (0.3, 0.03)}}
    flops, nbytes = family.costs["evabyte_decode_bytes"](
        v, module_match="jit_step")
    assert flops == 0.0 and nbytes == 3 * weights + rows * 8 * ROW_BYTES
    # a program that records no such rows: nothing to price
    assert family.costs["evabyte_paged_attention"](view_with(family)) \
        == (0.0, 0.0)


def metric(view, name):
    spec = harness.read_json("layer_metrics", f"{name}.json")
    return harness.load_plugin("readers", spec["reader"]).read(
        view, **spec.get("params", {}))


def test_the_cells_own_metrics_read_the_rows_and_the_trace(family, config):
    v = view_with(family, step_rows())
    assert metric(v, "serving.summary_block_share.evabyte") == pytest.approx(
        100 * (10 / 20 + 300 / 1000 + 300 / 1002 + 310 / 950) / 4)
    v["trace"] = {"module_s": {"jit_step": 0.06, "jit_prefill_window": 0.3},
                  "module_calls": {"jit_step": (0.06, 0.02)},
                  "kernel_s": {"paged_attention": 0.045}}
    rows = 70_000 + 70_046 + 68_000
    need = 3 * family.decode_step_weight_bytes(config) + rows * 8 * ROW_BYTES
    assert metric(v, "engine.decode_bandwidth_share.evabyte") == \
        pytest.approx(100 * need / 819e9 / 0.06)
    assert metric(v, "kernels.evabyte.paged_attention_roofline") == \
        pytest.approx(100 * rows * 8 * ROW_BYTES / 819e9 / 0.045)
    # a program without the counters (the parent): nothing, never zero
    old = view_with(family, [r for r in step_rows() if len(r[3]) < 2])
    assert metric(old, "serving.summary_block_share.evabyte") is None


def test_the_cell_reports_its_metrics_and_the_accepted_ones_it_must():
    own, shared = own_and_shared(BENCH, CELL["name"], "serve_tokens_per_s")
    assert own == {"kernels.evabyte.paged_attention_roofline",
                   "engine.prompt_attention_share.evabyte",
                   "engine.summarise_share.evabyte",
                   "engine.decode_bandwidth_share.evabyte",
                   "serving.summary_block_share.evabyte",
                   "serving.fold_idle_share.evabyte"}
    assert shared == {
        "serving.tokens_per_step", "serving.host_ms_per_step_p50.tput",
        "serving.step_ms_p50.tput", "serving.queue_wait_ms_p50",
        "serving.prefill_ms_p50.tput", "serving.pool_fill_share",
        "serving.pool_bound_share", "engine.prefill_share.tput",
        "device.idle_share.tput", "device.unscoped_share.tput"}
    assert [m["name"] for m in harness.cell_metrics(
        BENCH, "end_to_end", CELL["name"])] == ["serve_tokens_per_s",
                                                "setup_s"]
    assert (len(BENCH["configs"]), len(BENCH["workloads"]),
            sum(w["chips"] == 4 for w in BENCH["workloads"])) == (11, 14, 1)


# ----------------------------------------------------------- ISSUE 56's cell
def test_the_traffic_file_is_the_cell_issue_56_fixed():
    t = TRAFFIC
    assert (t["kind"], t["pool_requests"], t["queue_depth"]) == (
        "serve_backlog_folded", 768, 64)
    assert t["order_seed"] == 56
    [cls] = t["classes"]
    assert cls["prompt_tokens"] == {
        "kind": "lognormal", "median": 6144, "sigma": 0.6, "min": 2048,
        "max": 24576, "round_to": 256, "short_by": 16}
    assert cls["output_tokens"] == {
        "kind": "lognormal", "median": 512, "sigma": 0.5, "min": 128,
        "max": 1536}
    assert (cls["sampling"], cls["temperature"]) == ("alternate", 0.8)
    assert t["serving"]["batch_slots"] == 64
    assert t["serving"]["block_size"] == 64 and t["serving"]["kv_bits"] == 16
    assert t["serving"]["num_blocks"] <= BLOCKS
    assert (t["check"]["slots"], t["check"]["steps"]) == (5, 3)
    runner = harness.load_plugin("runners", t["kind"])
    a = runner.backlog(t, 1, 320)
    b = runner.backlog(t, 2 ** 31 + 5, 320)
    assert [(len(x.prompt), x.new_tokens, x.do_sample) for x in a] == \
        [(len(x.prompt), x.new_tokens, x.do_sample) for x in b]
    assert max(int(x.prompt.max()) for x in a) == 319
    # nine prefill executables: the whole window's and eight tails'
    assert len({-(-(len(x.prompt) % 2048) // 64) for x in a} - {0}) == 8
    # a mean stream holds some 23 blocks where a growing table would hold 125
    from deepspeed_tpu.inference import paged_kv as pk
    fold = pk.WindowFold(2048, 16, 64)
    held = [int(fold.held(len(x.prompt) + 1)) for x in a]
    grown = [-(-(len(x.prompt) + x.new_tokens) // 64) for x in a]
    assert 22 < sum(held) / len(a) < 24 and 124 < sum(grown) / len(a) < 127
    # what the warm-up seats: the shortest prompt of every tail bucket (and
    # of those with no tail), each behind one whole window
    warm = [len(it.prompt) for it in runner.warm_picks(a, 2048, 64)]
    assert sorted(runner.tail_bucket(n, 2048, 64) for n in warm) == \
        list(range(0, 2049, 256))
    assert warm[0] == 2048 and max(warm) < 2 * 2048 + 256
    # what the check seats: two tails that end a window within its 3 steps
    # (after many windows and after one), one just past a window, the
    # longest bucket, the median
    picks = runner.check_picks(a, 2048, t["check"]["steps"])
    covers = {k: len(it.prompt) for k, it in picks.items()}
    assert 1 <= -covers["ends_a_window"] % 2048 <= 3
    assert 1 <= -covers["ends_a_window_too"] % 2048 <= 3
    assert covers["ends_a_window_too"] < 3 * 2048 < 6 * 2048 < \
        covers["ends_a_window"]
    assert 0 < covers["just_past_a_window"] % 2048 <= 256
    assert covers["longest"] == 24576 == max(len(x.prompt) for x in a)
    assert len(covers) == t["check"]["slots"]


def tiny_traffic():
    t = copy.deepcopy(TRAFFIC)
    # whole windows of 16 less 0 to 3: tails that end a window in 1 to 3 steps
    t["classes"][0]["prompt_tokens"].update(median=40, min=12, max=100,
                                            round_to=16, short_by=4)
    t["classes"][0]["output_tokens"].update(median=10, min=4, max=24)
    t["trace_seconds"] = 1
    t["pool_requests"], t["queue_depth"] = 16, 4
    t["serving"].update(batch_slots=4, block_size=2, num_blocks=48)
    t["dtype"] = "float32"
    t["check"].update(logit_tol=2e-3, logit_rms_tol=2e-3)
    return t


def test_the_cell_through_its_runner_on_the_cpu():
    """ISSUE 56's closed backlog at a tiny size, the pool small enough to
    bind: windows are folded in decoding inside the window and their blocks
    given back, and the check compares a step that reads a fold made in
    decoding, after which every block is home."""
    r = run.run_cell(BENCH, CELL, seed=SEED, seconds=2.0, trace=False,
                     config=TINY, traffic=tiny_traffic(),
                     log=lambda msg: None)
    json.dumps(r)
    assert set(r["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    c = r["details"]["counters"]
    assert c["completed"] == r["attempted"] and c["in_window_compiles"] == 0
    check = r["details"]["facts"]["check"]
    assert check["logit_err"] < 2e-3 and check["logit_rms_err"] < 2e-3
    assert check["blocks_recycled"] and check["paged_impl"] == "kernel"
    assert check["folded_in_check"] >= 1
    assert check["blocks_released_by_fold"] > 0
    assert set(check["covers"]) - {"ends_a_window_too"} == {
        "ends_a_window", "just_past_a_window", "longest", "median"}
    assert check["folded_in_check"] >= sum(
        k.startswith("ends") for k in check["covers"])
    from deepspeed_tpu.monitor import spans
    t0, t1 = r["details"]["facts"]["window"]
    steps = [row.attrs for row in spans.recorder().rows("serving.step")
             if t0 <= row.t_start < t1]
    assert sum(a["blocks_released_by_fold"] for a in steps) > 0
    assert any(a["summary_blocks"] > 0 for a in steps)
    folds = [row for row in spans.recorder().rows("serving.fold")
             if t0 <= row.t_start < t1]
    assert folds and all(row.attrs["windows"] >= 1 for row in folds)


@pytest.mark.parametrize("fault, correct", [
    ("none", True), ("no_summaries", False), ("mean_value", False),
    ("no_mu", False), ("int8", False)])
def test_every_control_reads_not_correct_through_the_runners_check(
        fault, correct):
    """The program with one part of the fold computed otherwise, through
    ``serve_backlog_folded.check`` at float32: each reads not-correct BY THE
    LOGITS (served, recycled and folded as the sound program), and the sound
    program through the same door reads correct."""
    control = importlib.import_module("benchmark.control_evabyte")
    out = control.read_fault(BENCH, CELL, SEED, fault, lambda msg: None,
                             config=TINY, traffic=tiny_traffic())
    facts = out["facts"]
    assert out["correct"] is correct
    assert facts["served"] and facts["blocks_recycled"]
    assert facts["folded_in_check"] >= 1
    beyond = (facts["logit_err"] > facts["logit_tol"]
              or facts["logit_rms_err"] > facts["logit_rms_tol"])
    assert beyond is not correct


def test_the_witness_reads_correct():
    control = importlib.import_module("benchmark.control_evabyte")
    out = control.read_witness(BENCH, CELL, SEED, config=TINY,
                               traffic=tiny_traffic())
    assert out["correct"] and out["witness"] == "bf16_matmuls"


# ------------------------------- the step, compiled for a v5e at published widths
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds its lock
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def published(config, family, one_chip):
    import jax
    import jax.numpy as jnp
    model = family.build(config, jnp.bfloat16)
    on = lambda x, dtype=None: jax.ShapeDtypeStruct(
        x.shape, dtype or x.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda x: on(x, jnp.bfloat16),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = jax.tree_util.tree_map(on, jax.eval_shape(
        lambda: model.init_serving_state(64, BLOCKS, 64)))
    return model, params, pool


def compiled(one_chip, monkeypatch, fn, args, donate=()):
    import jax
    pa = importlib.import_module(
        "deepspeed_tpu.ops.transformer.paged_attention")
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    args = [a if hasattr(a, "sharding") or not isinstance(a, tuple)
            else jax.ShapeDtypeStruct(*a, sharding=one_chip) for a in args]
    return jax.jit(fn, donate_argnums=donate).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


HBM = 15.75 * 2 ** 30 - 258 * 2 ** 20      # the compiler's own limit, less
#                                            what it reserves (its error text)
POOL_BYTES = BLOCKS * 64 * 8 * ROW_BYTES    # 9,663,676,416


def test_the_decode_step_is_the_paged_kernel_over_a_table_of_62(
        published, one_chip, monkeypatch):
    import jax.numpy as jnp
    model, params, pool = published
    exe = compiled(one_chip, monkeypatch, model.decode_step_paged,
                   (params, ((64,), jnp.int32), pool, ((64, 62), jnp.int32),
                    ((64,), jnp.int32)), donate=(2,))
    m = exe.memory_analysis()
    assert exe.as_text().count("tpu_custom_call") >= 1
    assert m.alias_size_in_bytes == POOL_BYTES == 9_663_676_416
    assert m.temp_size_in_bytes < 64 * 2 ** 20
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM


@pytest.mark.parametrize("width, whole, own", [(2048, True, 2),
                                               (2048, False, 32),
                                               (256, False, 4)])
def test_a_prefill_segment_fits_a_v5e_beside_the_pool(
        published, one_chip, monkeypatch, width, whole, own):
    """The whole-window segment and the widest and a narrow tail: the pool
    written in place, the scores of 512 queries the largest temporary."""
    import jax.numpy as jnp
    model, params, pool = published
    fn = lambda p, t, pl, bl, st, n: model.prefill_paged(p, t, pl, bl, st, n,
                                                         fold=whole)
    exe = compiled(one_chip, monkeypatch, fn,
                   (params, ((1, width), jnp.int32), pool,
                    ((30 + own,), jnp.int32), ((), jnp.int32),
                    ((), jnp.int32)), donate=(2,))
    m = exe.memory_analysis()
    assert m.alias_size_in_bytes == POOL_BYTES
    assert m.temp_size_in_bytes < 1.6 * 2 ** 30
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM


def test_the_fold_of_a_decoded_window_writes_the_pool_in_place(
        published, one_chip, monkeypatch):
    """A layer at a time: all layers at once the compiler copies half the
    pool (4.85 GB of temporaries, which the chip has no room for)."""
    import jax.numpy as jnp
    model, params, pool = published
    exe = compiled(one_chip, monkeypatch, model.fold_paged,
                   (params, pool, ((32,), jnp.int32), ((2,), jnp.int32)),
                   donate=(1,))
    m = exe.memory_analysis()
    assert m.alias_size_in_bytes == POOL_BYTES
    assert m.temp_size_in_bytes < 256 * 2 ** 20
