"""The ``phi4flash`` family's files (``configs/phi-4-mini-flash-reasoning.json``,
``families/phi4flash.py``, ``reference/phi4flash.py``) and its cell
(``traffic/serve_reasoning_phi4flash.json``, the ``*.phi4flash`` metric files;
the runner is ``serve_backlog_hybrid``, new with them): the file against the
catalog row, the parameter counts against their closed form and the program's
own shapes, the family's costs against numbers worked by hand, each new
metric's reader on rows made by hand, the cell through its runner at a tiny
size on the CPU, and the decode step at 96 slots and the longest prefill
compiled for a described v5e at the published widths beside the traffic
file's three kinds of state.
"""

import collections
import copy
import importlib
import json
import os

import pytest

from benchmark import harness, run
from benchmark.tests.cell_metrics import own_and_shared
from benchmark.tests.test_runners_cpu import SEED

BENCH = harness.load_benchmark()
CELL = harness.cell_by_name(BENCH, "serve_reasoning_phi4flash")
TRAFFIC = harness.load_traffic(CELL["traffic"])
SERVING = TRAFFIC["serving"]
SLOTS, BLOCKS = SERVING["batch_slots"], SERVING["num_blocks"]
TOKEN_BYTES = 2 * 1280 * 2                    # K and V, 20 heads of 64
STATE = 9 * (16 * 5120 * 4 + 3 * 5120 * 2)    # a stream's recurrent rows
PARAMETERS = 3_852_562_944
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# the program's tiny preset in the file's key names
TINY = {"model_type": "phi4flash", "vocab_size": 128, "hidden_size": 64,
        "num_hidden_layers": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 96,
        "sliding_window": 8, "mb_per_layer": 2, "layer_norm_eps": 1e-5,
        "max_position_embeddings": 128, "hidden_act": "silu",
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "embd_pdrop": 0, "resid_pdrop": 0,
        "assumed": {"mamba_d_state": {"value": 4}, "mamba_d_conv": {"value": 4},
                    "mamba_expand": {"value": 2},
                    "mamba_dt_rank": {"value": 4}}}


@pytest.fixture(scope="module")
def config():
    return harness.read_json("configs", "phi-4-mini-flash-reasoning.json")


@pytest.fixture(scope="module")
def family(config):
    return harness.family(config)


# ------------------------------------------------------------ the configuration
def test_the_file_is_the_catalog_row_but_for_what_reduced_lists(config):
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "phi-4-mini-flash-reasoning")
    assert entry["reduced"] == config["reduced"] == ["max_position_embeddings"]
    assert entry["file"] == "benchmark/configs/phi-4-mini-flash-reasoning.json"
    assert entry["source"] == config["source"]
    assert config["max_position_embeddings"] == 9728 == 152 * 64 == 8192 + 1536
    assert config["published"] == {"max_position_embeddings": 262144}
    # every width, the depth and the vocabulary as published
    for key, value in {
            "hidden_size": 2560, "intermediate_size": 10240,
            "num_attention_heads": 40, "num_key_value_heads": 20,
            "num_hidden_layers": 32, "sliding_window": 512,
            "mb_per_layer": 2, "vocab_size": 200064,
            "layer_norm_eps": 1e-5, "tie_word_embeddings": True,
            "mlp_bias": False, "lm_head_bias": False,
            "model_type": "phi4flash"}.items():
        assert config[key] == value, key
    assumed = config["assumed"]
    assert {k: assumed[k]["value"] for k in (
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank")} \
        == {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
            "mamba_dt_rank": 160}
    for key in ("typed_without_a_network", "max_position_embeddings",
                "mamba_biases", "layer_kinds", "gated_memory_unit",
                "differential_attention", "attention_biases",
                "sliding_window", "positions", "state_precision", "layouts",
                "weights"):
        assert assumed[key], key
    assert "ONE v5e chip holds the whole model" in config["deployment"]
    assert config["parameters"] == PARAMETERS


def test_the_catalog_row_if_the_guide_is_here(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Phi-4-mini-flash-reasoning")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key != "max_position_embeddings":
            assert config[key] == value, key
    assert config["published"]["max_position_embeddings"] \
        == row["config"]["max_position_embeddings"]


def test_parameter_counts_match_the_published_sizes(config, family):
    import jax
    import jax.numpy as jnp
    mlp = 3 * 2560 * 10240 + 4 * 2560
    mamba = (2560 * 10240 + 5 * 5120 + 5120 * 192 + 160 * 5120 + 5120
             + 16 * 5120 + 5120 + 5120 * 2560)
    diff = 4 * 64 + 128 + 2560 * 2560 + 2560
    attention = 2560 * 5120 + 5120 + diff
    gmu = 2 * 2560 * 5120
    cross = 2560 * 2560 + 2560 + diff
    assert (mamba + mlp, attention + mlp, gmu + mlp, cross + mlp) == (
        119_895_040, 98_322_304, 104_867_840, 91_766_144)
    whole = (9 * (mamba + mlp) + 9 * (attention + mlp) + 7 * (gmu + mlp)
             + 7 * (cross + mlp) + 200064 * 2560 + 2 * 2560)
    assert whole == PARAMETERS == family.parameters(config)
    assert abs(whole - 3.8e9) / 3.8e9 < 0.02         # the row's "3.8B"
    model = family.build(config, jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == whole
    assert model.num_params() == whole
    assert family.layer_counts(config) == {
        "mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    # what a token multiplies: everything but the vectors
    vectors = (9 * (5 * 5120 + 5120 + 16 * 5120 + 5120)
               + 9 * (5120 + 4 * 64 + 128 + 2560)
               + 7 * (2560 + 4 * 64 + 128 + 2560) + 32 * 4 * 2560 + 2 * 2560)
    assert family.matmul_params_per_token(config) == whole - vectors
    # resident: the weights, the growing pool, the rings, the rows
    pool = BLOCKS * 64 * TOKEN_BYTES
    ring = (1 + SLOTS * 9) * 64 * 8 * TOKEN_BYTES
    assert family.state_bytes_per_stream(config) == STATE == 3_225_600
    assert (pool, ring, SLOTS * STATE) == (
        2_013_265_920, 2_267_545_600, 309_657_600)
    assert 12.2e9 < 2 * whole + pool + ring + SLOTS * STATE < 12.4e9


def test_a_file_the_program_cannot_run_is_refused(config, family):
    for key, value in (("embd_pdrop", 0.1), ("resid_pdrop", 0.1)):
        with pytest.raises(ValueError, match=key):
            family.build({**config, key: value}, "bfloat16")
    for key, value in (("mlp_bias", True), ("hidden_act", "gelu"),
                       ("num_hidden_layers", 30), ("mb_per_layer", 4)):
        with pytest.raises(AssertionError):
            family.build({**config, key: value}, "bfloat16")


# ------------------------------------------------------------------ the cell
def test_the_traffic_is_issue_47s(config):
    t = TRAFFIC
    assert (t["kind"], t["pool_requests"], t["queue_depth"],
            t["order_seed"]) == ("serve_backlog_hybrid", 512, 96, 47)
    (cls,) = t["classes"]
    assert cls["share"] == 1.0
    assert cls["prompt_tokens"] == {
        "kind": "lognormal", "median": 2048, "sigma": 0.5, "min": 512,
        "max": 8192, "round_to": 512, "short_by": 16}
    assert cls["output_tokens"] == {"kind": "lognormal", "median": 512,
                                    "sigma": 0.5, "min": 128, "max": 1536}
    assert (cls["sampling"], cls["temperature"]) == ("alternate", 0.8)
    assert SERVING == {"batch_slots": 96, "block_size": 64, "kv_bits": 16,
                       "num_blocks": 6144}
    assert (t["dtype"], t["drain_limit_s"], t["trace_seconds"]) == (
        "bfloat16", 120, 3)
    check = t["check"]
    assert check["state"] == {"leaves": ["ssm"], "dtype": "float32",
                              "coarser": "bfloat16", "fine_share_min": 0.9}
    from benchmark import traffic_gen
    runner = harness.load_plugin("runners", t["kind"])
    a = runner.backlog(t, 1, 200064)
    b = runner.backlog(t, 2 ** 31 + 5, 200064)
    shape = lambda items: [(len(x.prompt), x.new_tokens, x.do_sample)
                           for x in items]
    assert shape(a) == shape(b) and len(a) == 512
    buckets = traffic_gen.prefill_buckets(a, 64)
    assert len(buckets) <= 16 and buckets[0] == 512 and buckets[-1] == 8192
    assert max(len(x.prompt) + x.new_tokens for x in a) \
        <= config["max_position_embeddings"]
    # four tokens in five are prompt tokens; a mean context near 2,700
    prompts = sum(len(x.prompt) for x in a) / len(a)
    answers = sum(x.new_tokens for x in a) / len(a)
    assert 2300 < prompts < 2700 and 500 < answers < 650
    # the check's prompts: one under the window (496 tokens, in the warmed
    # bucket of 512), four past it, two past 4,096
    picks = runner.check_prompts(check, a)
    lens = [len(x.prompt) for x in picks]
    assert len(picks) == 8 and lens[0] == 496 < config["sliding_window"]
    assert lens[0] + check["steps"] + 4 < config["sliding_window"]
    assert sum(n > 512 for n in lens) >= 4 + check["rows_past_window_min"] - 4
    assert sum(n > check["rows_past"] for n in lens) >= check["rows_past_min"]
    assert {-(-n // 64) * 64 for n in lens} <= set(buckets)


def test_the_cell_reports_its_metrics_and_the_accepted_ones_it_must():
    own, shared = own_and_shared(BENCH, CELL["name"], "serve_tokens_per_s")
    assert own == {
        "kernels.phi4flash.shared_kv_attention_roofline",
        "kernels.phi4flash.window_paged_attention_roofline",
        "kernels.phi4flash.selective_scan_roofline",
        "engine.decode_bandwidth_share.phi4flash",
        "engine.shared_kv_share.phi4flash",
        "serving.cross_skipped_share.phi4flash",
        # the two accepted readings of the same attributes cannot take this
        # cell: test_afmoe.py and test_nemotron_h.py hold each to ONE cell
        "serving.window_pool_fill_share.phi4flash",
        "serving.state_fill_share.phi4flash"}
    assert shared == {
        "serving.tokens_per_step", "serving.step_ms_p50.tput",
        "serving.host_ms_per_step_p50.tput", "serving.queue_wait_ms_p50",
        "serving.prefill_ms_p50.tput", "serving.pool_fill_share",
        "serving.pool_bound_share", "engine.prefill_share.tput",
        "device.idle_share.tput"}
    assert {m["name"] for m in harness.cell_metrics(
        BENCH, "end_to_end", CELL["name"])} == {"serve_tokens_per_s",
                                                "setup_s"}
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200
    assert len(BENCH["per_layer"]) == 78 and len(BENCH["workloads"]) == 11 \
        and len(BENCH["configs"]) == 8


# -------------------------------------------------------------------- costs
Row = collections.namedtuple("Row", "name t_start t_end attrs")


def view_with(family, rows=()):
    cfg = harness.read_json("configs", "phi-4-mini-flash-reasoning.json")
    facts = {**family.dims(cfg), "kv_bytes_per_element": 2,
             "window": (0.0, 40.0),
             "live_tokens": [(20.0, 250_000), (20.5, 260_000), (60.0, 9)]}
    return {"facts": facts, "trace_span": (19.0, 21.0), "family": family,
            "config": cfg, "peaks": harness.peaks_for("TPU v5 lite"),
            "program_spans": {"rows": [Row(*r) for r in rows],
                              "dropped_until": None}}


def step_rows():
    """``serving.step`` rows: two inside the capture, one inside the window
    only, one before it, one as a program before this PR writes them; and
    two prefills inside the capture."""
    attrs = lambda seated, window_tokens, wblocks: {
        "n_active": seated, "emitted": seated, "blocks_in_use": 4000,
        "blocks_free": 2143, "kv_tokens": 250_000,
        "seated_slots": seated, "free_slots": 96 - seated,
        "window_blocks_in_use": wblocks, "window_blocks_free": 864 - wblocks,
        "window_kv_tokens": window_tokens,
        "window_capped_tokens": 250_000 - window_tokens,
        "positions_self": 18 * seated, "positions_cross": 14 * seated}
    prefill = lambda T: {"prompt_len": T, "bucket": -(-T // 64) * 64,
                         "scan_tokens": T, "positions_self": 18 * T,
                         "positions_cross": 14,
                         "positions_skipped": 14 * (T - 1)}
    return [("serving.step", -1.0, -0.9, attrs(9, 9, 9)),
            ("serving.step", 1.0, 1.1, attrs(48, 24_000, 400)),
            ("serving.step", 20.0, 20.1, attrs(96, 49_000, 860)),
            ("serving.step", 20.5, 20.6, attrs(90, 46_000, 810)),
            ("serving.step", 30.0, 30.1, {"n_active": 60}),
            ("serving.prefill", 20.2, 20.4, prefill(2000)),
            ("serving.prefill", 20.7, 20.9, prefill(500))]


def metric(view, name):
    spec = harness.read_json("layer_metrics", f"{name}.json")
    return harness.load_plugin("readers", spec["reader"]).read(
        view, **spec.get("params", {}))


def test_costs_of_the_three_kernels_against_hand_counts(family, config):
    v = view_with(family, step_rows())
    # a live token a reader: K and V of 20 heads of 64 (5,120 bytes); 20
    # pairs x (2 score maps of 64 + 2 value maps of 128) x 2 FLOPs = 15,360
    assert family.attention_need(config, 1, 1) == (15_360, 5_120)
    assert 20 * (2 * 64 + 2 * 128) * 2 == 15_360 == 6 * 2560
    # the shared cache: 510,000 live tokens in the capture, 8 readers
    assert family.costs["phi4flash_shared_kv_attention"](v) == (
        510_000 * 8 * 15_360, 510_000 * 8 * 5_120)
    # the rings: the streams' last 512 tokens, 8 window layers
    assert family.costs["phi4flash_window_paged_attention"](v) == (
        95_000 * 8 * 15_360, 95_000 * 8 * 5_120)
    # the recurrence: 2,500 tokens in 2 prompts, 9 layers; x, delta, z in
    # and y out at 2 bytes (layer 16 takes no z), B and C float32
    per_token = (4 * 9 - 1) * 5120 * 2 + 9 * 2 * 16 * 4
    per_call = 9 * (2 * 5120 * 16 + 5120) * 4
    assert family.costs["phi4flash_selective_scan"](v) == (
        9 * 2500 * 9 * 5120 * 16, 2500 * per_token + 2 * per_call)
    # a prefill holds no attention kernel: nothing is priced for it
    assert set(family.costs) == {
        "phi4flash_shared_kv_attention", "phi4flash_window_paged_attention",
        "phi4flash_selective_scan", "phi4flash_decode_step"}
    v["trace"] = {"module_calls": {"jit_step": (0.07, 0.035),
                                   "jit_prefill": (0.3, 0.1)}}
    flops, total = family.costs["phi4flash_decode_step"](
        v, module_match="jit_step")
    assert flops == 0.0
    assert total == 2 * 2 * PARAMETERS + 510_000 * 8 * 5_120 \
        + 95_000 * 8 * 5_120 + 2 * STATE * (96 + 90)
    # a program that records none of it: weights and the shared cache alone
    old = view_with(family, [r for r in step_rows() if len(r[3]) < 3])
    old["trace"] = v["trace"]
    assert family.costs["phi4flash_window_paged_attention"](old) == (0, 0)
    assert family.costs["phi4flash_selective_scan"](old) == (0, 0)


def test_every_new_metric_reads_a_recorded_fixture(family):
    v = view_with(family, step_rows())
    v["trace"] = {"window_s": 2.0,
                  "module_s": {"jit_step": 0.07, "jit_prefill": 0.3},
                  "module_calls": {"jit_step": (0.07, 0.035),
                                   "jit_prefill": (0.3, 0.1)},
                  "kernel_s": {"paged_attention_shared": 0.03,
                               "paged_attention_window": 0.006,
                               "selective_scan": 0.02}}
    _, kv = family.costs["phi4flash_shared_kv_attention"](v)
    assert metric(v, "kernels.phi4flash.shared_kv_attention_roofline") == \
        pytest.approx(100 * kv / 819e9 / 0.03)
    _, ring = family.costs["phi4flash_window_paged_attention"](v)
    assert metric(v, "kernels.phi4flash.window_paged_attention_roofline") == \
        pytest.approx(100 * ring / 819e9 / 0.006)
    flops, nbytes = family.costs["phi4flash_selective_scan"](v)
    assert nbytes / 819e9 > flops / 197e12           # the bytes bind
    assert metric(v, "kernels.phi4flash.selective_scan_roofline") == \
        pytest.approx(100 * nbytes / 819e9 / 0.02)
    _, need = family.costs["phi4flash_decode_step"](v, module_match="jit_step")
    assert metric(v, "engine.decode_bandwidth_share.phi4flash") == \
        pytest.approx(100 * need / 819e9 / 0.07)
    assert metric(v, "engine.shared_kv_share.phi4flash") == pytest.approx(1.5)
    assert metric(v, "engine.prefill_share.tput") == pytest.approx(15.0)
    assert metric(v, "serving.cross_skipped_share.phi4flash") == \
        pytest.approx(100 * (14 * 1999 / (32 * 2000)
                             + 14 * 499 / (32 * 500)) / 2)
    assert metric(v, "serving.window_pool_fill_share.phi4flash") == \
        pytest.approx(100 * (400 + 860 + 810) / (3 * 864))
    assert metric(v, "serving.state_fill_share.phi4flash") == pytest.approx(
        100 * (48 + 96 + 90) / (3 * 96))
    assert metric(v, "serving.pool_fill_share") == pytest.approx(
        100 * 4000 / 6143)
    # a program whose spans carry none of it (the parent): nothing, never 0
    old = view_with(family, [r for r in step_rows() if len(r[3]) < 3])
    old["trace"] = {"window_s": 2.0, "module_s": {}, "module_calls": {},
                    "kernel_s": {"fusion": 9.0}}
    for name in (m["name"] for m in BENCH["per_layer"]
                 if m.get("workloads") == [CELL["name"]]):
        assert metric(old, name) is None, name


# ----------------------------------------------------------- ISSUE 47's cell
def tiny_traffic():
    t = copy.deepcopy(TRAFFIC)
    (cls,) = t["classes"]
    cls["prompt_tokens"].update(median=20, min=6, max=60, round_to=8,
                                short_by=3)
    cls["output_tokens"].update(median=10, min=4, max=16)
    t["trace_seconds"] = 1
    t["pool_requests"], t["queue_depth"] = 12, 6
    t["serving"].update(batch_slots=4, block_size=8, num_blocks=60)
    t["dtype"] = "float32"
    t["check"].update(short_prompt=3, prompt_quantiles=[0.3, 0.6, 1.0],
                      rows_past_window_min=2, rows_past=40, rows_past_min=1,
                      logit_tol=1e-3, logit_rms_tol=1e-3)
    return t


def test_the_cell_through_its_runner_on_the_cpu():
    """ISSUE 47's closed backlog with the file's class at a tiny size: more
    requests than slots (every slot seated again by a stream after the one
    that left it), the check's prompts under and past the window of 8; the
    check (a live decode step through both pools and the recurrent rows
    against the float32 reference, whose recurrence runs token by token)
    holds and BOTH allocators' blocks are recycled."""
    r = run.run_cell(BENCH, CELL, seed=SEED, seconds=2.0, trace=False,
                     config=TINY, traffic=tiny_traffic(),
                     log=lambda msg: None)
    json.dumps(r)
    assert set(r["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    c = r["details"]["counters"]
    assert c["completed"] == r["attempted"] and c["in_window_compiles"] == 0
    check = r["details"]["facts"]["check"]
    assert check["logit_err"] < 1e-4 and check["logit_rms_err"] < 1e-4
    assert check["prefill_logit_err"] < 1e-4 \
        and check["prefill_logit_rms_err"] < 1e-4
    assert check["blocks_recycled"] and check["window_blocks_recycled"]
    assert check["paged_impl"] == "kernel" and check["rows_cross_what_is_new"]
    assert min(check["reference_rows"]) < 8 < max(check["reference_rows"])
    kept = check["state_precision"]["ssm"]
    assert check["state_kept_as_stated"] and kept["dtype"] == "float32"
    assert kept["nonzero"] > 0 and kept["fine_share"] > 0.99
    assert r["details"]["facts"]["kv_width"] == 32
    from deepspeed_tpu.monitor import spans
    t0, t1 = r["details"]["facts"]["window"]
    rows = [row for row in spans.recorder().rows()
            if t0 <= row.t_start < t1]
    steps = [row.attrs for row in rows if row.name == "serving.step"
             and row.attrs.get("emitted")]
    assert steps and all(
        a["seated_slots"] + a["free_slots"] == 4
        and a["window_blocks_in_use"] + a["window_blocks_free"] == 8
        and a["positions_self"] == 6 * a["n_active"]
        and a["positions_cross"] == 2 * a["n_active"] for a in steps)
    prefills = [row.attrs for row in rows if row.name == "serving.prefill"]
    assert prefills and all(
        a["positions_self"] == 6 * a["prompt_len"]
        and a["positions_cross"] == 2
        and a["positions_skipped"] == 2 * (a["prompt_len"] - 1)
        for a in prefills)


@pytest.mark.parametrize("fault", ["window_plus_one", "lambda_dropped",
                                   "ssm_bf16", "prefill_m_before"])
def test_a_planted_fault_fails_the_runners_check(fault):
    from benchmark import control_phi4flash
    out = control_phi4flash.read_fault(BENCH, CELL, SEED, fault,
                                       lambda msg: None, config=TINY,
                                       traffic=tiny_traffic())
    json.dumps(out)
    assert not out["correct"]
    facts = out["facts"]
    assert facts["served"] and facts["blocks_recycled"] \
        and facts["window_blocks_recycled"]
    # a state kept below float32 is told by its rows, not by the logits
    assert facts["state_kept_as_stated"] == (fault != "ssm_bf16")
    if fault == "ssm_bf16":
        assert facts["state_precision"]["ssm"]["fine_share"] == 0.0
    elif fault == "prefill_m_before":
        # every decode step is sound: the prefill's own row tells it
        assert facts["logit_err"] < 1e-4 < 1e-3 < facts["prefill_logit_err"]
    else:
        assert facts["logit_err"] > 1e-3


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
    """The model and the shapes of its weights and of the file's three kinds
    of state, on a described v5e."""
    import jax
    import jax.numpy as jnp
    model = family.build(config, jnp.bfloat16)
    on = lambda x, dtype=None: jax.ShapeDtypeStruct(
        x.shape, dtype or x.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda x: on(x, jnp.bfloat16),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = jax.tree_util.tree_map(on, jax.eval_shape(
        lambda: model.init_serving_state(SLOTS, BLOCKS, 64)))
    return model, params, pool


def compiled(one_chip, monkeypatch, fn, args, donate=()):
    import jax
    import deepspeed_tpu.ops as ops
    from deepspeed_tpu.ops import selective_scan
    for name in ("paged_attention", "flash_attention"):
        monkeypatch.setattr(importlib.import_module(
            f"deepspeed_tpu.ops.transformer.{name}"), "_interpret",
            lambda: False)
    monkeypatch.setattr(selective_scan, "_interpret", lambda: False)
    monkeypatch.setattr(ops, "flash_attention_available", lambda: True)
    args = [a if hasattr(a, "sharding") or not isinstance(a, tuple)
            else jax.ShapeDtypeStruct(*a, sharding=one_chip) for a in args]
    return jax.jit(fn, donate_argnums=donate).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


HBM = 15.75 * 2 ** 30 - 258 * 2 ** 20      # the compiler's own limit, less
#                                            what it reserves (its error text)
GATE = 0.92 * 15.75 * 2 ** 30              # ServingConfig.preflight_safety
STATE_BYTES = SLOTS * STATE
POOL_BYTES = BLOCKS * 64 * TOKEN_BYTES + (1 + SLOTS * 9) * 64 * 8 * TOKEN_BYTES


def test_the_decode_step_fits_a_v5e_with_the_named_calls(
        published, one_chip, monkeypatch):
    """96 slots over tables of 152 + 9 entries: the paged kernel under its
    two names (8 calls over the shared cache, 8 over the rings; a loop's body
    holds each once), both pools AND the recurrent rows written in place
    (aliased), weights and state inside the engine's gate."""
    import jax.numpy as jnp
    model, params, pool = published
    args = (params, ((SLOTS,), jnp.int32), pool,
            ((SLOTS, 152 + 9), jnp.int32), ((SLOTS,), jnp.int32))
    step = lambda p, t, pl, tb, ln: model.decode_step_paged(p, t, pl, tb, ln)
    exe = compiled(one_chip, monkeypatch, step, args, donate=(2,))
    m = exe.memory_analysis()
    text = exe.as_text()
    assert text.count("paged_attention_shared") >= 2       # layer 17, a loop
    assert text.count("paged_attention_window") >= 1
    assert m.alias_size_in_bytes >= POOL_BYTES + STATE_BYTES
    assert m.temp_size_in_bytes < 256 * 2 ** 20
    assert 2 * PARAMETERS + POOL_BYTES + STATE_BYTES \
        <= m.argument_size_in_bytes
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < GATE < HBM


@pytest.mark.parametrize("bucket", [8192])
def test_the_longest_prefill_fits_a_v5e_beside_the_state(
        published, one_chip, monkeypatch, bucket):
    """The traffic's longest bucket: the scan kernel under its own name and
    no attention kernel (the full layer's queries run at one position, so no
    (T, T) scores stand there; the window layers' band runs in query
    blocks), the slot's rows written into the donated state, and weights,
    state, pools and transients inside the engine's gate."""
    import jax.numpy as jnp
    model, params, pool = published
    fn = lambda p, t, pl, bl, s, n: model.prefill_paged(p, t, pl, bl, s, n)
    exe = compiled(one_chip, monkeypatch, fn,
                   (params, ((1, bucket), jnp.int32), pool,
                    ((bucket // 64 + 9,), jnp.int32), ((), jnp.int32),
                    ((), jnp.int32)), donate=(2,))
    m = exe.memory_analysis()
    text = exe.as_text()
    assert text.count("selective_scan") >= 2           # the loop's, layer 16's
    assert "flash_attention" not in text.replace("phi4flash", "")
    assert m.alias_size_in_bytes >= POOL_BYTES + STATE_BYTES
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < GATE < HBM
