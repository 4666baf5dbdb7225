"""The Nemotron-H family's files (``configs/nemotron-3-nano-30b-a3b.json``,
``families/nemotron_h.py``, ``reference/nemotron_h.py``) and its cell
(``traffic/serve_longanswer_nemotron3.json``, the ``*.nemotron`` metric
files; the runner is ``serve_backlog_recurrent``, new with them: the routed
check and the precision the recurrent rows are kept at): the file against
the catalog row, the parameter counts against their closed form and the
program's own shapes, the family's costs against numbers worked by hand, each
new metric's reader on rows made by hand, the cell through its runner at a
tiny size on the CPU, and the decode step at 256 slots and the longest
prefills compiled for a described v5e at the published widths beside the
traffic file's pool and recurrent rows.
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
CELL = harness.cell_by_name(BENCH, "serve_longanswer_nemotron3")
TRAFFIC = harness.load_traffic(CELL["traffic"])
SERVING = TRAFFIC["serving"]
SLOTS, BLOCKS = SERVING["batch_slots"], SERVING["num_blocks"]
TOKEN_LAYER_BYTES = 2 * 2 * 128 * 2            # K and V, 2 heads of 128
STATE = 64 * 64 * 128 * 4                      # a layer a stream, float32
CARRY = 3 * 6144 * 2                           # the convolution's, bfloat16
PARAMETERS, UNCUT = 2_871_333_696, 31_577_940_288
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
REDUCED = {"hybrid_override_pattern": PATTERN[:16], "num_hidden_layers": 16,
           "n_routed_experts": 32, "vocab_size": 32768,
           "max_position_embeddings": 4096}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# the program's tiny preset in the file's key names, as one chip's share:
# the first 6 of 8 layers, 4 of 16 experts, a quarter of the ids
TINY = {"model_type": "nemotron_h", "vocab_size": 128, "hidden_size": 64,
        "num_hidden_layers": 6, "hybrid_override_pattern": "MEM*EM",
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
        "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8, "expand": 2,
        "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 48, "n_routed_experts": 4,
        "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 1,
        "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
        "layer_norm_epsilon": 1e-5, "time_step_min": 1e-3,
        "time_step_max": 1e-1, "time_step_floor": 1e-4,
        "max_position_embeddings": 128,
        "published": {"hybrid_override_pattern": "MEM*EMME",
                      "num_hidden_layers": 8, "n_routed_experts": 16,
                      "vocab_size": 512},
        "layers_held": {str(l): k for l, k in enumerate("MEM*EM")},
        "experts_held": [4, 4], "vocab_held": [128, 128]}


@pytest.fixture(scope="module")
def config():
    return harness.read_json("configs", "nemotron-3-nano-30b-a3b.json")


@pytest.fixture(scope="module")
def family(config):
    return harness.family(config)


# ------------------------------------------------------------ the configuration
def test_the_file_is_the_catalog_row_but_for_what_reduced_lists(config):
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "nemotron-3-nano-30b-a3b")
    assert entry["reduced"] == config["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/nemotron-3-nano-30b-a3b.json"
    for key, value in REDUCED.items():
        assert config[key] == value, key
    assert config["published"] == {
        "hybrid_override_pattern": PATTERN, "num_hidden_layers": 52,
        "n_routed_experts": 128, "vocab_size": 131072,
        "max_position_embeddings": 262144}
    # every width as published; none is in ``reduced``
    for key, value in {
            "hidden_size": 2688, "head_dim": 128, "num_attention_heads": 32,
            "num_key_value_heads": 2, "mamba_num_heads": 64,
            "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
            "conv_kernel": 4, "chunk_size": 128, "expand": 2,
            "moe_intermediate_size": 1856, "intermediate_size": 1856,
            "moe_shared_expert_intermediate_size": 3712,
            "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
            "n_shared_experts": 1, "mlp_hidden_act": "relu2"}.items():
        assert config[key] == value and key not in REDUCED, key
    assert config["experts_held"] == [0, 32]
    assert config["vocab_held"] == [0, 32768]
    assert config["layers_held"] == {str(l): k
                                     for l, k in enumerate(PATTERN[:16])}
    assert [PATTERN[:16].count(k) for k in "ME*"] == [7, 7, 2]
    for key in ("typed_without_a_network", "block", "d_inner",
                "projection_order", "conv", "dt", "gated_norm", "attention",
                "router", "experts", "e_score_correction_bias",
                "state_precision", "weights", "precision", "training_only"):
        assert config["assumed"][key], key
    assert "FOUR" in config["deployment"]
    assert config["parameters"] == PARAMETERS


def test_the_catalog_row_if_the_guide_is_here(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == REDUCED.get(key, value), key
    assert config["published"] == {k: row["config"][k] for k in REDUCED}


def test_parameter_counts_closed_form_and_the_programs_shapes(config, family):
    import jax
    import jax.numpy as jnp
    mamba = (2688 * (4096 + 6144 + 64) + 4 * 6144 + 6144 + 3 * 64 + 4096
             + 4096 * 2688 + 2688)
    attention = 2 * 2688 * 128 * (32 + 2) + 2688
    expert = 2 * 2688 * 1856
    layer = lambda held: (held * expert + 2 * 2688 * 3712 + 2688 * 128 + 128
                          + 2688)
    assert (mamba, attention, expert, layer(32), layer(128)) == (
        38_744_896, 23_399_040, 9_977_856, 339_593_984, 1_297_468_160)
    here = 7 * mamba + 2 * attention + 7 * layer(32) + 2 * 32768 * 2688 + 2688
    whole = 23 * mamba + 6 * attention + 23 * layer(128) \
        + 2 * 131072 * 2688 + 2688
    assert here == PARAMETERS == config["parameters"] \
        == family.parameters(config)
    assert whole == UNCUT == family.parameters(config, uncut=True)
    model = family.build(config, jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == here
    assert model.num_params() == here
    assert model.config.kinds == tuple(PATTERN[:16])
    assert model.config.held == (0, 32) and model.config.vocab_rows == (
        0, 32768)
    uncut = family.build({**config, **config["published"],
                          "layers_held": None, "experts_held": [0, 128],
                          "vocab_held": [0, 131072]}, jnp.bfloat16)
    assert uncut.num_params() == whole
    # what a token multiplies here: 6 x 32 / 128 = an expert and a half
    assert family.matmul_params_per_token(config) == (
        7 * (2688 * 10304 + 4096 * 2688) + 2 * (attention - 2688)
        + 7 * (2688 * 128 + 2 * 2688 * 3712 + 1.5 * expert) + 32768 * 2688)
    # resident: the weights, 256 streams' recurrent rows, the pool
    state = SLOTS * 7 * (STATE + CARRY)
    pool = BLOCKS * 64 * 2 * TOKEN_LAYER_BYTES
    assert (state, pool) == (3_824_156_672, 1_073_741_824)
    assert 10.6e9 < 2 * here + state + pool < 10.7e9


def test_a_file_the_program_cannot_run_is_refused(config, family):
    for key, value in (("tie_word_embeddings", True), ("n_group", 2),
                       ("mlp_hidden_act", "gelu"), ("use_conv_bias", False),
                       ("attention_bias", True)):
        with pytest.raises(ValueError, match=key if key != "mlp_hidden_act"
                           else "gelu"):
            family.build({**config, key: value}, "bfloat16")
    with pytest.raises(ValueError, match="layers_held"):
        family.build({**config, "hybrid_override_pattern": "MEMEMEEMEMEM*EME"},
                     "bfloat16")
    with pytest.raises(ValueError, match="experts_held"):
        family.build({**config, "n_routed_experts": 16}, "bfloat16")


# ------------------------------------------------------------------ the cell
def test_the_traffic_is_issue_42s(config):
    t = TRAFFIC
    assert (t["kind"], t["pool_requests"], t["queue_depth"],
            t["order_seed"]) == ("serve_backlog_recurrent", 1024, 256, 42)
    (cls,) = t["classes"]
    assert cls["share"] == 1.0
    assert cls["prompt_tokens"] == {
        "kind": "lognormal", "median": 384, "sigma": 0.7, "min": 64,
        "max": 2048, "round_to": 128, "short_by": 16}
    assert cls["output_tokens"] == {"kind": "lognormal", "median": 512,
                                    "sigma": 0.5, "min": 128, "max": 1536}
    assert (cls["sampling"], cls["temperature"]) == ("alternate", 0.8)
    assert SERVING == {"batch_slots": 256, "block_size": 64, "kv_bits": 16,
                       "num_blocks": 8192}
    assert (t["dtype"], t["drain_limit_s"], t["trace_seconds"]) == (
        "bfloat16", 120, 3)
    assert (t["check"]["slots"], t["check"]["steps"]) == (48, 3)   # ISSUE 42: 8
    # the configuration's float32 state is held to, exactly
    assert t["check"]["state"] == {"leaves": ["ssm"], "dtype": "float32",
                                   "coarser": "bfloat16",
                                   "fine_share_min": 0.9}
    from benchmark import traffic_gen
    runner = harness.load_plugin("runners", t["kind"])
    a = runner.backlog(t, 1, 32768)
    b = runner.backlog(t, 2 ** 31 + 5, 32768)
    shape = lambda items: [(len(x.prompt), x.new_tokens, x.do_sample)
                           for x in items]
    assert shape(a) == shape(b) and len(a) == 1024
    assert max(x.prompt.max() for x in a) < 32768      # ids of the slice
    buckets = traffic_gen.prefill_buckets(a, 64)
    assert len(buckets) <= 16 and buckets[-1] == 2048
    assert max(len(x.prompt) + x.new_tokens for x in a) \
        <= config["max_position_embeddings"]
    # answers outweigh prompts, which no other cell has
    prompts = sum(len(x.prompt) for x in a) / len(a)
    answers = sum(x.new_tokens for x in a) / len(a)
    assert 400 < prompts < answers < 700
    # a seat reserves about 17 blocks: the 256 slots bind, the pool does not
    need = [-(-(len(x.prompt) + x.new_tokens) // 64) for x in a]
    assert SLOTS * sum(need) / len(need) < 0.6 * (BLOCKS - 1)


def test_the_cell_reports_its_metrics_and_the_accepted_ones_it_must():
    # its kernels', its state's and its decode step's costs are this cell's
    # own; the rest it shares with the other throughput cells (the last two
    # are ISSUE 42's that found no room among 128 entries)
    own, shared = own_and_shared(BENCH, CELL["name"], "serve_tokens_per_s")
    assert own == {
        "kernels.nemotron.ssm_state_update_roofline",
        "kernels.nemotron.ssd_prefill_roofline",
        "kernels.nemotron.paged_attention_roofline",
        "engine.state_update_share.nemotron", "engine.ssd_share.nemotron",
        "engine.decode_bandwidth_share.nemotron",
        "serving.state_fill_share.nemotron"}
    assert shared == {
        "engine.expert_share", "engine.prefill_share.tput",
        "moe.local_pair_share", "moe.experts_touched_share",
        "serving.step_ms_p50.tput", "serving.host_ms_per_step_p50.tput",
        "serving.tokens_per_step", "serving.prefill_ms_p50.tput",
        "device.idle_share.tput",
        "serving.pool_fill_share", "serving.queue_wait_ms_p50"}
    assert {m["name"] for m in harness.cell_metrics(
        BENCH, "end_to_end", CELL["name"])} == {"serve_tokens_per_s",
                                                "setup_s"}
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200


# -------------------------------------------------------------------- costs
Row = collections.namedtuple("Row", "name t_start t_end attrs")


def view_with(family, rows=()):
    cfg = harness.read_json("configs", "nemotron-3-nano-30b-a3b.json")
    facts = {**family.dims(cfg), "kv_bytes_per_element": 2,
             "window": (0.0, 40.0),
             "live_tokens": [(20.0, 200_000), (20.5, 240_000), (60.0, 9)]}
    return {"facts": facts, "trace_span": (19.0, 21.0), "family": family,
            "config": cfg, "peaks": harness.peaks_for("TPU v5 lite"),
            "program_spans": {"rows": [Row(*r) for r in rows],
                              "dropped_until": None}}


def step_rows():
    """``serving.step`` rows: two inside the capture, one inside the window
    only, one before it, one as a program before this PR writes them; and
    two prefills inside the capture."""
    attrs = lambda seated, held, touched: {
        "n_active": seated, "emitted": seated, "routed_pairs": held,
        "pairs_elsewhere": 6 * seated * 7 - held, "experts_touched": touched,
        "experts_idle": 224 - touched, "blocks_in_use": 4000,
        "blocks_free": 4191, "seated_slots": seated,
        # what the program SAYS it moves (half of it here) is not read
        "free_slots": 256 - seated, "state_bytes": seated * 7 * STATE}
    return [("serving.step", -1.0, -0.9, attrs(9, 9, 9)),
            ("serving.step", 1.0, 1.1, attrs(200, 2000, 200)),
            ("serving.step", 20.0, 20.1, attrs(256, 2700, 224)),
            ("serving.step", 20.5, 20.6, attrs(192, 2000, 210)),
            ("serving.step", 30.0, 30.1, {"n_active": 60}),
            ("serving.prefill", 20.2, 20.4,
             {"prompt_len": 1000, "bucket": 1024, "ssd_tokens": 1000,
              "ssd_chunks": 8}),
            ("serving.prefill", 20.7, 20.9,
             {"prompt_len": 300, "bucket": 384, "ssd_tokens": 300,
              "ssd_chunks": 3})]


def metric(view, name):
    spec = harness.read_json("layer_metrics", f"{name}.json")
    return harness.load_plugin("readers", spec["reader"]).read(
        view, **spec.get("params", {}))


def test_costs_read_the_capture(family, config):
    v = view_with(family, step_rows())
    assert family.state_bytes_per_layer(config) == STATE == 2_097_152
    # the state of the slots seated in the two captured steps, in and out,
    # from the configuration's float32 state and the rows' seated_slots alone
    assert family.costs["nemotron_state_update"](v) == (
        0.0, (256 + 192) * 7 * 2 * STATE)
    # the chunked scan of 1,300 real tokens in 2 prompts, 7 layers: 2.76
    # MFLOP and 20.7 KB a token a layer, and the state out a call
    flops = (8 * 2 * 128 + 64 * 2 * 64) * 129 / 2 + 2 * 64 * 2 * 64 * 128
    nbytes = 2 * 2 * 4096 + 2 * 2 * 1024 + 4 * 64
    assert (flops, nbytes) == (2_757_632.0, 20_736)
    assert family.costs["nemotron_ssd_prefill"](v) == (
        7 * 1300 * flops, 7 * (1300 * nbytes + 2 * STATE))
    # the 2 attention layers: 440,000 live tokens in the capture
    assert family.costs["nemotron_paged_attention"](v) == (
        440_000 * 2 * 2 * 2 * 32 * 128, 440_000 * 2 * TOKEN_LAYER_BYTES)
    dense = 2 * (PARAMETERS - 7 * 32 * 9_977_856 - 32768 * 2688)
    assert family.dense_weight_bytes(config) == dense == 1_096_427_136
    v["trace"] = {"module_calls": {"jit_step": (0.08, 0.04),
                                   "jit_prefill": (0.3, 0.1)}}
    flops, total = family.costs["nemotron_decode_step"](
        v, module_match="jit_step")
    assert flops == 0.0
    assert total == 2 * (dense + 217 * 2 * 9_977_856) \
        + 440_000 * 2 * TOKEN_LAYER_BYTES + (256 + 192) * 7 * 2 * STATE
    # a program that records none of it: every held expert, no state
    old = view_with(family, [r for r in step_rows() if len(r[3]) < 3])
    old["trace"] = v["trace"]
    assert family.costs["nemotron_state_update"](old) == (0.0, 0.0)
    assert family.costs["nemotron_ssd_prefill"](old) == (0, 0)
    _, total = family.costs["nemotron_decode_step"](old,
                                                    module_match="jit_step")
    assert total == 2 * 2 * (PARAMETERS - 32768 * 2688) \
        + 440_000 * 2 * TOKEN_LAYER_BYTES


def test_every_new_metric_reads_a_recorded_fixture(family):
    v = view_with(family, step_rows())
    v["trace"] = {"window_s": 2.0,
                  "module_s": {"jit_step": 0.08, "jit_prefill": 0.3},
                  "module_calls": {"jit_step": (0.08, 0.04),
                                   "jit_prefill": (0.3, 0.1)},
                  "kernel_s": {"mamba2_state_update": 0.04,
                               "mamba2_ssd_scan": 0.01, "gmm": 0.5,
                               "paged_attention": 0.004}}
    _, moved = family.costs["nemotron_state_update"](v)
    assert metric(v, "kernels.nemotron.ssm_state_update_roofline") == \
        pytest.approx(100 * moved / 819e9 / 0.04)
    flops, nbytes = family.costs["nemotron_ssd_prefill"](v)
    assert nbytes / 819e9 > flops / 197e12           # the bytes bind
    assert metric(v, "kernels.nemotron.ssd_prefill_roofline") == \
        pytest.approx(100 * nbytes / 819e9 / 0.01)
    _, kv = family.costs["nemotron_paged_attention"](v)
    assert metric(v, "kernels.nemotron.paged_attention_roofline") == \
        pytest.approx(100 * kv / 819e9 / 0.004)
    assert metric(v, "engine.state_update_share.nemotron") == \
        pytest.approx(2.0)
    assert metric(v, "engine.ssd_share.nemotron") == pytest.approx(0.5)
    assert metric(v, "engine.expert_share") == pytest.approx(25.0)
    assert metric(v, "engine.prefill_share.tput") == pytest.approx(15.0)
    _, need = family.costs["nemotron_decode_step"](v, module_match="jit_step")
    assert metric(v, "engine.decode_bandwidth_share.nemotron") == \
        pytest.approx(100 * need / 819e9 / 0.08)
    assert metric(v, "moe.local_pair_share") == pytest.approx(
        100 * (2000 / 200 + 2700 / 256 + 2000 / 192) / (3 * 6 * 7))
    assert metric(v, "moe.experts_touched_share") == pytest.approx(
        100 * (200 + 224 + 210) / (3 * 224))
    assert metric(v, "serving.state_fill_share.nemotron") == pytest.approx(
        100 * (200 + 256 + 192) / (3 * 256))
    v["counters"] = {"generated_tokens": 48_000, "decode_steps": 200}
    assert metric(v, "serving.tokens_per_step") == 240.0
    # a program whose spans carry none of it (the parent): nothing, never 0
    old = view_with(family, [r for r in step_rows() if len(r[3]) < 3])
    old["trace"] = {"window_s": 2.0, "module_s": {}, "module_calls": {},
                    "kernel_s": {"fusion": 9.0}}
    for name in (m["name"] for m in BENCH["per_layer"]
                 if CELL["name"] in m.get("workloads", ())
                 and m["source"] != "host_clock"
                 and not m["name"].startswith(("serving.tokens_per_step",
                                               "serving.prefill_ms",
                                               "serving.queue_wait",
                                               "serving.host_ms",
                                               "device.idle"))):
        assert metric(old, name) is None, name


# ----------------------------------------------------------- ISSUE 42's cell
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
    t["check"].update(slots=3, logit_tol=1e-3, logit_rms_tol=1e-3)
    return t


def test_the_cell_through_its_runner_on_the_cpu():
    """ISSUE 42's closed backlog with the file's class at a tiny size: a
    share of the layers, of the experts and of the vocabulary, more requests
    than slots (every slot seated again by a stream after the one that left
    it), prompts that end inside a chunk of 8; the check (a live decode step
    through the pool and the recurrent rows against the float32 reference,
    whose recurrence runs token by token, given the same share) holds and
    every block is recycled."""
    r = run.run_cell(BENCH, CELL, seed=SEED, seconds=2.0, trace=False,
                     config=TINY, traffic=tiny_traffic(),
                     log=lambda msg: None)
    json.dumps(r)
    assert set(r["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    c = r["details"]["counters"]
    assert c["completed"] == r["attempted"] and c["in_window_compiles"] == 0
    check = r["details"]["facts"]["check"]
    assert check["logit_err"] < 1e-3 and check["logit_rms_err"] < 1e-3
    assert check["blocks_recycled"] and check["paged_impl"] == "kernel"
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
        a["routed_pairs"] + a["pairs_elsewhere"] == 4 * a["n_active"] * 2
        and a["experts_touched"] + a["experts_idle"] == 4 * 2
        and a["seated_slots"] + a["free_slots"] == 4
        and a["state_bytes"] == a["seated_slots"] * 3 * 2 * 4 * 8 * 16 * 4
        for a in steps)
    assert sum(a["pairs_elsewhere"] for a in steps) > 0
    prefills = [row.attrs for row in rows if row.name == "serving.prefill"]
    assert prefills and all(
        a["ssd_tokens"] == a["prompt_len"]
        and a["ssd_chunks"] == -(-a["prompt_len"] // 8) for a in prefills)


@pytest.mark.parametrize("fault", ["norm_ungrouped", "relu_not_squared",
                                   "state_bf16"])
def test_a_planted_fault_fails_the_runners_check(fault):
    from benchmark import control_nemotron
    out = control_nemotron.read_fault(BENCH, CELL, SEED, fault,
                                      lambda msg: None, config=TINY,
                                      traffic=tiny_traffic())
    json.dumps(out)
    assert not out["correct"]
    assert out["facts"]["served"] and out["facts"]["blocks_recycled"]
    # a state kept below float32 is told by its rows, not by the logits
    assert out["facts"]["state_kept_as_stated"] == (fault != "state_bf16")
    if fault == "state_bf16":
        assert out["facts"]["state_precision"]["ssm"]["fine_share"] == 0.0


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
    """The model and the shapes of its weights and of the file's pool and
    recurrent rows, on a described v5e."""
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
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops import mamba2
    for name in ("paged_attention", "flash_attention"):
        monkeypatch.setattr(importlib.import_module(
            f"deepspeed_tpu.ops.transformer.{name}"), "_interpret",
            lambda: False)
    monkeypatch.setattr(mamba2, "_interpret", lambda: False)
    monkeypatch.setattr(dropless, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "flash_attention_available", lambda: True)
    args = [a if hasattr(a, "sharding") or not isinstance(a, tuple)
            else jax.ShapeDtypeStruct(*a, sharding=one_chip) for a in args]
    return jax.jit(fn, donate_argnums=donate).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


HBM = 15.75 * 2 ** 30 - 258 * 2 ** 20      # the compiler's own limit, less
#                                            what it reserves (its error text)
GATE = 0.92 * 15.75 * 2 ** 30              # ServingConfig.preflight_safety
STATE_BYTES = SLOTS * 7 * (STATE + CARRY)
POOL_BYTES = BLOCKS * 64 * 2 * TOKEN_LAYER_BYTES


def test_the_decode_step_fits_a_v5e_and_updates_the_state_in_place(
        published, one_chip, monkeypatch):
    """256 slots over tables of 64 entries: the named state update in every
    Mamba layer (7 Mosaic calls), the paged kernel in both attention layers,
    the pool AND the 3.8 GB of recurrent rows written in place (aliased: a
    copy of the state is the whole of what is left of the chip), weights,
    rows and pool inside the engine's gate."""
    import re
    import jax.numpy as jnp
    model, params, pool = published
    args = (params, ((SLOTS,), jnp.int32), pool,
            ((SLOTS, 4096 // 64), jnp.int32), ((SLOTS,), jnp.int32))
    step = lambda p, t, pl, tb, ln: model.decode_step_paged(p, t, pl, tb, ln)
    exe = compiled(one_chip, monkeypatch, step, args, donate=(2,))
    m = exe.memory_analysis()
    text = exe.as_text()
    assert text.count("mamba2_state_update") >= 7
    assert text.count("paged_attention") >= 2
    # the experts' two products a layer are the Pallas grouped matmul, not
    # XLA's ragged-dot in tiles of 128 x 128
    assert len(re.findall(r"%gmm[.\d]* = ", text)) >= 14
    assert "ragged-dot" not in text
    # no weight re-laid: ``up_w`` stored (in, out), (D, 1856), is copied
    # whole in front of every grouped product (2.2 GB seven times a step)
    assert not re.search(r"bf16\[\d+,\d+,\d{4,},\d{4,}\]\S* copy\(", text)
    assert m.alias_size_in_bytes >= POOL_BYTES + STATE_BYTES
    assert m.temp_size_in_bytes < 128 * 2 ** 20
    assert 2 * PARAMETERS + POOL_BYTES + STATE_BYTES \
        <= m.argument_size_in_bytes
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < GATE < HBM


@pytest.mark.parametrize("bucket", [2048, 4096])
def test_the_longest_prefill_fits_a_v5e_beside_the_state(
        published, one_chip, monkeypatch, bucket):
    """The traffic's longest bucket, and the served limit's (what the
    engine's preflight compiles): the chunked scan under its own name in
    every Mamba layer, the flash forward kernel on the attention layers (no
    (T, T) scores), the slot's rows written into the donated state, and
    weights, state, pool and transients inside the engine's gate."""
    import jax.numpy as jnp
    model, params, pool = published
    fn = lambda p, t, pl, bl, s, n: model.prefill_paged(p, t, pl, bl, s, n)
    exe = compiled(one_chip, monkeypatch, fn,
                   (params, ((1, bucket), jnp.int32), pool,
                    ((bucket // 64,), jnp.int32), ((), jnp.int32),
                    ((), jnp.int32)), donate=(2,))
    m = exe.memory_analysis()
    text = exe.as_text()
    assert text.count("mamba2_ssd_scan") >= 7
    assert m.alias_size_in_bytes >= POOL_BYTES + STATE_BYTES
    assert m.temp_size_in_bytes < 1.5e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < GATE < HBM
