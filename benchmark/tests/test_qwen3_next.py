"""The Qwen3-Next family's files (``configs/qwen3-next-80b-a3b.json``,
``families/qwen3_next.py``, ``reference/qwen3_next.py``) and its cell
(``traffic/serve_longctx_qwen3next.json``, the ``*.qwen3next`` metric files;
the runner is ``serve_backlog_recurrent`` as it stands): the file against the
catalog row, the parameter count against its closed form and the program's
own shapes, the family's costs against numbers worked by hand, each new
metric's reader on rows made by hand, the cell through its runner at a tiny
size on the CPU, planted faults through the same check, and the decode step
at the file's slots and its longest and shortest prefill buckets compiled
for a described v5e at the published widths beside the file's pool and
recurrent rows.
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
CELL = harness.cell_by_name(BENCH, "serve_longctx_qwen3next")
TRAFFIC = harness.load_traffic(CELL["traffic"])
SERVING = TRAFFIC["serving"]
SLOTS, BLOCKS = SERVING["batch_slots"], SERVING["num_blocks"]
TOKEN_LAYER_BYTES = 2 * 2 * 256 * 2            # K and V, 2 heads of 256
STATE = 32 * 128 * 128 * 4                     # a layer a stream, float32
CARRY = 3 * 8192 * 2                           # the convolution's, bfloat16
PARAMETERS, UNCUT = 2_929_374_400, 79_674_391_296
REDUCED = {"num_hidden_layers": 12, "num_experts": 64, "vocab_size": 18992,
           "max_position_embeddings": 18432}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# the program's tiny preset in the file's key names, as one chip's share: one
# period of the two, 4 of 16 experts, a quarter of the ids
TINY = {"model_type": "qwen3_next", "vocab_size": 128, "hidden_size": 64,
        "num_hidden_layers": 4, "full_attention_interval": 4,
        "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 16,
        "partial_rotary_factor": 0.25, "rope_theta": 10000000,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 8,
        "linear_conv_kernel_dim": 4, "num_experts": 4,
        "num_experts_per_tok": 4, "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 32, "intermediate_size": 160,
        "norm_topk_prob": True, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "hidden_act": "silu", "rms_norm_eps": 1e-6,
        "max_position_embeddings": 128,
        "published": {"num_hidden_layers": 8, "num_experts": 16,
                      "vocab_size": 512},
        "experts_held": [4, 4], "vocab_held": [128, 128]}


@pytest.fixture(scope="module")
def config():
    return harness.read_json("configs", "qwen3-next-80b-a3b.json")


@pytest.fixture(scope="module")
def family(config):
    return harness.family(config)


# ------------------------------------------------------------ the configuration
def test_the_file_is_the_catalog_row_but_for_what_reduced_lists(config):
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert entry["reduced"] == config["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/qwen3-next-80b-a3b.json"
    for key, value in REDUCED.items():
        assert config[key] == value, key
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936,
        "max_position_embeddings": 262144}
    # every width as published; none is in ``reduced``
    for key, value in {
            "hidden_size": 2048, "head_dim": 256, "num_attention_heads": 16,
            "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
            "linear_num_key_heads": 16, "linear_num_value_heads": 32,
            "linear_key_head_dim": 128, "linear_value_head_dim": 128,
            "linear_conv_kernel_dim": 4, "num_experts_per_tok": 10,
            "moe_intermediate_size": 512,
            "shared_expert_intermediate_size": 512,
            "intermediate_size": 5120, "full_attention_interval": 4,
            "rope_theta": 10000000, "norm_topk_prob": True}.items():
        assert config[key] == value and key not in REDUCED, key
    assert config["experts_held"] == [0, 64]
    assert config["vocab_held"] == [0, 18992]
    # floors of a cut: whole periods, at least 8 experts, an eighth of the ids
    assert config["num_hidden_layers"] % config["full_attention_interval"] == 0
    assert config["vocab_size"] * 8 == 151936
    assert config["layer_types"].count("full_attention") == 3
    assert config["layer_types"].count("linear_attention") == 9
    for key in ("typed_without_a_network", "layer_types", "block",
                "projection_order", "conv", "delta_rule", "gated_norm",
                "attention", "router", "experts", "state_precision",
                "weights", "precision", "mtp", "chunk_size"):
        assert config["assumed"][key], key
    assert "EIGHT" in config["deployment"]
    assert config["parameters"] == PARAMETERS


def test_the_catalog_row_if_the_guide_is_here(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == REDUCED.get(key, value), key
    assert config["published"] == {k: row["config"][k] for k in REDUCED}


def test_parameter_counts_closed_form_and_the_programs_shapes(config, family):
    import jax
    import jax.numpy as jnp
    delta = (2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128
             + 4096 * 2048)
    attention = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    expert = 3 * 2048 * 512
    rest = 2048 * 512 + expert + 2048 + 2 * 2048     # router, shared, norms
    assert (delta, attention, expert, rest) == (
        33_718_464, 27_263_488, 3_145_728, 4_200_448)
    assert (delta + rest, attention + rest) == (37_918_912, 31_463_936)
    here = 9 * (delta + rest) + 3 * (attention + rest) + 12 * 64 * expert \
        + 2 * 18992 * 2048 + 2048
    whole = 36 * (delta + rest) + 12 * (attention + rest) \
        + 48 * 512 * expert + 2 * 151936 * 2048 + 2048
    assert here == PARAMETERS == config["parameters"] \
        == family.parameters(config)
    assert whole == UNCUT == family.parameters(config, uncut=True)
    model = family.build(config, jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == here
    assert model.num_params() == here
    assert list(model.config.layer_types) == config["layer_types"]
    assert model.config.held == (0, 64)
    assert model.config.vocab_rows == (0, 18992)
    assert model.config.num_experts == 512          # the router's width
    assert model.config.chunk_size == 64
    # what a token multiplies here: 10 x 64 / 512 = an expert and a quarter
    assert family.matmul_params_per_token(config) == (
        9 * (delta - 4 * 8192 - 192) + 3 * (attention - 512)
        + 12 * (2048 * 512 + expert + 2048 + 1.25 * expert) + 18992 * 2048)
    # resident: the weights, the slots' recurrent rows, the pool
    state = SLOTS * 9 * (STATE + CARRY)
    pool = BLOCKS * 64 * 3 * TOKEN_LAYER_BYTES
    assert family.state_bytes_per_layer(config) == STATE == 2_097_152
    assert (state, pool) == (2_472_542_208, 4_026_531_840)      # ISSUE 54's
    assert 12.3e9 < 2 * here + state + pool < 12.4e9


def test_a_file_the_program_cannot_run_is_refused(config, family):
    for key, value in (("tie_word_embeddings", True),
                       ("use_sliding_window", True),
                       ("attention_bias", True), ("mlp_only_layers", [0]),
                       ("decoder_sparse_step", 2), ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=key if key != "hidden_act"
                           else "gelu"):
            family.build({**config, key: value}, "bfloat16")
    with pytest.raises(ValueError, match="num_hidden_layers"):
        family.build({**config, "num_hidden_layers": 10}, "bfloat16")
    with pytest.raises(ValueError, match="experts_held"):
        family.build({**config, "num_experts": 32}, "bfloat16")


# ------------------------------------------------------------------ the cell
def test_the_traffic_is_the_files(config):
    t = TRAFFIC
    assert (t["kind"], t["pool_requests"], t["queue_depth"],
            t["order_seed"]) == ("serve_backlog_recurrent", 512, 128, 54)
    (cls,) = t["classes"]
    assert cls["share"] == 1.0
    # ISSUE 54's, with its own remedy for the cold set-up (round_to 2,048)
    assert cls["prompt_tokens"] == {
        "kind": "lognormal", "median": 4096, "sigma": 0.6, "min": 1024,
        "max": 16384, "round_to": 2048, "short_by": 16}
    assert cls["output_tokens"] == {"kind": "lognormal", "median": 512,
                                    "sigma": 0.5, "min": 128, "max": 1536}
    assert (cls["sampling"], cls["temperature"]) == ("alternate", 0.8)
    assert SERVING == {"batch_slots": 128, "block_size": 64, "kv_bits": 16,
                       "num_blocks": 10240}
    assert (t["dtype"], t["drain_limit_s"], t["trace_seconds"]) == (
        "bfloat16", 120, 3)
    assert t["check"]["steps"] == 3 and t["check"]["slots"] >= 8
    # the configuration's float32 state is held to, exactly
    assert t["check"]["state"] == {"leaves": ["delta"], "dtype": "float32",
                                   "coarser": "bfloat16",
                                   "fine_share_min": 0.9}
    for key in ("serving", "prompt_tokens", "check", "logit_rms_tol",
                "logit_tol", "route_tie_margin", "route_tied_rows_max",
                "spread", "controls", "state"):
        assert t["notes"][key], key
    from benchmark import traffic_gen
    runner = harness.load_plugin("runners", t["kind"])
    a = runner.backlog(t, 1, 18992)
    b = runner.backlog(t, 2 ** 31 + 5, 18992)
    shape = lambda items: [(len(x.prompt), x.new_tokens, x.do_sample)
                           for x in items]
    assert shape(a) == shape(b) and len(a) == 512
    assert max(x.prompt.max() for x in a) < 18992      # ids of the slice
    buckets = traffic_gen.prefill_buckets(a, 64)
    assert buckets == [2048 * i for i in range(1, 9)]
    assert max(len(x.prompt) + x.new_tokens for x in a) \
        <= config["max_position_embeddings"] == 18432
    prompts = sum(len(x.prompt) for x in a) / len(a)
    answers = sum(x.new_tokens for x in a) / len(a)
    assert 5500 < prompts < 6200 and 500 < answers < 650
    # a mean stream holds more K/V than recurrent rows, and both matter
    kv = (prompts + answers / 2) * 3 * TOKEN_LAYER_BYTES
    assert 1.6 < kv / (9 * (STATE + CARRY)) < 2.2
    # slots and pool bind together: 128 mean streams want about the pool
    held = SLOTS * (prompts + answers / 2) / 64
    assert 0.9 < held / (BLOCKS - 1) < 1.3


def test_the_cell_reports_its_metrics_and_the_accepted_ones_it_must():
    own, shared = own_and_shared(BENCH, CELL["name"], "serve_tokens_per_s")
    assert own == {
        "kernels.qwen3next.delta_state_update_roofline",
        "kernels.qwen3next.paged_attention_roofline",
        "kernels.qwen3next.prefill_attention_roofline",
        "engine.delta_prefill_share.qwen3next",
        "engine.decode_bandwidth_share.qwen3next",
        "serving.state_fill_share.qwen3next"}
    assert shared == {
        "serving.tokens_per_step", "serving.host_ms_per_step_p50.tput",
        "serving.step_ms_p50.tput", "serving.queue_wait_ms_p50",
        "serving.prefill_ms_p50.tput", "serving.pool_fill_share",
        "serving.pool_bound_share", "moe.local_pair_share",
        "moe.experts_touched_share", "engine.prefill_share.tput",
        "engine.expert_share", "engine.state_step_share.tput",
        "engine.route_share", "device.idle_share.tput",
        "device.unscoped_share.tput"}
    assert {m["name"] for m in harness.cell_metrics(
        BENCH, "end_to_end", CELL["name"])} == {"serve_tokens_per_s",
                                                "setup_s"}
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200
    assert "1/8" in CELL["why"]      # the share of a deployment's tokens
    assert len(BENCH["per_layer"]) <= 128


# -------------------------------------------------------------------- costs
Row = collections.namedtuple("Row", "name t_start t_end attrs")


def view_with(family, rows=()):
    cfg = harness.read_json("configs", "qwen3-next-80b-a3b.json")
    facts = {**family.dims(cfg), "kv_bytes_per_element": 2,
             "window": (0.0, 40.0),
             "live_tokens": [(20.0, 300_000), (20.5, 320_000), (60.0, 9)]}
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
        "pairs_elsewhere": 10 * seated * 12 - held,
        "experts_touched": touched, "experts_idle": 768 - touched,
        "blocks_in_use": 5000, "blocks_free": 3191, "seated_slots": seated,
        # what the program SAYS it moves (half of it here) is not read
        "free_slots": 64 - seated, "state_bytes": seated * 9 * STATE}
    return [("serving.step", -1.0, -0.9, attrs(9, 90, 80)),
            ("serving.step", 1.0, 1.1, attrs(40, 600, 400)),
            ("serving.step", 20.0, 20.1, attrs(64, 960, 560)),
            ("serving.step", 20.5, 20.6, attrs(48, 700, 500)),
            ("serving.step", 30.0, 30.1, {"n_active": 60}),
            ("serving.prefill", 20.2, 20.4,
             {"prompt_len": 4000, "bucket": 4096, "delta_tokens": 4000,
              "delta_chunks": 63}),
            ("serving.prefill", 20.7, 20.9,
             {"prompt_len": 1000, "bucket": 1024, "delta_tokens": 1000,
              "delta_chunks": 16})]


def metric(view, name):
    spec = harness.read_json("layer_metrics", f"{name}.json")
    return harness.load_plugin("readers", spec["reader"]).read(
        view, **spec.get("params", {}))


def test_costs_read_the_capture(family, config):
    v = view_with(family, step_rows())
    # the state of the slots seated in the two captured steps, in and out,
    # from the configuration's float32 state and the rows' seated_slots alone
    assert family.costs["qwen3next_state_update"](v) == (
        0.0, (64 + 48) * 9 * 2 * STATE)
    # the 3 attention layers: 620,000 live tokens in the capture
    assert family.costs["qwen3next_paged_attention"](v) == (
        620_000 * 3 * 2 * 2 * 16 * 256, 620_000 * 3 * TOKEN_LAYER_BYTES)
    # two prompts' causal triangles, 2 matmuls of 2 x 16 x 256 a visible key
    pairs = 3 * (4000 * 4001 // 2 + 1000 * 1001 // 2)
    assert family.costs["qwen3next_prefill_attention"](v) == (
        pairs * 2 * 2 * 16 * 256, 0.0)
    dense = 2 * (PARAMETERS - 12 * 64 * 3_145_728 - 18992 * 2048)
    assert family.dense_weight_bytes(config) == dense == 949_119_360
    v["trace"] = {"module_calls": {"jit_step": (0.06, 0.03),
                                   "jit_prefill": (0.3, 0.15)}}
    flops, total = family.costs["qwen3next_decode_step"](
        v, module_match="jit_step")
    assert flops == 0.0
    assert total == 2 * (dense + 530 * 2 * 3_145_728) \
        + 620_000 * 3 * TOKEN_LAYER_BYTES + (64 + 48) * 9 * 2 * STATE
    # a program that records none of it: every held expert, no state
    old = view_with(family, [r for r in step_rows() if len(r[3]) < 3])
    old["trace"] = v["trace"]
    assert family.costs["qwen3next_state_update"](old) == (0.0, 0.0)
    assert family.costs["qwen3next_prefill_attention"](old) == (0, 0.0)
    _, total = family.costs["qwen3next_decode_step"](old,
                                                     module_match="jit_step")
    assert total == 2 * 2 * (PARAMETERS - 18992 * 2048) \
        + 620_000 * 3 * TOKEN_LAYER_BYTES


def test_every_new_metric_reads_a_recorded_fixture(family):
    v = view_with(family, step_rows())
    v["trace"] = {"window_s": 2.0,
                  "module_s": {"jit_step": 0.06, "jit_prefill": 0.3},
                  "module_calls": {"jit_step": (0.06, 0.03),
                                   "jit_prefill": (0.3, 0.15)},
                  "kernel_s": {"gated_delta_state_update": 0.02,
                               "prefill_flash_attention": 0.01, "gmm": 0.5,
                               "paged_attention": 0.012}}
    _, moved = family.costs["qwen3next_state_update"](v)
    assert metric(v, "kernels.qwen3next.delta_state_update_roofline") == \
        pytest.approx(100 * moved / 819e9 / 0.02)
    _, kv = family.costs["qwen3next_paged_attention"](v)
    assert metric(v, "kernels.qwen3next.paged_attention_roofline") == \
        pytest.approx(100 * kv / 819e9 / 0.012)
    flops, _ = family.costs["qwen3next_prefill_attention"](v)
    assert metric(v, "kernels.qwen3next.prefill_attention_roofline") == \
        pytest.approx(100 * flops / 197e12 / 0.01)
    _, need = family.costs["qwen3next_decode_step"](v,
                                                    module_match="jit_step")
    assert metric(v, "engine.decode_bandwidth_share.qwen3next") == \
        pytest.approx(100 * need / 819e9 / 0.06)
    assert metric(v, "serving.state_fill_share.qwen3next") == pytest.approx(
        100 * (40 + 64 + 48) / (3 * 64))
    assert metric(v, "moe.local_pair_share") == pytest.approx(
        100 * (600 / 40 + 960 / 64 + 700 / 48) / (3 * 10 * 12))
    assert metric(v, "moe.experts_touched_share") == pytest.approx(
        100 * (400 + 560 + 500) / (3 * 768))
    # no reading may pass its peak on this fixture's (made-up) times
    for name in ("kernels.qwen3next.delta_state_update_roofline",
                 "kernels.qwen3next.paged_attention_roofline",
                 "kernels.qwen3next.prefill_attention_roofline",
                 "engine.decode_bandwidth_share.qwen3next"):
        assert 0 < metric(v, name) < 100, name
    # a program whose spans carry none of it (the parent): nothing, never 0
    old = view_with(family, [r for r in step_rows() if len(r[3]) < 3])
    old["trace"] = {"window_s": 2.0, "module_s": {}, "module_calls": {},
                    "kernel_s": {"fusion": 9.0}}
    for name in ("kernels.qwen3next.delta_state_update_roofline",
                 "kernels.qwen3next.paged_attention_roofline",
                 "kernels.qwen3next.prefill_attention_roofline",
                 "engine.decode_bandwidth_share.qwen3next",
                 "serving.state_fill_share.qwen3next"):
        assert metric(old, name) is None, name
    # the chunked rule's share is read from a capture under a scope the
    # program books: the data file names that scope and nothing else
    spec = harness.read_json("layer_metrics",
                             "engine.delta_prefill_share.qwen3next.json")
    assert spec["reader"] == "trace_scope_share"
    assert spec["params"]["scopes"] == ["gdn.chunk"]


# ----------------------------------------------------------- ISSUE 54's cell
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
    t["check"].update(slots=3, logit_tol=1e-3, logit_rms_tol=1e-3,
                      route_tied_rows_max=2)
    return t


def test_the_cell_through_its_runner_on_the_cpu():
    """ISSUE 54's closed backlog with the file's class at a tiny size: a
    share of the layers, of the experts and of the vocabulary, more requests
    than slots (every slot seated again by a stream after the one that left
    it), prompts that end inside a chunk; the check (a live decode step
    through the pool and the recurrent rows against the float32 reference,
    whose delta rule runs token by token, given the same share) holds and
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
    kept = check["state_precision"]["delta"]
    assert check["state_kept_as_stated"] and kept["dtype"] == "float32"
    assert kept["nonzero"] > 0 and kept["fine_share"] > 0.99
    assert r["details"]["facts"]["kv_width"] == 16
    from deepspeed_tpu.monitor import spans
    t0, t1 = r["details"]["facts"]["window"]
    rows = [row for row in spans.recorder().rows()
            if t0 <= row.t_start < t1]
    steps = [row.attrs for row in rows if row.name == "serving.step"
             and row.attrs.get("emitted")]
    assert steps and all(
        a["routed_pairs"] + a["pairs_elsewhere"] == 4 * a["n_active"] * 4
        and a["experts_touched"] + a["experts_idle"] == 4 * 4
        and a["seated_slots"] + a["free_slots"] == 4
        and a["state_bytes"] == a["seated_slots"] * 3 * 2 * 4 * 8 * 8 * 4
        for a in steps)
    assert sum(a["pairs_elsewhere"] for a in steps) > 0
    prefills = [row.attrs for row in rows if row.name == "serving.prefill"]
    assert prefills and all(
        a["delta_tokens"] == a["prompt_len"]
        and a["delta_chunks"] == -(-a["prompt_len"] // 64) for a in prefills)


def test_the_witness_reads_nothing_where_matmuls_are_float32():
    """``control_qwen3next.py --witness bf16_matmuls`` through the runner's
    ``compare`` at a tiny size: the CPU multiplies in float32 whatever
    precision it is asked for, so the reference reads itself here (the
    chip's reading is PERF.md's)."""
    from benchmark import control_qwen3next
    out = control_qwen3next.read_witness(BENCH, CELL, SEED, config=TINY,
                                         traffic=tiny_traffic())
    json.dumps(out)
    facts = out["facts"]
    assert out["correct"] and out["witness"] == "bf16_matmuls"
    assert facts["rows_same_route"] == 3 and facts["logit_rms_err"] < 1e-5


@pytest.mark.parametrize("fault", ["delta_not_subtracted", "rope_all_dims",
                                   "state_bf16"])
def test_a_planted_fault_fails_the_runners_check(fault):
    from benchmark import control_qwen3next
    out = control_qwen3next.read_fault(BENCH, CELL, SEED, fault,
                                       lambda msg: None, config=TINY,
                                       traffic=tiny_traffic())
    json.dumps(out)
    assert not out["correct"]
    assert out["facts"]["served"] and out["facts"]["blocks_recycled"]
    # a state kept below float32 is told by its rows, not by the logits
    assert out["facts"]["state_kept_as_stated"] == (fault != "state_bf16")
    if fault == "state_bf16":
        assert out["facts"]["state_precision"]["delta"]["fine_share"] == 0.0


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
    from deepspeed_tpu.ops import gated_delta
    for name in ("paged_attention", "flash_attention"):
        monkeypatch.setattr(importlib.import_module(
            f"deepspeed_tpu.ops.transformer.{name}"), "_interpret",
            lambda: False)
    monkeypatch.setattr(gated_delta, "_interpret", lambda: False)
    monkeypatch.setattr(dropless, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "flash_attention_available", lambda: True)
    args = [a if hasattr(a, "sharding") or not isinstance(a, tuple)
            else jax.ShapeDtypeStruct(*a, sharding=one_chip) for a in args]
    return jax.jit(fn, donate_argnums=donate).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


HBM = 15.75 * 2 ** 30 - 258 * 2 ** 20      # the compiler's own limit, less
#                                            what it reserves (its error text)
GATE = 0.92 * 15.75 * 2 ** 30              # ServingConfig.preflight_safety
STATE_BYTES = SLOTS * 9 * (STATE + CARRY)
POOL_BYTES = BLOCKS * 64 * 3 * TOKEN_LAYER_BYTES


def test_the_decode_step_fits_a_v5e_and_updates_the_state_in_place(
        published, one_chip, monkeypatch):
    """The file's slots over tables of 288 entries: the named one-token
    update in every DeltaNet layer (9 Mosaic calls), the paged kernel in the
    three attention layers at head size 256, the pool AND the recurrent rows
    written in place (aliased), the experts' products the Pallas grouped
    matmul, and weights, rows and pool inside the engine's gate."""
    import re
    import jax.numpy as jnp
    model, params, pool = published
    args = (params, ((SLOTS,), jnp.int32), pool,
            ((SLOTS, 18432 // 64), jnp.int32), ((SLOTS,), jnp.int32))
    step = lambda p, t, pl, tb, ln: model.decode_step_paged(p, t, pl, tb, ln)
    exe = compiled(one_chip, monkeypatch, step, args, donate=(2,))
    m = exe.memory_analysis()
    text = exe.as_text()
    assert text.count("gated_delta_state_update") >= 9
    assert text.count("paged_attention") >= 3
    assert len(re.findall(r"%gmm[.\d]* = ", text)) >= 36
    assert "ragged-dot" not in text
    # no copy of the delta rows nor of a stack of experts' matrices
    assert not re.search(r"f32\[9,(128,32|4096),128,128\]\S* copy\(", text)
    assert not re.search(r"bf16\[(12,64|768),\d+,\d+\]\S* copy\(", text)
    assert m.alias_size_in_bytes >= POOL_BYTES + STATE_BYTES
    assert m.temp_size_in_bytes < 512 * 2 ** 20
    assert 2 * PARAMETERS + POOL_BYTES + STATE_BYTES \
        <= m.argument_size_in_bytes
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < GATE < HBM


@pytest.mark.parametrize("bucket", [2048, 16384, 18432])
def test_a_prefill_fits_a_v5e_beside_the_state(published, one_chip,
                                               monkeypatch, bucket):
    """The traffic's shortest and longest buckets and the served limit's
    (what the engine's preflight compiles): the flash forward kernel on the
    attention layers at head size 256 (no (T, T) scores), the slot's rows
    written into the donated state, and weights, state, pool and transients
    inside the engine's gate."""
    import jax.numpy as jnp
    model, params, pool = published
    fn = lambda p, t, pl, bl, s, n: model.prefill_paged(p, t, pl, bl, s, n)
    exe = compiled(one_chip, monkeypatch, fn,
                   (params, ((1, bucket), jnp.int32), pool,
                    ((bucket // 64,), jnp.int32), ((), jnp.int32),
                    ((), jnp.int32)), donate=(2,))
    m = exe.memory_analysis()
    text = exe.as_text()
    assert text.count("prefill_flash_attention") >= 3
    assert m.alias_size_in_bytes >= POOL_BYTES + STATE_BYTES
    assert m.temp_size_in_bytes < 2.0e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < GATE < HBM
