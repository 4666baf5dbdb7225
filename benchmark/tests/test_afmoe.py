"""The AFMoE family's files (``configs/trinity-large-preview.json``,
``families/afmoe.py``, ``reference/afmoe.py``) and its cell
(``traffic/serve_longmix_trinity.json``, the ``*.trinity`` metric files, the
runner ``serve_backlog_windowed``): the file against the catalog row, the
parameter count against its closed form and the program's own shapes, the
family's costs against numbers worked by hand, each new metric's reader on
rows made by hand, the cell through its runner at a tiny size on the CPU, and
the decode step and the longest prefill buckets compiled for a described v5e
at the published widths beside BOTH pools of the traffic file.
"""

import collections
import copy
import importlib
import json

import pytest

from benchmark import harness, run
from benchmark.tests.cell_metrics import own_and_shared
from benchmark.tests.test_runners_cpu import SEED

BENCH = harness.load_benchmark()
CELL = harness.cell_by_name(BENCH, "serve_longmix_trinity")
TRAFFIC = harness.load_traffic(CELL["traffic"])
SERVING = TRAFFIC["serving"]
SLOTS, BLOCKS, WBLOCKS = (SERVING["batch_slots"], SERVING["num_blocks"],
                          SERVING["window_num_blocks"])
TOKEN_LAYER_BYTES = 2 * 8 * 128 * 2            # K and V, 8 heads of 128
PARAMETERS = 4_321_903_872

# the catalog row's ``config`` (architectures.jsonl, Trinity-Large-Preview),
# typed again
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 15,
    "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
    "model_type": "afmoe", "moe_intermediate_size": 3072,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 48,
    "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256,
    "num_experts_per_tok": 4, "num_hidden_layers": 60,
    "num_key_value_heads": 8, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
    "score_func": "sigmoid", "sliding_window": 4096,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
REDUCED = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 32,
           "vocab_size": 25024, "max_position_embeddings": 17408}

# the program's tiny preset in the file's key names, as one chip's share:
# layers 0 (dense), 4, 5, 6, 7 of 8, 4 of 16 experts, a quarter of the ids
TINY = {"model_type": "afmoe", "vocab_size": 128, "hidden_size": 64,
        "intermediate_size": 160, "moe_intermediate_size": 32,
        "num_hidden_layers": 5, "num_dense_layers": 1,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_experts": 4, "num_experts_per_tok": 4, "num_shared_experts": 1,
        "score_func": "sigmoid", "route_norm": True, "route_scale": 2.448,
        "n_group": 1, "topk_group": 1, "sliding_window": 8,
        "global_attn_every_n_layers": 4, "hidden_act": "silu",
        "rms_norm_eps": 1e-5, "rope_theta": 10000, "rope_scaling": None,
        "max_position_embeddings": 96, "mup_enabled": True,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
        "published": {"num_hidden_layers": 8, "num_dense_layers": 2,
                      "num_experts": 16, "vocab_size": 512},
        "layers_held": [0, 4, 5, 6, 7], "experts_held": [4, 4],
        "vocab_held": [128, 128]}


@pytest.fixture(scope="module")
def config():
    return harness.read_json("configs", "trinity-large-preview.json")


@pytest.fixture(scope="module")
def family(config):
    return harness.family(config)


# ------------------------------------------------------------ the configuration
def test_the_file_is_the_catalog_row_but_for_what_reduced_lists(config):
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "trinity-large-preview")
    assert entry["reduced"] == config["reduced"] == list(REDUCED)
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    # no width is cut, inside a group either
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    assert config["experts_held"] == [0, 32]
    assert config["vocab_held"] == [0, 25024]
    assert sorted(map(int, config["layers_held"])) == [0, 8, 9, 10, 11]
    for l, said in config["layers_held"].items():
        assert said.startswith(PUBLISHED["layer_types"][int(l)])
        assert said.endswith("dense" if int(l) < 6 else "experts")
    for key in ("embedding_scale", "norms", "qk_norm", "gate", "rope",
                "window", "router", "expert_bias", "weights", "precision",
                "training_only", "typed_without_a_network"):
        assert config["assumed"][key], key
    assert "EIGHT" in config["deployment"]


def test_the_catalog_row_if_the_guide_is_here(config):
    import os
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide on this machine")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "Trinity-Large-Preview")
    assert row["config"] == PUBLISHED
    assert config["source"] == row["source_url"]


def test_parameter_count_closed_form_and_the_programs_shapes(config, family):
    import jax
    import jax.numpy as jnp
    attention = 3 * 3072 * 6144 + 2 * 3072 * 1024
    assert attention == 62_914_560
    dense_layer = attention + 256 + 4 * 3072 + 3 * 3072 * 12288
    expert = 3 * 3072 * 3072
    expert_layer = attention + 256 + 4 * 3072 + 3072 * 256 + 256 \
        + 33 * expert
    assert (dense_layer, expert, expert_layer) == (
        176_173_312, 28_311_552, 997_995_008)
    total = dense_layer + 4 * expert_layer + 2 * 25024 * 3072 + 3072
    assert total == PARAMETERS == config["parameters"] \
        == family.parameters(config)
    model = family.build(config, jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == total
    assert model.num_params() == total
    assert model.config.types == ("sliding_attention",) * 4 + (
        "full_attention",)
    # what a token multiplies here: 4 x 32 / 256 = half an expert reached
    assert family.matmul_params_per_token(config) == (
        5 * attention + 3 * 3072 * 12288
        + 4 * (3072 * 256 + 1.5 * expert) + 25024 * 3072)
    assert family.kind_counts(config) == (4, 1)
    # resident: the weights and both pools of the traffic file
    pools = (BLOCKS * 1 + WBLOCKS * 4) * 64 * TOKEN_LAYER_BYTES
    assert 2 * total + pools >= 12.5e9


def test_a_file_the_program_cannot_run_is_refused(config, family):
    for key, value in (("tie_word_embeddings", True), ("n_group", 2),
                       ("hidden_act", "gelu"),
                       ("rope_scaling", {"type": "linear", "factor": 2})):
        with pytest.raises(ValueError, match=key):
            family.build({**config, key: value}, "bfloat16")
    with pytest.raises(ValueError, match="layers_held"):
        family.build({**config, "num_dense_layers": 2}, "bfloat16")
    with pytest.raises(ValueError, match="experts_held"):
        family.build({**config, "num_experts": 16}, "bfloat16")


# ------------------------------------------------------------------ the cell
def test_the_traffic_is_issue_39s(config):
    t = TRAFFIC
    assert (t["kind"], t["pool_requests"], t["queue_depth"],
            t["order_seed"]) == ("serve_backlog_windowed", 256, 96, 39)
    short, long = t["classes"]
    assert short["share"] == long["share"] == 0.5
    assert short["prompt_tokens"] == {
        "kind": "lognormal", "median": 512, "sigma": 0.7, "min": 64,
        "max": 2048, "round_to": 256, "short_by": 16}
    assert long["prompt_tokens"] == {
        "kind": "lognormal", "median": 8192, "sigma": 0.4, "min": 4096,
        "max": 16384, "round_to": 1024, "short_by": 16}
    assert short["output_tokens"] == {"kind": "lognormal", "median": 256,
                                      "sigma": 0.5, "min": 32, "max": 768}
    assert long["output_tokens"] == {"kind": "lognormal", "median": 384,
                                     "sigma": 0.5, "min": 64, "max": 1024}
    for cls in t["classes"]:
        assert (cls["sampling"], cls["temperature"]) == ("alternate", 0.8)
    assert (SLOTS, SERVING["block_size"], SERVING["kv_bits"]) == (96, 64, 16)
    # ISSUE 39's pools (5,632 and 3,072) less one eighth each: its first
    # remedy, taken for the longest prefill's transients (the file's notes)
    assert (BLOCKS, WBLOCKS) == (5632 * 7 // 8, 3072 * 7 // 8)
    assert (t["check"]["slots"], t["check"]["steps"],
            t["check"]["rows_past_window_min"]) == (8, 3, 3)
    from benchmark import serving, traffic_gen
    runner = harness.load_plugin("runners", t["kind"])
    a = runner.backlog(t, 1, 25024)
    b = runner.backlog(t, 2 ** 31 + 5, 25024)
    assert [(len(x.prompt), x.new_tokens, x.do_sample) for x in a] == \
        [(len(x.prompt), x.new_tokens, x.do_sample) for x in b]
    assert max(x.prompt.max() for x in a) < 25024      # ids of the slice
    buckets = traffic_gen.prefill_buckets(a, 64)
    assert len(buckets) == 21 and buckets[-1] == 16384
    assert max(len(x.prompt) + x.new_tokens for x in a) \
        <= config["max_position_embeddings"]
    picks = sorted(len(x.prompt) + 7 for x in serving.check_picks(a, 8))
    assert sum(n > 4096 for n in picks) >= 3 and picks[-1] > 8192
    # a seat reserves about 84 global and 41 window blocks: both kinds bind
    # before the 96 slots do
    need = [-(-(len(x.prompt) + x.new_tokens) // 64) for x in a]
    by_global = (BLOCKS - 1) / (sum(need) / len(need))
    by_window = (WBLOCKS - 1) / (sum(min(n, 65) for n in need) / len(need))
    assert 50 < by_global < by_window < 80 < SLOTS


def test_the_cell_reports_its_metrics_and_the_accepted_ones_it_must():
    # its kernels' and its decode step's costs and the second pool are this
    # cell's own; the rest it shares with the other throughput cells
    own, shared = own_and_shared(BENCH, CELL["name"], "serve_tokens_per_s")
    assert own == {
        "kernels.trinity.window_paged_attention_roofline",
        "kernels.trinity.global_paged_attention_roofline",
        "kernels.trinity.prefill_attention_roofline",
        "engine.decode_bandwidth_share.trinity",
        "serving.global_pool_fill_share.trinity",
        "serving.window_pool_fill_share.trinity",
        "serving.window_capped_share.trinity"}
    assert shared == {
        "engine.expert_share", "engine.prefill_share.tput",
        "moe.local_pair_share", "moe.experts_touched_share",
        "serving.step_ms_p50.tput", "serving.host_ms_per_step_p50.tput",
        "serving.tokens_per_step", "serving.prefill_ms_p50.tput",
        "serving.queue_wait_ms_p50", "serving.pool_bound_share",
        "device.idle_share.tput"}
    assert {m["name"] for m in harness.cell_metrics(
        BENCH, "end_to_end", CELL["name"])} == {"serve_tokens_per_s",
                                                "setup_s"}
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200


# -------------------------------------------------------------------- costs
Row = collections.namedtuple("Row", "name t_start t_end attrs")


def view_with(family, rows=()):
    cfg = harness.read_json("configs", "trinity-large-preview.json")
    facts = {**family.dims(cfg), "kv_bytes_per_element": 2,
             "window": (0.0, 40.0),
             "live_tokens": [(20.0, 200_000), (20.5, 240_000), (60.0, 9)]}
    return {"facts": facts, "trace_span": (19.0, 21.0), "family": family,
            "config": cfg, "peaks": harness.peaks_for("TPU v5 lite"),
            "program_spans": {"rows": [Row(*r) for r in rows],
                              "dropped_until": None}}


def step_rows():
    """``serving.step`` rows: two inside the capture, two inside the window
    only, one before it, one as a program before PR 39 writes them; and two
    prefills inside the capture."""
    attrs = lambda held, touched, kv, seen, waits=False: {
        "n_active": 60, "emitted": 60, "routed_pairs": held,
        "pairs_elsewhere": 4 * 60 * 4 - held, "experts_touched": touched,
        "experts_idle": 128 - touched, "blocks_in_use": 4000,
        "blocks_free": 927, "window_blocks_in_use": 2000,
        "window_blocks_free": 687, "kv_tokens": kv,
        "window_kv_tokens": seen, "window_capped_tokens": kv - seen,
        "waits_for_blocks": waits}
    return [("serving.step", -1.0, -0.9, attrs(9, 9, 9, 9)),
            ("serving.step", 1.0, 1.1, attrs(120, 80, 100_000, 80_000)),
            ("serving.step", 2.0, 2.1, attrs(100, 70, 100_000, 60_000,
                                             True)),
            ("serving.step", 20.0, 20.1, attrs(130, 90, 200_000, 150_000)),
            ("serving.step", 20.5, 20.6, attrs(110, 98, 240_000, 170_000)),
            ("serving.step", 30.0, 30.1, {"n_active": 60}),
            ("serving.prefill", 20.2, 20.4, {"prompt_len": 1000,
                                             "bucket": 1024}),
            ("serving.prefill", 20.7, 20.9, {"prompt_len": 6000,
                                             "bucket": 6144})]


def metric(view, name):
    spec = harness.read_json("layer_metrics", f"{name}.json")
    return harness.load_plugin("readers", spec["reader"]).read(
        view, **spec.get("params", {}))


def test_costs_read_the_capture(family, config):
    v = view_with(family, step_rows())
    per_token = 2 * 2 * 48 * 128
    # the global layer: 440,000 live tokens in the capture, one layer
    assert family.costs["trinity_global_paged_attention"](v) == (
        440_000 * per_token, 440_000 * TOKEN_LAYER_BYTES)
    # the window layers: 320,000 tokens still inside a window, four layers
    assert family.costs["trinity_window_paged_attention"](v) == (
        320_000 * 4 * per_token, 320_000 * 4 * TOKEN_LAYER_BYTES)
    # a prompt of 1,000 sees its triangle in all five layers; one of 6,000
    # the band in four (4,096 keys from position 4,095 on) and the triangle
    # in one
    tri = lambda n: n * (n + 1) // 2
    band = tri(4096) + (6000 - 4096) * 4096
    flops, _ = family.costs["trinity_prefill_attention"](v)
    assert flops == (5 * tri(1000) + 4 * band + tri(6000)) * per_token
    assert family.costs["trinity_prefill_attention"](v, kinds="global") == (
        (tri(1000) + tri(6000)) * per_token, 0.0)
    dense = 2 * (PARAMETERS - 4 * 32 * 28_311_552 - 25024 * 3072)
    assert family.dense_weight_bytes(config) == dense == 1_242_302_976
    v["trace"] = {"module_calls": {"jit_step": (0.08, 0.04),
                                   "jit_prefill": (0.3, 0.1)}}
    flops, total = family.costs["trinity_decode_step"](
        v, module_match="jit_step")
    kv = (440_000 + 4 * 320_000) * TOKEN_LAYER_BYTES
    assert flops == 0.0
    assert total == 2 * (dense + 94 * 2 * 28_311_552) + kv
    # a program that records none of it: every held expert, no window K/V
    old = view_with(family, [r for r in step_rows() if len(r[3]) < 3])
    old["trace"] = v["trace"]
    assert family.costs["trinity_window_paged_attention"](old) == (0, 0)
    _, total = family.costs["trinity_decode_step"](old,
                                                   module_match="jit_step")
    assert total == 2 * 2 * (PARAMETERS - 25024 * 3072) \
        + 440_000 * TOKEN_LAYER_BYTES


def test_every_new_metric_reads_a_recorded_fixture(family):
    v = view_with(family, step_rows())
    v["trace"] = {"window_s": 2.0,
                  "module_s": {"jit_step": 0.08, "jit_prefill": 0.9},
                  "module_calls": {"jit_step": (0.08, 0.04),
                                   "jit_prefill": (0.9, 0.05)},
                  "kernel_s": {"paged_attention_window": 0.02,
                               "paged_attention_global": 0.008,
                               "gmm": 0.5,
                               "paged_attention": 9.0}}
    _, wbytes = family.costs["trinity_window_paged_attention"](v)
    _, gbytes = family.costs["trinity_global_paged_attention"](v)
    assert metric(v, "kernels.trinity.window_paged_attention_roofline") == \
        pytest.approx(100 * wbytes / 819e9 / 0.02)
    assert metric(v, "kernels.trinity.global_paged_attention_roofline") == \
        pytest.approx(100 * gbytes / 819e9 / 0.008)
    assert metric(v, "engine.expert_share") == pytest.approx(25.0)
    assert metric(v, "engine.prefill_share.tput") == pytest.approx(45.0)
    _, need = family.costs["trinity_decode_step"](v, module_match="jit_step")
    assert metric(v, "engine.decode_bandwidth_share.trinity") == \
        pytest.approx(100 * need / 819e9 / 0.08)
    assert metric(v, "moe.local_pair_share") == pytest.approx(
        100 * (120 + 100 + 130 + 110) / (4 * 960))
    assert metric(v, "moe.experts_touched_share") == pytest.approx(
        100 * (80 + 70 + 90 + 98) / (4 * 128))
    assert metric(v, "serving.global_pool_fill_share.trinity") == \
        pytest.approx(100 * 4000 / 4927)
    assert metric(v, "serving.window_pool_fill_share.trinity") == \
        pytest.approx(100 * 2000 / 2687)
    assert metric(v, "serving.pool_bound_share") == pytest.approx(25)
    assert metric(v, "serving.window_capped_share.trinity") == pytest.approx(
        100 * (0.2 + 0.4 + 0.25 + 70 / 240) / 4)
    v["counters"] = {"generated_tokens": 24_000, "decode_steps": 400}
    assert metric(v, "serving.tokens_per_step") == 60.0
    # a program whose spans carry none of it (the parent): nothing, never 0
    old = view_with(family, [r for r in step_rows() if len(r[3]) < 3])
    old["trace"] = {"window_s": 2.0, "module_s": {}, "module_calls": {},
                    "kernel_s": {"paged_attention": 9.0}}
    for name in (m["name"] for m in BENCH["per_layer"]
                 if CELL["name"] in m.get("workloads", ())
                 and m["source"] != "host_clock"
                 and not m["name"].startswith(("serving.tokens_per_step",
                                               "serving.prefill_ms",
                                               "serving.queue_wait",
                                               "serving.host_ms",
                                               "device.idle"))):
        assert metric(old, name) is None, name


# ----------------------------------------------------------- ISSUE 39's cell
def tiny_traffic():
    t = copy.deepcopy(TRAFFIC)
    short, long = t["classes"]
    short["prompt_tokens"].update(median=10, min=4, max=24, round_to=8,
                                  short_by=3)
    short["output_tokens"].update(median=6, min=3, max=10)
    long["prompt_tokens"].update(median=40, min=24, max=64, round_to=16,
                                 short_by=3)
    long["output_tokens"].update(median=10, min=4, max=16)
    t["trace_seconds"] = 1
    t["pool_requests"], t["queue_depth"] = 12, 6
    t["serving"].update(batch_slots=4, block_size=4, num_blocks=60,
                        window_num_blocks=10)
    t["dtype"] = "float32"
    t["check"].update(slots=4, logit_tol=1e-3, logit_rms_tol=1e-3)
    return t


def test_the_cell_through_its_runner_on_the_cpu():
    """ISSUE 39's closed backlog with the file's two classes at a tiny size
    (a window of 8 over blocks of 4: the long class's streams wrap their
    ring of 3 several times): a share of the layers, of the experts and of
    the vocabulary, more requests than slots, a window pool that binds; the
    check (a live decode step through both kinds of block against the
    float32 reference given the same share) holds, at least three compared
    rows are past the window, and every block of BOTH kinds is recycled."""
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
    assert check["blocks_recycled"] and check["window_blocks_recycled"]
    assert check["rows_past_window"] >= 3 and check["paged_impl"] == "kernel"
    from deepspeed_tpu.monitor import spans
    t0, t1 = r["details"]["facts"]["window"]
    steps = [row.attrs for row in spans.recorder().rows("serving.step")
             if t0 <= row.t_start < t1 and row.attrs.get("emitted")]
    assert steps and all(
        a["routed_pairs"] + a["pairs_elsewhere"] == 4 * a["n_active"] * 4
        and a["experts_touched"] + a["experts_idle"] == 4 * 4
        and a["window_kv_tokens"] + a["window_capped_tokens"]
        == a["kv_tokens"] for a in steps)
    assert sum(a["window_capped_tokens"] for a in steps) > 0
    assert any(a["waits_for_window_blocks"] for a in steps)


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
    """The model and the shapes of its weights and of the file's two pools,
    on a described v5e."""
    import jax
    import jax.numpy as jnp
    model = family.build(config, jnp.bfloat16)
    on = lambda x, dtype=None: jax.ShapeDtypeStruct(
        x.shape, dtype or x.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda x: on(x, jnp.bfloat16),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = jax.tree_util.tree_map(on, jax.eval_shape(
        lambda: model.init_serving_state(SLOTS, BLOCKS, 64,
                                         window_num_blocks=WBLOCKS)))
    return model, params, pool


def compiled(one_chip, monkeypatch, fn, args, donate=()):
    import jax
    import deepspeed_tpu.ops as ops
    from deepspeed_tpu.moe import dropless
    for name in ("paged_attention", "flash_attention"):
        monkeypatch.setattr(importlib.import_module(
            f"deepspeed_tpu.ops.transformer.{name}"), "_interpret",
            lambda: False)
    # the chip's branch of the grouped products, not the CPU's ragged_dot
    monkeypatch.setattr(dropless, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "flash_attention_available", lambda: True)
    args = [a if hasattr(a, "sharding") or not isinstance(a, tuple)
            else jax.ShapeDtypeStruct(*a, sharding=one_chip) for a in args]
    return jax.jit(fn, donate_argnums=donate).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


HBM = 15.75 * 2 ** 30 - 258 * 2 ** 20      # the compiler's own limit, less
#                                            what it reserves (its error text)
GATE = 0.92 * 15.75 * 2 ** 30              # ServingConfig.preflight_safety
POOL_BYTES = (BLOCKS + 4 * WBLOCKS) * 64 * TOKEN_LAYER_BYTES


def test_the_decode_step_fits_a_v5e_and_walks_both_kinds_in_place(
        published, one_chip, monkeypatch):
    """96 slots over tables of 272 + 65 entries: a Mosaic call under its own
    name for the window layers (4) and for the global one, both pools
    written in place, no weight re-laid (q_w and k_w stored (in, out) cost a
    transposition of both on every step), weights plus pools fit."""
    import re
    import jax.numpy as jnp
    model, params, pool = published
    ring = model.ring_entries(64)
    assert ring == 65
    args = (params, ((SLOTS,), jnp.int32), pool,
            ((SLOTS, 17408 // 64 + ring), jnp.int32), ((SLOTS,), jnp.int32))
    step = lambda p, t, pl, tb, ln: model.decode_step_paged(p, t, pl, tb, ln)
    exe = compiled(one_chip, monkeypatch, step, args, donate=(2,))
    m = exe.memory_analysis()
    text = exe.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*"
                          r"paged_attention_window", text)) == 4 or \
        text.count("paged_attention_window") >= 4
    assert text.count("paged_attention_global") >= 1
    # the experts' three products a layer (the four expert layers may be
    # one loop's body) are the Pallas grouped matmul (PR 43), not XLA's
    # ragged-dot, which the CPU alone still runs
    assert len(re.findall(r"%gmm[.\d]* = ", text)) >= 3
    assert "ragged-dot" not in text
    assert m.alias_size_in_bytes >= POOL_BYTES
    assert m.temp_size_in_bytes < 128 * 2 ** 20
    assert not re.search(r"bf16\[\d+,\d{4,}\]\S* copy\(", text)
    assert 2 * PARAMETERS + POOL_BYTES <= m.argument_size_in_bytes < HBM


@pytest.mark.parametrize("bucket", [16384, 17408])
def test_the_longest_prefill_fits_a_v5e_with_no_square_of_scores(
        published, one_chip, monkeypatch, bucket):
    """The traffic's longest bucket, and the served limit's (what the
    engine's preflight compiles): no (T, T) scores (48 heads of 16k x 16k
    float32 are 51 GB; the transients stay under 2.7 GB), and weights,
    pools and transients pass the engine's gate of 92 % of the chip."""
    import jax.numpy as jnp
    model, params, pool = published
    fn = lambda p, t, pl, bl, n: model.prefill_paged(p, t, pl, bl, None, n)
    exe = compiled(one_chip, monkeypatch, fn,
                   (params, ((1, bucket), jnp.int32), pool,
                    ((bucket // 64 + 65,), jnp.int32), ((), jnp.int32)),
                   donate=(2,))
    m = exe.memory_analysis()
    assert m.alias_size_in_bytes >= POOL_BYTES
    assert m.temp_size_in_bytes < 2.7e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < GATE < HBM
