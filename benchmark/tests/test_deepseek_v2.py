"""The DeepSeek-V2 family's files (``configs/deepseek-v2.json``,
``families/deepseek_v2.py``, ``reference/deepseek_v2.py``) and its cell
(``traffic/serve_batch_deepseek_v2.json``, the ``*.dsv2`` metric files): the
parameter count against its closed form and against the program's own shapes,
the file against the catalog row, the family's costs against numbers worked
by hand, the cell through its runner at a tiny size on the CPU, each new
metric's reader on rows made by hand, and the decode step and the longest
prefill compiled for a described v5e at the published widths with the file's
pool of 4,096 blocks of 64 and 128 slots: the latent pool's slices lower
(ROADMAP D11's wall is met here, not on the chip).
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
CELL = harness.cell_by_name(BENCH, "serve_batch_deepseek_v2")
TRAFFIC = harness.load_traffic(CELL["traffic"])
ROW_BYTES = 640 * 2                        # a token, a layer, as stored
TOKEN_BYTES = 7 * ROW_BYTES
BLOCKS, SLOTS = 4096, 128

# the catalog row's ``config`` (architectures.jsonl, line 11), typed again
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8,
    "n_routed_experts": 160, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 3, "topk_method": "group_limited_greedy",
    "v_head_dim": 128, "vocab_size": 102400}
REDUCED = {"num_hidden_layers": 7, "n_routed_experts": 20,
           "vocab_size": 12800, "max_position_embeddings": 4096}

TINY = {"model_type": "deepseek_v2", "vocab_size": 256, "hidden_size": 64,
        "intermediate_size": 160, "moe_intermediate_size": 32,
        "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "n_routed_experts": 4, "n_shared_experts": 2,
        "num_experts_per_tok": 6, "first_k_dense_replace": 1,
        "moe_layer_freq": 1, "n_group": 8, "topk_group": 3,
        "topk_method": "group_limited_greedy", "scoring_func": "softmax",
        "norm_topk_prob": False, "routed_scaling_factor": 16,
        "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "rope_scaling": dict(PUBLISHED["rope_scaling"],
                             original_max_position_embeddings=64),
        "max_position_embeddings": 256,
        # a share: 4 of 16 experts (two groups), a quarter of 1,024 ids
        "published": {"n_routed_experts": 16, "vocab_size": 1024},
        "experts_held": [4, 4], "vocab_held": [0, 256]}


@pytest.fixture(scope="module")
def config():
    return harness.read_json("configs", "deepseek-v2.json")


@pytest.fixture(scope="module")
def family(config):
    return harness.family(config)


# ------------------------------------------------------------ the configuration
def test_the_file_is_the_catalog_row_but_for_the_four_cuts(config):
    assert config["reduced"] == list(REDUCED)
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert config["experts_held"] == [0, 20]
    assert config["vocab_held"] == [0, 12800]
    for key in ("typed_without_a_network", "rope_pairing", "pool_layout",
                "weights", "yarn", "loss"):
        assert key in config["assumed"], key
    assert "2/sqrt(hidden_size)" in config["assumed"]["weights"]
    d = config["deployment"]
    assert "EIGHT" in d and "No training cell" in d and "pipeline" in d
    assert f"{BLOCKS * 64 * TOKEN_BYTES:,}" == "2,348,810,240" and \
        "2,348,810,240" in d
    assert f"{config['parameters']:,}" in d


def test_parameters_match_the_closed_form_and_the_programs_shapes(config,
                                                                  family):
    mla = (7_864_320 + 37_748_736 + 2_949_120 + 16_777_216 + 83_886_080
           + 2_048)
    assert mla == 149_227_520
    assert mla - 2_048 == family.mla_matrix_params(config)
    expert = 3 * 5120 * 1536
    assert expert == family.expert_params(config) == 23_592_960
    moe_layer = mla + 2 * expert + 160 * 5120 + 10_240 + 20 * expert
    assert moe_layer == 669_102_080
    dense_layer = mla + 10_240 + 3 * 5120 * 12288
    assert dense_layer == 337_981_440
    closed = dense_layer + 6 * moe_layer + 2 * 12800 * 5120 + 5120
    assert closed == 4_483_671_040 == config["parameters"]
    assert family.parameters(config) == closed
    assert family.parameters(PUBLISHED) == 235_741_434_880
    import jax
    import jax.numpy as jnp
    model = family.build(config, jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == closed
    assert model.num_params() == closed
    c = model.config
    assert (c.n_layer, c.kv_layers, c.n_head, c.n_kv_head, c.max_seq,
            c.held, c.vocab_rows, c.n_routed_experts) == (
        7, 7, 128, 1, 4096, (0, 20), (0, 12800), 160)
    assert shapes["moe"]["router_w"].shape == (6, 5120, 160)
    assert shapes["moe"]["gate_w"].shape == (6, 20, 5120, 1536)


def test_dims_what_a_token_multiplies_and_what_the_family_refuses(config,
                                                                  family):
    d = family.dims(config)
    assert (d["n_layer"], d["n_head"], d["n_kv_head"], d["head_dim"],
            d["kv_width"], d["vocab_size"], d["max_positions"]) == (
        7, 128, 1, 640, 640, 12800, 4096)
    mla, expert = 149_225_472, 23_592_960
    want = (7 * mla + 3 * 5120 * 12288
            + 6 * (160 * 5120 + (0.75 + 2) * expert) + 12800 * 5120)
    assert family.matmul_params_per_token(config) == want
    for key, value, word in [("tie_word_embeddings", True, "tie_word"),
                             ("topk_method", "noaux_tc", "topk_method"),
                             ("scoring_func", "sigmoid", "scoring_func"),
                             ("rope_scaling", {"type": "linear",
                                               "factor": 2.0}, "rope_scaling"),
                             ("experts_held", [0, 8], "experts_held")]:
        with pytest.raises(ValueError, match=word):
            family.build({**config, key: value}, None)


def test_the_traffic_file_is_the_cell_issue_34_fixed():
    t = TRAFFIC
    assert (t["kind"], t["pool_requests"], t["queue_depth"],
            t["order_seed"]) == ("serve_backlog_routed", 512, 128, 34)
    [cls] = t["classes"]
    assert cls["prompt_tokens"] == {
        "kind": "lognormal", "median": 768, "sigma": 0.6, "min": 128,
        "max": 2560, "round_to": 128, "short_by": 16}
    assert cls["output_tokens"] == {
        "kind": "lognormal", "median": 384, "sigma": 0.5, "min": 64,
        "max": 1024}
    assert (cls["sampling"], cls["temperature"]) == ("alternate", 0.8)
    assert t["serving"] == {"batch_slots": SLOTS, "block_size": 64,
                            "kv_bits": 16, "num_blocks": BLOCKS}
    assert t["dtype"] == "bfloat16"
    assert (t["check"]["slots"], t["check"]["steps"]) == (8, 3)
    assert f"{BLOCKS * 64 * TOKEN_BYTES:,}" in t["notes"]["serving"]
    from benchmark import traffic_gen
    runner = harness.load_plugin("runners", t["kind"])
    a = runner.backlog(t, 1, 12800)
    b = runner.backlog(t, 2 ** 31 + 5, 12800)
    assert [(len(x.prompt), x.new_tokens, x.do_sample) for x in a] == \
        [(len(x.prompt), x.new_tokens, x.do_sample) for x in b]
    assert max(x.prompt.max() for x in a) < 12800      # ids of the slice
    assert len(traffic_gen.prefill_buckets(a, 64)) <= 20
    assert max(len(x.prompt) + x.new_tokens for x in a) <= 2560 + 1024 <= 4096
    # a stream reserves about 1,350 tokens: 128 of them two thirds of the pool
    mean = sum(-(-(len(x.prompt) + x.new_tokens) // 64) for x in a) / len(a)
    assert 0.55 < SLOTS * mean / (BLOCKS - 1) < 0.8


# -------------------------------------------------------------------- costs
Row = collections.namedtuple("Row", "name t_start t_end attrs")


def view_with(family, rows=()):
    cfg = harness.read_json("configs", "deepseek-v2.json")
    facts = {**family.dims(cfg), "kv_bytes_per_element": 2,
             "window": (0.0, 40.0),
             "live_tokens": [(20.0, 100_000), (20.5, 140_000), (60.0, 9)]}
    return {"facts": facts, "trace_span": (19.0, 21.0), "family": family,
            "config": cfg, "peaks": harness.peaks_for("TPU v5 lite"),
            "program_spans": {"rows": [Row(*r) for r in rows],
                              "dropped_until": None}}


def step_rows():
    """``serving.step`` rows: two inside the capture, two inside the window
    only, one before it, and one as a program before PR 34 writes them."""
    attrs = lambda held, touched: {
        "n_active": 128, "emitted": 128, "routed_pairs": held,
        "pairs_elsewhere": 6 * 128 * 6 - held, "experts_touched": touched,
        "experts_idle": 120 - touched, "tokens_unrouted": 3,
        "blocks_in_use": 2700, "blocks_free": 1395}
    return [("serving.step", -1.0, -0.9, attrs(9, 9)),
            ("serving.step", 1.0, 1.1, attrs(576, 120)),
            ("serving.step", 2.0, 2.1, attrs(461, 90)),
            ("serving.step", 20.0, 20.1, attrs(576, 114)),
            ("serving.step", 20.5, 20.6, attrs(692, 118)),
            ("serving.step", 30.0, 30.1, {"n_active": 128}),
            ("serving.dispatch", 5.0, 5.01, {"ahead": True})]


def metric(view, name):
    spec = harness.read_json("layer_metrics", f"{name}.json")
    return harness.load_plugin("readers", spec["reader"]).read(
        view, **spec.get("params", {}))


def test_costs_read_the_capture(family, config):
    v = view_with(family, step_rows())
    # 240,000 live tokens in the capture, 7 layers
    flops, nbytes = family.costs["dsv2_mla_paged_attention"](v)
    assert flops == 240_000 * 7 * 278_528
    assert nbytes == 240_000 * TOKEN_BYTES == 2_150_400_000
    # what every step reads: all but the routed experts and the embedding
    dense = 2 * (4_483_671_040 - 6 * 20 * 23_592_960 - 12800 * 5120)
    assert family.dense_weight_bytes(config) == dense == 3_173_959_680
    v["trace"] = {"module_calls": {"jit_step": (0.04, 0.02),
                                   "jit_prefill": (0.3, 0.1)}}
    flops, total = family.costs["dsv2_decode_step"](
        v, module_match="jit_step")
    # two steps in the capture, (114 + 118) / 2 expert instances touched
    assert flops == 0.0
    assert total == 2 * (dense + 116 * 2 * 23_592_960) + nbytes
    # a program that records no such attribute: every held expert is priced
    old = view_with(family, [r for r in step_rows() if len(r[3]) < 3])
    old["trace"] = v["trace"]
    _, total = family.costs["dsv2_decode_step"](old, module_match="jit_step")
    assert total == 2 * 2 * (4_483_671_040 - 12800 * 5120) + nbytes


def test_every_new_metric_reads_a_recorded_fixture(family, config):
    v = view_with(family, step_rows())
    v["trace"] = {"window_s": 2.0,
                  "module_s": {"jit_step": 0.04, "jit_prefill": 0.9},
                  "module_calls": {"jit_step": (0.04, 0.02),
                                   "jit_prefill": (0.9, 0.05)},
                  "kernel_s": {"mla_paged_attention": 0.008,
                               "gmm": 0.5, "paged_attention": 9.0}}
    _, nbytes = family.costs["dsv2_mla_paged_attention"](v)
    assert metric(v, "kernels.dsv2.mla_paged_attention_roofline") == \
        pytest.approx(100 * max(240_000 * 7 * 278_528 / 197e12,
                                nbytes / 819e9) / 0.008)
    assert metric(v, "engine.expert_share") == pytest.approx(25.0)
    # the same work under the name it ran by until PR 43 (XLA's ragged dot)
    was = {**v, "trace": {**v["trace"], "kernel_s": {"ragged-dot-none": 0.5}}}
    assert metric(was, "engine.expert_share") == pytest.approx(25.0)
    _, need = family.costs["dsv2_decode_step"](v, module_match="jit_step")
    assert metric(v, "engine.decode_bandwidth_share.dsv2") == pytest.approx(
        100 * need / 819e9 / 0.04)
    assert metric(v, "engine.prefill_share.tput") == pytest.approx(45.0)
    pairs = 6 * 128 * 6
    assert metric(v, "moe.local_pair_share") == pytest.approx(
        100 * (576 + 461 + 576 + 692) / (4 * pairs))
    assert metric(v, "moe.experts_touched_share") == pytest.approx(
        100 * (120 + 90 + 114 + 118) / (4 * 120))
    assert metric(v, "serving.pool_fill_share") == pytest.approx(
        100 * 2700 / 4095)
    assert metric(v, "serving.ahead_share.tput") == 100.0
    v["counters"] = {"generated_tokens": 51_200, "decode_steps": 400}
    assert metric(v, "serving.tokens_per_step") == 128.0
    # a program whose spans carry none of it (the parent): nothing, never 0
    old = view_with(family, [r for r in step_rows() if len(r[3]) < 3])
    old["trace"] = {"window_s": 2.0, "module_s": {}, "module_calls": {},
                    "kernel_s": {"paged_attention": 9.0}}
    for name in ("kernels.dsv2.mla_paged_attention_roofline",
                 "engine.expert_share",
                 "engine.decode_bandwidth_share.dsv2",
                 "moe.local_pair_share",
                 "moe.experts_touched_share",
                 "serving.pool_fill_share"):
        assert metric(old, name) is None, name


def test_the_cell_reports_its_metrics_and_the_accepted_ones_it_must():
    # its kernel's and its decode step's costs are this cell's own; the
    # rest it shares with the other throughput cells
    own, shared = own_and_shared(BENCH, CELL["name"], "serve_tokens_per_s")
    assert own == {"kernels.dsv2.mla_paged_attention_roofline",
                   "engine.decode_bandwidth_share.dsv2"}
    assert shared == {
        "engine.expert_share", "engine.prefill_share.tput",
        "moe.local_pair_share", "moe.experts_touched_share",
        "serving.step_ms_p50.tput", "serving.host_ms_per_step_p50.tput",
        "serving.tokens_per_step", "serving.prefill_ms_p50.tput",
        "serving.queue_wait_ms_p50", "serving.state_reuse_share.tput",
        "serving.ahead_share.tput", "serving.pool_fill_share",
        "serving.pool_bound_share", "device.idle_share.tput"}
    assert [m["name"] for m in harness.cell_metrics(
        BENCH, "end_to_end", CELL["name"])] == ["serve_tokens_per_s",
                                                "setup_s"]
    assert CELL["chips"] == 1


# ----------------------------------------------------------- ISSUE 34's cell
def test_the_cell_through_its_runner_on_the_cpu():
    """ISSUE 34's closed backlog with the file's classes at a tiny size: a
    share of the experts and of the vocabulary, more requests than slots,
    the check (a live absorbed decode step against the expanded float32
    reference given the same share) holds and every block is recycled."""
    t = tiny_traffic()
    r = run.run_cell(BENCH, CELL, seed=SEED, seconds=2.0, trace=False,
                     config=TINY, traffic=t, log=lambda msg: None)
    json.dumps(r)
    assert set(r["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    c = r["details"]["counters"]
    assert c["completed"] == r["attempted"] and c["in_window_compiles"] == 0
    check = r["details"]["facts"]["check"]
    assert check["logit_err"] < 1e-3 and check["logit_rms_err"] < 1e-3
    assert check["blocks_recycled"] and check["paged_impl"] == "kernel"
    assert r["details"]["facts"]["kv_width"] == 128      # 40 values, a tile
    from deepspeed_tpu.monitor import spans
    t0, t1 = r["details"]["facts"]["window"]
    steps = [row.attrs for row in spans.recorder().rows("serving.step")
             if t0 <= row.t_start < t1 and row.attrs.get("emitted")]
    assert steps and all(
        a["routed_pairs"] + a["pairs_elsewhere"] == 6 * a["n_active"] * 2
        and a["experts_touched"] + a["experts_idle"] == 4 * 2 for a in steps)
    assert sum(a["pairs_elsewhere"] for a in steps) > 0


def tiny_traffic():
    t = copy.deepcopy(TRAFFIC)
    t["classes"][0]["prompt_tokens"].update(median=40, min=8, max=100,
                                            round_to=16, short_by=4)
    t["classes"][0]["output_tokens"].update(median=8, min=4, max=12)
    t["trace_seconds"] = 1
    t["pool_requests"], t["queue_depth"] = 12, 6
    t["serving"].update(batch_slots=4, block_size=16, num_blocks=40)
    t["dtype"] = "float32"
    t["check"].update(slots=3, logit_tol=1e-3, logit_rms_tol=1e-3)
    return t


# ------------------------------------- the comparison: a tie is not a fault
ROUTED = dict(TINY, n_group=4)     # 16 experts in 4 groups of 4; 4..7 held
# groups 0, 1 and 2 are kept (best .20, .15, .12 against .03); of their 12
# the six picked are 0, 4, 8, 5, 9 and 6 (.055, held); the seventh is 10 (.048)
SCORES = [.20, .02, .01, .01, .15, .10, .055, .01,
          .12, .09, .048, .01, .03, .03, .03, .03]
PICKED = [0, 4, 5, 6, 8, 9]


def routed_case():
    """Three rows, two expert layers, every token scored ``SCORES``; the
    program's routes the reference's own; logits that agree."""
    import numpy as np
    runner = harness.load_plugin("runners", "serve_backlog_routed")
    reference = harness.reference(ROUTED)
    scores = np.tile(np.asarray(SCORES, np.float32), (3, 2, 1))
    routes = np.tile(np.asarray(PICKED), (2, 3, 1))
    assert np.array_equal(routes, runner.route_ids(np.asarray(
        reference.picks(ROUTED, scores.reshape(6, 16))).reshape(3, 2, 16)))
    ref = np.random.RandomState(0).randn(3, 32).astype(np.float32)
    spec = dict(TRAFFIC["check"], logit_tol=1e-3, logit_rms_tol=1e-3,
                route_tie_margin=0.05, route_tied_rows_max=1)
    judge = lambda got, routes, scores=scores: runner.compare(
        spec, ROUTED, reference, got, ref, routes, scores)
    return judge, scores, routes, ref


def rerouted(routes, row, layer, out, new):
    moved = routes.copy()
    moved[layer, row] = sorted(new if e == out else e
                               for e in routes[layer, row])
    return moved


def test_the_comparison_sets_a_tie_aside_and_refuses_a_wrong_route():
    judge, scores, routes, ref = routed_case()
    ok, facts = judge(ref + 1e-5, routes)
    assert ok and (facts["rows_same_route"], facts["rows_tied"],
                   facts["rows_routed_wrong"]) == (3, 0, 0)
    assert facts["expert_set_differs"] == 0 and facts["logit_err"] < 1e-4
    # logits past the limit on a row that took the reference's route
    bad = ref.copy()
    bad[2] += 1.0
    assert not judge(bad, routes)[0]
    # row 2 gives the held expert 6 up for 12, of a group that was not kept
    # and far behind: routed WRONG, whatever the logits
    ok, facts = judge(ref, rerouted(routes, 2, 0, 6, 12))
    assert not ok and facts["rows_routed_wrong"] == 1
    assert facts["tie_margins_needed"] == [None]
    assert (facts["expert_set_differs"], facts["held_set_differs"]) == (1, 1)
    # for the seventh, 10: 13 % behind is no tie at a margin of 0.05 (which
    # covers 1 - 0.95 / 1.05 = 9.5 %) ...
    moved = rerouted(routes, 2, 0, 6, 10)
    ok, facts = judge(ref, moved)
    assert not ok and facts["tie_margins_needed"] == [0.07]
    # ... 1 % behind is: the row is set aside with its logits (a whole
    # expert's output away) and its later layers; the other rows decide
    close = scores.copy()
    close[2, 0, 10] = .055 * .99
    ok, facts = judge(bad, moved, close)
    assert ok and (facts["rows_same_route"], facts["rows_tied"]) == (2, 1)
    assert facts["tie_margins_needed"] == [0.01]
    assert facts["logit_err"] < 1e-4 < facts["logit_err_all_rows"]
    close[2, 0, 10] = .055 * .93
    ok, facts = judge(bad, moved, close)
    assert ok and facts["tie_margins_needed"] == [0.05]
    # two absent experts swapped: nothing this chip adds changes; judged
    ok, facts = judge(ref, rerouted(routes, 1, 1, 9, 10))
    assert ok and (facts["rows_same_route"], facts["held_set_differs"],
                   facts["expert_set_differs"]) == (3, 0, 1)
    # more ties than ``route_tied_rows_max``: refused
    close[0, 1, 10] = .055 * .995
    ok, facts = judge(ref, rerouted(moved, 0, 1, 6, 10), close)
    assert not ok and (facts["rows_tied"], facts["rows_routed_wrong"]) == (2, 0)


@pytest.mark.parametrize("fault", ["plain_top6", "no_scaling_factor",
                                   "held_dropped"])
def test_a_planted_routing_fault_comes_out_not_correct(fault, monkeypatch):
    """The cell's runner at a tiny size with a fault planted in the
    program's expert layer: the check that passes the sound program
    (``test_the_cell_through_its_runner_on_the_cpu``) refuses each."""
    from benchmark import control_routed
    from deepspeed_tpu.moe import dropless
    monkeypatch.setattr(dropless, "route", dropless.route)
    monkeypatch.setattr(dropless, "held_experts", dropless.held_experts)
    out = control_routed.read_fault(BENCH, CELL, SEED, fault,
                                    lambda msg: None, config=TINY,
                                    traffic=tiny_traffic())
    json.dumps(out)
    assert not out["correct"]
    f = out["facts"]
    assert f["served"] and f["blocks_recycled"]
    if fault == "plain_top6":
        assert f["rows_routed_wrong"] > 0
    else:
        assert f["rows_routed_wrong"] == 0 and f["logit_err"] > 1e-2


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
    """The model and the shapes of its weights and of the file's pool, on a
    described v5e; the latent kernel compiled, not interpreted."""
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
    from deepspeed_tpu.moe import dropless
    pla = importlib.import_module(
        "deepspeed_tpu.ops.transformer.paged_latent_attention")
    monkeypatch.setattr(pla, "_interpret", lambda: False)
    # the chip's branch of the grouped products, not the CPU's ragged_dot
    monkeypatch.setattr(dropless, "_on_tpu", lambda: True)
    args = [a if hasattr(a, "sharding") or not isinstance(a, tuple)
            else jax.ShapeDtypeStruct(*a, sharding=one_chip) for a in args]
    return jax.jit(fn, donate_argnums=donate).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


HBM = 15.75 * 2 ** 30 - 258 * 2 ** 20      # the compiler's own limit, less
#                                            what it reserves (its error text)


def test_the_decode_step_fits_a_v5e_and_reads_the_pool_in_place(
        published, one_chip, monkeypatch):
    """128 slots over tables of 64 blocks: the latent pool's (blocks of 64
    rows of 640) slices lower for Mosaic, the pool is written in place (no
    temporary of its size), no expert matrix and no up-projection is copied
    (a slice of the stacked experts in front of a grouped product would be
    315 MB a matrix), and weights plus pool, 11.3 GB, fit."""
    import re
    import jax.numpy as jnp
    model, params, pool = published
    pool_bytes = BLOCKS * 64 * TOKEN_BYTES
    args = (params, ((SLOTS,), jnp.int32), pool, ((SLOTS, 64), jnp.int32),
            ((SLOTS,), jnp.int32))
    step = lambda p, t, pl, tb, ln: model.decode_step_paged(p, t, pl, tb, ln)
    exe = compiled(one_chip, monkeypatch, step, args, donate=(2,))
    m = exe.memory_analysis()
    text = exe.as_text()
    assert "mla_paged_attention" in text
    # the experts' three products a layer (once in the text: the expert
    # layers are one loop's body) are the Pallas grouped matmul (PR 43), not
    # XLA's ragged-dot, which the CPU alone still runs
    assert len(re.findall(r"%gmm[.\d]* = ", text)) >= 3
    assert "ragged-dot" not in text
    assert text.count("tpu_custom_call") >= 2
    assert m.alias_size_in_bytes >= pool_bytes == 2_348_810_240
    assert m.temp_size_in_bytes < 64 * 2 ** 20
    assert 11.2e9 < m.argument_size_in_bytes < 11.4e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM


def test_the_longest_prefill_fits_a_v5e(published, one_chip, monkeypatch):
    import jax.numpy as jnp
    model, params, pool = published
    fn = lambda p, t, pl, bl, n: model.prefill_paged(p, t, pl, bl,
                                                     jnp.int32(0), n)
    exe = compiled(one_chip, monkeypatch, fn,
                   (params, ((1, 2560), jnp.int32), pool,
                    ((40,), jnp.int32), ((), jnp.int32)), donate=(2,))
    m = exe.memory_analysis()
    assert m.alias_size_in_bytes >= BLOCKS * 64 * TOKEN_BYTES
    # 16 heads' (2,560, 2,560) float32 scores at a time, not 128 (3.4 GB);
    # 15,360 token-expert pairs through the grouped products
    assert m.temp_size_in_bytes < 1.0 * 2 ** 30
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM
