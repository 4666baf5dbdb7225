"""The LongCat-Flash family's files (``configs/longcat-flash-chat.json``,
``families/longcat_flash.py``, ``reference/longcat_flash.py``) and its cell
(``traffic/serve_agent_longcat.json``, the ``*.longcat`` metric files, the
runner ``serve_backlog_zero_experts``): the parameter count against its closed
form and against the program's own shapes, the file against the catalog row,
the family's costs against numbers worked by hand, the runner's ``compare`` on
hand-made routes (an identity-for-absent swap is a difference, an
absent-for-absent swap is none), the cell through its runner at a tiny size on
the CPU, each planted fault through the same check, and the decode step at 160
slots and the 4,096 and 256 prefill buckets compiled for a described v5e at
the published widths with the file's pool of 4,608 blocks of 64.
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
CELL = harness.cell_by_name(BENCH, "serve_agent_longcat")
TRAFFIC = harness.load_traffic(CELL["traffic"])
ROW_BYTES = 640 * 2                        # a token, a sub-layer, as stored
TOKEN_BYTES = 8 * ROW_BYTES
BLOCKS, SLOTS = 4608, 160

# the catalog row's ``config`` (architectures.jsonl, line 37), typed again
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}
REDUCED = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384,
           "max_position_embeddings": 5120}

# a share of a tiny deployment: 4 of 16 real experts (ids 4..7) beside 8
# identity experts (ids 16..23), a quarter of 1,024 ids
TINY = {"model_type": "longcat_flash", "vocab_size": 256, "hidden_size": 64,
        "ffn_hidden_size": 160, "expert_ffn_hidden_size": 32,
        "num_layers": 2, "num_attention_heads": 4, "q_lora_rank": 48,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "n_routed_experts": 4,
        "zero_expert_num": 8, "zero_expert_type": "identity", "moe_topk": 6,
        "routed_scaling_factor": 6, "rms_norm_eps": 1e-5,
        "rope_theta": 10000, "max_position_embeddings": 256,
        "attention_method": "MLA", "attention_bias": False,
        "router_bias_std": 0.01,
        "published": {"n_routed_experts": 16, "vocab_size": 1024},
        "experts_held": [4, 4], "vocab_held": [0, 256]}


@pytest.fixture(scope="module")
def config():
    return harness.read_json("configs", "longcat-flash-chat.json")


@pytest.fixture(scope="module")
def family(config):
    return harness.family(config)


# ------------------------------------------------------------ the configuration
def test_the_file_is_the_catalog_row_but_for_the_four_cuts(config):
    assert config["reduced"] == list(REDUCED)
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert config["experts_held"] == [0, 16]
    assert config["vocab_held"] == [0, 16384]
    assert config["model_type"] == "longcat_flash"
    for key in ("typed_without_a_network", "model_type", "block_order",
                "identity_expert_input", "norm_topk_prob", "rope_pairing",
                "e_score_correction_bias", "weights", "mla_scales", "loss"):
        assert key in config["assumed"], key
    assert "2/sqrt(hidden_size)" in config["assumed"]["weights"]
    assert str(config["router_bias_std"]) in \
        config["assumed"]["e_score_correction_bias"]
    dsv2 = harness.read_json("configs", "deepseek-v2.json")
    assert config["assumed"]["rope_pairing"] == \
        dsv2["assumed"]["rope_pairing"]
    d = config["deployment"]
    assert "share of 32" in d and "No training cell" in d and "pipeline" in d
    assert f"{BLOCKS * 64 * TOKEN_BYTES:,}" == "3,019,898,880" and \
        "3,019,898,880" in d
    assert f"{config['parameters']:,}" in d
    entry = [c for c in BENCH["configs"] if c["name"] == CELL["config"]][0]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]


def test_parameters_match_the_closed_form_and_the_programs_shapes(config,
                                                                  family):
    mla = (9_437_184 + 18_874_368 + 3_538_944 + 8_388_608 + 50_331_648
           + 1_536 + 512)
    assert mla == 90_572_800
    assert mla - 2_048 == family.mla_matrix_params(config)
    assert family.dense_ffn_params(config) == 226_492_416
    expert = 3 * 6144 * 2048
    assert expert == family.expert_params(config) == 37_748_736
    assert family.router_width(config) == 768
    layer = (2 * (mla + 226_492_416 + 12_288) + 4_718_592 + 768
             + 16 * expert)
    assert layer == 1_242_854_144
    closed = 4 * layer + 2 * 16384 * 6144 + 6144
    assert closed == 5_172_749_312 == config["parameters"]
    assert family.parameters(config) == closed
    # beside its experts, a published layer: "about 637M" in the catalog
    assert layer - 16 * expert == 638_874_368
    import jax
    import jax.numpy as jnp
    model = family.build(config, jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == closed
    assert model.num_params() == closed
    c = model.config
    assert (c.n_layer, c.kv_layers, c.n_head, c.n_kv_head, c.max_seq,
            c.held, c.vocab_rows, c.n_routed_experts, c.router_width,
            c.router_bias_std) == (
        4, 8, 64, 1, 5120, (0, 16), (0, 16384), 512, 768, 0.0003)
    assert shapes["moe"]["router_w"].shape == (4, 6144, 768)
    assert shapes["moe"]["router_bias"].shape == (4, 768)
    assert shapes["moe"]["gate_w"].shape == (4, 16, 6144, 2048)
    assert shapes["attn"]["q_a_w"].shape == (8, 6144, 1536)
    assert shapes["dense"]["down_w"].shape == (8, 12288, 6144)
    assert model._mla.q_scale == 2.0
    assert model._mla.kv_scale == pytest.approx(12 ** 0.5)


def test_dims_what_a_token_multiplies_and_what_the_family_refuses(config,
                                                                  family):
    d = family.dims(config)
    assert (d["n_layer"], d["n_head"], d["n_kv_head"], d["head_dim"],
            d["kv_width"], d["vocab_size"], d["max_positions"]) == (
        8, 64, 1, 640, 640, 16384, 5120)
    mla, dense, expert = 90_570_752, 226_492_416, 37_748_736
    want = 4 * (2 * (mla + dense) + 768 * 6144 + 0.25 * expert) \
        + 16384 * 6144
    assert family.matmul_params_per_token(config) == want
    for key, value, word in [("tie_word_embeddings", True, "tie_word"),
                             ("norm_topk_prob", True, "norm_topk_prob"),
                             ("zero_expert_type", "constant",
                              "zero_expert_type"),
                             ("attention_method", "MHA", "attention_method"),
                             ("experts_held", [0, 8], "experts_held")]:
        with pytest.raises(ValueError, match=word):
            family.build({**config, key: value}, None)


def test_the_traffic_file_is_the_cell_issue_50_fixed():
    t = TRAFFIC
    assert (t["kind"], t["pool_requests"], t["queue_depth"],
            t["order_seed"]) == ("serve_backlog_zero_experts", 1024, 160, 50)
    tool, chat = t["classes"]
    assert tool["share"] == chat["share"] == 0.5
    assert tool["prompt_tokens"] == {
        "kind": "lognormal", "median": 2048, "sigma": 0.5, "min": 512,
        "max": 4096, "round_to": 256, "short_by": 16}
    assert tool["output_tokens"] == {
        "kind": "lognormal", "median": 128, "sigma": 0.5, "min": 32,
        "max": 384}
    assert chat["prompt_tokens"] == {
        "kind": "lognormal", "median": 512, "sigma": 0.6, "min": 256,
        "max": 2048, "round_to": 256, "short_by": 16}
    assert chat["output_tokens"] == {
        "kind": "lognormal", "median": 384, "sigma": 0.5, "min": 64,
        "max": 1024}
    for cls in (tool, chat):
        assert (cls["sampling"], cls["temperature"]) == ("alternate", 0.8)
    assert t["serving"] == {"batch_slots": SLOTS, "block_size": 64,
                            "kv_bits": 16, "num_blocks": BLOCKS}
    assert (t["dtype"], t["drain_limit_s"], t["trace_seconds"]) == (
        "bfloat16", 90, 3)
    assert t["check"] == {"slots": 32, "steps": 3, "logit_tol": 0.06,
                          "logit_rms_tol": 0.048, "route_tie_margin": 0.3,
                          "route_tied_rows_max": 29}
    assert f"{BLOCKS * 64 * TOKEN_BYTES:,}" in t["notes"]["serving"]
    for key in ("logit_tol", "logit_rms_tol", "route_tie_margin",
                "route_tied_rows_max", "controls", "spread", "kind",
                "check_slots"):
        assert key in t["notes"], key
    from benchmark import traffic_gen
    runner = harness.load_plugin("runners", t["kind"])
    a = runner.backlog(t, 1, 16384)
    b = runner.backlog(t, 2 ** 31 + 5, 16384)
    assert [(len(x.prompt), x.new_tokens, x.do_sample) for x in a] == \
        [(len(x.prompt), x.new_tokens, x.do_sample) for x in b]
    assert max(x.prompt.max() for x in a) < 16384      # ids of the slice
    assert len(traffic_gen.prefill_buckets(a, 64)) <= 16
    assert max(len(x.prompt) + x.new_tokens for x in a) <= 4096 + 1024 <= 5120
    # a stream reserves about 29 blocks: 160 of them the whole pool, so the
    # slots and the pool bind together
    mean = sum(-(-(len(x.prompt) + x.new_tokens) // 64) for x in a) / len(a)
    assert 0.95 < SLOTS * mean / (BLOCKS - 1) < 1.05


# -------------------------------------------------------------------- costs
Row = collections.namedtuple("Row", "name t_start t_end attrs")


def view_with(family, rows=()):
    cfg = harness.read_json("configs", "longcat-flash-chat.json")
    facts = {**family.dims(cfg), "kv_bytes_per_element": 2,
             "window": (0.0, 40.0),
             "live_tokens": [(20.0, 100_000), (20.5, 140_000), (60.0, 9)]}
    return {"facts": facts, "trace_span": (19.0, 21.0), "family": family,
            "config": cfg, "peaks": harness.peaks_for("TPU v5 lite"),
            "program_spans": {"rows": [Row(*r) for r in rows],
                              "dropped_until": None}}


def step_rows():
    """``serving.step`` rows: two inside the capture, two inside the window
    only, one before it, and one as a program without the attributes."""
    def attrs(held, zero, touched):
        return {"n_active": 160, "emitted": 160, "routed_pairs": held,
                "pairs_elsewhere": 12 * 160 * 4 - held - zero,
                "zero_pairs": zero, "experts_touched": touched,
                "experts_idle": 64 - touched, "tokens_unrouted": 30,
                "blocks_in_use": 4300, "blocks_free": 307}
    return [("serving.step", -1.0, -0.9, attrs(9, 9, 9)),
            ("serving.step", 1.0, 1.1, attrs(160, 2560, 60)),
            ("serving.step", 2.0, 2.1, attrs(150, 2400, 50)),
            ("serving.step", 20.0, 20.1, attrs(170, 2560, 58)),
            ("serving.step", 20.5, 20.6, attrs(160, 2720, 62)),
            ("serving.step", 30.0, 30.1, {"n_active": 160}),
            ("serving.dispatch", 5.0, 5.01, {"ahead": True})]


def metric(view, name):
    spec = harness.read_json("layer_metrics", f"{name}.json")
    return harness.load_plugin("readers", spec["reader"]).read(
        view, **spec.get("params", {}))


def test_costs_read_the_capture(family, config):
    v = view_with(family, step_rows())
    # 240,000 live tokens in the capture, 8 sub-layers
    flops, nbytes = family.costs["longcat_mla_paged_attention"](v)
    assert flops == 240_000 * 8 * 139_264
    assert nbytes == 240_000 * TOKEN_BYTES == 2_457_600_000
    assert 139_264 / ROW_BYTES == pytest.approx(108.8)
    # what every step reads: all but the routed experts and the embedding
    dense = 2 * (5_172_749_312 - 4 * 16 * 37_748_736 - 16384 * 6144)
    assert family.dense_weight_bytes(config) == dense == 5_312_333_824
    v["trace"] = {"module_calls": {"jit_step": (0.04, 0.02),
                                   "jit_prefill": (0.3, 0.1)}}
    flops, total = family.costs["longcat_decode_step"](
        v, module_match="jit_step")
    # two steps in the capture, (58 + 62) / 2 expert instances touched
    assert flops == 0.0
    assert total == 2 * (dense + 60 * 2 * 37_748_736) + nbytes
    # a program that records no such attribute: every held expert is priced
    old = view_with(family, [r for r in step_rows() if len(r[3]) < 3])
    old["trace"] = v["trace"]
    _, total = family.costs["longcat_decode_step"](old,
                                                   module_match="jit_step")
    assert total == 2 * 2 * (5_172_749_312 - 16384 * 6144) + nbytes


def test_every_new_metric_reads_a_recorded_fixture(family, config):
    v = view_with(family, step_rows())
    v["trace"] = {"window_s": 2.0,
                  "module_s": {"jit_step": 0.04, "jit_prefill": 0.9},
                  "module_calls": {"jit_step": (0.04, 0.02),
                                   "jit_prefill": (0.9, 0.05)},
                  "kernel_s": {"mla_paged_attention": 0.008, "gmm": 0.5}}
    _, nbytes = family.costs["longcat_mla_paged_attention"](v)
    # 109 FLOPs a byte: the bytes bound it
    assert metric(v, "kernels.longcat.mla_paged_attention_roofline") == \
        pytest.approx(100 * nbytes / 819e9 / 0.008)
    _, need = family.costs["longcat_decode_step"](v, module_match="jit_step")
    assert metric(v, "engine.decode_bandwidth_share.longcat") == \
        pytest.approx(100 * need / 819e9 / 0.04)
    pairs = 12 * 160 * 4
    assert metric(v, "moe.zero_pair_share.longcat") == pytest.approx(
        100 * (2560 + 2400 + 2560 + 2720) / (4 * pairs))
    # the shared readings leave the identity pairs out of their ratio
    assert metric(v, "moe.local_pair_share") == pytest.approx(100 * sum(
        h / (pairs - z) for h, z in [(160, 2560), (150, 2400), (170, 2560),
                                     (160, 2720)]) / 4)
    assert metric(v, "moe.experts_touched_share") == pytest.approx(
        100 * (60 + 50 + 58 + 62) / (4 * 64))
    assert metric(v, "engine.expert_share") == pytest.approx(25.0)
    assert metric(v, "serving.pool_fill_share") == pytest.approx(
        100 * 4300 / 4607)
    # a program whose spans carry none of it (the parent): nothing, never 0
    old = view_with(family, [r for r in step_rows() if len(r[3]) < 3])
    old["trace"] = {"window_s": 2.0, "module_s": {}, "module_calls": {},
                    "kernel_s": {"paged_attention": 9.0}}
    for name in ("kernels.longcat.mla_paged_attention_roofline",
                 "engine.decode_bandwidth_share.longcat",
                 "moe.zero_pair_share.longcat"):
        assert metric(old, name) is None, name
    # and another family's step rows, which carry no zero_pairs
    dsv2 = view_with(family, [(n, a, b, {k: x for k, x in at.items()
                                         if k != "zero_pairs"})
                              for n, a, b, at in step_rows()])
    assert metric(dsv2, "moe.zero_pair_share.longcat") is None


def test_the_cell_reports_its_metrics_and_the_accepted_ones_it_must():
    own, shared = own_and_shared(BENCH, CELL["name"], "serve_tokens_per_s")
    assert own == {"kernels.longcat.mla_paged_attention_roofline",
                   "engine.decode_bandwidth_share.longcat",
                   "moe.zero_pair_share.longcat"}
    dsv2 = own_and_shared(BENCH, "serve_batch_deepseek_v2",
                          "serve_tokens_per_s")[1]
    assert shared == dsv2
    assert [m["name"] for m in harness.cell_metrics(
        BENCH, "end_to_end", CELL["name"])] == ["serve_tokens_per_s",
                                                "setup_s"]
    assert CELL["chips"] == 1 and len(CELL["why"]) <= 200
    assert len(BENCH["workloads"]) == 12 and len(BENCH["per_layer"]) <= 82
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


# ------------------------------------- the comparison: which picks count here
# 16 real experts (4..7 held) and 8 identity experts (16..23); top-6
SCORES = [.20, .02, .01, .01, .15, .10, .01, .01,
          .01, .02, .055, .01, .01, .01, .01, .01,
          .12, .048, .01, .01, .01, .01, .01, .01]
PICKED = [0, 4, 5, 10, 16, 17]


def routed_case():
    """Three rows, two layers, every token scored ``SCORES``; the program's
    routes the reference's own; logits that agree."""
    import numpy as np
    runner = harness.load_plugin("runners", "serve_backlog_zero_experts")
    reference = harness.reference(TINY)
    scores = np.tile(np.asarray(SCORES, np.float32), (3, 2, 1))
    routes = np.tile(np.asarray(PICKED), (2, 3, 1))
    assert np.array_equal(routes, runner.route_ids(np.asarray(
        reference.picks(TINY, scores.reshape(6, 24))).reshape(3, 2, 24)))
    ref = np.random.RandomState(0).randn(3, 32).astype(np.float32)
    spec = dict(TRAFFIC["check"], logit_tol=1e-3, logit_rms_tol=1e-3,
                route_tie_margin=0.05, route_tied_rows_max=1)
    judge = lambda got, routes, scores=scores: runner.compare(
        spec, TINY, reference, got, ref, routes, scores)
    return judge, scores, routes, ref


def rerouted(routes, row, layer, out, new):
    moved = routes.copy()
    moved[layer, row] = sorted(new if e == out else e
                               for e in routes[layer, row])
    return moved


def test_an_identity_pick_counts_here_and_an_absent_one_does_not():
    judge, scores, routes, ref = routed_case()
    ok, facts = judge(ref + 1e-5, routes)
    assert ok and (facts["rows_same_route"], facts["rows_tied"],
                   facts["rows_routed_wrong"]) == (3, 0, 0)
    # two ABSENT experts swapped (10 for 8): nothing this chip adds changes
    ok, facts = judge(ref, rerouted(routes, 1, 1, 10, 8))
    assert ok and (facts["rows_same_route"], facts["held_set_differs"],
                   facts["zero_set_differs"],
                   facts["expert_set_differs"]) == (3, 0, 0, 1)
    # an IDENTITY expert given up for an absent one (17 for 8): this chip's
    # output moves by w x u; far behind, so ROUTED WRONG whatever the logits
    ok, facts = judge(ref, rerouted(routes, 2, 0, 17, 8))
    assert not ok and facts["rows_routed_wrong"] == 1
    assert (facts["held_set_differs"], facts["zero_set_differs"]) == (1, 1)
    assert facts["tie_margins_needed"] == [None]
    # ... and the parent's comparison, which counts held ids alone, sees
    # nothing there
    parent = harness.load_plugin("runners", "serve_backlog_routed")
    spec = dict(TRAFFIC["check"], logit_tol=1e-3, logit_rms_tol=1e-3)
    assert parent.compare(spec, TINY, harness.reference(TINY), ref, ref,
                          rerouted(routes, 2, 0, 17, 8), scores)[0]
    # the same swap with the absent one 1 % behind: a tie.  The reference
    # was sent to the program's picks, so the row's logits are held to the
    # limits like any other's (the parent sets such a row aside, logits and
    # all) ...
    bad = ref.copy()
    bad[2] += 1.0
    close = scores.copy()
    close[2, 0, 8] = .048 * .99
    tie = rerouted(routes, 2, 0, 17, 8)
    ok, facts = judge(ref + 1e-5, tie, close)
    assert ok and (facts["rows_same_route"], facts["rows_tied"]) == (2, 1)
    assert facts["tie_margins_needed"] == [0.01]
    assert facts["logit_err_tied_rows"][0] < 1e-4
    ok, facts = judge(bad, tie, close)
    assert not ok and (facts["rows_tied"], facts["rows_routed_wrong"]) == (1, 0)
    assert facts["logit_err_tied_rows"][0] > 0.1
    # ... and a tie is asked for in EVERY layer, its scores being those of a
    # stream that took the program's picks above: tied in layer 0 and far
    # off in layer 1 is routed wrong; tied in both is one tied row
    ok, facts = judge(ref, rerouted(tie, 2, 1, 17, 8), close)
    assert not ok and facts["tie_margins_needed"] == [0.01, None]
    close[2, 1, 8] = .048 * .99
    ok, facts = judge(ref, rerouted(tie, 2, 1, 17, 8), close)
    assert ok and (facts["rows_tied"], facts["tie_layers"]) == (1, [0, 1])
    # more rows differing than the file allows
    close[1, 1, 8] = .048 * .99
    ok, facts = judge(ref, rerouted(rerouted(tie, 2, 1, 17, 8), 1, 1, 17, 8),
                      close)
    assert not ok and (facts["rows_tied"], facts["rows_routed_wrong"]) == (2, 0)
    # one identity expert for another (17 for 18): the weight moves, so it
    # counts; a held expert for an absent one counts as in the parent
    assert not judge(ref, rerouted(routes, 0, 0, 17, 18))[0]
    ok, facts = judge(ref, rerouted(routes, 0, 1, 5, 8))
    assert not ok and (facts["held_set_differs"],
                       facts["zero_set_differs"]) == (1, 0)
    # logits past the limit on a row that took the reference's route
    assert not judge(bad, routes)[0]


def test_the_reference_sent_to_given_picks(family):
    """``logits_and_scores_at(..., forced=...)``: the compared token sent to
    its OWN picks is the plain forward; sent elsewhere in layer 0, layer 0's
    scores stand (they come before the pick), the stream below moves, and a
    row's picks reach no other row."""
    import jax.numpy as jnp
    import numpy as np
    runner = harness.load_plugin("runners", "serve_backlog_zero_experts")
    reference = harness.reference(TINY)
    params = harness.seeded_weights(family.build(TINY, jnp.float32), SEED)
    toks = jnp.asarray(np.random.RandomState(3).randint(0, 256, (2, 12)),
                       jnp.int32)
    last = jnp.asarray([11, 7])
    ref, x = reference.logits_and_scores_at(TINY, params, toks, last)
    own = np.moveaxis(runner.route_ids(np.asarray(
        reference.picks(TINY, x.reshape(4, 24))).reshape(2, 2, 24)), 0, 1)
    again, x_again = reference.logits_and_scores_at(
        TINY, params, toks, last, forced=jnp.asarray(own[:, :, ::-1]))
    np.testing.assert_allclose(again, ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(x_again, x, rtol=1e-5, atol=1e-7)
    # row 0, layer 0: its weakest identity pick for an identity expert it
    # did not pick
    zero = [e for e in own[0, 0] if e >= 16]
    other = [e for e in range(16, 24) if e not in zero]
    moved = own.copy()
    moved[0, 0] = sorted([e for e in own[0, 0] if e != zero[-1]] + other[:1])
    got, x_moved = reference.logits_and_scores_at(
        TINY, params, toks, last, forced=jnp.asarray(moved))
    np.testing.assert_allclose(x_moved[0, 0], x[0, 0], rtol=1e-6)
    assert float(jnp.abs(x_moved[0, 1] - x[0, 1]).max()) > 1e-6
    assert float(jnp.abs(got[0] - ref[0]).max()) > 1e-4
    np.testing.assert_allclose(got[1], ref[1], rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------- ISSUE 50's cell
def tiny_traffic():
    t = copy.deepcopy(TRAFFIC)
    t["classes"][0]["prompt_tokens"].update(median=60, min=16, max=100,
                                            round_to=16, short_by=4)
    t["classes"][0]["output_tokens"].update(median=6, min=4, max=10)
    t["classes"][1]["prompt_tokens"].update(median=24, min=8, max=64,
                                            round_to=16, short_by=4)
    t["classes"][1]["output_tokens"].update(median=10, min=4, max=14)
    t["trace_seconds"] = 1
    t["pool_requests"], t["queue_depth"] = 12, 6
    t["serving"].update(batch_slots=4, block_size=16, num_blocks=40)
    t["dtype"] = "float32"
    # float32 reads 2e-7; the faults that only nudge a weight or reorder two
    # additions read 2e-4 at these widths
    t["check"].update(slots=3, logit_tol=1e-5, logit_rms_tol=1e-5)
    return t


def test_the_cell_through_its_runner_on_the_cpu():
    """ISSUE 50's closed backlog with the file's two classes at a tiny size:
    a share of the experts and of the vocabulary, more requests than slots,
    the check (a live absorbed decode step against the expanded float32
    reference given the same share) holds and every block is recycled."""
    r = run.run_cell(BENCH, CELL, seed=SEED, seconds=2.0, trace=False,
                     config=TINY, traffic=tiny_traffic(),
                     log=lambda msg: None)
    json.dumps(r)
    assert set(r["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    c = r["details"]["counters"]
    assert c["completed"] == r["attempted"] and c["in_window_compiles"] == 0
    check = r["details"]["facts"]["check"]
    assert check["logit_err"] < 1e-5 and check["logit_rms_err"] < 1e-5
    assert check["rows_same_route"] == 3 and "zero_set_differs" in check
    assert check["blocks_recycled"] and check["paged_impl"] == "kernel"
    assert r["details"]["facts"]["kv_width"] == 128      # 40 values, a tile
    assert r["details"]["facts"]["n_layer"] == 4         # sub-layers
    from deepspeed_tpu.monitor import spans
    t0, t1 = r["details"]["facts"]["window"]
    steps = [row.attrs for row in spans.recorder().rows("serving.step")
             if t0 <= row.t_start < t1 and row.attrs.get("emitted")]
    assert steps and all(
        a["routed_pairs"] + a["pairs_elsewhere"] + a["zero_pairs"]
        == 6 * a["n_active"] * 2
        and a["experts_touched"] + a["experts_idle"] == 4 * 2 for a in steps)
    assert sum(a["pairs_elsewhere"] for a in steps) > 0
    assert sum(a["zero_pairs"] for a in steps) > 0


@pytest.mark.parametrize("fault", ["zero_dropped", "zero_reads_stream",
                                   "shortcut_joins_early", "no_lora_scales",
                                   "bias_in_weights", "none"])
def test_a_planted_fault_comes_out_not_correct(fault):
    """The cell's runner at a tiny size with a fault planted in the program:
    the check that passes the sound program (``none``) refuses each."""
    from benchmark import control_longcat
    out = control_longcat.read_fault(BENCH, CELL, SEED, fault,
                                     lambda msg: None, config=TINY,
                                     traffic=tiny_traffic())
    json.dumps(out)
    f = out["facts"]
    assert f["served"] and f["blocks_recycled"]
    assert out["correct"] == (fault == "none")
    if fault != "none":
        assert f["rows_routed_wrong"] > 0 or f["logit_err"] > 1e-4
    # nothing is left planted
    from deepspeed_tpu.models import longcat_flash
    from deepspeed_tpu.moe import dropless
    assert longcat_flash.swiglu.__module__.endswith("jamba")
    assert longcat_flash.LongcatFlash._moe.__name__ == "_moe"
    assert dropless.zero_experts.__module__ == dropless.__name__
    assert dropless.route.__module__ == dropless.__name__


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
    """160 slots over tables of 80 blocks (5,120 positions): the latent
    kernel takes 64 query rows a slot, the pool is written in place, no
    expert matrix, dense FFN or up-projection is copied, and weights plus
    pool, 13.4 GB, fit."""
    import re
    import jax.numpy as jnp
    model, params, pool = published
    pool_bytes = BLOCKS * 64 * TOKEN_BYTES
    args = (params, ((SLOTS,), jnp.int32), pool, ((SLOTS, 80), jnp.int32),
            ((SLOTS,), jnp.int32))
    step = lambda p, t, pl, tb, ln: model.decode_step_paged(p, t, pl, tb, ln)
    exe = compiled(one_chip, monkeypatch, step, args, donate=(2,))
    m = exe.memory_analysis()
    text = exe.as_text()
    assert "mla_paged_attention" in text
    assert len(re.findall(r"%gmm[.\d]* = ", text)) >= 3
    assert "ragged-dot" not in text
    assert text.count("tpu_custom_call") >= 2
    assert m.alias_size_in_bytes >= pool_bytes == 3_019_898_880
    assert m.temp_size_in_bytes < 96 * 2 ** 20
    assert 13.3e9 < m.argument_size_in_bytes < 13.5e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM


PREFLIGHT = 0.92 * 16.91e9      # what ``ServingEngine._preflight_gate`` lets
#                                 the decode step or the LARGEST bucket the
#                                 served positions allow (5,120) reach


@pytest.mark.parametrize("bucket", [5120, 4096, 256])
def test_a_prefill_bucket_fits_a_v5e(published, one_chip, monkeypatch,
                                     bucket):
    """The engine's preflight prices a bucket of all 5,120 served positions
    (no prompt of the cell is that long: its largest is 4,096).  16 heads'
    (T, T) float32 scores at a time, not 64 (4.3 GB at 4,096), and the
    expert layer in chunks of at most 2,048 tokens' pairs: whole, the 49,152
    pairs of a 4,096-token prompt were 2.4 GB of transients and the 5,120
    bucket 3.3 GB, which the preflight refused on the chip (PR 50's first
    call)."""
    import jax.numpy as jnp
    model, params, pool = published
    fn = lambda p, t, pl, bl, n: model.prefill_paged(p, t, pl, bl,
                                                     jnp.int32(0), n)
    exe = compiled(one_chip, monkeypatch, fn,
                   (params, ((1, bucket), jnp.int32), pool,
                    ((bucket // 64,), jnp.int32), ((), jnp.int32)),
                   donate=(2,))
    m = exe.memory_analysis()
    assert m.alias_size_in_bytes >= BLOCKS * 64 * TOKEN_BYTES
    assert m.temp_size_in_bytes < (1.7e9 if bucket > 256 else 0.15e9)
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < PREFLIGHT < HBM
