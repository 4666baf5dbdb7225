"""The Jamba family's files (``configs/jamba2-3b.json``, ``families/jamba.py``,
``reference/jamba.py``, the cell's traffic and metric files): the parameter
count against its closed form and against the program's own shapes, the
family's costs against numbers worked by hand, the new reader's arithmetic,
the cell through the open-loop runner at a tiny size on the CPU, and the new
kernels compiled for a described v5e at the published widths.

``test_benchmark_json.py::test_parameter_counts_match_the_published_sizes``
loops over every configuration with GPT-2's key names and cannot read this
family's file (PERF.md, Open questions): the count is held here instead.
"""

import collections
import copy
import importlib
import json

import pytest

from benchmark import harness, run
from benchmark.tests.cell_metrics import own_and_shared
from benchmark.tests.test_runners_cpu import SEED

TINY = {"model_type": "jamba", "vocab_size": 512, "hidden_size": 128,
        "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 1, "intermediate_size": 256,
        "attn_layer_period": 4, "attn_layer_offset": 1, "mamba_d_state": 16,
        "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 8,
        "rms_norm_eps": 1e-6, "max_position_embeddings": 256}
CELL = "serve_chat_burst_jamba"


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


@pytest.fixture(scope="module")
def config(bench):
    return harness.load_config(bench, "jamba2-3b")


@pytest.fixture(scope="module")
def family(config):
    return harness.family(config)


# ------------------------------------------------------------ the configuration
def test_parameters_match_the_closed_form_and_the_programs_shapes(config,
                                                                  family):
    mamba = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
             + 5120 * 16 + 5120 + 5120 * 2560 + 192)
    assert mamba == family.mamba_mixer_params(config) == 41_241_792
    mlp = 3 * 2560 * 8192
    attn = 2560 * 2560 + 2 * 2560 * 128 + 2560 * 2560
    assert attn == family.attention_mixer_params(config) == 13_762_560
    closed = (26 * (mamba + mlp + 5120) + 2 * (attn + mlp + 5120)
              + 65536 * 2560 + 2560)
    assert closed == 3_029_337_472 == config["parameters"]
    assert family.parameters(config) == closed
    import jax
    import jax.numpy as jnp
    model = family.build(config, jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == closed
    assert model.num_params() == closed
    c = model.config
    assert (c.n_layer, c.n_head, c.n_kv_head, c.head_dim, c.d_inner) == (
        28, 20, 1, 128, 5120)
    assert c.attn_layers == (7, 21)


def test_the_file_is_the_catalog_row_but_for_the_served_context(config, bench):
    entry = [c for c in bench["configs"] if c["name"] == "jamba2-3b"][0]
    assert entry["reduced"] == config["reduced"] == [
        "max_position_embeddings"]
    assert config["max_position_embeddings"] == 2048
    assert "262,144" in config["assumed"]["max_position_embeddings"]
    for key, value in {"hidden_size": 2560, "intermediate_size": 8192,
                       "num_hidden_layers": 28, "num_attention_heads": 20,
                       "num_key_value_heads": 1, "mamba_d_state": 16,
                       "mamba_d_conv": 4, "mamba_expand": 2,
                       "mamba_dt_rank": 160, "vocab_size": 65536,
                       "attn_layer_period": 14, "attn_layer_offset": 7,
                       "num_experts": 1, "rms_norm_eps": 1e-6}.items():
        assert config[key] == value, key


def test_dims_and_what_the_family_refuses(config, family):
    d = family.dims(config)
    assert (d["n_layer"], d["n_head"], d["n_kv_head"], d["head_dim"],
            d["kv_width"], d["max_positions"]) == (28, 20, 1, 128, 128, 2048)
    assert family.attention_layers(config) == [7, 21]
    # matrices only: 26 x 41,123,840 + 2 x 13,762,560 + 28 x 62,914,560
    #               + 167,772,160
    assert family.matmul_params_per_token(config) == 3_026_124_800
    with pytest.raises(ValueError, match="num_experts"):
        family.build({**config, "num_experts": 16}, None)


# -------------------------------------------------------------------- costs
def test_selective_scan_need(family):
    # one token, one layer, one call: 9 x 5120 x 16 FLOPs; bytes 4 x 5120 x 2
    # + 2 x 16 x 4 a token, and (2 x 5120 x 16 + 5120) x 4 a call
    assert family.selective_scan_need(1, 1, 5120, 16, 1) == (
        737_280, 41_088 + 675_840)
    # a 390-token prompt through 26 layers
    flops, nbytes = family.selective_scan_need(390, 26, 5120, 16, 1)
    assert flops == 9 * 390 * 26 * 5120 * 16 == 7_476_019_200
    assert nbytes == 26 * (390 * 41_088 + 675_840) == 434_204_160


def view_with(family, rows):
    Row = collections.namedtuple("Row", "name t_start t_end attrs")
    cfg = harness.load_config(harness.load_benchmark(), "jamba2-3b")
    facts = {**family.dims(cfg), "kv_bytes_per_element": 2,
             "live_tokens": [(20.0, 4000), (20.5, 4166), (60.0, 9999)]}
    return {"facts": facts, "trace_span": (19.0, 21.0), "family": family,
            "config": cfg,
            "program_spans": {"rows": [Row(*r) for r in rows],
                              "dropped_until": None}}


def test_costs_read_the_capture(family):
    rows = [("serving.prefill", 18.0, 18.1, {"scan_tokens": 999}),   # before
            ("serving.prefill", 19.5, 19.6, {"scan_tokens": 300,
                                             "pad_tokens": 4}),
            ("serving.step", 19.7, 19.8, None),
            ("serving.prefill", 20.2, 20.3, {"scan_tokens": 90}),
            ("serving.prefill", 21.5, 21.6, {"scan_tokens": 7})]      # after
    v = view_with(family, rows)
    assert family.prefills_in_capture(v) == (390, 2)
    assert family.costs["jamba_selective_scan"](v) == \
        family.selective_scan_need(390, 26, 5120, 16, 2)
    # 8,166 live tokens in the capture, 2 attention layers: K and V, 128
    # wide, 2 bytes; 2 matmuls x 2 FLOPs x 2,560 a token a layer
    flops, nbytes = family.costs["jamba_paged_attention"](v)
    assert nbytes == 8166 * 2 * 2 * 128 * 2 == 8_361_984
    assert flops == 8166 * 2 * 2 * 2 * 2560
    # no recorder rows (a program without them): nothing scanned
    v["program_spans"] = {"rows": [], "dropped_until": None}
    assert family.prefills_in_capture(v) == (0, 0)


def test_kernel_roofline_finds_the_familys_costs(family):
    reader = harness.load_plugin("readers", "kernel_roofline").read
    v = view_with(family, [("serving.prefill", 19.5, 19.6,
                            {"scan_tokens": 390})])
    v["peaks"] = harness.peaks_for("TPU v5 lite")
    v["trace"] = {"kernel_s": {"selective_scan": 0.004,
                               "paged_attention": 0.001}}
    spec = harness.read_json("layer_metrics",
                             "kernels.jamba.selective_scan_roofline.json")
    need = 434_204_160 / v["peaks"]["hbm_bytes_per_s"]
    assert reader(v, **spec["params"]) == pytest.approx(100 * need / 0.004)
    spec = harness.read_json("layer_metrics",
                             "kernels.jamba.paged_attention_roofline.json")
    need = 8_361_984 / v["peaks"]["hbm_bytes_per_s"]
    assert reader(v, **spec["params"]) == pytest.approx(100 * need / 0.001)
    v["trace"] = {"kernel_s": {}}
    assert reader(v, **spec["params"]) is None


def test_kernel_share_is_the_named_kernels_time_over_the_window():
    reader = harness.load_plugin("readers", "trace_kernel_share")
    spec = harness.read_json("layer_metrics", "engine.scan_share.jamba.json")
    v = {"trace": {"window_s": 3.0, "kernel_s": {"selective_scan": 0.06,
                                                  "paged_attention": 0.3}}}
    assert reader.read(v, **spec["params"]) == pytest.approx(2.0)
    # a program without the kernel (the parent): the metric is left out
    v["trace"]["kernel_s"] = {"paged_attention": 0.3}
    assert reader.read(v, **spec["params"]) is None


# ------------------------------------------------------------ the cell's files
def test_the_cells_traffic_is_serve_chats_mix_in_bursts(bench):
    cell = harness.cell_by_name(bench, CELL)
    assert (cell["config"], cell["chips"]) == ("jamba2-3b", 1)
    mine, chat = harness.load_traffic(CELL), harness.load_traffic("serve_chat")
    assert mine["classes"] == chat["classes"]
    for key in ("kind", "dtype", "drain_limit_s", "trace_seconds"):
        assert mine[key] == chat[key], key
    arrivals = mine["arrivals"]
    assert arrivals["process"] == "gamma" and arrivals["order_seed"] == 28
    assert arrivals["cv"] == 2.0 and arrivals["rate"] > 0
    assert mine["serving"] == {"batch_slots": 64, "block_size": 16,
                               "kv_bits": 16}
    check = mine["check"]
    assert (check["slots"], check["steps"]) == (4, 3)
    assert 0 < check["logit_rms_tol"] <= check["logit_tol"] < 0.15
    bound = [m for m in bench["end_to_end"] if m["name"] == "tpot_ms_p95"][0]
    assert CELL in bound["workloads"] and bound["bound"] == 0.05
    # three are the cell's own (its kernels' costs); fourteen it shares
    # with serve_chat, one entry a reading (PR 46)
    own, shared = own_and_shared(bench, CELL, "tpot_ms_p95")
    assert own == {
        "kernels.jamba.selective_scan_roofline",
        "kernels.jamba.paged_attention_roofline", "engine.scan_share.jamba"}
    assert len(shared) == 14


def test_the_cell_through_the_open_loop_runner_on_the_cpu(bench):
    cell = harness.cell_by_name(bench, CELL)
    t = copy.deepcopy(harness.load_traffic(CELL))
    t["arrivals"]["rate"] = 6.0
    t["classes"][0]["prompt_tokens"].update(median=40, min=8, max=150)
    t["classes"][0]["output_tokens"].update(median=10, min=4, max=24)
    t["serving"].update(batch_slots=4)
    t["trace_seconds"] = 1
    r = run.run_cell(bench, cell, seed=SEED, seconds=2.0, trace=False,
                     config=TINY, traffic=t, log=lambda msg: None)
    json.dumps(r)
    assert set(r["metrics"]) == {"tpot_ms_p95", "setup_s"}
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == 12
    c = r["details"]["counters"]
    assert c["completed"] == 12 and c["in_window_compiles"] == 0
    check = r["details"]["facts"]["check"]
    assert check["logit_err"] < 1e-2 and check["logit_rms_err"] < 2e-2
    assert check["blocks_recycled"] and check["paged_impl"] == "kernel"
    assert (r["details"]["facts"]["n_kv_head"],
            r["details"]["facts"]["kv_width"]) == (1, 32)


# --------------------------------------- the new kernels, compiled for a v5e
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


def compile_for(one_chip, fn, *shapes):
    import jax
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("seq", [48, 1520])
def test_selective_scan_compiles_at_the_published_widths(one_chip, seq):
    import jax.numpy as jnp
    from deepspeed_tpu.ops import selective_scan as ss
    di, n = 5120, 16
    exe = compile_for(
        one_chip,
        lambda *a: ss.selective_scan_kernel(*a, interpret=False),
        ((1, seq, di), jnp.bfloat16), ((1, seq, di), jnp.float32),
        ((n, di), jnp.float32), ((1, seq, n), jnp.float32),
        ((1, seq, n), jnp.float32), ((di,), jnp.float32),
        ((1, seq, di), jnp.bfloat16))
    assert exe.as_text().count("tpu_custom_call") == 1


def test_multi_query_paged_kernel_compiles(one_chip):
    """20 query heads over one K/V head of 128, 64 slots, 2,048 positions:
    the pool is 128 wide and the 20 heads ride the window axis."""
    import jax.numpy as jnp
    pa = importlib.import_module(
        "deepspeed_tpu.ops.transformer.paged_attention")
    slots, nb_max = 64, 128
    pool = ((2, slots * nb_max + 1, 16, 128), jnp.bfloat16)

    def fn(q, tables, lengths, k, v):
        return pa.paged_attention(q, {"k": k, "v": v}, tables, lengths, 1,
                                  mode="online", interpret=False)
    exe = compile_for(one_chip, fn, ((slots, 1, 20, 128), jnp.bfloat16),
                      ((slots, nb_max), jnp.int32), ((slots,), jnp.int32),
                      pool, pool)
    assert exe.as_text().count("tpu_custom_call") == 1


def test_the_serial_control_rounds_as_control_py_does():
    """``control_serial.coarser_in_place`` is ``control.coarser`` a layer at
    a time: the same numbers, whatever the leaf's depth of stacking."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import control, control_serial
    key = jax.random.PRNGKey(0)
    tree = {"wte": jax.random.normal(key, (64, 16), jnp.bfloat16),
            "mamba": {"in_w": jax.random.normal(key, (3, 16, 32),
                                                jnp.bfloat16),
                      "ln_in": jnp.ones((3, 16), jnp.bfloat16)},
            "lnf": jnp.ones((16,), jnp.bfloat16)}
    for precision in control.PRECISIONS:
        want = control.coarser(tree, precision)
        got = control_serial.coarser_in_place(
            {k: dict(v) if isinstance(v, dict) else v
             for k, v in tree.items()}, precision)
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
    # vectors stay as they are, matrices move
    assert (np.asarray(got["mamba"]["ln_in"], np.float32) == 1).all()
    assert not np.array_equal(np.asarray(got["wte"], np.float32),
                              np.asarray(tree["wte"], np.float32))
