"""The Ouro family's files (``configs/ouro-2.6b.json``, ``families/ouro.py``,
``reference/ouro.py``) and its cell (``traffic/serve_batch_ouro.json``,
``runners/serve_backlog.py``, the ``*.ouro`` metric files and the two readers
they brought, shared with later cells since PR 46): the parameter count against its closed form and against the
program's own shapes, the family's costs against numbers worked by hand, the
cell through its runner at a tiny size on the CPU, the new readers on rows
made by hand, and the decode step and the longest prefill compiled for a
described v5e at the published widths with the file's pool of 320 blocks:
donated, as the engine and ``serve_backlog``'s check run the step, it fits;
not donated, as ``serving.check`` runs it, the compiler refuses it.
"""

import collections
import copy
import importlib
import json

import pytest

from benchmark import harness, run
from benchmark.tests.cell_metrics import own_and_shared
from benchmark.tests.test_runners_cpu import SEED

TINY = {"model_type": "ouro", "vocab_size": 512, "hidden_size": 128,
        "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 32, "intermediate_size": 256,
        "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
        "max_position_embeddings": 256, "total_ut_steps": 4,
        "early_exit_threshold": 1}
TOKEN_BYTES = 192 * 2 * 2048 * 2            # K and V, 192 layer-applications
BLOCKS = 320                                # ISSUE 32's pool: 8.05 GB
BENCH = harness.load_benchmark()
CELL = harness.cell_by_name(BENCH, "serve_batch_ouro")
TRAFFIC = harness.load_traffic(CELL["traffic"])


def test_the_traffic_file_is_the_cell_issue_32_fixed():
    t = TRAFFIC
    assert (t["kind"], t["pool_requests"], t["queue_depth"]) == (
        "serve_backlog", 128, 16)
    assert t["order_seed"] == 32            # the PR's number, as 27 and 28
    [cls] = t["classes"]
    assert cls["prompt_tokens"] == {
        "kind": "lognormal", "median": 192, "sigma": 0.7, "min": 32,
        "max": 1024, "round_to": 64, "short_by": 16}
    assert cls["output_tokens"] == {
        "kind": "lognormal", "median": 128, "sigma": 0.6, "min": 32,
        "max": 384}
    assert (cls["sampling"], cls["temperature"]) == ("alternate", 0.8)
    assert t["serving"] == {"batch_slots": 16, "block_size": 16,
                            "kv_bits": 16, "num_blocks": BLOCKS}
    assert (t["check"]["slots"], t["check"]["steps"]) == (4, 3)
    assert f"{BLOCKS * 16 * TOKEN_BYTES:,}" in t["notes"]["serving"]
    # every seed offers the same queue; the token ids follow the seed
    from benchmark.runners import serve_backlog
    a = serve_backlog.backlog(t, 1, 49152)
    b = serve_backlog.backlog(t, 2 ** 31 + 5, 49152)
    assert [(len(x.prompt), x.new_tokens, x.do_sample) for x in a] == \
        [(len(x.prompt), x.new_tokens, x.do_sample) for x in b]
    assert any((x.prompt[:8] != y.prompt[:8]).any() for x, y in zip(a, b))
    assert len({-(-len(x.prompt) // 16) for x in a}) <= 17


@pytest.fixture(scope="module")
def config():
    return harness.read_json("configs", "ouro-2.6b.json")


@pytest.fixture(scope="module")
def family(config):
    return harness.family(config)


# ------------------------------------------------------------ the configuration
def test_parameters_match_the_closed_form_and_the_programs_shapes(config,
                                                                  family):
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert layer == family.layer_matrix_params(config) == 51_380_224
    closed = 48 * (layer + 4 * 2048) + 2 * 49152 * 2048 + 2048 + 2049
    assert closed == 2_667_974_657 == config["parameters"]
    assert family.parameters(config) == closed
    import jax
    import jax.numpy as jnp
    model = family.build(config, jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == closed
    assert model.num_params() == closed
    c = model.config
    assert (c.n_layer, c.kv_layers, c.n_head, c.n_kv_head, c.head_dim,
            c.max_seq) == (48, 192, 16, 16, 128, 2048)


def test_the_file_is_the_catalog_row_but_for_the_served_context(config):
    assert config["reduced"] == ["max_position_embeddings"]
    assert config["max_position_embeddings"] == 2048
    assert "65,536" in config["assumed"]["max_position_embeddings"]
    for key, value in {"hidden_size": 2048, "intermediate_size": 5632,
                       "num_hidden_layers": 48, "num_attention_heads": 16,
                       "num_key_value_heads": 16, "head_dim": 128,
                       "vocab_size": 49152, "total_ut_steps": 4,
                       "early_exit_threshold": 1, "rope_theta": 1000000,
                       "rope_scaling": None, "rms_norm_eps": 1e-6,
                       "tie_word_embeddings": False, "hidden_act": "silu",
                       "max_window_layers": 48, "sliding_window": None,
                       "use_sliding_window": False}.items():
        assert config[key] == value, key
    assert config["layer_types"] == ["full_attention"] * 48
    for key in ("sandwich_norms", "norm_between_loops", "exit_gate",
                "biases", "weights", "typed_without_a_network"):
        assert key in config["assumed"], key
    assert "no training cell" in config["deployment"].lower()
    assert f"{BLOCKS} blocks" in config["deployment"]
    assert f"{BLOCKS * 16 * TOKEN_BYTES:,}" in config["deployment"]


def test_dims_and_what_the_family_refuses(config, family):
    d = family.dims(config)
    assert (d["n_layer"], d["n_head"], d["n_kv_head"], d["head_dim"],
            d["kv_width"], d["max_positions"]) == (48, 16, 16, 128, 2048, 2048)
    assert family.kv_layers(config) == 192
    # 4 loops x 48 layers' matrices, and the head
    assert family.matmul_params_per_token(config) == \
        192 * 51_380_224 + 49152 * 2048 == 9_965_666_304
    for key, value, word in [("tie_word_embeddings", True, "tie_word"),
                             ("early_exit_threshold", 0.9, "early_exit"),
                             ("rope_scaling", {"factor": 2.0}, "rope_scaling"),
                             ("layer_types", ["sliding_attention"] * 48,
                              "layer_types")]:
        with pytest.raises(ValueError, match=word):
            family.build({**config, key: value}, None)


# -------------------------------------------------------------------- costs
def view_with(family, rows=()):
    Row = collections.namedtuple("Row", "name t_start t_end attrs")
    cfg = harness.read_json("configs", "ouro-2.6b.json")
    facts = {**family.dims(cfg), "kv_bytes_per_element": 2,
             "window": (0.0, 40.0),
             "live_tokens": [(20.0, 2000), (20.5, 2166), (60.0, 9999)]}
    return {"facts": facts, "trace_span": (19.0, 21.0), "family": family,
            "config": cfg, "peaks": harness.peaks_for("TPU v5 lite"),
            "program_spans": {"rows": [Row(*r) for r in rows],
                              "dropped_until": None}}


def test_costs_read_the_capture(family, config):
    v = view_with(family)
    # 4,166 live tokens in the capture, 192 layer-applications
    flops, nbytes = family.costs["ouro_paged_attention"](v)
    assert nbytes == 4166 * TOKEN_BYTES == 6_552_551_424
    assert flops == 4166 * 192 * 2 * 2 * 2048
    # a decode step's weights: every layer once a loop, and the head
    weights = 2 * (192 * (51_380_224 + 4 * 2048) + 49152 * 2048)
    assert family.decode_step_weight_bytes(config) == weights \
        == 19_934_478_336
    # two steps of 0.05 s in the capture (module time over the median call)
    v["trace"] = {"module_calls": {"jit_step": (0.1, 0.05),
                                   "jit_prefill": (0.3, 0.1)}}
    flops, nbytes = family.costs["ouro_decode_step"](v, module_match="jit_step")
    assert flops == 0.0
    assert nbytes == 2 * weights + 4166 * TOKEN_BYTES


# ------------------------------------------------- the readers the cell brought
def pool_rows():
    """Four ``serving.step`` rows in the window, one before it, and one as a
    program before PR 32 writes them."""
    attrs = lambda used, waits: {"n_active": 11, "blocks_in_use": used,
                                 "blocks_free": 319 - used,
                                 "kv_tokens": used * 14,
                                 "waits_for_blocks": waits}
    return [("serving.step", -1.0, -0.9, attrs(10, False)),
            ("serving.step", 1.0, 1.1, attrs(319, True)),
            ("serving.step", 2.0, 2.1, attrs(300, True)),
            ("serving.step", 3.0, 3.1, attrs(290, False)),
            ("serving.step", 4.0, 4.1, attrs(0, False)),
            ("serving.step", 5.0, 5.1, {"n_active": 11}),
            ("serving.dispatch", 5.0, 5.01, {"ahead": True})]


def metric(view, name):
    spec = harness.read_json("layer_metrics", f"{name}.json")
    return harness.load_plugin("readers", spec["reader"]).read(
        view, **spec.get("params", {}))


def test_pool_fill_and_pool_bound_read_the_step_rows(family):
    v = view_with(family, pool_rows())
    assert metric(v, "serving.pool_fill_share") == pytest.approx(
        100 * (319 + 300 + 290 + 0) / (4 * 319))
    assert metric(v, "serving.pool_bound_share") == pytest.approx(50.0)
    assert metric(v, "serving.ahead_share.tput") == 100.0
    # a program whose step span has no such attributes: nothing, never zero
    old = view_with(family, [r for r in pool_rows() if len(r[3]) < 2])
    assert metric(old, "serving.pool_fill_share") is None
    assert metric(old, "serving.pool_bound_share") is None
    # a ring that dropped rows of the window: nothing
    v["program_spans"]["dropped_until"] = 0.5
    assert metric(v, "serving.pool_fill_share") is None


def test_the_decode_steps_share_of_the_bytes_it_must_move(family, config):
    v = view_with(family)
    v["trace"] = {"module_s": {"jit_step": 0.1, "jit_prefill": 0.3},
                  "module_calls": {"jit_step": (0.1, 0.05),
                                   "jit_prefill": (0.3, 0.1)},
                  "kernel_s": {"paged_attention": 0.02}}
    need = 2 * family.decode_step_weight_bytes(config) + 4166 * TOKEN_BYTES
    assert metric(v, "engine.decode_bandwidth_share.ouro") == pytest.approx(
        100 * need / 819e9 / 0.1)
    assert metric(v, "kernels.ouro.paged_attention_roofline") == \
        pytest.approx(100 * 4166 * TOKEN_BYTES / 819e9 / 0.02)
    v["trace"]["module_s"] = {"jit_prefill": 0.3}       # no decode module
    assert metric(v, "engine.decode_bandwidth_share.ouro") is None
    with pytest.raises(ValueError, match="unknown cost"):
        harness.load_plugin("readers", "module_roofline").read(
            v | {"trace": {"module_s": {"jit_step": 1.0}}},
            match="jit_step", cost="no_such_cost")


def test_the_cell_reports_its_metrics_and_the_accepted_ones_it_must():
    # its kernel's and its decode step's costs are this cell's own; the
    # rest it shares with the other throughput cells
    own, shared = own_and_shared(BENCH, CELL["name"], "serve_tokens_per_s")
    assert own == {"kernels.ouro.paged_attention_roofline",
                   "engine.decode_bandwidth_share.ouro"}
    assert shared == {
        "engine.prefill_share.tput", "serving.step_ms_p50.tput",
        "serving.host_ms_per_step_p50.tput", "serving.tokens_per_step",
        "serving.prefill_ms_p50.tput", "serving.queue_wait_ms_p50",
        "serving.state_reuse_share.tput", "serving.ahead_share.tput",
        "serving.pool_fill_share", "serving.pool_bound_share",
        "device.idle_share.tput"}
    pool_bound = [m for m in BENCH["per_layer"]
                  if m["name"] == "serving.pool_bound_share"]
    assert pool_bound[0]["better"] == "lower"
    assert [m["name"] for m in harness.cell_metrics(
        BENCH, "end_to_end", CELL["name"])] == ["serve_tokens_per_s",
                                                "setup_s"]


# ----------------------------------------------------------- ISSUE 32's cell
def test_the_cell_through_its_runner_on_the_cpu():
    """ISSUE 32's closed backlog at a tiny size, the pool small enough to
    bind: requests wait for blocks beside free slots, and the check (which
    donates the pool and hands it back) holds and recycles every block."""
    bench, cell = BENCH, CELL
    t = copy.deepcopy(TRAFFIC)
    t["classes"][0]["prompt_tokens"].update(median=40, min=8, max=100,
                                            round_to=16, short_by=4)
    t["classes"][0]["output_tokens"].update(median=8, min=4, max=12)
    t["trace_seconds"] = 1
    t["pool_requests"], t["queue_depth"] = 12, 4
    t["serving"].update(batch_slots=4, num_blocks=13)
    t["check"].update(logit_tol=5e-2, logit_rms_tol=5e-2)    # bfloat16, tiny
    r = run.run_cell(bench, cell, seed=SEED, seconds=2.0, trace=False,
                     config=TINY, traffic=t, log=lambda msg: None)
    json.dumps(r)
    assert set(r["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    c = r["details"]["counters"]
    assert c["completed"] == r["attempted"] and c["in_window_compiles"] == 0
    check = r["details"]["facts"]["check"]
    assert check["logit_err"] < 5e-2 and check["logit_rms_err"] < 5e-2
    assert check["blocks_recycled"] and check["paged_impl"] == "kernel"
    assert r["details"]["facts"]["kv_width"] == 128
    # the pool bound: some step ran with a free slot while the head waited
    from deepspeed_tpu.monitor import spans
    t0, t1 = r["details"]["facts"]["window"]
    steps = [row.attrs for row in spans.recorder().rows("serving.step")
             if t0 <= row.t_start < t1]
    assert any(a["waits_for_blocks"] and a["n_active"] < 4 for a in steps)


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
    described v5e; the paged kernel compiled, not interpreted."""
    import jax
    import jax.numpy as jnp
    model = family.build(config, jnp.bfloat16)
    blocks = BLOCKS
    on = lambda x, dtype=None: jax.ShapeDtypeStruct(
        x.shape, dtype or x.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda x: on(x, jnp.bfloat16),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = jax.tree_util.tree_map(on, jax.eval_shape(
        lambda: model.init_serving_state(16, blocks, 16)))
    return model, params, pool, blocks


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


def test_the_decode_step_fits_a_v5e_and_the_harness_check_does_not(
        published, one_chip, monkeypatch):
    """The decode step with the pool donated writes in place (no temporary
    of the pool's size, no copy of a weight stack): weights and ISSUE 32's
    320 blocks, 13.4 GB, fit; that is the engine's step and the one
    ``runners/serve_backlog.py::check`` compares.  ``serving.check`` runs
    the same step WITHOUT donating, so a second pool has to stand beside the
    first: 21.4 GB, which the compiler refuses.  That is why the cell's
    runner brings a check of its own."""
    import jax.numpy as jnp
    model, params, pool, blocks = published
    pool_bytes = blocks * 16 * TOKEN_BYTES
    args = (params, ((16,), jnp.int32), pool, ((16, 128), jnp.int32),
            ((16,), jnp.int32))
    step = lambda p, t, pl, tb, ln: model.decode_step_paged(p, t, pl, tb, ln)
    exe = compiled(one_chip, monkeypatch, step, args, donate=(2,))
    m = exe.memory_analysis()
    assert exe.as_text().count("tpu_custom_call") >= 1
    assert m.alias_size_in_bytes == pool_bytes
    assert m.temp_size_in_bytes < 64 * 2 ** 20
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM
    assert m.argument_size_in_bytes + pool_bytes > HBM
    with pytest.raises(Exception, match="(?i)memory|RESOURCE_EXHAUSTED"):
        compiled(one_chip, monkeypatch, lambda *a: step(*a)[0], args)


def test_the_longest_prefill_fits_a_v5e(published, one_chip, monkeypatch):
    import jax.numpy as jnp
    model, params, pool, blocks = published
    fn = lambda p, t, pl, bl, n: model.prefill_paged(p, t, pl, bl,
                                                     jnp.int32(0), n)
    exe = compiled(one_chip, monkeypatch, fn,
                   (params, ((1, 1024), jnp.int32), pool,
                    ((64,), jnp.int32), ((), jnp.int32)), donate=(2,))
    m = exe.memory_analysis()
    assert m.alias_size_in_bytes == blocks * 16 * TOKEN_BYTES
    # each layer-application's K/V goes into the pool as it is computed:
    # stacked first, 192 x 1,024 tokens would stand beside it (1.6 GB)
    assert m.temp_size_in_bytes < 512 * 2 ** 20
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM
