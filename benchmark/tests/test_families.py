"""A model family is files found by name: a configuration of another
``model_type`` runs every kind of runner with NEW files only (a family and a
reference beside the benchmark's own), and GPT-2, the first family, gives
the sizes and the parameter count the harness used before it had families.
"""

import os
import textwrap

import pytest

from benchmark import costs, harness, run
from benchmark.tests.test_runners_cpu import SEED, tiny_traffic

# gpt2-tiny under another architecture's key names (HF's Llama-style ones)
OTHER = {"model_type": "other", "hidden_size": 128, "num_hidden_layers": 4,
         "num_attention_heads": 4, "max_position_embeddings": 256,
         "vocab_size": 1024}

FAMILY = '''
    """A family the benchmark does not have, in files of its own.  The
    program can only run a GPT-2 block, so that is what it builds."""
    from benchmark.families import gpt2

    def as_gpt2(cfg):
        return {"n_embd": cfg["hidden_size"],
                "n_layer": cfg["num_hidden_layers"],
                "n_head": cfg["num_attention_heads"],
                "n_positions": cfg["max_position_embeddings"],
                "vocab_size": cfg["vocab_size"], "layer_norm_epsilon": 1e-5}

    def build(cfg, dtype, **extra):
        return gpt2.build(as_gpt2(cfg), dtype, **extra)

    def dims(cfg):
        return gpt2.dims(as_gpt2(cfg))

    def matmul_params_per_token(cfg):
        return gpt2.matmul_params_per_token(as_gpt2(cfg))
'''

REFERENCE = '''
    from benchmark.reference import gpt2

    def _called(name):      # the harness loads this file anew for each use
        with open(__file__ + ".calls", "a") as f:
            print(name, file=f)

    def _cfg(cfg):
        return {"n_embd": cfg["hidden_size"],
                "n_layer": cfg["num_hidden_layers"],
                "n_head": cfg["num_attention_heads"],
                "layer_norm_epsilon": 1e-5}

    def logits_at(cfg, params, tokens, positions):
        _called("logits_at")
        return gpt2.logits_at(_cfg(cfg), params, tokens, positions)

    def loss(cfg, params, batch):
        _called("loss")
        return gpt2.loss(_cfg(cfg), params, batch)
'''


@pytest.fixture
def bench_dir_with_another_family(tmp_path, monkeypatch):
    """A ``BENCH_DIR`` that holds the benchmark's runners, readers and data
    as they are (links) and two new files; no file of ``benchmark/`` is
    edited."""
    for folder in ("runners", "readers", "traffic", "layer_metrics"):
        os.symlink(os.path.join(harness.BENCH_DIR, folder),
                   tmp_path / folder)
    for folder, name, text in (("families", "other", FAMILY),
                               ("reference", "other", REFERENCE)):
        (tmp_path / folder).mkdir()
        (tmp_path / folder / f"{name}.py").write_text(textwrap.dedent(text))
    monkeypatch.setattr(harness, "BENCH_DIR", str(tmp_path))
    return tmp_path


@pytest.mark.parametrize("cell_name, seconds, compared", [
    ("serve_chat", 2.0, "logits_at"), ("serve_docs_offline", 2.0, "logits_at"),
    ("train_z1", 1.0, "loss")])
def test_another_model_type_runs_with_new_files_only(
        bench_dir_with_another_family, cell_name, seconds, compared):
    bench = harness.load_benchmark()
    cell = harness.cell_by_name(bench, cell_name)
    result = run.run_cell(bench, cell, seed=SEED, seconds=seconds,
                          trace=False, config=OTHER,
                          traffic=tiny_traffic(cell["traffic"]),
                          log=lambda msg: None)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    # the sizes came through the new family's ``dims``
    assert result["details"]["facts"]["d_model"] == 128
    assert result["details"]["facts"]["kv_width"] == 128
    # and what decided ``correct`` was the new family's reference
    calls = bench_dir_with_another_family / "reference" / "other.py.calls"
    assert compared in calls.read_text().split()


def test_the_reference_compared_with_is_the_new_familys(
        bench_dir_with_another_family):
    ref = harness.reference(OTHER)
    assert ref.__file__ == str(
        bench_dir_with_another_family / "reference" / "other.py")
    assert harness.family(OTHER).dims(OTHER)["max_positions"] == 256


def test_an_unknown_model_type_names_the_missing_file():
    with pytest.raises(SystemExit) as err:
        harness.family({"model_type": "mamba9"})
    assert os.path.join(harness.BENCH_DIR, "families", "mamba9.py") \
        in str(err.value)
    with pytest.raises(SystemExit) as err:
        harness.reference({"model_type": "mamba9"})
    assert os.path.join("reference", "mamba9.py") in str(err.value)


@pytest.mark.parametrize("name, dims, matmul_params", [
    ("gpt2-large",
     {"n_layer": 36, "n_head": 20, "n_kv_head": 20, "head_dim": 64,
      "d_model": 1280, "kv_width": 1280, "vocab_size": 50257,
      "max_positions": 1024}, 707_788_800 + 64_328_960),
    ("cerebras-gpt-1.3b",
     {"n_layer": 24, "n_head": 16, "n_kv_head": 16, "head_dim": 128,
      "d_model": 2048, "kv_width": 2048, "vocab_size": 50257,
      "max_positions": 2048}, 1_207_959_552 + 102_926_336)])
def test_gpt2_family_gives_the_sizes_the_harness_had(name, dims,
                                                     matmul_params):
    """What ``shape_facts`` and ``costs.matmul_params`` gave for the two
    configurations before the harness had families (``n_embd`` is now
    ``d_model``, and ``kv_width`` beside it: equal, GPT-2 is multi-head)."""
    bench = harness.load_benchmark()
    cfg = harness.load_config(bench, name)
    family = harness.family(cfg)
    assert family.__name__ == "benchmark.families.gpt2"
    assert family.dims(cfg) == dims
    assert family.matmul_params_per_token(cfg) == matmul_params \
        == costs.matmul_params(cfg["n_embd"], cfg["n_layer"],
                               cfg["vocab_size"])
    seq = 1024
    assert costs.train_flops_per_token_of(
        matmul_params, dims["n_head"] * dims["head_dim"], dims["n_layer"],
        seq) == costs.train_flops_per_token(
            cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"], seq)


def test_gpt2_family_refuses_what_the_program_cannot_run():
    bench = harness.load_benchmark()
    cfg = harness.load_config(bench, "gpt2-large")
    with pytest.raises(ValueError, match="n_inner"):
        harness.build_model({**cfg, "n_inner": 3 * cfg["n_embd"]}, None)
    with pytest.raises(ValueError, match="gelu_new"):
        harness.build_model({**cfg, "activation_function": "relu"}, None)
