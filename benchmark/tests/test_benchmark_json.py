"""``BENCHMARK.json`` against the letter of its contract, and the data files
every name in it must lead to."""

import json
import os
import re

import pytest

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def all_metrics(bench):
    return bench["end_to_end"] + bench["per_layer"]


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(harness.ROOT,
                                        "BENCHMARK.json")) <= 64 << 10
    # a full check with all 24 cells has to fit the driver's budget
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        group_names = [x["name"] for x in bench[group]]
        assert len(group_names) == len(set(group_names)), group
        names += group_names
    for w in bench["workloads"]:
        names += [w["config"], w["traffic"]]
    for c in bench["configs"]:
        names += c["reduced"]
        assert len(c["reduced"]) <= 16
    for n in names:
        assert NAME.match(n), n
    metric_names = [m["name"] for m in all_metrics(bench)]
    assert len(metric_names) == len(set(metric_names))
    for m in all_metrics(bench):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for text in ([w["why"] for w in bench["workloads"]]
                 + [c["why"] for c in bench["configs"]]
                 + [c["source"] for c in bench["configs"]]
                 + [m["layer"] for m in bench["per_layer"]]
                 + bench["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_entries_have_just_their_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_cells(bench):
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)
    used = {w["config"] for w in cells}
    assert used == {c["name"] for c in bench["configs"]}
    cell_names = {w["name"] for w in cells}
    for m in all_metrics(bench):
        assert set(m.get("workloads", cell_names)) <= cell_names, m["name"]


def test_every_cell_reports_what_it_must(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.1
    for w in bench["workloads"]:
        mine = [m["name"] for m in harness.cell_metrics(
            bench, "end_to_end", w["name"])]
        # bound to the set-up time and to at least one metric besides
        assert "setup_s" in mine and len(set(mine) - {"setup_s"}) >= 1, \
            w["name"]
        layers = harness.cell_metrics(bench, "per_layer", w["name"])
        assert layers, w["name"]
        for m in layers:
            # what a per-layer metric should move is reported in this cell
            assert m["moves"] in mine, (m["name"], w["name"])


def test_layer_names_are_perf_md_layers(bench):
    with open(os.path.join(harness.ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in bench["per_layer"]:
        assert m["layer"] in perf, m["layer"]


def test_every_name_leads_to_its_files(bench):
    files = set()
    for c in bench["configs"]:
        assert c["file"].startswith("benchmark/")
        assert c["file"] not in files
        files.add(c["file"])
        cfg = harness.load_config(bench, c["name"])
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert "assumed" in cfg and "deployment" in cfg
        # its ``model_type`` leads to a family and to a reference of the
        # same name, and the family can build it (the program can run it)
        family = harness.family(cfg)
        assert callable(family.build) and callable(
            family.matmul_params_per_token)
        assert set(family.dims(cfg)) == {
            "n_layer", "n_head", "n_kv_head", "head_dim", "d_model",
            "kv_width", "vocab_size", "max_positions"}
        ref = harness.reference(cfg)
        assert callable(ref.logits_at) and callable(ref.loss)
        import jax.numpy as jnp
        assert harness.build_model(cfg, jnp.bfloat16) is not None
    for w in bench["workloads"]:
        traffic = harness.load_traffic(w["traffic"])
        runner = harness.load_plugin("runners", traffic["kind"])
        assert callable(runner.run)
        assert traffic["mesh"] if traffic["kind"] == "train" else True
    for m in bench["per_layer"]:
        spec = harness.read_json("layer_metrics", f"{m['name']}.json")
        reader = harness.load_plugin("readers", spec["reader"])
        assert callable(reader.read)


def test_file_names_use_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for folder, _, names in os.walk(harness.BENCH_DIR):
        if "__pycache__" in folder:
            continue
        for n in names:
            rel = os.path.relpath(os.path.join(folder, n), harness.ROOT)
            assert ok.match(rel), rel


# ------------------------------------------------------- the per-layer table
def reading(entry):
    """What decides a per-layer value and where it is shown: the data
    file's reader and parameters, and the entry's own keys but its name and
    its cells."""
    spec = harness.read_json("layer_metrics", f"{entry['name']}.json")
    return json.dumps([spec["reader"], spec.get("params"), entry["unit"],
                       entry["better"], entry["source"], entry["layer"],
                       entry["moves"]], sort_keys=True)


# the one pair of equal readings that stands (PR 46): which of Trinity's TWO
# pools, beside the other cells' one
SAME_READING = {("serving.global_pool_fill_share.trinity",
                 "serving.pool_fill_share")}


@pytest.fixture(scope="module")
def frozen():
    """The parent's 128 entries as PR 46 found them, each with the name it
    has now."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "per_layer_renames.json")) as f:
        return json.load(f)


def test_the_table_holds_one_entry_a_reading(bench, frozen):
    """A cell that reads what an entry already reads is appended to its
    ``workloads`` and brings no entry of its own (128 is all there are):
    no two of the entries PR 46 left agree in reader, parameters, unit,
    ``better``, source, layer and ``moves`` but the named pair, and no
    cell, then or later, reports one reading under two names."""
    assert 1 <= len(bench["per_layer"]) <= 128
    merged = {old["now"] for old in frozen["parent"].values()}
    cells = [w["name"] for w in bench["workloads"]]
    by_reading = {}
    for m in bench["per_layer"]:
        by_reading.setdefault(reading(m), []).append(m)
    for same in by_reading.values():
        names = sorted(m["name"] for m in same)
        listed = [w for m in same for w in m.get("workloads", cells)]
        assert len(listed) == len(set(listed)), names
        of_pr46 = tuple(n for n in names if n in merged)
        assert len(of_pr46) <= 1 or of_pr46 in SAME_READING, names
    # every entry a data file, every data file an entry
    files = {n[:-len(".json")] for n in os.listdir(
        os.path.join(harness.BENCH_DIR, "layer_metrics"))}
    assert files == {m["name"] for m in bench["per_layer"]}
    assert all(m.get("workloads", True) for m in bench["per_layer"])


def test_no_reading_changed_when_the_entries_were_merged(bench, frozen):
    """PR 46 made one entry of the entries that read the same thing and
    retired two.  ``data/per_layer_renames.json`` freezes the parent's 128:
    each one's cells are listed under the name it has now, whose file gives
    the reader and the parameters the old file gave, so every cell reads
    what it read, under another name."""
    parent, widened = frozen["parent"], frozen["widened"]
    assert len(parent) == 128 and set(widened) <= set(parent)
    now = {m["name"]: m for m in bench["per_layer"]}
    retired = {n for n, old in parent.items() if old["now"] is None}
    assert retired == {"train.host_ms_per_step_p50", "cache.acquire_s"}
    assert not retired & set(now)
    assert len({old["now"] for old in parent.values()} - {None}) <= 76
    for name, old in parent.items():
        if name in retired:
            continue
        entry = now[old["now"]]
        if old["workloads"] is None:
            assert "workloads" not in entry, name
        else:
            assert set(old["workloads"]) <= set(entry["workloads"]), name
        spec = harness.read_json("layer_metrics", f"{old['now']}.json")
        assert spec["reader"] == old["reader"], name
        if name in widened:
            # the two that read nothing: the new names hold the old ones
            assert set(old["params"]["kernels"]) < set(
                spec["params"]["kernels"]), name
            assert {k: v for k, v in old["params"].items()
                    if k != "kernels"} == {
                k: v for k, v in spec["params"].items() if k != "kernels"}
        else:
            assert spec.get("params") == old["params"], name
    # of the parent's cells, none gained a reading but ISSUE 42's two that
    # had found no room
    had = {(old["now"], w) for old in parent.values()
           for w in old["workloads"] or ()}
    cells = {w for _, w in had}
    gained = {(m["name"], w) for m in bench["per_layer"]
              for w in m.get("workloads", ()) if w in cells} - had
    assert gained == {(n, w) for n, listed in frozen["joined"].items()
                      for w in listed}


def test_parameter_counts_match_the_published_sizes(bench):
    def gpt2(c):
        d, L = c["n_embd"], c["n_layer"]
        return (c["vocab_size"] * d + c["n_positions"] * d
                + L * (12 * d * d + 13 * d) + 2 * d)
    for c in bench["configs"]:
        cfg = harness.load_config(bench, c["name"])
        # a family's own closed form where it has one; GPT-2's otherwise
        count = getattr(harness.family(cfg), "parameters", gpt2)
        assert count(cfg) == cfg["parameters"], c["name"]
    assert harness.load_config(bench, "gpt2-large")["parameters"] \
        == 774_030_080
    assert harness.load_config(bench, "cerebras-gpt-1.3b")["parameters"] \
        == 1_315_723_264


def test_unknown_device_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
