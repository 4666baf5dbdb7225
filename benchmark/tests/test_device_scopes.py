"""A capture's device time by the program's scopes
(``benchmark/device_scopes.py``, ``readers/trace_scope_share.py``), on rows
worked by hand (``data/device_scopes_rows.json``: its ``doc`` says what ran
when)."""

import json
import os

import pytest

from benchmark import device_scopes as dsc, harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e-3


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(DATA, "device_scopes_rows.json")) as f:
        got = json.load(f)
    trace = {"devices": {
        plane: {line: [tuple(r) for r in rows] for line, rows in lines.items()}
        for plane, lines in got["devices"].items()}, "spans": []}
    maps = {}
    for module, instructions in got["maps"].items():
        dsc.merge(maps, module, instructions)
    return trace, maps


def test_two_modules_that_both_hold_a_fusion_3_are_booked_apart(fixture):
    selfs, window, idle = dsc.self_seconds(*fixture[:1])
    assert selfs[("jit_step", "fusion.3")] == pytest.approx(1 * MS)
    assert selfs[("jit_prefill_256", "fusion.3")] == pytest.approx(4 * MS)
    got = dsc.table(*fixture)
    assert got["by_scope"]["ssm.step"] == pytest.approx(3 * MS)
    assert got["by_scope"]["attn.window"] == pytest.approx(4 * MS)
    # a while's self time is what its body's operations leave of it
    assert selfs[("jit_step", "while.1")] == pytest.approx(1 * MS)


def test_an_operation_cut_by_the_edge_counts_by_the_part_that_is_there(
        fixture):
    got = dsc.table(*fixture)
    # 1 ms of the first fusion.2 is in the capture, and the whole second
    assert got["by_scope"]["lm_head+sentinel"] == pytest.approx(3 * MS)
    assert got["longest"]["lm_head+sentinel"] == [
        ("fusion", pytest.approx(3 * MS))]


def test_scopes_unscoped_ambiguous_and_idle_are_the_window(fixture):
    got = dsc.table(*fixture)
    assert got["window_s"] == pytest.approx(20 * MS)
    assert got["idle_s"] == pytest.approx(4 * MS)
    # while.1's own millisecond, copy.5, and jit_make's fusion.9 (a module
    # the program keeps no map of)
    assert got["by_scope"][dsc.UNSCOPED] == pytest.approx(3 * MS)
    assert got["by_scope"][dsc.AMBIGUOUS] == pytest.approx(1 * MS)
    assert sum(got["by_scope"].values()) + got["idle_s"] \
        == pytest.approx(got["window_s"])


@pytest.mark.parametrize("scopes,modules,percent", [
    (["ssm.step"], None, 15.0),
    (["attn.window"], "prefill", 20.0),
    (["attn.window"], "jit_step", 0.0),
    (["lm_head", "sentinel"], None, 15.0),    # the fusion over both
    (["lm_head"], None, 0.0),                 # not split: neither alone
    (["moe.route"], None, 10.0),
    (["unscoped", "ambiguous"], None, 20.0),
])
def test_share_counts_operations_all_of_whose_scopes_are_asked_for(
        fixture, scopes, modules, percent):
    got = dsc.booked(*fixture)
    assert dsc.share(got, scopes, modules) == pytest.approx(percent)


def test_on_several_devices_the_one_that_was_idle_longest_is_booked(fixture):
    """As ``device_idle_share``: the parts then add up to the window, which
    is every device's (first to last event of any)."""
    trace, maps = fixture
    one = trace["devices"]["/device:TPU:0"]
    busier = {"XLA Ops": [("fusion.2", 0, 22e6, "")],
              "XLA Modules": [("jit_step(11)", 0, 22e6, "")]}
    both = {"devices": {"/device:TPU:0": one, "/device:TPU:1": busier},
            "spans": []}
    got = dsc.table(both, maps)
    assert got["window_s"] == pytest.approx(22 * MS)
    assert got["idle_s"] == pytest.approx(6 * MS)
    assert got["by_scope"]["ssm.step"] == pytest.approx(3 * MS)
    assert sum(got["by_scope"].values()) + got["idle_s"] \
        == pytest.approx(got["window_s"])
    only = dsc.table(both, maps, n_devices=1)
    assert only["window_s"] == pytest.approx(20 * MS)


def test_the_reader_returns_none_without_a_map(monkeypatch):
    read = harness.load_plugin("readers", "trace_scope_share").read
    monkeypatch.setattr(dsc, "maps_of_program", lambda: None)
    assert read({"facts": {}}, scopes=["ssm.step"]) is None
    # a program that kept maps but captured nothing gives nothing either
    monkeypatch.setattr(dsc, "maps_of_program", lambda: {"jit_step": {}})
    assert read({"facts": {}}, scopes=["ssm.step"],
                trace_root="benchmark/tests/data/no_such_dir") is None


def test_a_program_that_acquired_nothing_offers_no_map(monkeypatch):
    from deepspeed_tpu.monitor import scope_maps
    monkeypatch.setattr(scope_maps, "_MAPS", {})
    monkeypatch.setattr(scope_maps, "_PENDING", [])
    assert dsc.maps_of_program() is None


def test_maps_of_a_store_and_of_a_file(tmp_path):
    for key, module, ins in (("a", "jit_step", {"fusion.3": "ssm.step"}),
                             ("b", "jit_step", {"fusion.3": "moe.route",
                                                "fusion.4": ["x", "y"]})):
        os.makedirs(tmp_path / key)
        with open(tmp_path / key / dsc.SCOPES_FILE, "w") as f:
            json.dump({"module": module, "instructions": ins}, f)
    os.makedirs(tmp_path / "older_entry")
    want = {"jit_step": {"fusion.3": dsc.AMBIGUOUS, "fusion.4": ("x", "y")}}
    assert dsc.maps_of_store(str(tmp_path)) == want
    with open(tmp_path / "maps.json", "w") as f:
        json.dump({"jit_step": {"fusion.4": ["x", "y"]}}, f)
    assert dsc.maps_of_file(str(tmp_path / "maps.json")) == {
        "jit_step": {"fusion.4": ("x", "y")}}


def test_the_table_prints_and_adds_up(fixture, tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(dsc.tr, "load", lambda path: fixture[0])
    dsc.print_table("x.xplane.pb", fixture[1])
    out = capsys.readouterr().out
    assert "sum 100.000 % of the window" in out
    assert "ssm.step" in out and "custom-call" in out
