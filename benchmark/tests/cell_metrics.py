"""What every cell's own test asks of its per-layer metrics, in one place:
the cell reports every entry without a ``workloads`` list, every listed
entry moves the end-to-end metric the cell is judged on and leads to a data
file and a reader.  Returns the names that list this cell ALONE (the
readings that are its own: its kernels', its decode step's costs) and the
names it shares with other cells (one entry a reading, since PR 46), for the
caller to state whole."""

from benchmark import harness


def own_and_shared(bench, cell_name, moves):
    per_layer = {m["name"]: m for m in harness.cell_metrics(
        bench, "per_layer", cell_name)}
    for m in bench["per_layer"]:
        assert "workloads" in m or m["name"] in per_layer, m["name"]
    for name, m in per_layer.items():
        if "workloads" in m:
            assert m["moves"] == moves, name
        spec = harness.read_json("layer_metrics", f"{name}.json")
        assert callable(harness.load_plugin("readers", spec["reader"]).read)
    listed = {n for n, m in per_layer.items() if "workloads" in m}
    own = {n for n in listed if per_layer[n]["workloads"] == [cell_name]}
    return own, listed - own
