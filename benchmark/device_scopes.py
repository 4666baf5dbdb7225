"""A capture's device time by the PROGRAM's own scopes.

A device event of a profiler capture is named by its HLO instruction
(``fusion.180``) and lies inside an ``XLA Modules`` event (``jit_step(<id>)``);
it carries no ``jax.named_scope``.  The program keeps, for every executable it
acquires, the map from instruction to scope
(``deepspeed_tpu.monitor.device_scopes()``, docs/monitoring.md#device-scopes).
(module, instruction) is the join: one pass over the capture, each operation
booked to the module event that holds it in time on its device, self times a
module (``trace_reduce.self_times``), then the map.

A program that offers no map (any commit before it came) gives nothing to
read: ``maps_of_program`` returns ``None`` and the metric is left out.

    python3 benchmark/device_scopes.py --table <file.xplane.pb> [--maps <file.json> | --aot <dir>]

prints, for any cell's capture, the device's seconds by scope, unscoped and
ambiguous apart, under each its five longest operations by name without the
number, and the sum with the idle share (``trace_reduce``'s window and
devices: the parts add up to 100 %).  The maps come from the file
``device_scopes()``'s result was written to (JSON), or from the AOT store
the run used (every entry's ``device_scopes.json``; by default
``compile_cache.aot_dir()``).
"""

import bisect
import collections
import json
import os
import sys

if __package__ in (None, ""):      # run as a script: find ``benchmark``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import harness, trace_reduce as tr

UNSCOPED = "unscoped"
AMBIGUOUS = "ambiguous"
SCOPES_FILE = "device_scopes.json"


# --------------------------------------------------------------- the maps
def maps_of_program():
    """``{module: {instruction: scope}}`` as the program of this process
    offers it, or ``None`` where it offers none."""
    try:
        from deepspeed_tpu.monitor import device_scopes
    except ImportError:
        return None
    return device_scopes() or None


def merge(maps, module, instructions):
    """One executable's map into ``maps``; same-named modules share a map
    and an instruction they book differently reads ``AMBIGUOUS`` (what the
    program's own registry does)."""
    have = maps.setdefault(module, {})
    for name, scope in instructions.items():
        scope = tuple(scope) if isinstance(scope, list) else scope
        if have.setdefault(name, scope) != scope:
            have[name] = AMBIGUOUS
    return maps


def maps_of_store(aot_dir):
    """The maps of every entry of an AOT store."""
    maps = {}
    for key in sorted(os.listdir(aot_dir)):
        path = os.path.join(aot_dir, key, SCOPES_FILE)
        if os.path.isfile(path):
            with open(path) as f:
                got = json.load(f)
            merge(maps, got["module"], got["instructions"])
    return maps


def maps_of_file(path):
    with open(path) as f:
        given = json.load(f)
    maps = {}
    for module, instructions in given.items():
        merge(maps, module, instructions)
    return maps


def labels(scope):
    """A map's value as a tuple of labels: a scope's name, a fusion's
    several, ``UNSCOPED`` for none (or an instruction the map has not),
    ``AMBIGUOUS``."""
    if not scope:
        return (UNSCOPED,)
    return (scope,) if isinstance(scope, str) else tuple(scope)


# ------------------------------------------------------------ the capture
def module_of(mods, starts, start, end):
    """The module event that holds an operation in time: the last one that
    began at or before it, or the next one where the operation reaches into
    it (a capture that began mid-module cuts both)."""
    i = bisect.bisect_right(starts, start) - 1
    if i >= 0 and start < mods[i][1] + mods[i][2]:
        return tr.base_module(mods[i][0])
    if i + 1 < len(mods) and mods[i + 1][1] < end:
        return tr.base_module(mods[i + 1][0])
    return ""


def self_seconds(trace, n_devices=None):
    """``({(module, operation): seconds}, window_s, idle_s)``: exclusive
    device time of every operation by the module it ran in, the window
    (first to last device event on the devices used) and the idle time in
    it, all three ON THE DEVICE THAT WAS IDLE LONGEST: the window and the
    device of ``trace_reduce.reduce_rows``'s ``idle_share_worst``, so that
    the operations and the idle time add up to the window on four chips
    too."""
    devices = trace["devices"]
    names = sorted(devices)[:n_devices] if n_devices else sorted(devices)
    if not names:
        raise ValueError("the trace has no device plane")
    t_lo, t_hi, per = float("inf"), float("-inf"), []
    for plane in names:
        ops = devices[plane].get(tr.OPS_LINE, [])
        mods = sorted(devices[plane].get(tr.MODULES_LINE, []),
                      key=lambda r: r[1])
        starts = [m[1] for m in mods]
        rows = ops or mods
        for _, s, d, _ in rows:
            t_lo, t_hi = min(t_lo, s), max(t_hi, s + d)
        busy = tr.total(tr.union([(s, s + d) for _, s, d, _ in rows]))
        per.append((busy, ops, mods, starts))
    busy, ops, mods, starts = min(per, key=lambda p: p[0])
    booked = [((module_of(mods, starts, s, s + d), name), s, d, kind)
              for name, s, d, kind in ops]
    window = (t_hi - t_lo) * 1e-9
    return ({key: ns * 1e-9 for key, ns in tr.self_times(booked).items()},
            window, window - busy * 1e-9)


def booked(trace, maps, n_devices=None):
    """``{"window_s", "idle_s", "rows": [(module, operation, labels,
    seconds)]}``: every operation's self time with the labels its module's
    map gives it."""
    selfs, window, idle = self_seconds(trace, n_devices)
    rows = [(module, op, labels(maps.get(module, {}).get(op)), sec)
            for (module, op), sec in selfs.items() if sec > 0]
    return {"window_s": window, "idle_s": idle, "rows": rows}


def table(trace, maps, n_devices=None, top=5):
    """``{"window_s", "idle_s", "by_scope": {label: seconds}, "longest":
    {label: [(operation without its number, seconds)]}}``; a fusion over
    several scopes stands under ``a+b``.  Scopes, unscoped, ambiguous and
    idle add up to the window."""
    got = booked(trace, maps, n_devices)
    by_scope = collections.Counter()
    ops = collections.defaultdict(collections.Counter)
    for _, op, labs, sec in got["rows"]:
        label = "+".join(labs)
        by_scope[label] += sec
        ops[label][tr.base_name(op)] += sec
    return {"window_s": got["window_s"], "idle_s": got["idle_s"],
            "by_scope": dict(by_scope),
            "longest": {k: v.most_common(top) for k, v in ops.items()}}


def share(got, scopes, modules=None):
    """Percent of the window in operations ALL of whose labels are in
    ``scopes``, in modules whose name contains ``modules`` where given."""
    seconds = sum(sec for module, _, labs, sec in got["rows"]
                  if all(l in scopes for l in labs)
                  and (modules is None or modules in module))
    return 100.0 * seconds / got["window_s"]


_BOOKED = {}       # (path, mtime, n_devices) -> booked: ten metrics of one
#                    run read one capture


def booked_capture(trace_root, maps, n_devices=None):
    """``booked`` of the newest capture under ``trace_root`` (relative to
    the repository's root), or ``None`` where there is none."""
    from deepspeed_tpu.monitor.trace import newest_trace_artifact
    path = newest_trace_artifact(os.path.join(harness.ROOT, trace_root))
    if path is None or not path.endswith(".xplane.pb"):
        return None
    key = (path, os.path.getmtime(path), n_devices)
    if key not in _BOOKED:
        _BOOKED.clear()
        _BOOKED[key] = booked(tr.load(path), maps, n_devices)
    return _BOOKED[key]


def print_table(path, maps, out=None):
    got = table(tr.load(path), maps)
    w = got["window_s"]
    print(f"window {w:.6f} s; {len(maps)} modules mapped", file=out)
    for label, sec in sorted(got["by_scope"].items(), key=lambda kv: -kv[1]):
        print(f"  {sec:10.6f} s  {100 * sec / w:6.2f} %  {label}", file=out)
        for op, s in got["longest"][label]:
            print(f"      {s:10.6f} s  {100 * s / w:6.2f} %  {op}", file=out)
    busy = sum(got["by_scope"].values())
    print(f"  {got['idle_s']:10.6f} s  {100 * got['idle_s'] / w:6.2f} %  idle "
          f"(the device that was idle longest: all of the above is its)",
          file=out)
    print(f"sum {100 * (busy + got['idle_s']) / w:.3f} % of the window",
          file=out)


def main(argv):
    if len(argv) not in (2, 4) or argv[0] != "--table" \
            or (len(argv) == 4 and argv[2] not in ("--maps", "--aot")):
        sys.exit(__doc__)
    if len(argv) == 4 and argv[2] == "--maps":
        maps = maps_of_file(argv[3])
    else:
        if len(argv) == 4:
            aot = argv[3]
        else:
            from deepspeed_tpu.runtime import compile_cache
            aot = compile_cache.aot_dir()
        maps = maps_of_store(aot)
    print_table(argv[1], maps)


if __name__ == "__main__":
    main(sys.argv[1:])
