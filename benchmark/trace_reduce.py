"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, time by operation and by XLA module, collective time that no compute
covers, and the longest idle gaps named by what the host was doing.

Read with nothing but ``jax.profiler.ProfileData``.  ``load`` turns the file
into plain rows; everything after works on rows, so the tests check the
arithmetic on a small recorded trace kept as rows (``tests/data``).

    python3 benchmark/trace_reduce.py --describe <file.xplane.pb>

prints the planes, lines and the most frequent event names: look at one real
trace by hand before trusting a name match.
"""

import collections
import json
import statistics
import sys

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"      # an async operation from its start to its done
SPAN_PREFIX = "bench."
# HLO collectives, as the op names in the trace begin
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


CUSTOM_CALL = "custom-call"


def load(path):
    """``{"devices": {plane: {line: [(name, start_ns, dur_ns, kind)]}},
    "spans": [(name, start_ns, dur_ns)]}`` from an xplane file.  ``kind`` is
    ``custom-call`` for a kernel (a Pallas kernel is one) and empty
    otherwise."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE, ASYNC_LINE):
                    continue
                rows = lines.setdefault(line.name, [])
                for ev in line.events:
                    kind = CUSTOM_CALL if " custom-call(" in ev.name else ""
                    rows.append((op_name(ev.name), float(ev.start_ns),
                                 float(ev.duration_ns), kind))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      float(ev.start_ns),
                                      float(ev.duration_ns)))
    return {"devices": devices, "spans": sorted(spans, key=lambda r: r[1])}


def op_name(text):
    """An operation's name from the trace's event name, which on a TPU is
    the whole HLO line: ``%fusion.180 = bf16[16,1,2048]{...} fusion(...)``
    -> ``fusion.180``."""
    return text.split(" = ", 1)[0].lstrip("%")


# ----------------------------------------------------------------- intervals
def union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The part of merged intervals ``a`` that merged intervals ``b`` do not
    cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def self_times(rows):
    """Exclusive time by event name for one line whose events nest (a
    ``while`` holds its body's operations): an event's self time is its
    duration minus its children's."""
    out = collections.Counter()
    stack = []                       # (end, name, child_time)
    for name, start, dur, _ in sorted(rows, key=lambda r: (r[1], -r[2])):
        while stack and stack[-1][0] <= start:
            end, n, child = stack.pop()
            out[n] -= child
        if stack:
            stack[-1][2] += dur
        out[name] += dur
        stack.append([start + dur, name, 0.0])
    for end, n, child in stack:
        out[n] -= child
    return out


def is_collective(name):
    return name.startswith(COLLECTIVES)


def base_name(name):
    """``fusion.123`` -> ``fusion``: operation names without their number,
    so the same operation of every layer adds up."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


# -------------------------------------------------------------------- reduce
def reduce_rows(trace, n_devices=None, top=10):
    """The summary the readers use.  Times in seconds."""
    devices = trace["devices"]
    names = sorted(devices)[:n_devices] if n_devices else sorted(devices)
    if not names:
        raise ValueError("the trace has no device plane: no operation ran "
                         "on a device while it was recorded")
    per = []
    t_lo, t_hi = float("inf"), float("-inf")
    for plane in names:
        ops = devices[plane].get(OPS_LINE, [])
        mods = devices[plane].get(MODULES_LINE, [])
        rows = ops or mods
        for _, s, d, _ in rows:
            t_lo, t_hi = min(t_lo, s), max(t_hi, s + d)
        busy = union([(s, s + d) for _, s, d, _ in rows])
        # a collective is in flight from its start to its done (the async
        # line) or for as long as its own operation runs (the ops line)
        coll = union([(s, s + d) for n, s, d, _ in
                      ops + devices[plane].get(ASYNC_LINE, [])
                      if is_collective(n)])
        selfs = self_times(ops)
        # compute = leaf operations that are not collectives
        compute = union([(s, s + d) for n, s, d, _ in ops
                         if not is_collective(n) and selfs[n] > 0
                         and not n.startswith(("while",
                                                           "conditional",
                                                           "call"))])
        by_module = collections.defaultdict(list)
        for n, s, d, _ in mods:
            by_module[base_module(n)].append(d)
        kernels = {n for n, _, _, kind in ops if kind == CUSTOM_CALL}
        per.append({"busy": busy, "collective": coll, "kernels": kernels,
                    "exposed": subtract(coll, compute), "self": selfs,
                    "modules": by_module})
    # the traced window: from the first to the last device event, the same
    # for every device
    spans = trace["spans"]
    window = (t_hi - t_lo) * 1e-9
    n = len(per)
    op_self = collections.Counter()
    modules = collections.Counter()
    for p in per:
        for k, v in p["self"].items():
            op_self[k] += v * 1e-9 / n
        for k, v in p["modules"].items():
            modules[k] += sum(v) * 1e-9 / n
    kernel_s = collections.Counter()
    for p in per:
        for k in p["kernels"]:
            kernel_s[base_name(k)] += p["self"][k] * 1e-9 / n
    busy_each = [total(p["busy"]) * 1e-9 for p in per]
    by_base = collections.Counter()
    for k, v in op_self.items():
        by_base[base_name(k)] += v
    worst = min(range(n), key=lambda i: busy_each[i])
    gaps = label_gaps(per[worst]["busy"], spans, t_lo, t_hi)
    return {
        "window_s": window,
        "busy_s": sum(busy_each) / n,
        "busy_s_by_device": busy_each,
        "idle_share_worst": 1.0 - min(busy_each) / window,
        "collective_s": sum(total(p["collective"]) for p in per) * 1e-9 / n,
        "collective_exposed_s":
            max(total(p["exposed"]) for p in per) * 1e-9,
        "op_self_s": dict(op_self),
        # kernels (custom calls) by name without the number
        "kernel_s": dict(kernel_s),
        "module_s": dict(modules),
        # (seconds, median call) by module: a call cut by the window's edge
        # counts by the part that is there
        "module_calls": {k: (v, statistics.median(
            d for p in per for d in p["modules"].get(k, ())) * 1e-9)
            for k, v in modules.items()},
        "breakdown": {
            "device_ops": [[k, v] for k, v in by_base.most_common(top)],
            "idle_gaps": [[k, v] for k, v in gaps.most_common(top)]},
    }


def base_module(name):
    """``jit_prefill(1234567)`` -> ``jit_prefill``."""
    return name.split("(", 1)[0]


def label_gaps(busy, spans, t_lo, t_hi):
    """Idle time on one device by the host span that was open when each gap
    began (``host_other`` where none was): seconds by label."""
    out = collections.Counter()
    edges = [t_lo] + [x for iv in busy for x in iv] + [t_hi]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        label = "host_other"
        for name, s, d in spans:
            if s <= g0 < s + d:
                label = name          # the innermost open span wins
        out[label] += (g1 - g0) * 1e-9
    return out


def reduce_file(path, n_devices=None):
    if path is None:
        raise ValueError("no trace was captured")
    return reduce_rows(load(path), n_devices=n_devices)


def describe(path, top=25):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            dur = collections.Counter()
            for e in events:
                dur[e.name] += e.duration_ns
            print(f"  LINE {line.name}: {len(events)} events")
            for name, ns in dur.most_common(top):
                print(f"    {ns / 1e6:10.3f} ms {names[name]:7d}x  {name}")
            if events:
                e = max(events, key=lambda e: e.duration_ns)
                print(f"    stats of the longest event: {list(e.stats)[:12]}")


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--describe":
        describe(sys.argv[2])
    elif len(sys.argv) == 3 and sys.argv[1] == "--summary":
        s = reduce_file(sys.argv[2])
        s.pop("op_self_s")
        print(json.dumps(s, indent=1))
    else:
        sys.exit(__doc__)
