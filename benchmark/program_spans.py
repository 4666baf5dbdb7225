"""What the PROGRAM recorded about itself, for the benchmark's readers: the
rows of its process-wide span recorder (``deepspeed_tpu/monitor/spans.py``),
and its ``ds.*`` annotations in a profiler capture, against which the
device's idle time is divided.

A program that has no recorder or writes no annotation (any commit before
the recorder came) gives nothing to read: every function here then returns
``None`` and the metric is left out of the line.

    python3 benchmark/program_spans.py --gaps <file.xplane.pb> [--root serving.step]

prints, for any cell's capture, the device's idle seconds by the program
span that was open: each direct child of the root span, the root alone, and
no program span at all.  The parts add up to the idle time of
``trace_reduce``'s ``idle_share_worst`` (same device, same window).
"""

import collections
import os
import sys

if __package__ in (None, ""):      # run as a script: find ``benchmark``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import harness, trace_reduce as tr

ANNOTATION_PREFIX = "ds."
ROOT_ONLY = "root only"
NO_SPAN = "no program span"


# ------------------------------------------------------------ recorder rows
def ring(view):
    """``(rows, dropped_until)`` of the program's span recorder: its rows,
    oldest first, and the end time of the newest row it has dropped (``None``
    if it has dropped none).  A test hands both in as
    ``view["program_spans"]``.  ``None`` where the program has no recorder."""
    given = view.get("program_spans")
    if given is not None:
        return given["rows"], given["dropped_until"]
    try:
        from deepspeed_tpu.monitor import spans
        rec = spans.recorder()
        return rec.rows(), rec.dropped_until
    except (ImportError, AttributeError):
        return None


def rows_from(view, t_from):
    """The recorder's rows if it still holds every row that ended at or
    after ``t_from`` (``None`` for "since the process began"); else
    ``None``."""
    got = ring(view)
    if got is None:
        return None
    rows, dropped_until = got
    if dropped_until is not None and (t_from is None
                                      or dropped_until >= t_from):
        return None
    return rows


# ------------------------------------------------ annotations in a capture
def load(path):
    """One pass over an xplane file: ``(trace, annotations)``.  ``trace`` is
    what ``idle_intervals`` needs of ``trace_reduce.load``'s rows, the
    device planes' operation and module lines as ``(name, start_ns, dur_ns,
    "")``; ``annotations`` are the program's ``ds.*`` host annotations as
    ``(name, start_ns, end_ns)``, names without the prefix, sorted by
    start."""
    from jax.profiler import ProfileData
    devices, annotations = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tr.DEVICE_PLANE):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (tr.OPS_LINE, tr.MODULES_LINE):
                    lines[line.name] = [
                        ("", float(ev.start_ns), float(ev.duration_ns), "")
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        annotations.append((
                            ev.name[len(ANNOTATION_PREFIX):],
                            float(ev.start_ns),
                            float(ev.start_ns + ev.duration_ns)))
    annotations.sort(key=lambda r: (r[1], -r[2]))
    return {"devices": devices}, annotations


def idle_intervals(trace, n_devices=None):
    """``(idle, t_lo, t_hi)`` as ``trace_reduce.reduce_rows`` takes them for
    ``idle_share_worst``: the window from the first to the last device
    event, and in it the intervals in which the device that was idle longest
    ran nothing.  Nanoseconds."""
    devices = trace["devices"]
    names = sorted(devices)[:n_devices] if n_devices else sorted(devices)
    t_lo, t_hi, busies = float("inf"), float("-inf"), []
    for plane in names:
        rows = (devices[plane].get(tr.OPS_LINE)
                or devices[plane].get(tr.MODULES_LINE, []))
        for _, s, d, _ in rows:
            t_lo, t_hi = min(t_lo, s), max(t_hi, s + d)
        busies.append(tr.union([(s, s + d) for _, s, d, _ in rows]))
    if not busies or t_hi <= t_lo:
        return None
    busy = min(busies, key=tr.total)
    return tr.subtract([[t_lo, t_hi]], busy), t_lo, t_hi


def label_intervals(annotations, root, t_lo, t_hi):
    """The window cut into labelled pieces that cover it exactly once: each
    direct child of a ``root`` span under its own name, ``ROOT_ONLY`` where
    a root is open and none of its children is, ``NO_SPAN`` elsewhere.
    ``{label: merged intervals}``."""
    roots = [(s, e) for n, s, e in annotations if n == root]
    by_label = collections.defaultdict(list)
    children = []
    for r0, r1 in roots:
        inside = [(n, s, e) for n, s, e in annotations
                  if n != root and r0 <= s and e <= r1]
        end = r0                    # sorted by start: a span that begins
        for n, s, e in inside:      # before ``end`` lies inside a sibling
            if s >= end:
                by_label[n].append((s, e))
                children.append((s, e))
                end = e
    window = [[t_lo, t_hi]]
    root_u = clip(tr.union(roots), t_lo, t_hi)
    child_u = clip(tr.union(children), t_lo, t_hi)
    out = {n: clip(tr.union(iv), t_lo, t_hi) for n, iv in by_label.items()}
    out[ROOT_ONLY] = tr.subtract(root_u, child_u)
    out[NO_SPAN] = tr.subtract(window, root_u)
    return out


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def divide_idle(idle, labelled):
    """Each idle interval divided among the labels it overlaps, by
    overlap: ``{label: idle ns}``.  The labels cover the window exactly
    once, so the parts add up to the idle time."""
    whole = tr.total(idle)
    return {label: whole - tr.total(tr.subtract(idle, iv))
            for label, iv in labelled.items()}


def gaps_table(trace, annotations, root, n_devices=None):
    """``{"window_s", "idle_s", "by_span": {label: seconds}}`` or ``None``
    where the capture holds no device event or no ``root`` annotation."""
    got = idle_intervals(trace, n_devices)
    if got is None or not any(n == root for n, _, _ in annotations):
        return None
    idle, t_lo, t_hi = got
    parts = divide_idle(idle, label_intervals(annotations, root, t_lo, t_hi))
    return {"window_s": (t_hi - t_lo) * 1e-9, "idle_s": tr.total(idle) * 1e-9,
            "by_span": {k: v * 1e-9 for k, v in parts.items()}}


_TABLES = {}       # (path, mtime, root, n_devices) -> table: five metrics
#                    of one run read one capture


def gaps_of_capture(trace_root, root, n_devices=None):
    """``gaps_table`` of the newest capture under ``trace_root`` (relative
    to the repository's root)."""
    from deepspeed_tpu.monitor.trace import newest_trace_artifact
    path = newest_trace_artifact(os.path.join(harness.ROOT, trace_root))
    if path is None or not path.endswith(".xplane.pb"):
        return None
    key = (path, os.path.getmtime(path), root, n_devices)
    if key not in _TABLES:
        _TABLES.clear()
        _TABLES[key] = gaps_table(*load(path), root, n_devices)
    return _TABLES[key]


def main(argv):
    if len(argv) not in (2, 4) or argv[0] != "--gaps" \
            or (len(argv) == 4 and argv[2] != "--root"):
        sys.exit(__doc__)
    trace, annotations = load(argv[1])
    roots = [argv[3]] if len(argv) == 4 else sorted(
        {n for n, _, _ in annotations if n.endswith(".step")})
    if not roots:
        sys.exit(f"{argv[1]} holds no ds.*.step annotation")
    for root in roots:
        table = gaps_table(trace, annotations, root)
        if table is None:
            continue
        w = table["window_s"]
        print(f"root ds.{root}: window {w:.6f} s, device idle "
              f"{table['idle_s']:.6f} s ({100 * table['idle_s'] / w:.2f} %)")
        for label, sec in sorted(table["by_span"].items(),
                                 key=lambda kv: -kv[1]):
            print(f"  {sec:10.6f} s  {100 * sec / w:6.2f} %  {label}")


if __name__ == "__main__":
    main(sys.argv[1:])
