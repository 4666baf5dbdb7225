"""Share of the traced window, in percent, in which the device ran nothing
AND the program span ``span`` was the open direct child of a ``root`` span
(``ds.*`` annotations of the capture under ``trace_root``).  Each idle
interval is divided among the spans it overlaps, by overlap; the device and
the window are those of ``device_idle_share``, so the shares of all children,
of the root alone and of no span at all add up to it."""

from benchmark import program_spans


def read(view, span, root, trace_root):
    table = program_spans.gaps_of_capture(
        trace_root, root, n_devices=view["facts"].get("chips"))
    if table is None:
        return None
    return 100.0 * table["by_span"].get(span, 0.0) / table["window_s"]
