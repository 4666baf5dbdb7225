"""Seconds of one phase of set-up (``before_program``, ``import``,
``engine_init``, ``trace_lower``, ``cache_load``, ``build``, ``warmup_run``,
``unattributed``): the time from the process's start to the window's start,
divided among the rows of the program's set-up store, which step rows never
displace.  The division is the program's own
(``deepspeed_tpu/monitor/startup.py::partition``), so the eight phases add up
to that interval.  Like ``setup_s`` it leaves out the one call in which the
TPU runtime started (``view["chip_reach_s"]``, taken by ``run.py``): the
clock starts that much later, and ``before_program`` is that much shorter.
Nothing is returned for a program without the store, or once the store has
refused a row."""


def store(view):
    """``(rows, whole, t_process_start)`` of the program's set-up store; a
    test hands them in as ``view["program_setup"]``.  ``None`` where the
    program has none."""
    given = view.get("program_setup")
    if given is not None:
        return given["rows"], given["whole"], given["t_process_start"]
    try:
        from deepspeed_tpu.monitor import spans
        rec = spans.recorder()
        rows, whole = rec.setup_rows()
        return rows, whole, rec.t_process_start
    except (ImportError, AttributeError):
        return None


def read(view, phase):
    got = store(view)
    if got is None:
        return None
    rows, whole, t_process_start = got
    if not whole:
        return None
    from deepspeed_tpu.monitor import startup
    t0, _ = view["facts"]["window"]
    t_process_start += view.get("chip_reach_s", 0.0)
    return startup.partition(rows, t_process_start, t0)[phase]
