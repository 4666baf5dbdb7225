"""Shared gauge math: device memory, peak FLOPS, batch token counts.

These helpers read ALREADY-AVAILABLE host state (backend memory stats,
compiled-executable analyses, host batch shapes) — never device values,
never anything that forces a sync.
"""

import os

import numpy as np

# Per-chip-generation nominal capability table (public datasheet
# numbers): bf16 peak FLOPS, HBM bandwidth, and aggregate one-direction
# ICI bandwidth per chip.  ONE table for the MFU gauge AND the roofline
# attribution (`analysis/roofline.py` / `ds_explain`) — the tool and the
# hand math cannot drift.  Keyed by a
# lowercased `device_kind` substring; matched top-down.
CHIP_TABLE = {
    "v5 lite":    {"peak_bf16_flops": 197e12, "hbm_gb_s": 819.0,
                   "ici_gb_s": 200.0},
    "v5e":        {"peak_bf16_flops": 197e12, "hbm_gb_s": 819.0,
                   "ici_gb_s": 200.0},
    "v5litepod":  {"peak_bf16_flops": 197e12, "hbm_gb_s": 819.0,
                   "ici_gb_s": 200.0},
    "v4":         {"peak_bf16_flops": 275e12, "hbm_gb_s": 1228.0,
                   "ici_gb_s": 300.0},
    "v5p":        {"peak_bf16_flops": 459e12, "hbm_gb_s": 2765.0,
                   "ici_gb_s": 600.0},
    "v6e":        {"peak_bf16_flops": 918e12, "hbm_gb_s": 1640.0,
                   "ici_gb_s": 448.0},
    "v6 lite":    {"peak_bf16_flops": 918e12, "hbm_gb_s": 1640.0,
                   "ici_gb_s": 448.0},
}
_CPU_NOMINAL = "v5e"     # the row non-TPU backends price against


def chip_specs(device_kind=None) -> dict:
    """The :data:`CHIP_TABLE` row for ``device_kind`` (default: the
    local backend's device), plus the matched kind under
    ``device_kind``.  A TPU whose kind matches no row is an ERROR, never
    priced as some other chip.  Non-TPU backends (CPU tests) get the
    v5e row flagged ``nominal`` — MFU/roofline fractions are then a
    relative series, not a hardware claim."""
    if device_kind is None:
        import jax
        dev = jax.devices()[0]
        device_kind, on_tpu = dev.device_kind, dev.platform == "tpu"
    else:
        on_tpu = "tpu" in str(device_kind).lower()
    kind = str(device_kind).lower()
    for key, row in CHIP_TABLE.items():
        if key in kind:
            return dict(row, device_kind=device_kind, matched=key)
    if on_tpu:
        raise ValueError(
            f"no CHIP_TABLE row for TPU device_kind {device_kind!r}: add "
            "its datasheet peaks to monitor/gauges.py before pricing "
            "anything against it")
    return dict(CHIP_TABLE[_CPU_NOMINAL], device_kind=device_kind,
                matched=_CPU_NOMINAL, nominal=True)


def peak_flops_per_chip() -> float:
    """bf16 peak per chip by TPU generation.  On non-TPU backends (CPU
    tests) the returned peak is nominal — MFU is then a relative
    series, not an absolute fraction."""
    return chip_specs()["peak_bf16_flops"]


def memory_stats() -> dict:
    """THE shared ``memory_stats()`` read site (raw backend dict).

    Every consumer — :func:`device_memory`, the serving HBM budget,
    ``runtime/utils.see_memory_usage``, ``utils/timer.memory_usage``,
    the autotuner's HBM probe — reads through here instead of each
    calling ``jax.devices()[0].memory_stats()`` itself.  The CPU backend
    reports none (``{}``): callers needing a *peak* fall back to the
    compiled executable's ``memory_analysis()`` projection
    (:func:`executable_peak_bytes` / ``engine.preflight_memory``),
    callers needing a *budget* to their own default.  On a TPU a failing
    read raises."""
    import jax
    return jax.local_devices()[0].memory_stats() or {}


def hbm_limit_bytes(default=None):
    """The backend's per-device memory budget (``bytes_limit``) — the
    shared denominator of every HBM preflight gate.  ``default`` where
    the backend reports none (CPU); on a TPU a missing limit is an
    error, or the gates would pass by never running."""
    limit = memory_stats().get("bytes_limit")
    if limit:
        return int(limit)
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "TPU backend reports no memory_stats()['bytes_limit']: the "
            "HBM preflight gates have nothing to check against")
    return default


def host_rss_bytes() -> int:
    """Current host resident-set bytes of this process (Linux
    ``/proc/self/statm``; 0 where unavailable) — the live host-memory
    gauge the memory ledger reconciles its attributions against."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return 0


def host_rss_hwm_bytes() -> int:
    """Host RSS high-water mark of this process via ``ru_maxrss``.

    Unit note (so the conversion stops being re-derived per call site):
    on **Linux** ``ru_maxrss`` is in **kilobytes** (KiB), on macOS it is
    in bytes — this helper returns BYTES on both.  The MAXPARAMS rungs'
    ``rss_hwm_gb`` figures are this reading divided by 2**30."""
    try:
        import resource
        import sys
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(rss) if sys.platform == "darwin" else int(rss) * 1024
    except Exception:
        return 0


def device_memory() -> dict:
    """Live device-memory gauges from the backend's ``memory_stats()``,
    or ``{}`` when the backend exposes none (CPU — callers fall back to
    the executable's ``memory_analysis()`` projection)."""
    stats = memory_stats()
    out = {}
    if stats.get("bytes_in_use") is not None:
        out["device_mem_in_use"] = int(stats["bytes_in_use"])
    if stats.get("peak_bytes_in_use") is not None:
        out["device_mem_peak"] = int(stats["peak_bytes_in_use"])
    return out


def tokens_in_batch(batch) -> int:
    """Approximate token count of one step batch: the LARGEST
    integer-dtype leaf with a sequence axis (``ndim >= 2``).  Largest,
    not the sum — a batch carrying separate (input_ids, labels) integer
    leaves of the same shape must count its tokens once, not twice.
    For LM batches shaped ``(gas, B, T)`` this is ``gas*B*T``; for
    regression data with no integer leaves it returns 0 and the caller
    reports samples/s instead of tokens/s."""
    import jax
    best = 0
    for leaf in jax.tree_util.tree_leaves(batch):
        dt = getattr(leaf, "dtype", None)
        shape = getattr(leaf, "shape", None)
        if dt is None or shape is None or len(shape) < 2:
            continue
        if np.issubdtype(np.dtype(dt), np.integer):
            best = max(best, int(np.prod(shape)))
    return best


def latest_executable(fn):
    """The MOST RECENTLY acquired live executable of a ``CachedStep``
    (dict insertion order), or None.  Per-program gauges price exactly
    one program: summing over every live signature would double-count a
    shape-polymorphic run (e.g. curriculum cropping) — the most recent
    signature is the one dispatching."""
    exes = getattr(fn, "_exes", None)
    if not exes:
        return None
    return next(reversed(list(exes.values())))[0]


def live_signature_count(fn) -> int:
    """How many argument signatures currently hold live executables —
    the cache-invalidation term for per-program gauge pricing (a new
    signature means the priced program may no longer be the one
    dispatching)."""
    return len(getattr(fn, "_exes", {}) or {})


def _cost_analysis(fn) -> dict:
    exe = latest_executable(fn)
    if exe is None:
        return {}
    try:
        ca = exe.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca if isinstance(ca, dict) else {}


def executable_flops(fn) -> int:
    """Compiled-step FLOPs from the dispatching executable's XLA cost
    analysis (the flops-profiler reading, shared here so the live MFU
    gauge and the profiler price the same program).  0 when no
    executable is live yet or the backend exposes no analysis."""
    try:
        return int(_cost_analysis(fn).get("flops", 0) or 0)
    except (AttributeError, TypeError, ValueError):
        return 0


def executable_bytes_accessed(fn) -> int:
    """Total memory-traffic bytes of the dispatching executable per XLA
    cost analysis (the ``"bytes accessed"`` reading) — the numerator of
    the HBM-roofline term in ``analysis/roofline.py``.  0 when no
    executable/analysis is available."""
    try:
        return int(_cost_analysis(fn).get("bytes accessed", 0) or 0)
    except (AttributeError, TypeError, ValueError):
        return 0


def executable_wire_report(fn) -> dict:
    """Per-executed-step wire accounting from the dispatching
    executable's HLO collective census (``analysis/comms.py``).  This
    prices the census once per program — the resulting bytes are
    constant per step for a fixed executable, which is exactly what
    makes them cheap to emit as a runtime series.  ``{}`` when no
    executable/HLO is available."""
    from ..analysis.comms import wire_report
    from ..analysis.jaxpr_audit import census_from_hlo_text
    exe = latest_executable(fn)
    if exe is None:
        return {}
    try:
        hlo = exe.runtime_executable().hlo_modules()[0].to_string()
    except Exception:
        return {}
    wr = wire_report(census_from_hlo_text(hlo))
    return {"wire_bytes_per_step": wr["wire_bytes"],
            "wire_logical_bytes_per_step": wr["logical_bytes"],
            "wire_quantized_bytes_per_step": wr["quantized_wire_bytes"]}


def executable_peak_bytes(fn) -> int:
    """Projected peak bytes of the dispatching executable's
    ``memory_analysis()`` — the preflight fallback HBM gauge for
    backends whose ``memory_stats()`` is unavailable.  0 when no
    analysis is exposed."""
    from ..runtime.compile_cache import executable_memory_analysis
    exe = latest_executable(fn)
    if exe is None:
        return 0
    ma = executable_memory_analysis(exe)
    return int(ma.get("peak_bytes", 0)) if ma else 0
