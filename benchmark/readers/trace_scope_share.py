"""Share of the traced window, in percent, that the device spent in
operations ALL of whose scopes are among ``scopes`` (``jax.named_scope``s of
the program, docs/monitoring.md#device-scopes; ``unscoped`` and ``ambiguous``
name the operations no scope covers and those two executables of one module
name book differently), in XLA modules whose name contains ``modules`` where
given.  Self time by (module, instruction) of the capture under
``trace_root``, joined with the program's own map
(``benchmark/device_scopes.py``); window and device are those of
``device_idle_share`` (the device that was idle longest), so the scopes,
``unscoped``, ``ambiguous`` and the idle share add up to 100.  A fusion over several scopes counts only where every
one of them is asked for.  ``None`` where the program offers no map (any
commit before it kept one)."""

from benchmark import device_scopes


def read(view, scopes, modules=None, trace_root=".bench_out/trace"):
    maps = device_scopes.maps_of_program()
    if maps is None:
        return None
    got = device_scopes.booked_capture(
        trace_root, maps, n_devices=view["facts"].get("chips"))
    if got is None:
        return None
    return device_scopes.share(got, scopes, modules)
