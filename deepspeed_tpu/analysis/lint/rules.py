"""Repo-specific lint rules (tracing safety + IO hygiene).

Rule catalog (see ``docs/static-analysis.md``):

  DSTPU001  bare ``except:``                                       (error)
  DSTPU002  silently swallowed OSError (``except OSError: pass``)  (error)
  DSTPU101  host-impure call inside a jit-traced function:
            ``time.time()``, ``np.random.*``, stdlib ``random.*``,
            ``global`` mutation — all evaluate ONCE at trace time and
            bake a stale value into every step                      (error)
  DSTPU102  raw ``jax.lax`` collective outside
            ``parallel/collectives.py`` — scheduled collectives go
            through the one reviewed wrapper layer                  (error)
  DSTPU103  traced-value materialization inside a jit-traced
            function: ``float()``, ``np.asarray()``/``np.array()``,
            ``jax.device_get()``, ``.item()`` — a host sync (or a
            tracer error) in the hot path                           (error)
  DSTPU104  ad-hoc metric emission (``print``/direct ``json.dump``)
            in runtime/inference code — metrics go through the
            monitor bus (one schema) or the logger; deliberate
            contractual outputs carry per-site suppressions         (error)
"""

import ast

from . import Rule, register

JIT_WRAPPERS = {"jit", "pjit", "shard_map", "pallas_call"}

LAX_COLLECTIVES = {"psum", "psum_scatter", "pmean", "pmax", "pmin",
                   "ppermute", "pshuffle", "all_gather", "all_to_all",
                   "pbroadcast"}

_HOST_IMPURE_EXACT = {
    "time.time", "time.perf_counter", "time.monotonic",
    "time.process_time", "time.time_ns", "time.perf_counter_ns",
}
_HOST_IMPURE_PREFIXES = ("np.random.", "numpy.random.", "random.")

_MATERIALIZERS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
                  "jax.device_get"}


def _dotted(node):
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _terminal(node):
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _jit_traced_functions(tree):
    """Function/Lambda nodes in this module that get traced by a
    jit-family wrapper: passed to ``jax.jit(...)``/``shard_map(...)``/
    ``pallas_call(...)``, or decorated with one (incl.
    ``@partial(jax.jit, ...)``).  Name-based matching is a deliberate
    over-approximation (same-name methods all count) — a lint, not a
    type system."""
    traced_nodes = []
    traced_names = set()

    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _terminal(node.func) in JIT_WRAPPERS:
            if node.args:
                target = node.args[0]
                if isinstance(target, ast.Lambda):
                    traced_nodes.append(target)
                else:
                    name = _terminal(target)
                    if name:
                        traced_names.add(name)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _terminal(dec) in JIT_WRAPPERS:
                    traced_nodes.append(node)
                elif isinstance(dec, ast.Call):
                    if _terminal(dec.func) in JIT_WRAPPERS:
                        traced_nodes.append(node)
                    elif (_terminal(dec.func) == "partial" and dec.args
                          and _terminal(dec.args[0]) in JIT_WRAPPERS):
                        traced_nodes.append(node)

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in traced_names:
            traced_nodes.append(node)
    return traced_nodes


def _walk_traced(tree):
    """Yield every AST node inside any jit-traced function body."""
    seen = set()
    for fn in _jit_traced_functions(tree):
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if id(node) not in seen:
                    seen.add(id(node))
                    yield node


@register
class BareExcept(Rule):
    id = "DSTPU001"
    name = "bare-except"
    severity = "error"
    description = ("`except:` catches SystemExit/KeyboardInterrupt and "
                   "hides the real failure; name the exception types")

    def check(self, tree, src, relpath):
        for node in ast.walk(tree):
            if isinstance(node, ast.ExceptHandler) and node.type is None:
                yield self.finding(relpath, node.lineno, "bare `except:`")


def _exception_names(node):
    if node is None:
        return []
    elts = node.elts if isinstance(node, ast.Tuple) else [node]
    return [_terminal(e) for e in elts if _terminal(e)]


@register
class SwallowedOSError(Rule):
    id = "DSTPU002"
    name = "swallowed-oserror"
    severity = "error"
    description = ("IO errors must be retried, logged, or re-raised — "
                   "never silently dropped (docs/fault-tolerance.md)")

    def check(self, tree, src, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.ExceptHandler) or node.type is None:
                continue
            swallows = (len(node.body) == 1
                        and isinstance(node.body[0], ast.Pass))
            mentions = any(n in ("OSError", "IOError", "EnvironmentError")
                           for n in _exception_names(node.type))
            if swallows and mentions:
                yield self.finding(relpath, node.lineno,
                                   "silently swallowed OSError")


@register
class HostImpureInJit(Rule):
    id = "DSTPU101"
    name = "host-impure-in-jit"
    severity = "error"
    description = ("time.time()/np.random/global mutation inside a "
                   "jit-traced function runs ONCE at trace time; the "
                   "compiled step replays the stale value forever")

    def check(self, tree, src, relpath):
        for node in _walk_traced(tree):
            if isinstance(node, ast.Call):
                dotted = _dotted(node.func)
                if dotted is None:
                    continue
                if dotted in _HOST_IMPURE_EXACT or \
                        any(dotted.startswith(p)
                            for p in _HOST_IMPURE_PREFIXES):
                    yield self.finding(
                        relpath, node.lineno,
                        f"`{dotted}(...)` inside a jit-traced function "
                        "(traces once, bakes the value into the step; "
                        "use jax.random / pass host values as args)")
            elif isinstance(node, ast.Global):
                yield self.finding(
                    relpath, node.lineno,
                    f"`global {', '.join(node.names)}` inside a "
                    "jit-traced function (trace-time side effect; the "
                    "compiled step will not repeat it)")


@register
class RawCollective(Rule):
    id = "DSTPU102"
    name = "raw-collective"
    severity = "error"
    description = ("raw jax.lax collectives live in "
                   "parallel/collectives.py; call the wrappers so the "
                   "comms layer stays auditable in one place")

    ALLOWED_FILES = ("parallel/collectives.py",)

    def check(self, tree, src, relpath):
        norm = relpath.replace("\\", "/")
        if any(norm.endswith(ok) for ok in self.ALLOWED_FILES):
            return
        for node in ast.walk(tree):
            if not isinstance(node, ast.Attribute):
                continue
            if node.attr not in LAX_COLLECTIVES:
                continue
            base = _dotted(node.value)
            if base in ("lax", "jax.lax"):
                yield self.finding(
                    relpath, node.lineno,
                    f"raw collective `{base}.{node.attr}` outside "
                    "parallel/collectives.py (use the "
                    "parallel.collectives wrapper)")


@register
class AdhocMetricEmission(Rule):
    id = "DSTPU104"
    name = "adhoc-metric-emission"
    severity = "error"
    description = ("runtime/inference code must emit metrics through the "
                   "monitor bus (deepspeed_tpu/monitor) or the logger; "
                   "bare print()/json.dump() invents a one-off format "
                   "ds_top and the schema tests cannot see")

    # scope: the runtime + inference trees (where the monitor bus is the
    # one sanctioned metric path)
    SCOPE_DIRS = ("runtime/", "inference/")

    def _in_scope(self, relpath):
        norm = relpath.replace("\\", "/")
        if "/monitor/" in norm or norm.startswith("monitor/"):
            return False              # the bus itself (and ds_top's table)
        return any(d in norm for d in self.SCOPE_DIRS)

    def check(self, tree, src, relpath):
        if not self._in_scope(relpath):
            return
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func)
            if dotted == "print":
                yield self.finding(
                    relpath, node.lineno,
                    "`print(...)` in runtime/inference code — emit "
                    "metrics via the monitor bus or logger (suppress "
                    "per-site for contractual stdout protocols)")
            elif dotted == "json.dump":
                yield self.finding(
                    relpath, node.lineno,
                    "direct `json.dump(...)` of a metrics/artifact dict "
                    "— route it through the monitor bus (artifact "
                    "events), or suppress per-site with the reviewed "
                    "reason")


@register
class TracedValueMaterialization(Rule):
    id = "DSTPU103"
    name = "traced-materialization"
    severity = "error"
    description = ("float()/np.asarray()/.item() on a traced value is a "
                   "host sync (or ConcretizationTypeError) inside the "
                   "step program")

    def check(self, tree, src, relpath):
        for node in _walk_traced(tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func)
            if dotted == "float" or dotted in _MATERIALIZERS:
                yield self.finding(
                    relpath, node.lineno,
                    f"`{dotted}(...)` inside a jit-traced function — "
                    "materializes a traced value on the host (use "
                    "jnp.asarray / .astype, or hoist the host math "
                    "out of the step)")
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr == "item" and not node.args
                  and not node.keywords):
                yield self.finding(
                    relpath, node.lineno,
                    "`.item()` inside a jit-traced function — host "
                    "sync on a traced value")
