"""Jaxpr-level auditor for compiled train steps.

The perf properties that kill a TPU run are invisible at runtime until
they cost a bench cycle: a host callback serializing the step, an fp32
matmul hiding in a bf16 path, donation that silently didn't apply
(doubling peak HBM), an unbudgeted collective, a weak-typed Python
scalar forcing retrace churn.  This module checks them STATICALLY from
the three artifacts every jitted callable already exposes:

  closed jaxpr   → host callbacks, dtype promotions, explicit
                   collectives, weak-typed/constant recompile hazards
  lowered HLO    → per-argument donation aliasing (``tf.aliasing_output``)
  compiled exe   → executable-level ``input_output_alias`` + the SPMD
                   partitioner's inserted collectives

Rule ids (audit namespace DSTPU2xx):

  DSTPU201  host callback / infeed / outfeed inside the step (error)
  DSTPU202  dtype promotion above the configured compute dtype (warning;
            f64 anywhere is error)
  DSTPU203  collective census over the declared comms budget (error)
  DSTPU204  donation declared but not honored by the executable (error)
  DSTPU205  recompile hazard: weak-typed scalar argument (warning) or
            large closure-captured constant (info)
"""

import re
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from .comms import CensusEntry, CommsBudget, canonical_kind, check_budget, \
    summarize
from .findings import Finding, counts_by_severity
from .hlo_scopes import COMPUTATION_RE as _HLO_COMPUTATION_RE, \
    OP_NAME_RE as _HLO_OP_NAME_RE, kernel_scope as _kernel_scope

# primitives that round-trip through the host (serialize the step on the
# dispatch path); anything name-matching *callback is caught too.
# ``debug_print`` is what ``jax.debug.print`` traces to on jax 0.9.0.
HOST_SYNC_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "debug_print", "callback", "infeed", "outfeed",
                   "host_local_array_to_global_array"}

# primitives whose operand dtypes define the "compute dtype" of a path
COMPUTE_PRIMS = {"dot_general", "conv_general_dilated"}

_F64_NAMES = ("float64", "complex128")

_LARGE_CONST_BYTES = 1 << 20     # 1 MB baked into the program text


def _dtype_name(aval) -> Optional[str]:
    dt = getattr(aval, "dtype", None)
    try:
        return None if dt is None else np.dtype(dt).name
    except TypeError:
        return None      # extended dtypes (PRNG keys) have no numpy name


def _aval_bytes(aval) -> int:
    dt = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    if dt is None or shape is None:
        return 0
    try:
        itemsize = np.dtype(dt).itemsize
    except TypeError:
        return 0
    return int(np.prod(shape or (1,))) * itemsize


def _float_width(name: str) -> int:
    return {"bfloat16": 16, "float16": 16, "float32": 32,
            "float64": 64}.get(name, 0)


def iter_eqns(jaxpr, path=""):
    """Yield ``(eqn, eqn_path)`` over a jaxpr and every sub-jaxpr
    (pjit/scan/cond/while/custom_* bodies), depth-first."""
    for i, eqn in enumerate(getattr(jaxpr, "eqns", ())):
        here = f"{path}/{eqn.primitive.name}[{i}]" if path else \
            f"{eqn.primitive.name}[{i}]"
        yield eqn, here
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, here)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in _as_jaxprs(v):
            yield sub


def _as_jaxprs(v):
    if hasattr(v, "eqns"):                      # core.Jaxpr
        yield v
    elif hasattr(v, "jaxpr"):                   # core.ClosedJaxpr
        yield v.jaxpr
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _as_jaxprs(item)


def _all_consts(closed):
    """Consts of a closed jaxpr AND of every nested closed sub-jaxpr
    (jit hoists closure captures into the inner pjit's consts)."""
    seen = set()

    def walk(node):
        consts = getattr(node, "consts", None)
        if consts is not None:
            for c in consts:
                if id(c) not in seen:
                    seen.add(id(c))
                    yield c
        for eqn in getattr(getattr(node, "jaxpr", node), "eqns", ()):
            for v in eqn.params.values():
                for item in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(item, "jaxpr") or hasattr(item, "eqns"):
                        yield from walk(item)

    yield from walk(closed)


@dataclass
class AuditReport:
    findings: list = field(default_factory=list)
    census: list = field(default_factory=list)       # CensusEntry list
    donation: dict = field(default_factory=dict)
    n_eqns: int = 0

    @property
    def host_callbacks(self):
        return [f for f in self.findings if f.rule == "DSTPU201"]

    @property
    def promotions(self):
        return [f for f in self.findings if f.rule == "DSTPU202"]

    @property
    def recompile_hazards(self):
        return [f for f in self.findings if f.rule == "DSTPU205"]

    def ok(self) -> bool:
        return not any(f.severity == "error" for f in self.findings)

    def to_dict(self) -> dict:
        return {"findings": [f.to_dict() for f in self.findings],
                "census": [c.to_dict() for c in self.census],
                "census_summary": summarize(self.census),
                "donation": self.donation,
                "n_eqns": self.n_eqns,
                "counts": counts_by_severity(self.findings),
                "ok": self.ok()}


# --------------------------------------------------------------- jaxpr pass
def _audit_jaxpr(closed, compute_dtype, report):
    compute_name = (np.dtype(compute_dtype).name
                    if compute_dtype is not None else None)
    compute_width = _float_width(compute_name) if compute_name else None

    for eqn, path in iter_eqns(closed.jaxpr):
        report.n_eqns += 1
        name = eqn.primitive.name

        # --- host round-trips -----------------------------------------
        if name in HOST_SYNC_PRIMS or name.endswith("callback"):
            cb = eqn.params.get("callback", None)
            report.findings.append(Finding(
                "DSTPU201", "error",
                f"host callback `{name}` inside the compiled step "
                f"({getattr(cb, '__name__', None) or 'opaque'}): every "
                "dispatch round-trips to Python, serializing the step",
                eqn_path=path))

        # --- explicit collectives -------------------------------------
        kind = canonical_kind(name)
        if kind is not None:
            axes = eqn.params.get("axes",
                                  eqn.params.get("axis_name", ()))
            if not isinstance(axes, (tuple, list)):
                axes = (axes,)
            payload = sum(_aval_bytes(v.aval) for v in eqn.outvars)
            dtypes = tuple(d for d in (_dtype_name(v.aval)
                                       for v in eqn.outvars) if d)
            report.census.append(CensusEntry(
                kind=kind, op=name, axes=tuple(str(a) for a in axes),
                bytes=payload, eqn_path=path, level="jaxpr",
                dtypes=dtypes))

        # --- dtype promotion ------------------------------------------
        for v in eqn.outvars:
            dn = _dtype_name(v.aval)
            if dn in _F64_NAMES:
                report.findings.append(Finding(
                    "DSTPU202", "error",
                    f"f64 value produced by `{name}` — silent float64 "
                    "promotion (TPUs emulate f64; check jax_enable_x64 "
                    "and np-scalar leaks)", eqn_path=path))
                break
        if compute_width and name in COMPUTE_PRIMS:
            op_widths = {_dtype_name(v.aval) for v in eqn.invars
                         if hasattr(v, "aval")}
            wide = sorted(w for w in op_widths
                          if w and _float_width(w) > compute_width)
            if wide:
                report.findings.append(Finding(
                    "DSTPU202", "warning",
                    f"`{name}` consumes {'/'.join(wide)} operands in a "
                    f"{compute_name} path — a missing cast runs this "
                    "matmul above the configured compute dtype",
                    eqn_path=path,
                    extra={"operand_dtypes": wide,
                           "compute_dtype": compute_name}))

    # --- recompile hazards --------------------------------------------
    for i, v in enumerate(closed.jaxpr.invars):
        aval = v.aval
        if getattr(aval, "weak_type", False) and \
                getattr(aval, "shape", None) == ():
            report.findings.append(Finding(
                "DSTPU205", "warning",
                f"argument {i} is a weak-typed scalar (a Python "
                "int/float leaked into the step): a type flip across "
                "steps forces recompilation; pass "
                "jnp.asarray(x, explicit_dtype) instead",
                eqn_path=f"invars[{i}]"))
    for i, const in enumerate(_all_consts(closed)):
        nbytes = getattr(const, "nbytes", 0)
        if nbytes >= _LARGE_CONST_BYTES:
            report.findings.append(Finding(
                "DSTPU205", "info",
                f"{nbytes / 1e6:.1f} MB constant baked into the program "
                "(closure-captured array): it is re-traced and re-staged "
                "on every compile — pass it as an argument",
                eqn_path=f"consts[{i}]"))


# ------------------------------------------------------- lowered / compiled
_ALIAS_ENTRY_RE = re.compile(r"\((\d+),\s*\{[^}]*\},\s*[\w-]+\)")


def _alias_param_numbers(hlo_text):
    """Entry-parameter numbers aliased to an output, from the HloModule
    header's ``input_output_alias={ {out}: (param, {idx}, kind), ... }``
    (brace-matched by hand: the set nests braces)."""
    idx = hlo_text.find("input_output_alias=")
    if idx < 0:
        return set()
    start = hlo_text.find("{", idx)
    depth, end = 0, start
    for end in range(start, len(hlo_text)):
        if hlo_text[end] == "{":
            depth += 1
        elif hlo_text[end] == "}":
            depth -= 1
            if depth == 0:
                break
    seg = hlo_text[start:end + 1]
    return {int(m.group(1)) for m in _ALIAS_ENTRY_RE.finditer(seg)}


# one result shape `f32[8,16]` — or a variadic tuple of them `(f32[..], ..)`
# (XLA's combiner merges per-tensor reductions into ONE tuple-result op;
# missing those would under-count exactly the dominant traffic)
_HLO_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_HLO_COLLECTIVE_RE = re.compile(
    r"\s(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_HLO_CALLEE_RE = re.compile(
    r"(body|condition|calls|to_apply)=(%?[\w.\-]+)"
    r"|(branch_computations)=\{([^}]*)\}")
_HLO_TRIPS_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_HLO_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_HLO_S32_CONSTANT_RE = re.compile(r"=\s*s32\[\][^ ]*\s+constant\((\d+)\)")

_HLO_DTYPE_NP = {"bf16": "uint16", "f16": "float16", "f32": "float32",
                 "f64": "float64", "s32": "int32", "s8": "int8",
                 "u8": "uint8", "u16": "uint16", "u32": "uint32",
                 "pred": "bool", "s64": "int64", "u64": "uint64",
                 "s16": "int16"}


# replica_groups={{0,4},{1,5}} (explicit) or =[2,4]<=[8] (iota: 2 groups of 4)
_REPLICA_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_REPLICA_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{(\{[^=]*?\})\}")


def _groups(line: str):
    """(group count, devices a group) of a collective's line; 0 unknown."""
    m = _REPLICA_GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(1)), int(m.group(2))
    m = _REPLICA_GROUPS_EXPL_RE.search(line)
    if m:
        n = m.group(1).count("{")
        return n, (m.group(1).count(",") + 1) // max(n, 1)
    return 0, 0


def _tuple_elements(text: str):
    """Top-level elements of ``(a, (b, c), d)``; ``[text]`` if no tuple."""
    text = text.strip()
    if not text.startswith("("):
        return [text]
    out, depth, last = [], 0, 1
    for i, ch in enumerate(text):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                out.append(text[last:i])
                break
        elif ch == "," and depth == 1:
            out.append(text[last:i])
            last = i + 1
    return out


def _hlo_loops(lines):
    """``{computation: (innermost while body or None, trips)}``: for each
    computation of the module the loop body it runs in (through fusions
    and calls) and the product of the enclosing loops' known trip counts
    (an unknown count is taken as 1)."""
    parent, comp = {}, None      # callee -> (caller, is_body, trips)
    limits, whiles = {}, []      # computation -> its s32 constants
    for line in lines:
        m = _HLO_COMPUTATION_RE.match(line)
        if m:
            comp = m.group(1)
            continue
        if comp is None or "=" not in line:
            continue
        m = _HLO_S32_CONSTANT_RE.search(line)
        if m:
            limits.setdefault(comp, set()).add(int(m.group(1)))
        called = {}
        for role, name, _, names in _HLO_CALLEE_RE.findall(line):
            for name in (name or names).split(","):
                name = name.strip().lstrip("%")
                called[role] = name
                parent.setdefault(name, (comp, role == "body", 1))
        if "body" in called:
            trips = _HLO_TRIPS_RE.search(line)
            whiles.append((called["body"], called.get("condition"),
                           int(trips.group(1)) if trips else None))
    for body, cond, trips in whiles:
        if trips is None:
            # the TPU's final text drops known_trip_count: a counted
            # loop's condition compares against its one s32 constant
            bound = limits.get(cond, ())
            trips = next(iter(bound)) if len(bound) == 1 else 1
        parent[body] = (parent[body][0], True, trips)
    out = {}

    def resolve(name, seen=()):
        if name in out:
            return out[name]
        if name not in parent or name in seen:
            return None, 1
        caller, is_body, n = parent[name]
        loop, trips = resolve(caller, seen + (name,))
        out[name] = (name if is_body else loop, trips * n)
        return out[name]

    for name in list(parent):
        resolve(name)
    return out


def census_from_hlo_text(hlo_text):
    """Collective census entries from an HLO module's text (parses both
    array-result and variadic tuple-result collectives).  Entries carry
    the payload dtype names (int8/int4 wire = a QUANTIZED collective,
    ``comms.QUANT_DTYPE_NAMES``), the payload's arrays as ``(dims,
    bytes)``, the replica-group count (>1 marks a sub-axis phase of a
    two-level decomposition), the ``while`` body the op runs in and how
    often (``loop``, ``trips``).  One entry a channel and payload: the
    TPU compiler repeats an asynchronous collective in its start, fused
    and done computations.  A reduce-scatter is priced at the tensor
    REDUCED (its result times the group), as the all-reduce + slice the
    TPU lowers it to is."""
    lines = hlo_text.splitlines()
    loops = _hlo_loops(lines)
    out, comp, seen = [], None, set()
    for line in lines:
        m = _HLO_COMPUTATION_RE.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _HLO_COLLECTIVE_RE.search(line)
        if not m or "=" not in line[:m.start()]:
            continue
        op, started = m.group(1), bool(m.group(2))
        result = line[line.index("=") + 1:m.start()]
        if started and op in ("all-gather", "collective-permute"):
            # (operand, result, ...): the payload is the result
            result = (_tuple_elements(result) + [""])[1]
        channel = _HLO_CHANNEL_RE.search(line)
        if channel is not None:
            # the same channel AND payload: a repeat (the CPU backend
            # gives distinct collectives one channel id)
            key = (op, channel.group(1),
                   tuple(_HLO_SHAPE_RE.findall(result)))
            if key in seen:
                continue
            seen.add(key)
        groups, group_size = _groups(line)
        kind = canonical_kind(op) or op
        scale = 1
        if op == "reduce-scatter":
            scale = max(group_size, 1)
        elif op == "all-reduce" and (comp or "").startswith(
                "all-reduce-scatter"):
            kind = "reduce_scatter"      # the TPU's fused all-reduce + slice
        payload, dtypes, shapes = 0, [], []
        for dtype_name, dims in _HLO_SHAPE_RE.findall(result):
            try:
                itemsize = np.dtype(
                    _HLO_DTYPE_NP.get(dtype_name, dtype_name)).itemsize
            except TypeError:
                continue
            dims = tuple(int(d) for d in dims.split(",") if d)
            nbytes = int(np.prod(dims or (1,))) * itemsize * scale
            payload += nbytes
            dtypes.append(dtype_name)
            shapes.append((dims, nbytes))
        loop, trips = loops.get(comp, (None, 1))
        out.append(CensusEntry(
            kind=kind, op=op, axes=(), bytes=payload, eqn_path=None,
            level="hlo", dtypes=tuple(dtypes), groups=groups,
            shapes=tuple(shapes), loop=loop, trips=trips))
    return out


_HLO_MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


def custom_calls_from_hlo_text(hlo_text) -> dict:
    """``{scope: calls a step}``: the Mosaic (Pallas TPU) custom calls of
    an HLO module's text by :func:`_kernel_scope`, each times the trip
    counts of the loops it sits in, as the collective census counts.
    What the compiler kept, not what the program traced: a kernel whose
    results nothing reads is gone.  Empty where kernels are interpreted
    (any backend but a TPU)."""
    lines = hlo_text.splitlines()
    loops = _hlo_loops(lines)
    out, comp = {}, None
    for line in lines:
        m = _HLO_COMPUTATION_RE.match(line)
        if m:
            comp = m.group(1)
            continue
        if _HLO_MOSAIC_CALL not in line:
            continue
        m = _HLO_OP_NAME_RE.search(line)
        scope = _kernel_scope(m.group(1) if m else "")
        out[scope] = out.get(scope, 0) + loops.get(comp, (None, 1))[1]
    return out


def _flat_args_info(lowered):
    """Flattened (donated, aval) per lowered argument, or None."""
    try:
        import jax
        infos = jax.tree_util.tree_leaves(lowered.args_info)
        return [(bool(getattr(a, "donated", False)), a) for a in infos]
    except Exception:
        return None


def _donor_args(lowered_text):
    """``{lowered main arg number: tensor type}`` for every argument the
    lowering marked as a donor — ``tf.aliasing_output`` (aliasing pinned
    by jax) or ``jax.buffer_donor`` (aliasing deferred to XLA, the
    sharded-lowering path).  Lowered arg numbering == the executable's
    entry-parameter numbering; note jit DROPS donated-but-unused args
    from the lowered main, so these are a subset of ``args_info``."""
    sig = lowered_text[lowered_text.find("func.func public @main"):]
    cut = sig.find("{\n")
    sig = sig[:cut if cut > 0 else len(sig)]
    donors, n_args = {}, 0
    for seg in re.split(r"(?=%arg\d+)", sig):
        m = re.match(r"%arg(\d+):\s*tensor<([^>]*)>", seg)
        if not m:
            continue
        n_args += 1
        if "tf.aliasing_output" in seg or "jax.buffer_donor" in seg:
            donors[int(m.group(1))] = (m.group(2), "tf.aliasing_output" in seg)
    return donors, n_args


def _audit_donation(lowered, compiled, report):
    """Donation declared (``args_info.donated``) vs honored (the compiled
    executable's ``input_output_alias`` set; for un-compiled audits, the
    ``tf.aliasing_output`` pins in the lowered module)."""
    infos = _flat_args_info(lowered)
    try:
        text = lowered.as_text()
    except Exception as e:
        report.donation = {"checked": False, "reason": f"lowering: {e}"}
        return
    donors, n_main_args = _donor_args(text)

    # lowering refused the donation outright (no output matches the
    # arg's shape/sharding): the arg appears in main WITHOUT a donor
    # marker.  Attributable per-arg only when no unused args were
    # dropped (then lowered arg order == flattened args_info order).
    unusable = []
    if infos is not None and n_main_args == len(infos):
        unusable = [i for i, (don, _) in enumerate(infos)
                    if don and i not in donors]

    exe_aliased = None
    if compiled is not None:
        try:
            hlo = compiled.runtime_executable().hlo_modules()[0].to_string()
            exe_aliased = _alias_param_numbers(hlo)
        except Exception:
            exe_aliased = None

    if exe_aliased is not None:
        honored = sorted(set(donors) & exe_aliased)
    else:
        # without an executable only the pinned aliases are provable;
        # jax.buffer_donor args stay "unknown" and are reported unhonored
        honored = sorted(a for a, (_, pinned) in donors.items() if pinned)
    unaliased = sorted(set(donors) - set(honored))
    unhonored = unaliased + unusable
    n_declared = (sum(1 for don, _ in infos if don)
                  if infos is not None else len(donors))
    report.donation = {
        "checked": True,
        "declared": n_declared,
        "lowered_donors": len(donors),
        # args the lowering dropped entirely (unused under
        # keep_unused=False): a donated one is freed at dispatch anyway,
        # so this is waste on the call wire, not a live-memory hazard
        "args_dropped_by_lowering": (len(infos) - n_main_args
                                     if infos is not None else 0),
        "honored": len(honored),
        "unhonored_args": unhonored,
        "source": "executable" if exe_aliased is not None else "lowered",
    }
    for i in unusable:
        aval = infos[i][1]
        report.findings.append(Finding(
            "DSTPU204", "error",
            f"donation declared for argument {i} (shape "
            f"{getattr(aval, 'shape', '?')}) but the lowering could not "
            "use it: no output matches its shape/sharding, so the input "
            "buffer cannot be reused (peak memory = old + new copies)",
            eqn_path=f"main/%arg{i}"))
    for a in unaliased:
        report.findings.append(Finding(
            "DSTPU204", "error",
            f"donation declared for input %arg{a} "
            f"(tensor<{donors[a][0]}>) but the compiled executable does "
            "not alias it to any output: the input buffer stays live "
            "through the step (peak memory = old + new copies)",
            eqn_path=f"main/%arg{a}"))


def _audit_hlo_collectives(compiled, report):
    if compiled is None:
        return
    try:
        hlo = compiled.runtime_executable().hlo_modules()[0].to_string()
    except Exception:
        return
    report.census.extend(census_from_hlo_text(hlo))


# ------------------------------------------------------------- public API
def train_step_jaxpr_text(engine, batch=None, rng=None) -> str:
    """Normalized jaxpr text of an engine's traced train step — the
    byte-identity term of the monitor purity gate (``--audit-step
    monitor`` and the tier-1 twin test compare armed vs unarmed engines
    through this ONE helper so the normalization cannot drift).  Object
    addresses (``0x...`` inside partial/function reprs) are scrubbed:
    instance noise, not program content."""
    import jax

    if batch is None:
        batch = engine._stack_microbatches([next(engine._data_iterator)])
    if rng is None:
        rng = jax.random.fold_in(engine._base_rng, 0)
    with jax.set_mesh(engine.mesh):
        text = str(jax.make_jaxpr(engine._train_step)(engine.state, batch,
                                                      rng))
    return re.sub(r"0x[0-9a-f]+", "0x", text)


def audit_fn(fn, *example_args, donate_argnums=(), compute_dtype=None,
             comms_budget: Optional[CommsBudget] = None, mesh=None,
             compile: bool = True, **example_kwargs) -> AuditReport:
    """Audit a callable (or an already-``jax.jit``-wrapped one) on example
    arguments.  Tracing/lowering only — the step is never executed, and
    donated example buffers are not consumed."""
    import jax
    from contextlib import nullcontext

    wrapped = fn if hasattr(fn, "lower") else \
        jax.jit(fn, donate_argnums=donate_argnums)
    report = AuditReport()
    ctx = jax.set_mesh(mesh) if mesh is not None else nullcontext()
    with ctx:
        closed = jax.make_jaxpr(wrapped)(*example_args, **example_kwargs)
        _audit_jaxpr(closed, compute_dtype, report)
        lowered = wrapped.lower(*example_args, **example_kwargs)
        compiled = None
        if compile:
            # CachedStep entry points: audit THE executable that is (or
            # will be) dispatching — for a warm-started engine that is the
            # DESERIALIZED executable, so DSTPU204 (donation honored) is
            # proven for AOT warm starts, not just fresh compiles.
            live = getattr(wrapped, "live_executable", None)
            if live is not None:
                compiled = live(*example_args, **example_kwargs)
            if compiled is None:
                acquire = getattr(wrapped, "executable", None)
                try:
                    compiled = (acquire(*example_args, **example_kwargs)
                                if acquire is not None
                                else lowered.compile())
                except Exception as e:
                    report.findings.append(Finding(
                        "DSTPU200", "warning",
                        f"could not compile for executable-level checks: {e}",
                        eqn_path="compile"))
        _audit_donation(lowered, compiled, report)
        _audit_hlo_collectives(compiled, report)
    if comms_budget is not None:
        # budget the compiled program when available (it holds BOTH the
        # explicit collectives and the ones the SPMD partitioner inserted);
        # the jaxpr census would double-count the explicit ones
        hlo_census = [c for c in report.census if c.level == "hlo"]
        report.findings.extend(check_budget(
            hlo_census if hlo_census else report.census, comms_budget))
    return report


def audit_engine(engine, batch=None, rng=None,
                 comms_budget: Optional[CommsBudget] = None,
                 compile: bool = True) -> AuditReport:
    """Audit a ``DeepSpeedEngine``'s compiled train step on a real batch.

    Audits ``_jit_train_step`` (donating the state, exactly as
    ``train_batch`` dispatches it); offload engines audit the device
    half (``_jit_grad_step``) instead, since their optimizer update is a
    host-side design decision, not a hidden host sync.
    """
    import jax

    if batch is None:
        it = getattr(engine, "_data_iterator", None)
        assert it is not None, \
            "audit_engine needs a batch= or an engine built with training_data"
        gas = engine.gradient_accumulation_steps()
        batch = engine._stack_microbatches([next(it) for _ in range(gas)])
    if rng is None:
        rng = jax.random.fold_in(engine._base_rng, 0)
    if getattr(engine, "_param_stream", None) is not None:
        raise NotImplementedError(
            "audit_engine: the streamed (offload_param) step is a Python "
            "loop over per-layer programs; audit those via audit_fn")
    if getattr(engine, "_offload", None) is not None:
        fn = engine._jit_grad_step
    else:
        fn = engine._jit_train_step
    return audit_fn(fn, engine.state, batch, rng,
                    compute_dtype=engine.compute_dtype,
                    comms_budget=comms_budget, mesh=engine.mesh,
                    compile=compile)
