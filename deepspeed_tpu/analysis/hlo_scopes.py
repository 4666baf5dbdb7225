"""Which ``jax.named_scope`` an instruction of a compiled program works for,
from the program's optimised HLO text.

A device trace names an event by its HLO instruction (``fusion.180``) inside
its module (``jit_step``) and carries no scope; the text carries, on every
instruction JAX emitted, ``op_name="jit(step)/blocks/while/body/attn.window/
dot_general"``.  :func:`scope_map` joins the two: ``(module, {instruction:
scope})`` over the instructions a trace can show (entry computation, loop and
branch bodies, called computations), a fusion as ONE instruction.  The
vocabulary of scopes is ``docs/monitoring.md#device-scopes``.

The rule for one ``op_name`` (:func:`user_scopes`) is also how the census of
Mosaic custom calls names a kernel (``jaxpr_audit.custom_calls_from_hlo_text``).
"""

import re

OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
# op_name components that say how an operation was reached, not what it is
WRAPPER_SCOPES = frozenset({
    "pallas_call", "shard_map", "checkpoint", "rematted_computation",
    "while", "body", "cond", "custom_vjp_call", "custom_jvp_call",
    "closed_call"})
# a scope under a transformation: ``transpose(jvp(blocks))`` is ``blocks``
_TRANSFORMED_RE = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
# a scope's name; ``jit(f)`` and a qualified name (``f.<locals>.g``) are not
_SCOPE_RE = re.compile(r"^[\w.\-]+$")
COMPUTATION_RE = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)")
_INSTRUCTION_RE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_CALLED_RE = re.compile(
    r"(body|condition|calls|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
# instructions that take no device time of their own: never a trace event
_NUMBERED_RE = re.compile(r"\.\d+$")
_FREE_OPCODES = frozenset({
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id"})
UNSCOPED = ""


def user_scopes(op_name: str) -> tuple:
    """The user scopes among an ``op_name``'s components, outermost first:
    JAX's own are passed over (``jit(f)``, a transformation with nothing
    under it, ``shard_map``, ``checkpoint``, loop and branch bodies), a
    scope under a transformation is the scope (``transpose(jvp(blocks))``
    -> ``blocks``)."""
    out = []
    for c in op_name.split("/"):
        m = _TRANSFORMED_RE.match(c)
        while m:
            c = m.group(1)
            m = _TRANSFORMED_RE.match(c)
        if _SCOPE_RE.match(c) and c not in WRAPPER_SCOPES \
                and not c.startswith("branch_"):
            out.append(c)
    return tuple(out)


def kernel_scope(op_name: str) -> str:
    """The innermost user scope of a kernel's ``op_name``: a Mosaic custom
    call carries no kernel name in its HLO text, so it is named by the
    ``jax.named_scope`` it sits in (``attention`` for the flash kernels of
    a GPT-2 block)."""
    scopes = user_scopes(op_name)
    return scopes[-1] if scopes else "pallas_call"


def instruction_scopes(line: str) -> tuple:
    """:func:`user_scopes` of the ``op_name`` on an instruction's line,
    less its last component, the primitive the instruction came from
    (``.../attn.window/dot_general``); ``()`` where the line has none."""
    m = OP_NAME_RE.search(line)
    return user_scopes(m.group(1).rpartition("/")[0]) if m else ()


def _settle(paths, containers=()):
    """One instruction's answer from the scope paths of what it holds: the
    innermost scope of each.  One scope -> its name, several -> the sorted
    tuple (the compiler fused the work of all of them, and a trace cannot
    split it), none -> unscoped.  A scope of ``containers`` (one that only
    wraps a loop over other scopes' work: its own instructions are the
    loop's slices and stacked writes) gives way to any other: a layer
    loop's slice of the stacked weights fused into the ``mlp`` matmul that
    reads it is ``mlp`` work."""
    scopes = {p[-1] for p in paths if p}
    scopes = sorted(scopes.difference(containers) or scopes)
    if not scopes:
        return UNSCOPED
    return scopes[0] if len(scopes) == 1 else tuple(scopes)


def scope_map(hlo_text: str, vocabulary=None, containers=()):
    """``(module name, {instruction name: scope})`` of an optimised HLO
    module's text.  A plain instruction is booked to the innermost user
    scope of its ``op_name``; a fusion to the scopes of the instructions of
    its fused computation that carry one (nested fusions looked through),
    or to its own ``op_name`` where none does: a name, a sorted tuple of
    names (``("lm_head", "sentinel")``: the compiler fused the work of
    both), or ``""`` for an instruction no scope of the program covers.
    Instructions that take no device time of their own (parameters,
    constants, tuples, bitcasts) are left out.

    A Pallas kernel called with ``name=`` puts that name into its
    ``op_name`` (``.../ssm.step/mamba2_state_update/pallas_call``) and on
    its instruction (``%mamba2_state_update.3``); one called without is
    named after the scope it sits in (``.../attention/pallas_call``,
    ``%attention.1``).  The text cannot tell the two apart, ``vocabulary``
    (the program's scope names) can: a custom call whose innermost
    component is its own name and no scope of the vocabulary is booked to
    the scope it sits in.  ``containers``: see :func:`_settle`."""
    module, comp = "", None
    bodies = {}              # computation -> [(name, opcode, paths, callees)]
    fused, shown = set(), set()
    for line in hlo_text.splitlines():
        if comp is None or not line.startswith(" "):
            m = COMPUTATION_RE.match(line)
            if m:
                comp = m.group(1)
                bodies[comp] = []
                if line.startswith("ENTRY"):
                    shown.add(comp)
                continue
            m = _MODULE_RE.match(line)
            if m:
                module = m.group(1)
            continue
        m = _INSTRUCTION_RE.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OPCODE_RE.search(rest)
        opcode = op.group(1) if op else ""
        callees = []
        for role, one, many in _CALLED_RE.findall(rest):
            names = [one] if one else [
                n.strip().lstrip("%") for n in many.split(",") if n.strip()]
            if opcode == "fusion" and role == "calls":
                fused.update(names)
                callees += names
            elif role != "to_apply" or opcode == "call":
                shown.update(names)     # runs as instructions of its own
        path = instruction_scopes(line)
        if opcode == "custom-call" and vocabulary is not None \
                and len(path) > 1 and path[-1] not in vocabulary \
                and path[-1] == _NUMBERED_RE.sub("", name):
            path = path[:-1]
        bodies[comp].append((name, opcode, path, callees))

    def paths_of(computation, seen=()):
        out = []
        for _, _, path, callees in bodies.get(computation, ()):
            if path:
                out.append(path)
            for callee in callees:
                if callee not in seen:
                    out += paths_of(callee, seen + (callee,))
        return out

    out = {}
    for computation in shown - fused:
        for name, opcode, path, callees in bodies.get(computation, ()):
            if opcode in _FREE_OPCODES:
                continue
            paths = [p for c in callees for p in paths_of(c, (c,))]
            out[name] = _settle(paths or [path], containers)
    return module, out
