"""DSTPU3xx: typestate lint for the serving control plane's lifecycles.

The inference control plane (``deepspeed_tpu/inference/``) is ~3.4k LoC
of host-side resource-lifecycle code — KV blocks, request uids, replica
health — where a bug is silent corruption, not a crash.  The jaxpr
auditor can't see it (nothing here is traced), so these rules check the
AST against **declarative lifecycle specs**: each finite-state machine
is written down ONCE (states, legal transitions, owning APIs) and the
rules verify every transition site in the source matches the table.
The runtime shadow sanitizer (``analysis/sanitize.py``) enforces the
same tables dynamically — one spec, two enforcement layers.

Spec syntax (``docs/static-analysis.md#lifecycle-specs``): an FSM is a
dict with ``states``, ``initial``, and ``transitions`` (state -> tuple
of legal successors).  The per-file bindings below attach an FSM to a
source attribute (``attr``), name the only functions allowed to assign
it (``owners`` / ``init_owners``), and name the transition API whose
call sites are checked against the table.

Rules (scoped to ``deepspeed_tpu/inference/``):

- **DSTPU301** illegal lifecycle transition: a state attribute assigned
  outside its owning transition API, or a transition-API call whose
  (guarded-from, to) pair is not in the declared table.
- **DSTPU302** out-of-API mutation: allocator free-lists, per-sequence
  block lists, slot block tables, replica assignment sets, or journal
  buffers mutated outside their owning methods.
- **DSTPU303** unpaired alloc: a ``.alloc(...)``-bound variable reaches
  a ``return``/``raise`` exit path (exception edges included) without
  being freed or escaping to an owner.
- **DSTPU304** set-once result: terminal result fields
  (outcome/tokens/t_done) written outside the declared finalizers, the
  result table created or popped outside its owning APIs.
"""

import ast

from . import Rule, register

# --------------------------------------------------------------------------
# declarative lifecycle specs — the single source of truth shared by the
# static rules here, the runtime shadow sanitizer (analysis/sanitize.py)
# and docs/static-analysis.md#lifecycle-specs.

KV_BLOCK_FSM = {
    "name": "kv-block",
    "states": ("free", "allocated", "quarantined", "shared", "cow"),
    "initial": "free",
    "transitions": {
        "free": ("allocated",),
        # prefix-cache sharing (PR 19): a second holder (co-tenant or the
        # cache itself) promotes allocated -> shared; the block is
        # read-only until every extra holder drops (shared -> allocated)
        # or a diverging writer clones it (shared -> cow -> allocated,
        # the writer's fresh PRIVATE copy).  Scrub/quarantine is legal
        # only from the sole-owner state — never while shared.
        "allocated": ("free", "quarantined", "shared"),
        "shared": ("allocated", "cow"),
        "cow": ("allocated",),
        # quarantined blocks are scrubbed, then returned to the free list
        "quarantined": ("free",),
    },
}

REQUEST_FSM = {
    "name": "request-uid",
    "states": ("submitted", "queued", "placed", "journaled", "transferred",
               "completed", "popped"),
    "initial": "submitted",
    "transitions": {
        # shed/deadline-at-admit may complete a uid from any pre-placed
        # state; results are set once, then popped exactly once
        "submitted": ("queued", "completed"),
        "queued": ("placed", "completed"),
        # disaggregation (docs/serving.md#disaggregation): a prefill
        # worker retires the uid with the TRANSFERRED outcome — the
        # handoff edge, not a terminal answer; the decode side (or the
        # router's recompute fallback) completes it.  transferred ->
        # placed is the re-seat: the stream is admitted again on the
        # decode worker through the restore path.
        "placed": ("journaled", "transferred", "completed"),
        "journaled": ("transferred", "completed"),
        "transferred": ("placed", "completed"),
        "completed": ("popped",),
        "popped": (),
    },
}

REPLICA_FSM = {
    "name": "replica-health",
    "states": ("HEALTHY", "SUSPECT", "DRAINING", "DEAD"),
    "initial": "HEALTHY",
    "transitions": {
        "HEALTHY": ("SUSPECT", "DRAINING", "DEAD"),
        "SUSPECT": ("HEALTHY", "DEAD"),
        "DRAINING": ("SUSPECT", "HEALTHY", "DEAD"),
        "DEAD": (),                     # dead is terminal — never left
    },
}

FSMS = (KV_BLOCK_FSM, REQUEST_FSM, REPLICA_FSM)

# file bindings: which FSM guards which attribute in which file, and the
# owner functions allowed to touch it.  Paths match by suffix so fixture
# tests can replay a binding under a synthetic path.
STATE_BINDINGS = {
    "inference/router.py": {
        "attr": "state",
        "fsm": REPLICA_FSM,
        "owners": ("_set_state",),
        # __init__ may only seed the FSM's initial state
        "init_owners": ("__init__",),
        "api": "_set_state",
        "state_arg": 1,     # self._set_state(st, STATE, now, ...) -> args[1]
    },
}

# attribute name -> owning function/class names (either matches).  A
# store or mutating method call on these outside an owner is DSTPU302.
PROTECTED_ATTRS = {
    "_free": ("BlockAllocator",),        # allocator free list
    "_in_use": ("BlockAllocator",),      # allocator live-block set
    "_refs": ("BlockAllocator",),        # per-block refcounts (sharing)
    "_entries": ("PrefixIndex",),        # radix cache: key -> entry
    "_by_block": ("PrefixIndex",),       # radix cache: block -> key
    "_lru": ("PrefixIndex",),            # radix cache eviction order
    "_buf": ("RequestJournal",),         # journal append buffer
    "assigned": ("_ReplicaState", "_place", "_record_result", "_handoff",
                 "_seat_transfer"),
    # slot block tables: every seat (_start, _start_shared, the restore)
    # and the clear in _finish go through _set_slot, which also marks the
    # device-resident copy of the slot state stale; a block granted to a
    # seated row goes through _grant_blocks, which tells the allocator, the
    # sanitizer and the timeline's mirrors, and whose caller sends the grant
    # to the device's copy; where the cache folds itself a row's table is
    # rewritten in mid-life by _fold_ended_windows (a window's blocks out,
    # its summary blocks in), which tells the same three and marks the
    # device's copy stale
    "_tables": ("__init__", "_set_slot", "_grant_blocks",
                "_fold_ended_windows"),
    # per-sequence block list (_Slot)
    "blocks": ("__init__", "_grant_blocks", "_fold_ended_windows"),
    # and its list of the second kind (a window layer's ring; the ring's
    # entries of `_tables` are written by `_set_slot` like the rest)
    "wblocks": ("__init__",),
}

_MUTATING_METHODS = ("append", "extend", "insert", "pop", "popleft",
                     "remove", "clear", "add", "discard", "update",
                     "setdefault")

# result-table discipline per file: who may create records, who may
# write the terminal (set-once) fields, who may pop.
RESULT_BINDINGS = {
    "inference/router.py": {
        "create": ("submit",),
        "finalize": ("_finalize",),
        "pop": ("pop_result",),
    },
    "inference/serving.py": {
        "create": ("submit", "_recover"),
        "finalize": ("_finalize_unseated", "_finish"),
        "pop": ("pop_result", "reset_stats"),
    },
}

TERMINAL_FIELDS = ("outcome", "tokens", "t_done")

SCOPE_DIR = "deepspeed_tpu/inference/"
_SCOPE_FILES = ("inference/router.py", "inference/serving.py",
                "inference/journal.py", "inference/paged_kv.py",
                "inference/transfer.py")


def _norm(relpath):
    return relpath.replace("\\", "/")


def _in_scope(relpath):
    return _norm(relpath).endswith(_SCOPE_FILES)


def _binding_for(relpath, table):
    norm = _norm(relpath)
    for suffix, binding in table.items():
        if norm.endswith(suffix):
            return binding
    return None


def _parents(tree):
    out = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            out[child] = node
    return out


def _enclosing_scopes(node, parents):
    """Names of enclosing functions/classes, innermost first."""
    names = []
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)):
            names.append(cur.name)
        cur = parents.get(cur)
    return names


def _owned_by(node, parents, owners):
    return any(name in owners for name in _enclosing_scopes(node, parents))


def _guard_states(node, parents, constants):
    """Intersect the from-states implied by the enclosing positive
    ``if``/``elif`` guards of ``node`` (``x.state == K`` / ``x.state in
    (A, B)``).  Returns a set, empty when nothing is provable."""
    states = None
    prev, cur = node, parents.get(node)
    while cur is not None:
        if isinstance(cur, ast.If) and _stmt_in(prev, cur.body):
            got = _states_from_test(cur.test, constants)
            if got is not None:
                states = got if states is None else states & got
        prev, cur = cur, parents.get(cur)
        if isinstance(prev, (ast.FunctionDef, ast.AsyncFunctionDef)):
            break
    return states or set()


def _stmt_in(node, stmts):
    return any(node is s or _contains(s, node) for s in stmts)


def _contains(root, node):
    return any(child is node for child in ast.walk(root))


def _states_from_test(test, constants):
    if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And):
        sets = [s for s in (_states_from_test(v, constants)
                            for v in test.values) if s is not None]
        if not sets:
            return None
        out = set(sets[0])
        for s in sets[1:]:
            out &= s
        return out
    if isinstance(test, ast.Compare) and len(test.ops) == 1:
        left, op, comp = test.left, test.ops[0], test.comparators[0]
        if isinstance(left, ast.Attribute) and left.attr == "state":
            if (isinstance(op, ast.Eq) and isinstance(comp, ast.Name)
                    and comp.id in constants):
                return {comp.id}
            if (isinstance(op, ast.In)
                    and isinstance(comp, (ast.Tuple, ast.List, ast.Set))):
                names = {e.id for e in comp.elts
                         if isinstance(e, ast.Name) and e.id in constants}
                if names:
                    return names
    return None


@register
class LifecycleTransition(Rule):
    id = "DSTPU301"
    name = "illegal-lifecycle-transition"
    severity = "error"
    description = ("State-machine attribute assigned outside its owning "
                   "transition API, or a transition not in the declared "
                   "lifecycle table (docs/static-analysis.md"
                   "#lifecycle-specs).")

    def check(self, tree, src, relpath):
        binding = _binding_for(relpath, STATE_BINDINGS)
        if binding is None:
            return
        fsm = binding["fsm"]
        constants = set(fsm["states"])
        parents = _parents(tree)
        for node in ast.walk(tree):
            # (a) direct assignment to the guarded attribute
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if (isinstance(tgt, ast.Attribute)
                            and tgt.attr == binding["attr"]):
                        yield from self._check_store(
                            node, tgt, parents, binding, fsm, relpath)
            # (b) transition-API call sites vs the table
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == binding["api"]
                    and len(node.args) > binding["state_arg"]):
                arg = node.args[binding["state_arg"]]
                if not (isinstance(arg, ast.Name) and arg.id in constants):
                    continue
                to = arg.id
                for frm in sorted(_guard_states(node, parents, constants)):
                    if to not in fsm["transitions"].get(frm, ()):
                        yield self.finding(
                            relpath, node.lineno,
                            f"illegal {fsm['name']} transition "
                            f"{frm} -> {to} (allowed: "
                            f"{', '.join(fsm['transitions'].get(frm, ())) or 'none — terminal state'})")

    def _check_store(self, node, tgt, parents, binding, fsm, relpath):
        scopes = _enclosing_scopes(node, parents)
        if any(n in binding["owners"] for n in scopes):
            return
        if any(n in binding["init_owners"] for n in scopes):
            v = node.value
            if isinstance(v, ast.Name) and v.id == fsm["initial"]:
                return
            yield self.finding(
                relpath, node.lineno,
                f"{fsm['name']} FSM must start in {fsm['initial']!r}; "
                f"__init__ may not seed any other state")
            return
        yield self.finding(
            relpath, node.lineno,
            f".{binding['attr']} assigned outside "
            f"{'/'.join(binding['owners'])} — all {fsm['name']} "
            f"transitions must go through the owning API so the "
            f"table, logging and handoff hooks apply")


@register
class OutOfApiMutation(Rule):
    id = "DSTPU302"
    name = "out-of-api-mutation"
    severity = "error"
    description = ("Lifecycle-owned internals (allocator free lists, "
                   "block tables, assignment sets, journal buffers) "
                   "mutated outside their owning API.")

    def check(self, tree, src, relpath):
        if not _in_scope(relpath):
            return
        parents = _parents(tree)
        for node in ast.walk(tree):
            attr = self._mutated_attr(node)
            if attr is None or attr not in PROTECTED_ATTRS:
                continue
            if _owned_by(node, parents, PROTECTED_ATTRS[attr]):
                continue
            yield self.finding(
                relpath, node.lineno,
                f".{attr} mutated outside its owner "
                f"({'/'.join(PROTECTED_ATTRS[attr])}) — go through the "
                f"owning API so the lifecycle bookkeeping (and the "
                f"shadow sanitizer, when armed) stays truthful")

    @staticmethod
    def _mutated_attr(node):
        # store/del: x._free = ..., x._free[i] = ..., del x._free[i]
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.Delete)):
            tgts = (node.targets if isinstance(node, ast.Assign)
                    else [node.target] if isinstance(node, ast.AugAssign)
                    else node.targets)
            for tgt in tgts:
                base = tgt
                if isinstance(base, ast.Subscript):
                    base = base.value
                if isinstance(base, ast.Attribute):
                    return base.attr
        # mutating method call: x._free.append(...), x.assigned.clear()
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _MUTATING_METHODS
                and isinstance(node.func.value, ast.Attribute)):
            return node.func.value.attr
        return None


@register
class UnpairedAlloc(Rule):
    id = "DSTPU303"
    name = "unpaired-alloc"
    severity = "error"
    description = ("A block allocation reaches a return/raise exit path "
                   "(exception edges included) without being freed or "
                   "escaping to an owner — a pool leak.")

    def check(self, tree, src, relpath):
        if not _in_scope(relpath):
            return
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield from self._check_fn(fn, relpath)

    def _check_fn(self, fn, relpath):
        for var, alloc_stmt, chain in self._allocs(fn):
            leaks = []
            released = False
            for block, idx in chain:
                released = self._scan(block[idx:], var, released, leaks)
            if not released and not leaks:
                leaks.append((fn.end_lineno or fn.lineno, "falls out of "
                              "scope at function end"))
            for lineno, how in leaks:
                yield self.finding(
                    relpath, lineno,
                    f"{var!r} allocated at line {alloc_stmt.lineno} "
                    f"{how} without free() or escaping to an owner "
                    f"(kv-block FSM: allocated blocks must return to "
                    f"'free' on every exit path)")

    # -------------------------------------------------------- discovery
    def _allocs(self, fn):
        """(var, alloc_stmt, [(block, next_index), ...innermost first])
        for each ``var = <...>.alloc(...)`` binding in ``fn``."""
        out = []

        def visit(block, chain):
            for i, st in enumerate(block):
                if (isinstance(st, ast.Assign) and len(st.targets) == 1
                        and isinstance(st.targets[0], ast.Name)
                        and isinstance(st.value, ast.Call)
                        and isinstance(st.value.func, ast.Attribute)
                        and st.value.func.attr == "alloc"):
                    out.append((st.targets[0].id, st,
                                [(block, i + 1)] + chain))
                for sub in self._sub_blocks(st):
                    visit(sub, [(block, i + 1)] + chain)
        visit(fn.body, [])
        return out

    @staticmethod
    def _sub_blocks(st):
        for field in ("body", "orelse", "finalbody"):
            blk = getattr(st, field, None)
            if blk and not isinstance(st, (ast.FunctionDef,
                                           ast.AsyncFunctionDef)):
                yield blk
        for h in getattr(st, "handlers", ()):
            yield h.body

    # ------------------------------------------------------ path walker
    def _scan(self, stmts, var, released, leaks):
        """Walk a statement block; record exits where ``var`` is still
        held.  Any Load of ``var`` counts as free/escape (passed to a
        call, returned, stored, iterated); exits under a ``var is None``
        guard are the alloc-failed path and exempt."""
        for st in stmts:
            if isinstance(st, (ast.Return, ast.Raise)):
                if self._loads(st, var):
                    return True
                if not released:
                    kind = ("returns" if isinstance(st, ast.Return)
                            else "raises")
                    leaks.append((st.lineno, kind))
                return released
            if isinstance(st, ast.If):
                exempt = self._none_guard(st.test, var)
                # a non-None-guard test that inspects the var (cleanup
                # code deciding whether to free) releases for the
                # BRANCHES only — the straight-line remainder must
                # still free
                test_rel = (not exempt) and self._loads(st.test, var)
                body_rel = self._scan(st.body, var,
                                      released or exempt or test_rel,
                                      leaks)
                else_rel = self._scan(st.orelse, var,
                                      released or test_rel, leaks)
                released = released or (body_rel and else_rel)
                continue
            if isinstance(st, ast.Try):
                pre = released
                body_rel = self._scan(st.body, var, released, leaks)
                for h in st.handlers:
                    # exception edge: the try body may have aborted
                    # before its release — handlers start un-released
                    self._scan(h.body, var, pre, leaks)
                if st.orelse:
                    body_rel = self._scan(st.orelse, var, body_rel, leaks)
                if st.finalbody:
                    fin_rel = self._scan(st.finalbody, var, pre, leaks)
                    body_rel = body_rel or fin_rel
                released = body_rel
                continue
            if isinstance(st, (ast.For, ast.While)):
                if self._loads(st.iter if isinstance(st, ast.For)
                               else st.test, var):
                    released = True
                self._scan(st.body, var, released, leaks)
                self._scan(st.orelse, var, released, leaks)
                continue
            if isinstance(st, ast.With):
                released = self._scan(
                    st.body, var, released or self._loads(st, var), leaks)
                continue
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            if self._loads(st, var):
                released = True
        return released

    @staticmethod
    def _loads(node, var):
        if node is None:
            return False
        return any(isinstance(n, ast.Name) and n.id == var
                   and isinstance(n.ctx, ast.Load)
                   for n in ast.walk(node))

    @staticmethod
    def _none_guard(test, var):
        """``if var is None:`` / ``if not var:`` — the alloc-failed
        branch, where there is nothing to free."""
        if (isinstance(test, ast.Compare) and len(test.ops) == 1
                and isinstance(test.ops[0], ast.Is)
                and isinstance(test.left, ast.Name)
                and test.left.id == var
                and isinstance(test.comparators[0], ast.Constant)
                and test.comparators[0].value is None):
            return True
        return (isinstance(test, ast.UnaryOp)
                and isinstance(test.op, ast.Not)
                and isinstance(test.operand, ast.Name)
                and test.operand.id == var)


@register
class SetOnceResult(Rule):
    id = "DSTPU304"
    name = "set-once-result"
    severity = "error"
    description = ("Result-table discipline: records created, terminal "
                   "fields (outcome/tokens/t_done) written, or records "
                   "popped outside the declared owning APIs — the "
                   "set-once contract the crash-handoff dedup relies "
                   "on.")

    def check(self, tree, src, relpath):
        binding = _binding_for(relpath, RESULT_BINDINGS)
        if binding is None:
            return
        parents = _parents(tree)
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    yield from self._check_store(node, tgt, parents,
                                                 binding, relpath)
            if isinstance(node, ast.Delete):
                for tgt in node.targets:
                    if (isinstance(tgt, ast.Subscript)
                            and self._is_results(tgt.value)
                            and not _owned_by(node, parents,
                                              binding["pop"])):
                        yield self.finding(
                            relpath, node.lineno,
                            f"result record deleted outside "
                            f"{'/'.join(binding['pop'])}")
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pop"
                    and self._is_results(node.func.value)
                    and not _owned_by(node, parents, binding["pop"])):
                yield self.finding(
                    relpath, node.lineno,
                    f"result record popped outside "
                    f"{'/'.join(binding['pop'])} — uids must be served "
                    f"exactly once (request-uid FSM: completed -> "
                    f"popped)")

    def _check_store(self, node, tgt, parents, binding, relpath):
        if not isinstance(tgt, ast.Subscript):
            return
        # results[uid] = {...}: record creation
        if self._is_results(tgt.value):
            if not _owned_by(node, parents, binding["create"]):
                yield self.finding(
                    relpath, node.lineno,
                    f"result record created outside "
                    f"{'/'.join(binding['create'])}")
            return
        # rec["outcome"] = ...: terminal set-once field
        key = tgt.slice
        if (isinstance(key, ast.Constant)
                and key.value in TERMINAL_FIELDS
                and not _owned_by(node, parents, binding["finalize"])):
            yield self.finding(
                relpath, node.lineno,
                f"terminal result field {key.value!r} written outside "
                f"{'/'.join(binding['finalize'])} — results are "
                f"set-once (the crash-handoff dedup contract)")

    @staticmethod
    def _is_results(node):
        return ((isinstance(node, ast.Attribute)
                 and node.attr == "results")
                or (isinstance(node, ast.Name) and node.id == "results"))
