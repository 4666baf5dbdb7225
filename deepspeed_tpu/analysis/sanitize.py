"""Runtime shadow sanitizer for the serving control plane (DSTPU31x).

ASan for KV blocks and request uids: a **shadow table** mirrors every
lifecycle the static DSTPU3xx rules check declaratively
(``analysis/lint/lifecycle.py`` — one spec, two enforcement layers) and
validates each transition as it happens.  Armed, the ``ServingEngine``
calls the hooks below at its alloc/seat/scrub/free/pop/close
boundaries; each hook is pure host-side bookkeeping over Python ints —
nothing touches a traced function, so the compiled decode step is
**byte-identical armed vs off** (proven by the ``--audit-step
serving-lifecycle`` jaxpr-equality stage and the tier-1 twin test, the
same discipline the fault harness and request tracing established).

What it catches (each a typed :class:`~..findings.Finding`):

- **DSTPU310 double-free** — a block freed while the shadow says
  ``free`` (the allocator's own check can be bypassed by a direct
  free-list edit; the shadow cannot).
- **DSTPU311 use-after-free** — a freed (or never-allocated) block
  still referenced by a live sequence's block table, or handed out
  while the shadow says it is already live.
- **DSTPU312 leak-at-close** — blocks still ``allocated``/
  ``quarantined`` when the engine closes.
- **DSTPU313 scratch-block write** — the reserved block 0 entering a
  live slot's block table.
- **DSTPU314 uid double-serve** — one uid's result handed to a caller
  twice (the crash-handoff dedup contract, enforced at the engine).
- **DSTPU315 scrub-while-referenced** — scrubbing/poisoning a block a
  DIFFERENT live sequence still reads (the refcount check the radix
  prefix cache needs; ROADMAP item 1).
- **DSTPU316 scrub-while-shared** — scrubbing/re-zeroing a block the
  prefix cache (or a second co-tenant) still holds a read-only
  reference to: the kv-block FSM allows quarantine only from the
  sole-owner ``allocated`` state, never from ``shared``.
- **DSTPU317 double-import** — a restore imported a private copy of a
  prompt block the PrefixIndex already holds resident: the correct
  path increfs-and-shares the resident block (restore re-share,
  docs/serving.md#disaggregation); importing a duplicate is silent
  pool waste that admission then double-charges.

Arming (OFF by default, resolution highest-wins):
``deepspeed --sanitize`` (launcher) -> env ``DSTPU_SANITIZE`` -> config
``analysis.sanitize.enabled``.  ``halt=True`` (default) raises
:class:`SanitizerError` at the first finding — a lifecycle bug is
corruption in flight, and stopping at the site beats diagnosing the
blast radius; ``halt=False`` collects findings for forensic runs.
"""

import os

from .findings import Finding
from .lint.lifecycle import KV_BLOCK_FSM, REQUEST_FSM  # noqa: F401

# shadow block states — the kv-block FSM's states, verbatim
FREE, ALLOCATED, QUARANTINED, SHARED, COW = KV_BLOCK_FSM["states"]

DOUBLE_FREE = "DSTPU310"
USE_AFTER_FREE = "DSTPU311"
LEAK_AT_CLOSE = "DSTPU312"
SCRATCH_WRITE = "DSTPU313"
DOUBLE_SERVE = "DSTPU314"
SCRUB_REFERENCED = "DSTPU315"
SCRUB_SHARED = "DSTPU316"
DOUBLE_IMPORT = "DSTPU317"

SANITIZER_CODES = (DOUBLE_FREE, USE_AFTER_FREE, LEAK_AT_CLOSE,
                   SCRATCH_WRITE, DOUBLE_SERVE, SCRUB_REFERENCED,
                   SCRUB_SHARED, DOUBLE_IMPORT)


def env_enabled():
    """Tri-state env override: True/False when ``DSTPU_SANITIZE`` is
    set, None when unset (fall through to config)."""
    val = os.environ.get("DSTPU_SANITIZE")
    if val is None:
        return None
    return val.strip().lower() not in ("", "0", "false", "off", "no")


def resolve_enabled(config_enabled=False):
    """The engine's arming decision: env wins over config, config over
    the OFF default."""
    env = env_enabled()
    return bool(config_enabled) if env is None else env


class SanitizerError(RuntimeError):
    """Raised at the first finding when ``halt=True``; carries the
    typed finding so tests (and forensics) see the class, not a
    string."""

    def __init__(self, finding: Finding):
        super().__init__(str(finding))
        self.finding = finding


class ShadowSanitizer:
    """Shadow lifecycle table for one ``BlockAllocator`` + uid table.

    All hooks are O(blocks touched) dict/set updates on host ints —
    call them from host-side scheduler code only, never under trace.
    """

    def __init__(self, num_blocks: int, *, scratch_block: int = 0,
                 halt: bool = True, kind: str = ""):
        # a model with window layers has a second allocator, whose ids are
        # its own (docs/serving.md#window-layers): one shadow table each,
        # ``kind`` ("window") named in that table's findings
        self.kind = kind
        self.num_blocks = int(num_blocks)
        self.scratch_block = int(scratch_block)
        self.halt = bool(halt)
        self.shadow = {b: FREE for b in range(self.num_blocks)}
        self.refs = {}          # block id -> SET of uids referencing it
        self.cache_blocks = set()   # blocks the prefix cache holds a ref on
        self.attached = {}      # uid -> list of block ids in its table
        self.served = set()     # uids whose result left the engine
        self.findings = []
        self.checks = 0         # hook invocations (bench observability)

    # ------------------------------------------------------------ emit
    def _emit(self, code, message, **extra):
        if self.kind:
            message = f"[{self.kind} blocks] {message}"
            extra["kind"] = self.kind
        f = Finding(code, "error", message,
                    eqn_path=f"sanitize/{code}", extra=extra)
        self.findings.append(f)
        if self.halt:
            raise SanitizerError(f)

    # ----------------------------------------------------- block hooks
    def on_alloc(self, blocks, uid=None):
        """Allocator handed out ``blocks`` (kv-block FSM free ->
        allocated)."""
        self.checks += 1
        for b in blocks:
            b = int(b)
            if b == self.scratch_block:
                self._emit(SCRATCH_WRITE,
                           f"allocator handed out the reserved scratch "
                           f"block {b}", block=b, uid=uid)
                continue
            if self.shadow.get(b, FREE) != FREE:
                self._emit(USE_AFTER_FREE,
                           f"block {b} allocated while shadow state is "
                           f"{self.shadow.get(b)!r} (held by uid "
                           f"{self.refs.get(b)}) — overlapping tenants",
                           block=b, uid=uid)
                continue
            self.shadow[b] = ALLOCATED

    def on_attach(self, uid, blocks):
        """A live slot's block table now references ``blocks`` for
        ``uid`` (the seat after prefill)."""
        self.checks += 1
        uid = int(uid)
        blocks = [int(b) for b in blocks]
        for b in blocks:
            if b == self.scratch_block:
                self._emit(SCRATCH_WRITE,
                           f"scratch block {self.scratch_block} entered "
                           f"uid {uid}'s live block table — decode "
                           f"writes would corrupt the shared scratch "
                           f"row", block=b, uid=uid)
                continue
            if self.shadow.get(b, FREE) == FREE:
                self._emit(USE_AFTER_FREE,
                           f"uid {uid}'s block table references block "
                           f"{b}, which the shadow says is free — "
                           f"use-after-free", block=b, uid=uid)
                continue
            self.refs.setdefault(b, set()).add(uid)
            self._reshade(b)
        self.attached[uid] = blocks

    def on_detach(self, uid):
        """``uid``'s slot is being torn down; its table rows are about
        to be zeroed."""
        self.checks += 1
        uid = int(uid)
        for b in self.attached.pop(uid, ()):
            holders = self.refs.get(b)
            if holders is not None:
                holders.discard(uid)
                if not holders:
                    del self.refs[b]
            self._reshade(b)

    # ------------------------------------------------- sharing helpers
    def _holder_count(self, b):
        return len(self.refs.get(b, ())) + (1 if b in self.cache_blocks
                                            else 0)

    def _other_holder(self, b, uid):
        """A live uid other than ``uid`` referencing ``b`` (or None)."""
        for h in self.refs.get(b, ()):
            if uid is None or h != int(uid):
                return h
        return None

    def _reshade(self, b):
        """Recompute ALLOCATED vs SHARED from the holder count (the
        kv-block FSM's allocated <-> shared edges)."""
        state = self.shadow.get(b, FREE)
        if state in (FREE, QUARANTINED):
            return
        self.shadow[b] = SHARED if self._holder_count(b) >= 2 \
            else ALLOCATED

    def on_share(self, blocks, uid=None):
        """The prefix cache took a read-only reference on ``blocks``
        (insert at finish, or a restore re-established sharing)."""
        self.checks += 1
        for b in blocks:
            b = int(b)
            if self.shadow.get(b, FREE) in (FREE, QUARANTINED):
                self._emit(USE_AFTER_FREE,
                           f"prefix cache taking a reference on block "
                           f"{b} whose shadow state is "
                           f"{self.shadow.get(b)!r}", block=b, uid=uid)
                continue
            self.cache_blocks.add(b)
            self._reshade(b)

    def on_unshare(self, blocks):
        """The prefix cache dropped its reference (eviction or
        clear)."""
        self.checks += 1
        for b in blocks:
            self.cache_blocks.discard(int(b))
            self._reshade(int(b))

    def on_cow(self, src, dst, uid=None):
        """Copy-on-write: ``uid`` diverged inside shared block ``src``
        and received the fresh private clone ``dst`` (kv-block FSM
        shared -> cow -> allocated for the writer's copy)."""
        self.checks += 1
        src, dst = int(src), int(dst)
        if self.shadow.get(src, FREE) == FREE:
            self._emit(USE_AFTER_FREE,
                       f"copy-on-write from block {src}, which the "
                       f"shadow says is free", block=src, uid=uid)
        if self.shadow.get(dst, FREE) != ALLOCATED:
            self._emit(USE_AFTER_FREE,
                       f"copy-on-write into block {dst} whose shadow "
                       f"state is {self.shadow.get(dst)!r} — the clone "
                       f"must be a fresh private allocation",
                       block=dst, uid=uid)

    def on_quarantine(self, blocks, uid=None):
        """Blocks poisoned/quarantined (kv-block FSM allocated ->
        quarantined)."""
        self.checks += 1
        for b in blocks:
            b = int(b)
            if self.shadow.get(b, FREE) == SHARED \
                    or b in self.cache_blocks:
                self._emit(SCRUB_SHARED,
                           f"quarantining block {b} while shared "
                           f"(holders: uids "
                           f"{sorted(self.refs.get(b, ()))}, cache="
                           f"{b in self.cache_blocks}) — quarantine is "
                           f"legal only from the sole-owner "
                           f"'allocated' state", block=b, uid=uid)
                continue
            holder = self._other_holder(b, uid)
            if holder is not None:
                self._emit(SCRUB_REFERENCED,
                           f"quarantining block {b} still referenced by "
                           f"live uid {holder} (quarantine requested "
                           f"for uid {uid})", block=b, uid=uid,
                           holder=holder)
                continue
            if self.shadow.get(b, FREE) == ALLOCATED:
                self.shadow[b] = QUARANTINED

    def on_scrub(self, blocks, uid=None):
        """Blocks being scrubbed before returning to the pool.
        Scrubbing a block ANOTHER live sequence (or the prefix cache)
        still reads is the refcount violation sharing must never
        commit."""
        self.checks += 1
        for b in blocks:
            b = int(b)
            if self.shadow.get(b, FREE) == SHARED \
                    or b in self.cache_blocks:
                self._emit(SCRUB_SHARED,
                           f"scrubbing block {b} while shared (holders: "
                           f"uids {sorted(self.refs.get(b, ()))}, "
                           f"cache={b in self.cache_blocks}) — its K/V "
                           f"would be zeroed under other tenants",
                           block=b, uid=uid)
                continue
            holder = self._other_holder(b, uid)
            if holder is not None:
                self._emit(SCRUB_REFERENCED,
                           f"scrubbing block {b} while live uid "
                           f"{holder} still references it — its K/V "
                           f"would be zeroed under a running decode",
                           block=b, uid=uid, holder=holder)

    def on_free(self, blocks, uid=None):
        """Blocks returned to the free list (kv-block FSM allocated/
        quarantined -> free).  With sharing armed the allocator only
        reports blocks whose refcount actually hit zero here."""
        self.checks += 1
        for b in blocks:
            b = int(b)
            state = self.shadow.get(b, FREE)
            if state == FREE:
                self._emit(DOUBLE_FREE,
                           f"double free of block {b} (shadow already "
                           f"says free)", block=b, uid=uid)
                continue
            if b in self.cache_blocks:
                self._emit(USE_AFTER_FREE,
                           f"freeing block {b} the prefix cache still "
                           f"holds — cached prefixes would decode from "
                           f"a reused block", block=b, uid=uid)
            holder = self._other_holder(b, uid)
            if holder is not None:
                self._emit(USE_AFTER_FREE,
                           f"freeing block {b} still referenced by live "
                           f"uid {holder} — its table row would decode "
                           f"from a reused block", block=b, uid=uid,
                           holder=holder)
            self.shadow[b] = FREE

    def on_import(self, blocks, uid=None, resident=()):
        """A restore imported wire K/V into the fresh private ``blocks``
        (disaggregated handoff or crash migration).  ``resident`` is
        the engine's evidence list: cache-resident prompt blocks the
        restore imported a DUPLICATE of instead of incref-and-sharing —
        non-empty means the re-share path regressed (DSTPU317).  An
        imported block that the shadow says the cache holds is the same
        defect caught from the other side: wire bytes would overwrite a
        cached prefix under its readers."""
        self.checks += 1
        resident = [int(b) for b in resident]
        if resident:
            self._emit(DOUBLE_IMPORT,
                       f"restore of uid {uid} imported private "
                       f"duplicate(s) of {len(resident)} prefix-cache-"
                       f"resident block(s) {resident[:16]} — the restore "
                       f"path must incref-and-share resident prefixes, "
                       f"not re-import them", blocks=resident[:64],
                       uid=uid)
        for b in blocks:
            b = int(b)
            if b in self.cache_blocks:
                self._emit(DOUBLE_IMPORT,
                           f"restore of uid {uid} imported wire K/V "
                           f"into block {b}, which the prefix cache "
                           f"still holds — cached readers would decode "
                           f"the imported stream's bytes", block=b,
                           uid=uid)

    # ------------------------------------------------------- uid hooks
    def on_serve(self, uid):
        """A result left the engine (request-uid FSM completed ->
        popped; popped is terminal)."""
        self.checks += 1
        uid = int(uid)
        if uid in self.served:
            self._emit(DOUBLE_SERVE,
                       f"uid {uid} served twice — results are "
                       f"pop-once (the crash-handoff dedup contract)",
                       uid=uid)
            return
        self.served.add(uid)

    # ------------------------------------------------------------ close
    def on_close(self):
        """Engine teardown: every block must have come home."""
        self.checks += 1
        leaked = sorted(b for b, s in self.shadow.items() if s != FREE)
        if leaked:
            holders = {b: sorted(self.refs[b]) for b in leaked
                       if self.refs.get(b)}
            self._emit(LEAK_AT_CLOSE,
                       f"{len(leaked)} block(s) still "
                       f"allocated/quarantined at close: {leaked[:16]}"
                       f"{'...' if len(leaked) > 16 else ''}",
                       blocks=leaked[:64], holders={str(k): v for k, v
                                                    in holders.items()
                                                    if v is not None})

    # ------------------------------------------------------------ stats
    def stats(self) -> dict:
        live = sum(1 for s in self.shadow.values()
                   if s in (ALLOCATED, SHARED))
        shared = sum(1 for s in self.shadow.values() if s == SHARED)
        return {"checks": self.checks, "findings": len(self.findings),
                "live_blocks": live, "shared_blocks": shared,
                "cache_blocks": len(self.cache_blocks),
                "served_uids": len(self.served)}


def describe(config_enabled=False, halt=True) -> dict:
    """Resolved sanitize policy for ``ds_report`` (mirrors the
    comms-compression/monitor describe pattern)."""
    env = env_enabled()
    return {
        "enabled": resolve_enabled(config_enabled),
        "source": ("env DSTPU_SANITIZE" if env is not None
                   else "config analysis.sanitize"
                   if config_enabled else "default (off)"),
        "halt": bool(halt),
        "codes": dict(zip(SANITIZER_CODES,
                          ("double-free", "use-after-free",
                           "leak-at-close", "scratch-block-write",
                           "uid-double-serve",
                           "scrub-while-referenced",
                           "scrub-while-shared",
                           "double-import"))),
    }
