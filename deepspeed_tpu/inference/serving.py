"""Production inference serving: continuous batching over the paged KV pool.

Role parity: the reference ships fused inference kernels and an
``InferenceEngine`` but no request scheduler — serving is delegated to
MII/externals.  This module is that missing layer, built TPU-first:

- **continuous (in-flight) batching** — a FIFO request queue feeds a
  fixed-width decode batch (``batch_slots``); sequences JOIN a free slot
  the step after their prefill and EVICT the step they finish, so the
  decode executable never re-specializes while traffic churns (one
  compiled step per serving configuration, AOT-warm-started from the
  persistent compile cache across restarts);
- **paged KV cache** — slots hold per-sequence block lists into one
  shared pool (``paged_kv.py``), with slot/block reuse on completion and
  an optional int8 pool (block-quantized via the ZeRO++ quantizer,
  ``runtime/comm/quantized.py``) halving the KV byte term;
- **fused decode** — the token step is the models' stacked-scan paged
  decode (``GPT2.decode_step_paged``): ONE executable per step for all
  slots, not 4·L separately scheduled small matmuls with scheduling
  gaps between them;
- **one round trip a step** — the step's slot state (tables, lengths,
  tokens, seeds, indices, temperatures, flags) stays on the device and
  is advanced in-graph; the host sends it up, as one packed buffer, only
  after a slot was seated, cleared or ingested, and reads one packed
  buffer back (docs/serving.md#step-anatomy);
- **one step in flight** — while no slot changes, ``step()`` dispatches
  decode step N+1 on the device's own advanced state BEFORE it reads
  step N, so the chip computes while the host wakes up, reads and books;
  whatever changes a slot or needs the host's view of a token (an
  admission, a finish, a snapshot, a drain, any outside
  reader of slot state) settles the unread step first
  (docs/serving.md#one-step-in-flight);
- **admission control** — capacity math (blocks needed vs free) gates
  the queue, and the decode executable's ``memory_analysis()`` is
  preflighted against the HBM budget BEFORE any step executes (the same
  protocol as ``DeepSpeedEngine.preflight_memory`` / the bench ladder),
  so a mis-sized pool refuses to start instead of dying
  RESOURCE_EXHAUSTED mid-traffic;
- **latency accounting** — per-request submit→first-token and
  submit→done stamps; p50/p99/p999 from mergeable log-bucketed
  histograms over EVERY completion (``stats()``; exact counts, ≤1%
  value error, bounded memory — ``monitor/histogram.py``); long-running
  servers drain finished records with ``pop_result(uid)`` so
  ``results`` never grows unbounded.

Resilience (docs/serving.md#resilience — the serving twin of the
training fault ladder, PR 1/3/7 composed):

- **deadlines + overload policy** — per-request ``deadline_ms``
  enforced at admit (predictively, against the measured decode-step
  EMA) and per decode step; queue admission follows
  ``ServingConfig.overload`` (``reject`` | ``shed_oldest`` | ``block``)
  with hysteresis watermarks, so sustained overload degrades to
  bounded-latency shedding instead of unbounded queueing;
- **poisoned-request quarantine** — an in-graph per-slot non-finite
  sentinel on the decode logits (``runtime/health.rows_nonfinite``; no
  host callbacks, sampling branchlessly forced to a sentinel token)
  with host-side eviction, block scrubbing + return, and a circuit
  breaker that trips to reject-all with a forensic ring dump when the
  poison rate exceeds ``poison_budget``;
- **crash-recoverable in-flight state** — a rank-0 append-only request
  journal (``inference/journal.py``); a restarted engine re-queues lost
  in-flight requests and regenerates token-identical answers;
- **graceful drain** — ``drain(timeout_s)`` stops admission, finishes
  the active slots and journals a clean shutdown; ``close()`` drains.

Every terminal outcome is typed (``OK``/``SHED``/``DEADLINE``/
``POISONED`` in the result record's ``outcome``; ``QueueFullError``/
``ServingStalledError``/``CircuitOpenError`` raised), and the
shed/deadline/poisoned/requeued totals ride the monitor bus as counters
(rendered by ``ds_top``).

Determinism: each request's sampling stream is
``fold_in(PRNGKey(request.seed), token_index)`` — a function of the
request alone, never of batch composition — and slots compute
independently (row-independent matmuls, per-slot attention masks), so
the same requests produce the same tokens REGARDLESS of arrival order,
slot assignment, or what else shares the batch (tested:
``tests/test_serving.py::test_arrival_order_determinism``).
"""

import collections
import dataclasses
import functools
import os
import shutil
import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import paged_kv as pk
from .. import fault
from ..monitor import spans as monspans
from ..monitor.histogram import LogHistogram
from ..monitor.ring import RingBuffer
from ..runtime.health import rows_nonfinite, write_forensics
from ..utils.logging import logger, log_dist


# ------------------------------------------------------------ typed results
# terminal outcomes, carried in every result record's "outcome" field
OK = "ok"                 # completed normally (length or eos)
SHED = "shed"             # dropped by the overload policy before serving
DEADLINE = "deadline"     # could not finish by its deadline (at admit or
#                           mid-decode; mid-decode keeps the partial tokens)
POISONED = "poisoned"     # quarantined: drove the decode logits non-finite
TRANSFERRED = "transferred"   # prefill role: handed off to the transfer
#                               queue — the DECODE worker owns the stream
#                               now (docs/serving.md#disaggregation)

OUTCOMES = (OK, SHED, DEADLINE, POISONED, TRANSFERRED)

# token the in-graph sentinel forces into a poisoned slot's sample (the
# value is irrelevant — the scheduler evicts the slot the same step and
# never appends it — it only has to be a valid vocab id)
POISON_SENTINEL_TOKEN = 0


class ServingError(RuntimeError):
    """Base of the serving layer's typed errors."""


class QueueFullError(ServingError):
    """``submit()`` refused: the queue is at its high watermark under
    ``overload: reject`` (callers can distinguish load shedding from a
    malformed request, which raises ``ValueError``)."""


class KVRestoreError(ServingError):
    """A KV snapshot could not be restored into this engine (torn or
    corrupt image, mismatched geometry, no capacity).  Always caught by
    :meth:`ServingEngine.submit_restored`, which degrades the stream to
    the plain recompute queue with a typed ``migration_fallback``
    monitor event — the error type exists so that fallback is a
    decision, never an accident."""


class ServingStalledError(ServingError):
    """The scheduler cannot make progress: requests are queued, zero
    slots are active, and admission seated nothing — or ``run()``
    overran its step bound.  The message carries the blocking request's
    block math."""


class CircuitOpenError(ServingError):
    """The poison circuit breaker tripped: new submissions are rejected
    until the operator investigates (the forensic dump path is in the
    message and on the monitor bus)."""


# ------------------------------------------- KV snapshot/migration config
KV_SNAPSHOT_DIR = "kv_snapshots"


def stream_snapshot_dir(journal_dir: str, uid: int) -> str:
    """On-disk home of one stream's committed KV snapshot images —
    beside the request journal, one atomic-checkpoint ``save_dir`` per
    uid (tags inside, newest = deepest decode position), so a router
    reaches a dead replica's snapshots exactly the way it already
    reaches its journal."""
    return os.path.join(journal_dir, KV_SNAPSHOT_DIR, f"uid-{int(uid):08d}")


@dataclasses.dataclass
class KVSnapshotConfig:
    """The ``serving.kv_snapshot`` block (docs/serving.md#kv-migration).

    Off by default.  Arming needs ``journal_dir``: snapshots only make
    sense where a journal already makes the uid durable, and they live
    beside it.  Everything here is host-side — the compiled decode step
    is byte-identical armed vs off (PR-9 discipline, asserted by the
    tier-1 jaxpr-equality test)."""
    every_tokens: int = 32    # per-stream cadence, in emitted tokens
    keep_n: int = 2           # retained images per stream (the
    #                           checkpoint.keep_n mirror; retention's
    #                           terminal half is deletion at finish/close)
    export_on_evict: bool = True  # final image at a DEADLINE eviction —
    #                               the partial work stays restorable
    verify: str = "full"      # manifest level a restore demands:
    #                           full | size | off (per-block digests
    #                           are always checked)

    def __post_init__(self):
        assert self.every_tokens >= 1, \
            f"kv_snapshot.every_tokens must be >= 1, got {self.every_tokens}"
        assert self.keep_n >= 1, \
            f"kv_snapshot.keep_n must be >= 1, got {self.keep_n}"
        assert self.verify in ("full", "size", "off"), \
            f"kv_snapshot.verify must be full|size|off, got {self.verify!r}"

    @classmethod
    def from_value(cls, v):
        """None/False → off; True → defaults; dict → the JSON block."""
        if not v:
            return None
        if v is True:
            return cls()
        if isinstance(v, cls):
            return v
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(v) - known
        if unknown:
            raise ValueError(
                f"unknown serving.kv_snapshot keys: {sorted(unknown)} "
                f"(known: {sorted(known)})")
        return cls(**v)

    def describe(self) -> dict:
        return {"enabled": True, "every_tokens": self.every_tokens,
                "keep_n": self.keep_n,
                "export_on_evict": self.export_on_evict,
                "verify": self.verify,
                "handoff": "restore-first, recompute-fallback",
                "wire_format": "int8+scales block image, per-block sha256"}


def describe_kv_snapshot(value=None) -> dict:
    """Resolved snapshot/migration policy for ``bin/ds_report``."""
    kvs = KVSnapshotConfig.from_value(value)
    if kvs is None:
        return {"enabled": False,
                "defaults_when_armed": KVSnapshotConfig().describe()}
    return kvs.describe()


# ------------------------------------------- prefix sharing config (PR 19)
@dataclasses.dataclass
class PrefixCacheConfig:
    """The ``serving.prefix_cache`` block (docs/serving.md#prefix-
    sharing): block-granular copy-on-write radix cache over the paged
    pool.  Off by default.  Entirely host-side bookkeeping — block
    tables are runtime operands of the compiled decode step, so the
    decode jaxpr is byte-identical armed vs off, and outputs are
    token-identical to the unshared path (the suffix-only prefill
    replays the prompt through the SAME decode executable and samples
    the first token at the same ``fold_in(seed, 0)`` index)."""
    max_blocks: int = 0        # cached-block cap; 0 = evict only under
    #                            pool pressure (admission's retry path)
    min_prefix_blocks: int = 1  # smallest full-block match worth sharing

    def __post_init__(self):
        assert self.max_blocks >= 0, \
            f"prefix_cache.max_blocks must be >= 0, got {self.max_blocks}"
        assert self.min_prefix_blocks >= 1, \
            f"prefix_cache.min_prefix_blocks must be >= 1, " \
            f"got {self.min_prefix_blocks}"

    @classmethod
    def from_value(cls, v):
        """None/False → off; True → defaults; dict → the JSON block."""
        if not v:
            return None
        if v is True:
            return cls()
        if isinstance(v, cls):
            return v
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(v) - known
        if unknown:
            raise ValueError(
                f"unknown serving.prefix_cache keys: {sorted(unknown)} "
                f"(known: {sorted(known)})")
        return cls(**v)

    def describe(self) -> dict:
        return {"enabled": True, "max_blocks": self.max_blocks,
                "min_prefix_blocks": self.min_prefix_blocks,
                "hash": "chained sha256 over int32 token blocks, "
                        "full-content verified (collision -> miss)",
                "cow": "first divergent token (private block clone)",
                "eviction": "LRU over unreferenced leaf entries only",
                "capacity": "admission charges unique blocks "
                            "(analysis/capacity.request_unique_blocks)"}


def describe_prefix_cache(value=None) -> dict:
    """Resolved prefix-sharing policy for ``bin/ds_report``."""
    pc = PrefixCacheConfig.from_value(value)
    if pc is None:
        return {"enabled": False,
                "defaults_when_armed": PrefixCacheConfig().describe()}
    return pc.describe()


@dataclasses.dataclass
class ServingConfig:
    """Knobs for one serving deployment (docs/serving.md has the
    capacity math; JSON surface: the ``serving`` block in
    docs/config-json.md)."""
    batch_slots: int = 8            # fixed decode batch width
    block_size: int = 16            # tokens per KV block
    # pool blocks INCLUDING the reserved scratch block 0; 0 → auto:
    # every slot can hold max_seq tokens (the no-eviction-safe maximum)
    num_blocks: int = 0
    # for a model with sliding-window layers (docs/serving.md#window-layers):
    # blocks of the WINDOW kind, its scratch block included; 0 → auto:
    # every slot can hold a full ring
    window_num_blocks: int = 0
    kv_bits: int = 16               # 16 | 8 (int8 payloads + block scales)
    kv_quant_block: int = 64        # quantizer block over the head dim
    max_new_tokens: int = 64        # per-request default
    top_k: Optional[int] = None     # static: part of the compiled step
    eos_token_id: Optional[int] = None
    preflight: bool = True          # memory-gate startup (see preflight())
    hbm_budget_bytes: Optional[int] = None   # None → backend memory_stats
    preflight_safety: float = 0.92  # allocator headroom
    max_queue: int = 4096
    # ---- resilience block (docs/serving.md#resilience) ----
    deadline_ms: Optional[float] = None   # per-request default; None = none
    overload: str = "reject"        # reject | shed_oldest | block
    queue_high_watermark: int = 0   # 0 → max_queue
    queue_low_watermark: int = 0    # 0 → 3/4 of the high watermark
    poison_budget: int = 4          # breaker trips when poisoned count in
    poison_window: int = 64         # the last `poison_window` outcomes
    #                                 EXCEEDS the budget
    journal_dir: Optional[str] = None     # None = journaling off
    forensic_dir: Optional[str] = None    # None → journal_dir or cwd
    drain_timeout_s: float = 60.0   # close()'s drain bound
    # ---- request tracing (docs/monitoring.md#request-tracing) ----
    # fraction of requests that carry a host-side trace (submit →
    # queue-wait → prefill → per-decode-step → finish, emitted as a
    # schema-v2 `trace` event; exportable as Chrome trace-event JSON).
    # Sampling is a pure function of the uid, so replicas/restarts
    # sample the same requests.  0.0 = off; needs an armed monitor.
    trace_sample_rate: float = 0.0
    # ---- shadow sanitizer (docs/static-analysis.md#sanitizer) ----
    # None → resolve from env DSTPU_SANITIZE / `deepspeed --sanitize`
    # (OFF by default); True/False pin it.  Pure host-side shadow
    # bookkeeping — the compiled decode step is byte-identical armed
    # vs off (--audit-step serving-lifecycle proves it).
    sanitize: Optional[bool] = None
    sanitize_halt: bool = True      # raise at the first finding
    # ---- KV snapshot/migration (docs/serving.md#kv-migration) ----
    # None/false = off; true = defaults; or the JSON block
    # {"every_tokens": 32, "keep_n": 2, "export_on_evict": true,
    # "verify": "full"}.  Needs journal_dir (images live beside the
    # journal); restore-first crash handoff reads them via the router.
    kv_snapshot: Any = None
    # ---- prefix sharing (docs/serving.md#prefix-sharing) ----
    # None/false = off; true = defaults; or the JSON block
    # {"max_blocks": 0, "min_prefix_blocks": 1}.  Copy-on-write radix
    # cache over the paged pool: co-batched and successive requests
    # share the KV blocks of a common prompt prefix, prefill skips
    # every shared block, and admission charges UNIQUE blocks.  Outputs
    # stay token-identical to the unshared path and the compiled decode
    # step is byte-identical on/off.
    prefix_cache: Any = None
    # ---- prefill/decode disaggregation (docs/serving.md#disaggregation) ----
    # "mixed" (default) = the classic engine, byte-identical to a build
    # without roles.  "prefill" runs bucketed prefill only and publishes
    # each stream's paged-KV blocks + seat record on the transfer queue;
    # "decode" admits from the queue via the KVRestoreError-guarded
    # restore path and runs pure fused-scan decode at steady cadence.
    # Either role degrades to mixed per-stream when the queue misbehaves
    # (backpressure, torn image) — never blocks, never drops.
    role: str = "mixed"
    # None/false = off; true = defaults; or the JSON block
    # {"dir": ..., "max_pending": 64, "keep_n": 128, "verify": "full"}.
    # The queue dir defaults to <journal_dir>/kv_transfer.  Armed
    # implicitly by role != "mixed".
    transfer: Any = None

    @classmethod
    def from_dict(cls, d: dict) -> "ServingConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown serving config keys: {sorted(unknown)}"
                             f" (known: {sorted(known)})")
        return cls(**d)


@dataclasses.dataclass
class Request:
    """One generation request.  ``seed`` alone determines the sampling
    stream (see module docstring); ``uid`` is assigned by ``submit``
    when absent."""
    tokens: Any                     # 1-D int32 prompt
    max_new_tokens: Optional[int] = None
    temperature: float = 1.0
    do_sample: bool = False
    seed: int = 0
    uid: Optional[int] = None
    # latency budget from submit time (None → serving.deadline_ms;
    # float("inf") opts OUT of a config default).  A relative budget,
    # not a wall-clock instant: a recovered engine re-arms it at requeue
    # time (monotonic clocks don't survive a restart, and a re-run
    # request deserves a fresh budget).
    deadline_ms: Optional[float] = None


def _pack_read(*parts):
    """In-graph: everything the host reads after a decode dispatch — (B,)
    or (B, n) parts — as ONE (B, columns) int32 array: one device-to-host
    copy, not one per value.  The poison flag rides beside the token: the
    sentinel token is a valid id."""
    return jnp.concatenate(
        [p.astype(jnp.int32).reshape(p.shape[0], -1) for p in parts], axis=1)


def _mem_analysis(exe) -> Optional[dict]:
    """Shared executable-memory reading (``runtime/compile_cache.py``)
    — one implementation for every preflight gate."""
    from ..runtime.compile_cache import executable_memory_analysis
    return executable_memory_analysis(exe)


# A decode step that has been dispatched and not yet read: the buffer on
# its way down, the slots it was dispatched for, and when its upload began.
_Unread = collections.namedtuple("_Unread", "read active t0")


class _Slot:
    """Host-side state of one active decode-batch slot."""

    def __init__(self, req: Request, blocks: List[int], prompt_len: int,
                 max_new: int, wblocks: Optional[List[int]] = None):
        self.req = req
        self.blocks = blocks
        # window-kind blocks, the slot's ring (docs/serving.md#window-layers)
        self.wblocks = wblocks or []
        self.prompt_len = prompt_len
        self.max_new = max_new
        self.out_tokens: List[int] = []
        # committed token history (prompt + emitted), kept as the tokens
        # arrive: what the prefix cache keys a finished stream's blocks by
        self.hist: List[int] = [int(t) for t in np.asarray(req.tokens)]
        # ---- prefix sharing (docs/serving.md#prefix-sharing) ----
        # pending is None on the plain path; a prefix-hit slot seats
        # with the not-yet-ingested prompt tail here and replays it
        # through the decode step (teacher-forced), so TTFT collapses
        # to the new-suffix cost without a second prefill executable
        self.pending: Optional[List[int]] = None
        self.shared_blocks = 0          # leading blocks borrowed read-only
        self.shared_keys: List[str] = []  # their radix chain (insert parents)
        # restored-from-image KV is wire-precision, not prefill output:
        # never publish it into the prefix cache
        self.wire_kv = False
        # disaggregation: a prefill-role stream the transfer queue
        # refused (backpressure / publish failure) decodes LOCALLY —
        # the per-stream degrade-to-mixed latch
        self.no_transfer = False
        # where the cache folds (docs/serving.md#folded-cache): the windows
        # whose rows are summary blocks by now, at the head of ``blocks``
        self.folded = 0


# ------------------------------------------- what needs a stream to be its blocks
# `prefix_cache`, `kv_snapshot`, `transfer` and a `role` each move or share a
# stream BY its K/V blocks: they need a stream to be nothing but the `k` / `v`
# blocks of one growing table.  Four kinds of serving state are more than
# that (a model may show more than one), and serving one with such a feature
# armed would serve a silently wrong stream: refused at construction, by name,
# until the feature learns the kind (ROADMAP, Queue 2).  kind -> (what the
# error calls it, {feature: reason}); the kind is also its anchor in
# docs/serving.md.
_SHIPS_IMAGES = ("the transfer queue ships block images: the decode side would "
                 "seat K/V without the recurrent rows")
_RING_IMAGE = ("a block image covers the growing table's blocks alone: a "
               "restored stream's window layers would read another stream's "
               "ring")
_KV_IMAGE = "a block image is int8 K and V with scales a head"
_FOLDED_IMAGE = ("a block image is the blocks of a growing table, one a "
                 "block of tokens: a folded stream's table is summary blocks "
                 "and a window, and the image does not say which is which")
_NEEDS_BLOCKS_ALONE = {
    "recurrent-state": ("recurrent state", {
        "prefix_cache": "a shared prefix's blocks carry no recurrent state: "
                        "the borrower's scan would start from zeros, not from "
                        "the prefix's end",
        "kv_snapshot": "a block image holds K/V only: a restored stream would "
                       "resume with another stream's recurrent rows",
        "transfer": _SHIPS_IMAGES, "role": _SHIPS_IMAGES}),
    "window-layers": ("sliding-window layers", {
        "prefix_cache": "a ring block is overwritten as the window slides: a "
                        "prefix's window-layer blocks cannot be shared "
                        "read-only",
        "kv_snapshot": _RING_IMAGE, "transfer": _RING_IMAGE,
        "role": _RING_IMAGE}),
    "latent-pool": ("a latent KV pool", {
        "prefix_cache": "the radix cache's copy-on-write is written for k and "
                        "v leaves",
        "kv_snapshot": _KV_IMAGE, "transfer": _KV_IMAGE, "role": _KV_IMAGE}),
    "compacted-window": ("a cache that folds its windows", {
        "prefix_cache": "the radix cache keys a block by the block of token "
                        "ids it holds: a summary block is no function of one "
                        "block of ids, and a window's blocks go back to the "
                        "pool at its fold",
        "kv_snapshot": _FOLDED_IMAGE, "transfer": _FOLDED_IMAGE,
        "role": _FOLDED_IMAGE}),
}


def _refuse_what_needs_blocks_alone(config, model, pool):
    """Raise, by name, for the first feature of ``_NEEDS_BLOCKS_ALONE`` that
    ``config`` arms over a kind of state that ``model`` and its ``pool``
    show: EVERY kind they show is named, each with its reason and its anchor
    (a model may show two: recurrent rows beside a ring)."""
    shown = {"recurrent-state": getattr(model, "has_recurrent_state", False),
             "window-layers": getattr(model, "has_window_layers", False),
             "latent-pool": pk.is_latent_pool(pool),
             "compacted-window": getattr(model, "has_folded_cache", False)}
    kinds = [kind for kind in _NEEDS_BLOCKS_ALONE if shown[kind]]
    # the table may be ragged: a feature is refused for the kinds that list it
    for name in dict.fromkeys(n for k in kinds
                              for n in _NEEDS_BLOCKS_ALONE[k][1]):
        value = getattr(config, name)
        if value in (None, False, "mixed"):
            continue
        hit = [k for k in kinds if name in _NEEDS_BLOCKS_ALONE[k][1]]
        whats = " and ".join(_NEEDS_BLOCKS_ALONE[k][0] for k in hit)
        reasons = "; ".join(_NEEDS_BLOCKS_ALONE[k][1][name] for k in hit)
        anchors = ", ".join(f"docs/serving.md#{k}" for k in hit)
        raise ValueError(f"serving.{name}={value!r} cannot serve a model "
                         f"with {whats}: {reasons} ({anchors})")


class ServingEngine:
    """Continuous-batching scheduler over an :class:`InferenceEngine`.

    Build from a model (``ServingEngine(model=..., params=...)``) or an
    existing engine (``ServingEngine(engine=...)`` — int8 weights, TP
    mesh and the persistent compile cache carry over).  ``config`` is a
    :class:`ServingConfig`, a plain dict (the JSON ``serving`` block),
    or None for defaults.
    """

    @monspans.in_setup_span("setup.engine_init", engine="ServingEngine")
    def __init__(self, model=None, params=None, engine=None, config=None,
                 mesh=None, compile_cache=None, monitor=None,
                 **engine_kwargs):
        from .engine import InferenceEngine
        self._owns_engine = engine is None
        if engine is None:
            engine = InferenceEngine(model=model, params=params, mesh=mesh,
                                     compile_cache=compile_cache,
                                     **engine_kwargs)
        self.engine = engine
        # unified telemetry (docs/monitoring.md): pass a Monitor, True
        # (env-default run dir), or None -> env DSTPU_MONITOR decides.
        # The serving stats export rides the same bus/schema as training.
        from ..monitor import core as moncore
        if monitor is None:
            monitor = bool(moncore.env_enabled(False))
        self._owns_monitor = not hasattr(monitor, "armed")
        if monitor is True:
            monitor = moncore.Monitor(run_dir=moncore.resolve_run_dir(),
                                      role="serving")
        self.monitor = monitor if monitor else moncore.NullMonitor()
        if config is None:
            config = ServingConfig()
        elif isinstance(config, dict):
            config = ServingConfig.from_dict(config)
        self.config = config
        assert config.kv_bits in (8, 16)
        assert config.batch_slots >= 1 and config.block_size >= 1
        assert config.overload in ("reject", "shed_oldest", "block"), \
            f"serving.overload must be reject|shed_oldest|block, " \
            f"got {config.overload!r}"
        assert 0.0 <= config.trace_sample_rate <= 1.0, \
            f"serving.trace_sample_rate must be in [0, 1], " \
            f"got {config.trace_sample_rate!r}"

        # quantized-weight routing: the SAME helper InferenceEngine
        # .generate uses (models whose decode consumes int8 leaves
        # directly get raw params; otherwise dequantize once per jitted
        # call) — one implementation, no drift between the paths
        from ..module_inject.module_quantize import resolve_decode_params
        inner, self._deq = resolve_decode_params(engine.module)
        assert getattr(inner, "supports_paged_decode", False), \
            f"{type(inner).__name__} has no paged decode path"
        self.model = inner
        mc = inner.config
        self.max_seq = mc.max_seq
        # a model whose cache folds itself (docs/serving.md#folded-cache)
        # says what a stream holds at each length: the table's columns, the
        # admission sum and the granted block all read it
        self._fold = (inner.cache_fold(config.block_size)
                      if getattr(inner, "has_folded_cache", False) else None)
        if self._fold is None:
            self.nb_max = pk.blocks_needed(mc.max_seq, config.block_size)
        else:
            self.nb_max = self._fold.table_blocks(mc.max_seq)
            if config.kv_bits != 16:
                raise ValueError(
                    f"serving.kv_bits={config.kv_bits} cannot serve a model "
                    "with a cache that folds its windows: a summary row is "
                    "kept at 16 bits (docs/serving.md#compacted-window)")
        self.num_blocks = config.num_blocks or (
            1 + config.batch_slots * (
                self.nb_max if self._fold is None
                else self._fold.life_peak(mc.max_seq)))
        assert self.num_blocks >= 2, "num_blocks must be >= 2"

        cache_dtype = getattr(inner, "dtype", jnp.bfloat16)
        # block images quantize over the same blocks an int8 pool does:
        # a divisor of the head dim, never spanning two heads
        self._kv_quant_block = pk.pick_block(mc.head_dim,
                                             config.kv_quant_block)
        # the model owns its serving state: K/V blocks for every family,
        # and for one with recurrent layers the per-slot rows beside them
        # (docs/serving.md#recurrent-state) — ONE pytree, donated whole
        # through every prefill and decode dispatch
        self._recurrent = bool(getattr(inner, "has_recurrent_state", False))
        # a model with sliding-window layers keeps TWO kinds of block
        # (docs/serving.md#window-layers): the growing table's, and a ring
        # of `ring` entries over its window layers with an allocator of
        # its own.  The slot's table carries both, the ring last.
        self.ring = self.window_num_blocks = 0
        self.window_allocator = None
        state_kw = {}
        if getattr(inner, "has_window_layers", False):
            self.ring = inner.ring_entries(config.block_size)
            self.window_num_blocks = config.window_num_blocks or (
                1 + config.batch_slots * self.ring)
            assert self.window_num_blocks >= 2, "window_num_blocks >= 2"
            self.window_allocator = pk.BlockAllocator(self.window_num_blocks)
            state_kw["window_num_blocks"] = self.window_num_blocks
        with monspans.recorder().setup_span("setup.pool_alloc") as alloc, \
                jax.set_mesh(engine.mesh):
            self.pool = inner.init_serving_state(
                config.batch_slots, self.num_blocks, config.block_size,
                kv_bits=config.kv_bits, quant_block=config.kv_quant_block,
                dtype=cache_dtype, **state_kw)
            alloc.attrs = {"bytes": pk.pool_bytes(self.pool)}
        self._recurrent_bytes = (inner.recurrent_state_bytes(self.pool)
                                 if self._recurrent else 0)
        # what a decode step's state update moves for one live slot, where
        # the model says (a step's span carries it times the slots seated)
        self._state_step_bytes = (inner.state_step_bytes()
                                  if hasattr(inner, "state_step_bytes")
                                  else None)
        _refuse_what_needs_blocks_alone(config, inner, self.pool)
        # what the model's layers count in a dispatch (an expert layer's
        # routed pairs): a small leaf of its serving state, read back with
        # the step's tokens and written into the step's span
        self._counter_names = tuple(getattr(inner, "step_counters", ()))
        self._route_attrs = {}
        self._kv_pool_bytes = pk.pool_bytes(self.pool) - self._recurrent_bytes \
            - (self.pool["counters"].nbytes if self._counter_names else 0)
        # what a token costs the pool: the model says how many layer-
        # applications keep K/V for it (every layer; a hybrid's attention
        # layers; a looped model's loops x layers)
        self._loop_attrs = {"kv_layers": int(mc.kv_layers),
                            "loop_steps": int(getattr(mc, "loop_steps", 1))}
        self._pool_attrs = {}
        self._state_seats = 0
        self.allocator = pk.BlockAllocator(self.num_blocks)
        # shadow lifecycle sanitizer (docs/static-analysis.md#sanitizer):
        # OFF by default; config pin wins, else env DSTPU_SANITIZE /
        # `deepspeed --sanitize`.  Pure host-side shadow bookkeeping —
        # one `is not None` test per hook when disarmed, and the
        # compiled decode step is byte-identical armed vs off
        # (--audit-step serving-lifecycle).
        from ..analysis import sanitize as _sanitize
        self._sanitizer = None
        armed = (_sanitize.resolve_enabled(False)
                 if config.sanitize is None else bool(config.sanitize))
        self._window_sanitizer = None
        if armed:
            self._sanitizer = _sanitize.ShadowSanitizer(
                self.num_blocks, scratch_block=pk.SCRATCH_BLOCK,
                halt=config.sanitize_halt)
            if self.ring:
                # the second kind's ids are its own: a shadow table each
                self._window_sanitizer = _sanitize.ShadowSanitizer(
                    self.window_num_blocks, scratch_block=pk.SCRATCH_BLOCK,
                    halt=config.sanitize_halt, kind="window")
            logger.warning("serving: shadow sanitizer ARMED "
                           "(DSTPU31x lifecycle checks, halt="
                           f"{config.sanitize_halt})")

        # KV snapshot/migration (docs/serving.md#kv-migration): periodic
        # per-stream block images beside the journal, restore-first crash
        # handoff.  Off by default; host-side only.
        self.kvs = KVSnapshotConfig.from_value(config.kv_snapshot)
        if self.kvs is not None and not config.journal_dir:
            raise ValueError(
                "serving.kv_snapshot needs journal_dir: snapshot images "
                "live beside the request journal, and a snapshot without "
                "a durable uid is unrestorable (docs/serving.md#kv-"
                "migration)")
        # restore-path compile warmup fires after the FIRST decode step
        # (see _warm_restore_path for why it cannot run here)
        self._kv_warm_pending = self.kvs is not None

        # prefix sharing (docs/serving.md#prefix-sharing): block-granular
        # COW radix cache over the paged pool.  Host-side bookkeeping
        # only — the decode jaxpr is byte-identical armed vs off
        # (--audit-step decode with the cache armed proves it).
        self.prefix = PrefixCacheConfig.from_value(config.prefix_cache)
        self._prefix_index = None
        if self.prefix is not None:
            self._prefix_index = pk.PrefixIndex(
                self.allocator, max_blocks=self.prefix.max_blocks)
            logger.info("serving: prefix cache ARMED "
                        f"({self.prefix.describe()})")

        # prefill/decode disaggregation (docs/serving.md#disaggregation):
        # role "mixed" is the classic engine — no queue, no publish, the
        # compiled decode step byte-identical to a roleless build.  A
        # role worker needs a queue directory (serving.transfer.dir or
        # <journal_dir>/kv_transfer).  Everything transfer-shaped is
        # host-side file I/O: the step jaxpr never changes.
        from . import transfer as xfer
        self.role = config.role or "mixed"
        if self.role not in xfer.ROLES:
            raise ValueError(
                f"serving.role must be one of {xfer.ROLES}, "
                f"got {config.role!r} (docs/serving.md#disaggregation)")
        self.transfer = xfer.TransferConfig.from_value(config.transfer)
        if self.role != "mixed" and self.transfer is None:
            self.transfer = xfer.TransferConfig()
        self._txq = None
        if self.transfer is not None:
            qdir = self.transfer.dir or (
                xfer.transfer_dir(config.journal_dir)
                if config.journal_dir else None)
            if qdir is None:
                raise ValueError(
                    "serving.role/transfer needs a queue directory: set "
                    "serving.transfer.dir or serving.journal_dir (the "
                    "queue defaults to <journal_dir>/kv_transfer — "
                    "docs/serving.md#disaggregation)")
            self._txq = xfer.TransferQueue(qdir, self.transfer)
            log_dist(
                f"serving: role={self.role} transfer queue at {qdir} "
                f"(max_pending={self.transfer.max_pending} "
                f"keep_n={self.transfer.keep_n})", ranks=[0])
        # transfer accounting (this engine's own publishes/claims; the
        # queue object carries the directory-level totals)
        self._transfers_total = 0
        self._transfer_bytes_total = 0
        self._transfer_backpressure_total = 0
        self._transfer_pub_ms: List[float] = []
        self._transfer_outbox: Dict[int, dict] = {}

        # Whoever else holds or reads a stream's blocks (the radix cache, a
        # snapshot image, the transfer queue) takes them all at the seat, as
        # every engine did before the pool's timeline: there a seat IS the
        # stream's whole life (docs/serving.md#capacity-math--admission-control)
        self._whole_life = (self.prefix is not None or self.kvs is not None
                            or self._txq is not None or self.role != "mixed")

        S = config.batch_slots
        self._slots: List[Optional[_Slot]] = [None] * S
        self._snap_last = np.zeros((S,), np.int32)  # ngen at last snapshot
        # what the pool's timeline sums, a column a slot (zeros where none
        # is seated) and one more for the stream that asks: tokens written
        # (filled in from `_lengths` when the rule is asked), the tokens
        # after which the stream has left at the latest, the blocks it holds
        self._timeline = np.zeros((3, S + 1), np.int64)
        _, self._ends, self._held = self._timeline[:, :S]
        self._promised = 0            # the timeline's peak at the last seat
        self._grown_total = 0         # blocks granted to seated rows
        self._folded_total = 0        # windows folded in decoding
        self._folded_reported = 0     # of them, by the last step's row
        self._foldfn = None           # a window's rows into summary rows
        self._tables = np.zeros((S, self.nb_max + self.ring), np.int32)
        self._lengths = np.zeros((S,), np.int32)
        self._toks = np.zeros((S,), np.int32)
        self._seeds = np.zeros((S,), np.int32)
        self._ngen = np.zeros((S,), np.int32)
        self._temps = np.ones((S,), np.float32)
        self._flags = np.zeros((S,), bool)
        # the seven mirrors above are the truth the scheduler reasons
        # with; the decode step runs on a device-resident copy of them
        # (operand order), advanced in-graph and re-sent — as ONE packed
        # buffer — only after a write other than the plain advance
        # (docs/serving.md#step-anatomy)
        self._resident = None
        self._state_dirty = True
        self._unpack = None           # packed (S, table + 6) → the seven
        self._grow = None             # a granted block into the device's tables
        self._reused_steps = 0        # decode steps that sent no state up
        self._state_uploads = 0
        # at most ONE decode step is dispatched and unread
        # (docs/serving.md#one-step-in-flight): the mirrors are then one
        # step behind the device's copy until `_settle` books it
        self._unread: Optional[_Unread] = None
        self._ahead_steps = 0         # dispatched while the last was unread
        self._admits_under = 0        # prefills dispatched under such a step
        self._t_read = float("-inf")  # when the newest step was read

        self.queue: deque = deque()
        # uid → record; completed records stay until the caller
        # pop_result()s them.  The latency aggregates are mergeable
        # log-bucketed histograms (monitor/histogram.py): bounded
        # memory, EXACT counts over the whole run — the bounded deques
        # they replace silently dropped history under sustained traffic,
        # so "p99" was really "p99 of the last 4096 completions"
        # (regression-tested in test_serving.py)
        self.results: Dict[int, dict] = {}
        self._lat_hist = LogHistogram()
        self._ttft_hist = LogHistogram()
        self._step_wall_hist = LogHistogram()   # decode-step wall, ms
        self._completed_total = 0
        self._generated_total = 0
        self._next_uid = 0
        self._steps = 0
        self._decode = None
        self._prefills = {}       # bucket length → CachedStep
        self._blockset = None     # jitted poison/scrub scatter (lazy)
        self._blockcopy = None    # jitted COW block clone (lazy)
        self._preflight_done = False
        self._preflight = None    # what the startup gate compared, once run

        # ---- resilience state (docs/serving.md#resilience) ----
        self._outcomes = {k: 0 for k in OUTCOMES}
        self._requeued_total = 0
        # KV migration accounting (docs/serving.md#kv-migration)
        self._kv_snapshots_total = 0
        self._kv_migrated_total = 0
        self._kv_fallback_total = 0
        self._kv_tokens_saved_total = 0
        self._kv_restore_ms: List[float] = []
        # (terminal, bad) totals at the last error_rate emission — the
        # SLO engine's windowed error-rate series (monitor/slo.py)
        self._err_window_last = (0, 0)
        # prefix-sharing accounting (counted once per SEATED request)
        self._prefix_requests_total = 0
        self._prefix_hits_total = 0
        self._prefix_shared_blocks_total = 0
        self._prefix_cow_total = 0
        self._prefix_evicted_total = 0
        self._breaker_open = False
        self._forensic_path = None
        self._draining = False
        self._closed = False
        self._step_ema_s = None   # measured decode-step wall EMA (the
        self._step_last_s = None  # predictive-deadline denominator; see
        #                           _step_estimate_s for the fast-bias)
        # bounded ring of recent terminal outcomes: the poison-rate
        # window AND the breaker's forensic payload (PR-9 RingBuffer)
        self._recent = RingBuffer(max(1, int(config.poison_window)))
        # ---- spans + request tracing (docs/monitoring.md) ------------
        # host-side only; nothing here touches the compiled step
        # (--audit-step tracing proves jaxpr equality armed vs disarmed).
        # The process-wide span recorder records armed or not.
        self._spans = monspans.recorder()
        self._startup_line_due = True
        # sampled live requests -> wall-clock submit time (the `trace`
        # event's anchor; everything else it carries is in results[uid])
        self._traces: Dict[int, float] = {}
        self._token_stamp = None      # the clock read of the newest step
        self._traces_emitted = 0
        self._exe_cost_emitted = False
        self.journal = None
        if config.journal_dir:
            from . import journal as jr
            recovered = jr.replay(config.journal_dir)
            self.journal = jr.RequestJournal(config.journal_dir)
            self._recover(recovered)
        log_dist(
            f"ServingEngine ready: slots={S} block_size={config.block_size} "
            f"blocks={self.num_blocks} (nb_max={self.nb_max}) "
            f"kv_bits={config.kv_bits} "
            f"pool={pk.pool_bytes(self.pool) / 1e6:.1f} MB", ranks=[0])

    # ------------------------------------------------------------- recovery
    def _recover(self, state):
        """Fold a replayed journal into this engine: finished records are
        restored into ``results`` (tokens + outcome — a caller polling a
        pre-crash uid still gets its answer), pending requests are
        RE-QUEUED in journal order, and ``_next_uid`` resumes past every
        journaled uid so fresh traffic cannot collide."""
        if state["max_uid"] >= 0:
            self._next_uid = state["max_uid"] + 1
        if state["clean_shutdown"] and not state["pending"]:
            # the previous generation drained clean with nothing left:
            # every journaled uid was answered and handed over, so the
            # history is dead weight — rotate instead of re-materializing
            # every request ever served into results on each restart
            self.journal.rotate()
            log_dist(
                f"serving journal: clean shutdown with nothing pending — "
                f"rotated {self.config.journal_dir}", ranks=[0])
            return
        for uid, rec in state["finished"].items():
            self.results[uid] = {
                "tokens": rec.get("tokens"), "outcome": rec.get("outcome"),
                "t_submit": None, "t_admit": None, "t_first": None,
                "t_tokens": None, "t_done": rec.get("t", 0.0),
                "prompt_len": None, "deadline": None, "recovered": True}
        for spec in state["pending"]:
            dl_ms = spec.get("deadline_ms")
            if dl_ms == "inf":     # journal spelling of float("inf")
                dl_ms = float("inf")
            req = Request(tokens=np.asarray(spec["tokens"], np.int32),
                          max_new_tokens=spec["max_new_tokens"],
                          temperature=spec.get("temperature", 1.0),
                          do_sample=spec.get("do_sample", False),
                          seed=spec.get("seed", 0), uid=spec["uid"],
                          deadline_ms=dl_ms)
            try:
                self.submit(req, _requeue=True)
            except ValueError as e:
                # the restart may run a SMALLER serving configuration
                # (fewer blocks, shorter max_seq — the elastic-resize
                # workflows): a pending request that no longer fits gets
                # a typed terminal outcome and a journal finish record
                # instead of wedging every restart in __init__ (degrade,
                # never die — recovery must recover the rest)
                logger.warning(
                    f"journal recovery: pending request {req.uid} no "
                    f"longer fits this serving configuration ({e}); "
                    f"finalized as '{SHED}'")
                self.results[req.uid] = {
                    "tokens": None, "outcome": None, "t_submit": None,
                    "t_admit": None, "t_first": None, "t_tokens": None,
                    "t_done": None, "prompt_len": None, "deadline": None,
                    "recovered": True}
                self._finalize_unseated(
                    req, SHED, "recovery: no longer fits this "
                    "configuration")
                continue
            self.journal.requeue(req.uid)
            self._requeued_total += 1
        if state["pending"]:
            self.journal.flush()
            torn = state.get("torn_lines", 0)
            foreign = state.get("foreign_lines", 0)
            log_dist(
                f"serving journal recovery: re-queued "
                f"{len(state['pending'])} in-flight request(s), restored "
                f"{len(state['finished'])} finished record(s) "
                f"(clean_shutdown={state['clean_shutdown']}"
                + (f", torn_lines={torn}" if torn else "")
                + (f", foreign_lines={foreign}" if foreign else "")
                + f") from {self.config.journal_dir}", ranks=[0])

    # ------------------------------------------------------------- capacity
    def capacity(self) -> dict:
        """The admission math (docs/serving.md): pool size, per-request
        block cost at the default generation length, concurrent-request
        bound."""
        c = self.config
        # the ONE function every capacity owner shares (admission here,
        # ds_mem serving_plan/max_streams, the ledger split) — PR 19
        from ..analysis.capacity import request_unique_blocks
        ub = request_unique_blocks(
            prompt_tokens=c.block_size, max_new_tokens=c.max_new_tokens,
            block_size=c.block_size, max_seq=self.max_seq)
        out = {
            "batch_slots": c.batch_slots,
            "block_size": c.block_size,
            "num_blocks": self.num_blocks,
            "allocatable_blocks": self.num_blocks - 1,
            "capacity_tokens": pk.capacity_tokens(self.pool),
            "pool_bytes": pk.pool_bytes(self.pool),
            "kv_bits": c.kv_bits,
            # a folded cache's request peaks at its last window's fold
            "blocks_per_request_at_defaults": (
                ub["total_blocks"] if self._fold is None
                else self._life_blocks(c.block_size + c.max_new_tokens)),
            "free_blocks": self.allocator.free_blocks,
        }
        if self.ring:
            # the second kind (docs/serving.md#window-layers): a stream
            # reserves what its own tokens fill, at most the ring
            out.update(
                window_num_blocks=self.window_num_blocks,
                window_ring_blocks=self.ring,
                window_free_blocks=self.window_allocator.free_blocks,
                window_blocks_per_request_at_defaults=self._window_need(
                    c.block_size + c.max_new_tokens))
        if self._prefix_index is not None:
            # admission counts UNIQUE blocks when the cache is armed —
            # surface the sharing split next to the classic math
            out["unique_blocks_in_use"] = self.allocator.used_blocks
            out["shared_blocks"] = self.allocator.shared_blocks
            out["logical_blocks"] = self.allocator.logical_blocks
            out["prefix_cached_blocks"] = self._prefix_index.cached_blocks
        return out

    # ------------------------------------------------------------ preflight
    def preflight_memory(self) -> Optional[dict]:
        """Peak-HBM estimate of the serving executables via
        ``memory_analysis()``, BEFORE anything executes — same protocol
        as ``DeepSpeedEngine.preflight_memory``.  Covers the decode step
        (the hot loop; its detail is the flat keys) AND the largest
        prefill bucket — a near-max_seq prompt arriving mid-traffic must
        not be the first time that executable's peak is discovered.
        ``peak_bytes`` is the max of the two.  None when the backend
        exposes no analysis."""
        self._build_decode()
        c = self.config
        bucket = self.nb_max * c.block_size
        if self._fold is None:
            pf = self._prefill_fn(bucket)
            blocks = jnp.zeros((bucket // c.block_size + self.ring,),
                               jnp.int32)
        else:
            # the largest segment of a folded prompt: a whole window
            bucket = self._fold.window
            pf = self._prefill_fn(bucket, window=True)
            blocks = jnp.zeros(
                (self.model.summary_table_blocks(c.block_size)
                 + self._fold.summary_blocks,), jnp.int32)
        toks = jnp.zeros((1, min(bucket, self.max_seq)), jnp.int32)
        with jax.set_mesh(self.engine.mesh):
            dec_exe = self._decode.executable(*self._decode_args())
            pre_exe = pf.executable(*self._prefill_args(
                toks, blocks, 0, 1, 0, 1.0, False))
        dec = _mem_analysis(dec_exe)
        if dec is None:
            return None
        out = dict(dec)
        pre = _mem_analysis(pre_exe)
        if pre is not None:
            out["prefill_max_bucket_peak_bytes"] = pre["peak_bytes"]
            out["peak_bytes"] = max(dec["peak_bytes"], pre["peak_bytes"])
        return out

    def _budget_bytes(self) -> Optional[int]:
        if self.config.hbm_budget_bytes is not None:
            return int(self.config.hbm_budget_bytes)
        from ..monitor.gauges import hbm_limit_bytes
        return hbm_limit_bytes()

    def _preflight_gate(self):
        """Refuse to serve a configuration whose decode step cannot fit
        the chip (admission control's outer gate; the inner gate is the
        per-request block math).  ``_preflight_done`` is only set on a
        PASS — a caller catching the MemoryError and calling ``step()``
        again re-runs the gate (and re-raises) instead of serving the
        configuration the preflight just rejected."""
        if not self.config.preflight:
            self._preflight_done = True
            return
        budget = self._budget_bytes()
        if budget is None:       # no budget, nothing to gate on — and no
            self._preflight_done = True       # point compiling the max-
            return                            # bucket prefill eagerly
        pre = self.preflight_memory()
        if pre is None:
            self._preflight_done = True
            return
        self._preflight = {"budget_bytes": budget,
                           "peak_bytes": pre["peak_bytes"]}
        if pre["peak_bytes"] > budget * self.config.preflight_safety:
            # pre-written post-mortem: the ledger + capacity verdict name
            # which subsystem blew the budget and which knob buys
            # headroom (docs/monitoring.md#memory-explainability)
            path = self._memory_forensics(
                f"serving preflight: peak {pre['peak_bytes']} B over "
                f"budget {budget} B", budget_bytes=budget,
                extra={"preflight": pre})
            raise MemoryError(
                f"serving preflight: decode step peak "
                f"{pre['peak_bytes'] / 1e9:.2f} GB exceeds "
                f"{self.config.preflight_safety:.0%} of the "
                f"{budget / 1e9:.2f} GB budget — shrink num_blocks/"
                "batch_slots, use kv_bits=8, or quantize the weights "
                "(docs/serving.md capacity math)"
                + (f"; memory forensics: {path}" if path else ""))
        self._preflight_done = True

    # ------------------------------------------------------------ submission
    def _breaker_gate(self):
        if self._breaker_open:
            raise CircuitOpenError(
                "serving circuit breaker is OPEN (poison rate exceeded "
                f"budget {self.config.poison_budget}); forensics: "
                f"{self._forensic_path}")

    def _watermarks(self):
        # clamped to max_queue: a high watermark beyond it must not
        # silently disable the queue's absolute bound
        high = min(self.config.queue_high_watermark
                   or self.config.max_queue, self.config.max_queue)
        low = self.config.queue_low_watermark or max(1, (high * 3) // 4)
        return high, min(low, high)

    def _apply_overload_policy(self):
        """The queue-admission gate at the high watermark: ``reject``
        raises typed, ``shed_oldest`` sheds queue-head requests down past
        the LOW watermark (hysteresis: one burst of shedding absorbs a
        sustained overload wave instead of per-submit churn), ``block``
        drives the scheduler until the queue drains below the mark."""
        high, low = self._watermarks()
        if len(self.queue) < high:
            return
        pol = self.config.overload
        if pol == "reject":
            raise QueueFullError(
                f"queue full ({len(self.queue)} >= high watermark {high}; "
                "overload=reject) — retry later, raise the watermark, or "
                "use overload=shed_oldest/block (docs/serving.md)")
        if pol == "shed_oldest":
            shed = 0
            while self.queue and len(self.queue) >= low:
                self._finalize_unseated(self.queue.popleft(), SHED,
                                      "overload: shed_oldest watermark")
                shed += 1
            logger.warning(
                f"serving overload: shed {shed} oldest queued request(s) "
                f"(queue hit {high}, drained below {low})")
            return
        # pol == "block": serve until the backlog clears the mark — the
        # scheduler makes progress or raises ServingStalledError itself
        while len(self.queue) >= high:
            self.step()

    def submit(self, req: Request, _requeue: bool = False) -> int:
        """Queue a request; returns its uid.  Rejects prompts whose
        worst-case length cannot fit ``max_seq`` or the pool (ValueError),
        refuses new work while the poison breaker is open
        (:class:`CircuitOpenError`) or a drain is in progress, and applies
        the configured overload policy at the queue's high watermark."""
        self._breaker_gate()
        if self._draining:
            raise ServingError("serving engine is draining: admission "
                               "is stopped")
        toks = np.asarray(req.tokens, np.int32).reshape(-1)
        if toks.size == 0:
            raise ValueError("empty prompt")
        new = (self.config.max_new_tokens if req.max_new_tokens is None
               else int(req.max_new_tokens))
        if new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {new}")
        total = toks.size + new
        if total > self.max_seq:
            raise ValueError(
                f"prompt {toks.size} + max_new_tokens {new} = {total} "
                f"exceeds max_seq {self.max_seq}")
        nb = self._life_blocks(total)
        if nb > self.num_blocks - 1:
            raise ValueError(
                f"request needs {nb} blocks; the pool only has "
                f"{self.num_blocks - 1} allocatable")
        need_w = self._window_need(total)
        if need_w > max(0, self.window_num_blocks - 1):
            raise ValueError(
                f"request needs {need_w} window blocks; the window pool "
                f"only has {self.window_num_blocks - 1} allocatable")
        if req.uid is not None and req.uid in self.results:
            # validated BEFORE the overload gate: an inadmissible
            # (duplicate-uid) submission must not shed legitimate queued
            # work on its way to a ValueError
            raise ValueError(
                f"uid {req.uid} already submitted — a duplicate would "
                "corrupt that request's result record")
        if not _requeue:
            # recovered requests were admitted once already; only fresh
            # traffic passes the overload gate
            self._apply_overload_policy()
            # overload='block' drove the scheduler, which may have
            # quarantined poison and TRIPPED the breaker mid-call —
            # reject-all must hold for this submission too
            self._breaker_gate()
        # mutate in place: the caller's handle keeps the uid submit
        # assigns and the resolved generation length
        req.tokens = toks
        req.max_new_tokens = new
        if req.uid is None:
            req.uid = self._next_uid
        self._next_uid = max(self._next_uid, req.uid) + 1
        dl_ms = (req.deadline_ms if req.deadline_ms is not None
                 else self.config.deadline_ms)
        if self.journal is not None and not _requeue:
            # durability contract: an ACCEPTED request survives a crash —
            # the submit record (plus any buffered shed finishes from the
            # overload gate above) flushes now, not at the next step, and
            # BEFORE the request enters the queue: if the flush fails
            # (retry exhausted), submit raises with nothing enqueued,
            # so the caller's view ("acceptance failed") stays true
            self.journal.submit(req, deadline_ms=dl_ms)
        # the request's lifecycle stamps, all on time.monotonic():
        # t_submit <= t_admit (seated or refused; the terminal time for a
        # request never seated) <= t_first == t_tokens[0] <= ... <= t_done,
        # one t_tokens entry per emitted token
        now = time.monotonic()
        self.results[req.uid] = {"tokens": None, "outcome": None,
                                 "t_submit": now, "t_admit": None,
                                 "t_first": None, "t_tokens": None,
                                 "t_done": None,
                                 "prompt_len": int(toks.size),
                                 "deadline": (now + dl_ms / 1e3
                                              if dl_ms is not None else None)}
        if self._tracing and self._trace_sampled(req.uid):
            self._traces[req.uid] = time.time()
        self.queue.append(req)
        return req.uid

    def _finalize_unseated(self, req: Request, outcome: str, why: str):
        """Terminal result for a request that never held a slot (overload
        shed / deadline-at-admit / prefill quarantine): typed outcome, no
        tokens."""
        rec = self.results[req.uid]
        rec["tokens"] = None
        rec["outcome"] = outcome
        rec["t_done"] = time.monotonic()
        if rec["t_admit"] is None:
            rec["t_admit"] = rec["t_done"]
        self._outcomes[outcome] += 1
        self._recent.append({"uid": req.uid, "outcome": outcome,
                             "why": why, "t": time.time()})
        self._record_request(req.uid, rec)
        if self.journal is not None:
            self.journal.finish(req.uid, outcome, None)

    # ------------------------------------------------------- request tracing
    # Host-side only (docs/monitoring.md#request-tracing).  Every request's
    # lifecycle stamps live in results[uid]; at its terminal outcome they
    # become one `serving.request` row of the span recorder, and, for a
    # sampled request under an armed monitor, ONE schema-v2 `trace` event
    # BUILT from the same stamps — queue wait, prefill, one decode span a
    # token.  Nothing here is visible to jit: the compiled decode step is
    # byte-identical armed vs disarmed (--audit-step tracing).

    @property
    def _tracing(self) -> bool:
        return self.config.trace_sample_rate > 0.0 and self.monitor.armed

    def _trace_sampled(self, uid: int) -> bool:
        """Deterministic sampling: a Knuth multiplicative hash of the
        uid against the rate — a pure function of the request, so a
        journal replay (and every replica of an item-3 router) samples
        the SAME requests, keeping merged trace sets coherent."""
        if self.config.trace_sample_rate >= 1.0:
            return True
        return ((uid * 2654435761) & 0xFFFFFFFF) < (
            self.config.trace_sample_rate * 4294967296.0)

    def _record_request(self, uid: int, rec: dict):
        """The terminal outcome of a request: its whole life as one
        ``serving.request`` row, and its ``trace`` event if sampled."""
        if rec["t_submit"] is not None:     # else another process's life
            self._spans.record(
                "serving.request", rec["t_submit"], rec["t_done"],
                step=self._steps, uid=uid,
                attrs={"outcome": rec["outcome"],
                       "prompt_len": rec["prompt_len"],
                       "t_admit": rec["t_admit"], "t_first": rec["t_first"],
                       "t_tokens": rec["t_tokens"]})
        t0_unix = self._traces.pop(uid, None)
        if t0_unix is not None:
            self._emit_trace(uid, rec, t0_unix)

    def _emit_trace(self, uid: int, rec: dict, t0_unix: float):
        t_submit, t_admit, t_first = (rec["t_submit"], rec["t_admit"],
                                      rec["t_first"])

        def span(name, start, end, **extra):
            return {"name": name, "start_ms": (start - t_submit) * 1e3,
                    "dur_ms": (end - start) * 1e3, **extra}

        stamps = rec["t_tokens"] or []
        spans = [span("queue_wait", t_submit, t_admit)]
        if t_admit < rec["t_done"]:
            # seated: prefill runs to the first token (to the end, for a
            # request quarantined or evicted before it had one)
            spans.append(span("prefill", t_admit, t_first if t_first
                              is not None else rec["t_done"]))
        steps = self._steps_of(stamps)
        for prev, t in zip(stamps, stamps[1:]):
            step = steps.get(t)
            spans.append(span("decode", prev, t,
                              **({} if step is None else {"step": step})))
        self.monitor.trace(
            "request", step=self._steps, uid=uid, outcome=rec["outcome"],
            t0_unix=t0_unix, prompt_len=rec["prompt_len"],
            generated=len(rec["tokens"] or ()),
            queue_wait_ms=spans[0]["dur_ms"],
            ttft_ms=((t_first - t_submit) * 1e3 if t_first is not None
                     else None),
            total_ms=(rec["t_done"] - t_submit) * 1e3, spans=spans)
        self._traces_emitted += 1

    def _steps_of(self, stamps) -> dict:
        """The scheduler step that emitted each token stamp after the
        first: every token of a step carries that step's one clock read,
        which the step's ``serving.step`` row keeps (``t_tokens``); the
        step still open is the current one.  A stamp whose row the ring no
        longer holds is left out."""
        steps = {self._token_stamp: self._steps}
        if len(stamps) > 1:
            for row in self._spans.newest_first():
                if row.t_end < stamps[1]:
                    break
                if row.name == "serving.step" and row.attrs:
                    steps[row.attrs["t_tokens"]] = row.step
        return steps

    # ------------------------------ slot state: mirrors and the device's copy
    def _set_slot(self, slot: int, blocks=(), length=0, tok=0, seed=0,
                  ngen=0, temp=1.0, flag=False, wblocks=(), end=0):
        """Seat a stream in ``slot`` — or, with the defaults, clear the
        row — in all seven mirrors and in the timeline's two (``end``:
        prompt + ``max_new_tokens``).
        Every write of slot state other than the decode step's plain advance
        and a granted block comes through here or marks ``_state_dirty``
        itself, so the next dispatch re-sends the state."""
        self._ends[slot] = end
        self._held[slot] = len(blocks)
        self._tables[slot] = pk.SCRATCH_BLOCK
        self._tables[slot, :len(blocks)] = blocks
        # the ring's entries stand after the growing table's
        self._tables[slot, self.nb_max:self.nb_max + len(wblocks)] = wblocks
        self._lengths[slot] = length
        self._toks[slot] = tok
        self._seeds[slot] = seed
        self._ngen[slot] = ngen
        self._temps[slot] = temp
        self._flags[slot] = flag
        self._state_dirty = True

    def _sync_state(self):
        """Make the device-resident slot state equal the mirrors: nothing
        to do while no slot changed since the last upload (the compiled
        step advances its own copy exactly as ``bookkeeping`` advances the
        mirrors); otherwise ONE packed host buffer goes up and the unpack
        program splits it on the device.  The mirrors are re-sent only
        once every dispatched step has been booked into them."""
        if not self._state_dirty:
            return
        assert self._unread is None, "settle before re-sending slot state"
        self._build_decode()
        # (S, table + 6) int32, the temperatures as their bits
        buf = np.column_stack((
            self._tables, self._lengths, self._toks, self._seeds,
            self._ngen, self._temps.view(np.int32), self._flags))
        with jax.set_mesh(self.engine.mesh):
            first = self._resident is None
            self._resident = res = list(self._unpack(jnp.asarray(buf)))
            if first:
                # the growth program is acquired with the first upload and
                # not at the first block edge some stream crosses, which a
                # warm-up of short answers never reaches: this call grants
                # nothing
                res[0] = self._grow(res[0], res[1], np.full(
                    self._lengths.shape, -1, np.int32))
                if self._fold is not None:
                    # the fold's program likewise: scratch into scratch
                    self.pool = self._foldfn(
                        self.engine.params, self.pool,
                        np.zeros((self._fold.window_blocks,), np.int32),
                        np.zeros((self._fold.summary_blocks,), np.int32))
        self._state_dirty = False
        self._state_uploads += 1

    def _operands(self):
        """The decode executable's nine operands as they stand: params,
        pool and the resident slot state."""
        return (self.engine.params, self.pool, *self._resident)

    def _decode_args(self):
        """The nine live operands of the NEXT decode step, for a reader
        outside :meth:`step`: the unread step, if there is one, is settled
        first, so the operands equal the mirrors and the slots' token
        histories (then synced, if a slot changed; calling this between
        steps consumes nothing and moves no later token).  The tables cover
        the position each seated row writes next: a block that the next
        dispatch would grant is granted here."""
        self._settle()
        with jax.set_mesh(self.engine.mesh):
            self._ready_state(False)
        return self._operands()

    # ------------------------------------- what a stream holds at a length
    def _column(self, position):
        """The table column that stream position ``position`` is written
        into (host mirrors and the device's lengths alike): the position's
        block, or where the cache folds, its block behind the summaries."""
        if self._fold is None:
            return position // self.config.block_size
        return self._fold.column(position)

    def _life_blocks(self, total: int) -> int:
        """The most blocks a stream of ``total`` tokens ever holds."""
        if self._fold is None:
            return pk.blocks_needed(total, self.config.block_size)
        return self._fold.life_peak(total)

    # ----------------------------------------- growth: a block at its first write
    def _grant_blocks(self, ahead: bool):
        """Give every seated row whose next write opens a block its table
        lacks that block: from the allocator into the slot's list and the
        mirrors.  Returns the grants a row (-1: none), or None where no row
        grows.  ``ahead``: a step is unread, so the rows write one position
        past the mirrors' lengths (the host counts it, reading nothing).

        The admission rule planned for these blocks when it seated the
        streams (:meth:`_plan`), so the allocator has them: a seated stream
        never waits for a block."""
        col = self._column(self._lengths + ahead)
        rows = np.flatnonzero((col >= self._held) & (self._ends > 0))
        if not rows.size:
            return None
        fresh = self.allocator.alloc(len(rows))
        assert fresh is not None, (
            f"the pool's timeline broke its promise: {len(rows)} seated "
            f"row(s) open a block and the allocator has "
            f"{self.allocator.free_blocks} free of {self.num_blocks - 1} "
            f"allocatable ({int(self._held.sum())} held by "
            f"{int((self._ends > 0).sum())} stream(s), "
            f"{self._promised} promised at the last seat)")
        grant = np.full(self._lengths.shape, -1, np.int32)
        for i, b in zip(rows, fresh):
            s = self._slots[i]
            s.blocks.append(b)
            self._tables[i, col[i]] = grant[i] = b
            if self._sanitizer is not None:
                self._sanitizer.on_alloc([b], uid=s.req.uid)
                self._sanitizer.on_attach(s.req.uid, s.blocks)
        self._held[rows] += 1
        self._grown_total += len(rows)
        return grant

    def _ready_state(self, ahead: bool) -> int:
        """Before a dispatch (or a reader of the operands): grant the blocks
        the next write opens, then make the device's copy of the slot state
        equal the mirrors.  An upload carries the grants with everything
        else; where nothing else changed, the grants alone go up (a few
        bytes a row) and one small program writes them into the resident
        tables, in device order behind the step that may still be running:
        growth settles nothing.  Returns the blocks granted."""
        before = self._grown_total
        grant = self._grant_blocks(ahead)
        if self._state_dirty:
            self._sync_state()
        elif grant is not None:
            res = self._resident
            # the host array as it is: the executable's own transfer is a
            # fifth of `jnp.asarray`'s way there
            res[0] = self._grow(res[0], res[1], grant)
        return self._grown_total - before

    # ---------------------------------------------------------- jitted steps
    def _sample_tokens(self, logits, seeds, ngen, temps, flags):
        """(B, V) fp32 → (B,) int32: per-slot greedy/sampled select with
        the request-deterministic key stream (module docstring)."""
        with jax.named_scope("sample"):
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            lg = logits / jnp.maximum(temps, 1e-6)[:, None]
            if self.config.top_k is not None:
                kth = jax.lax.top_k(lg, self.config.top_k)[0][:, -1:]
                lg = jnp.where(lg < kth, -jnp.inf, lg)
            keys = jax.vmap(lambda s, n: jax.random.fold_in(
                jax.random.PRNGKey(s), n))(seeds, ngen)
            sampled = jax.vmap(
                lambda k, row: jax.random.categorical(k, row))(keys, lg)
            return jnp.where(flags, sampled.astype(jnp.int32), greedy)

    def _build_decode(self):
        if self._decode is not None:
            return
        deq = self._deq

        def step(params, pool, tables, lengths, toks, seeds, ngen, temps,
                 flags):
            logits, pool = self.model.decode_step_paged(
                deq(params), toks, pool, tables, lengths)
            # quarantine sentinel (docs/serving.md#resilience): per-slot
            # non-finite flag computed IN-GRAPH (no host callback — the
            # PR-3 discipline, audited by --audit-step serving-resilience)
            # and the poisoned slot's sample branchlessly forced to a
            # sentinel.  Slots are row-independent, so neighbors' tokens
            # are bit-identical to a run without the poisoned request.
            poisoned = rows_nonfinite(logits)
            nxt = self._sample_tokens(logits, seeds, ngen, temps, flags)
            nxt = jnp.where(poisoned, jnp.int32(POISON_SENTINEL_TOKEN), nxt)
            # the state of the NEXT step, advanced as `bookkeeping`
            # advances the mirrors (a row the host holds inactive points
            # at the scratch block and stays as it is); whatever else the
            # host does to a row marks the state dirty and overwrites this
            n = (tables[:, 0] != pk.SCRATCH_BLOCK).astype(jnp.int32)
            read = (nxt, poisoned)
            if self._counter_names:
                # the same (B, columns) buffer, the counters on every row
                read += (jnp.broadcast_to(pool["counters"],
                                          nxt.shape + pool["counters"].shape),)
            return (_pack_read(*read), pool, lengths + n,
                    jnp.where(n > 0, nxt, toks), ngen + n)

        nb = self._tables.shape[1]

        def unpack(buf):
            return (buf[:, :nb], buf[:, nb], buf[:, nb + 1], buf[:, nb + 2],
                    buf[:, nb + 3],
                    jax.lax.bitcast_convert_type(buf[:, nb + 4], jnp.float32),
                    buf[:, nb + 5] != 0)

        c = self.config

        def grow(tables, lengths, grant):
            # a granted block goes where the row's next write lands: one
            # select over the table, no scatter (which a TPU runs a row at
            # a time)
            here = jnp.arange(nb)[None, :] == self._column(lengths)[:, None]
            return jnp.where(here & (grant >= 0)[:, None], grant[:, None],
                             tables)

        self._unpack = self.engine._wrap_step(
            f"serving.unpack[{c.batch_slots}x{nb}]", unpack)
        self._grow = self.engine._wrap_step(
            f"serving.grow[{c.batch_slots}x{nb}]", grow)
        if self._fold is not None:
            def fold(params, pool, src, dst):
                return self.model.fold_paged(deq(params), pool, src, dst)

            self._foldfn = self.engine._wrap_step(
                f"serving.fold[{self._fold.window}/{self._fold.chunk},"
                f"{c.block_size}]", fold, donate_argnums=(1,))
        self._decode = self.engine._wrap_step(
            f"serving.decode[{c.batch_slots}x{nb}"
            f"x{c.block_size},kv{c.kv_bits},{c.top_k}]",
            step, donate_argnums=(1,))

    def _prefill_fn(self, bucket: int, window: bool = False):
        """Jitted prefill for prompts padded to ``bucket`` tokens: runs
        the model's contiguous cached forward on ONE sequence, scatters
        its K/V into the slot's first blocks, and returns the real last
        token's logits.  One executable per bucket (buckets are
        block-size multiples, so their count is bounded by nb_max).

        The FORWARD runs at ``min(bucket, max_seq)`` tokens — a bucket
        rounded past ``max_seq`` (max_seq not a block multiple) would
        trip ``init_cache``'s position-table guard — and the extracted
        K/V rows zero-pad up to the bucket for the block scatter (pad
        rows sit beyond the slot's length: masked, then overwritten by
        decode writes).  The FIRST generated token samples inside this
        executable (same ``_sample_tokens`` stream as the decode step)
        — an eager per-request sampling tail would sit directly on the
        time-to-first-token metric."""
        key = (bucket, window) if window else bucket
        fn = self._prefills.get(key)
        if fn is not None:
            return fn
        deq = self._deq
        run = self.model.prefill_paged
        if window:
            # a whole window of a folded cache leaves its summaries alone
            run = functools.partial(run, fold=True)

        def prefill(params, toks, pool, blocks, t_real, seed, temp, flag,
                    extra=None):
            # `extra`: the family's own operand (`_prefill_args`)
            row, pool = run(deq(params), toks, pool, blocks, extra, t_real)
            # prefill half of the quarantine sentinel: without it, a
            # request whose PREFILL logits are already non-finite would
            # sample a garbage first token — and at max_new_tokens == 1
            # complete typed OK, invisibly to the circuit breaker
            bad = rows_nonfinite(row)[0]
            first = self._sample_tokens(
                row, seed[None],
                jnp.zeros((1,), jnp.int32), temp[None], flag[None])
            first = jnp.where(bad, jnp.int32(POISON_SENTINEL_TOKEN),
                              first[0])
            # [first token, poison flag]: one read for the host, not two
            read = jnp.stack([first, bad.astype(jnp.int32)])
            if self._counter_names:
                read = jnp.concatenate([read, pool["counters"]])
            return read, pool

        # every bucket its own XLA module (``jit_prefill_<bucket>``): a
        # device trace tells the executables apart by module name alone,
        # and ``monitor.device_scopes()`` books an instruction by it
        prefill.__name__ = "prefill_window" if window else f"prefill_{bucket}"
        fn = self.engine._wrap_step(
            f"serving.prefill[{'window,' if window else ''}{bucket},"
            f"kv{self.config.kv_bits}]", prefill, donate_argnums=(2,))
        self._prefills[key] = fn
        return fn

    def _prefill_args(self, toks, blocks, extra, t_real, seed, temp, flag):
        """The prefill executable's operands.  ``extra`` is the one operand
        a family may ask for beside them, handed to its ``prefill_paged``
        in the fifth place: the SLOT for a model that keeps state per slot,
        the segment's FIRST POSITION where the cache folds.  Every other
        family's prefill keeps the eight operands it always had."""
        args = (self.engine.params, jnp.asarray(toks), self.pool,
                jnp.asarray(blocks), jnp.int32(t_real), jnp.int32(seed),
                jnp.float32(temp), jnp.asarray(flag))
        if self._recurrent or self._fold is not None:
            return args + (jnp.int32(extra),)
        return args

    # ------------------------------------------------------------- scheduler
    def _admit(self):
        """Move queue-head requests into free slots while the pool's
        timeline has room (strict FIFO: a blocked head waits for blocks
        rather than being overtaken — no starvation).  Deadline
        enforcement's admit half lives here: a head whose deadline already
        passed, or provably cannot be met (remaining budget < max_new ·
        measured step EMA), is shed with a typed ``DEADLINE`` result instead
        of occupying a slot it cannot use.

        A decode step may be unread (:meth:`_behind_unread`): the first seat
        then books it, and what it booked is returned (else None)."""
        if self._draining:
            return None
        fault.site("serving.admit")
        booked = None
        while self.queue:
            req: Request = self.queue[0]
            if self._deadline_unmeetable(req):
                self.queue.popleft()
                self._finalize_unseated(req, DEADLINE,
                                        "deadline unmeetable at admit")
                continue
            free = [i for i, s in enumerate(self._slots) if s is None]
            if not free:
                break
            new = req.max_new_tokens       # resolved >= 1 by submit()
            share = self._prefix_match(req)
            ns = share["ns"] if share is not None else 0
            # the one rule (`_plan`): the seat's blocks of the growing table
            # now, the rest promised over the stream's life; the ring is
            # reserved here, once; the head waits on whichever is short
            plan = self._head_plan(req, ns)
            if any(plan[1]):
                break
            seat, need_w, peak = plan[0]
            fresh = self._alloc_blocks(seat, uid=req.uid)
            if fresh is None:
                # the radix cache held more than an eviction could free
                break
            self._promised = peak
            wblocks = self._alloc_window(need_w, uid=req.uid)
            if ns:
                # borrow the cached prefix read-only: one refcount per
                # co-tenant on top of the cache's own reference
                self.allocator.incref(share["blocks"])
                blocks = list(share["blocks"]) + fresh
            else:
                blocks = fresh
            self.queue.popleft()
            if self.journal is not None:
                self.journal.admit(req.uid)
            slot = free[0]
            self._prefix_requests_total += (
                1 if self._prefix_index is not None else 0)
            try:
                booked = self._start(slot, req, blocks, new, share=share,
                                     wblocks=wblocks) or booked
            except Exception:
                # a prefill that dies mid-dispatch (device OOM, a
                # poisoned executable) must not leak the blocks: free
                # them unless _start already seated the slot (the slot
                # owns them then) or already returned them itself (the
                # quarantine-at-prefill path).  The guard is keyed on
                # the FRESH blocks — shared ones stay allocated under
                # the cache's reference either way; free() decrefs our
                # borrow exactly once and reports only truly-released
                # ids to the sanitizer.  InjectedCrash is a
                # BaseException on purpose — a simulated kill skips
                # this cleanup exactly like a real one would.
                s = self._slots[slot]
                if ((s is None or s.blocks is not blocks)
                        and all(self.allocator.is_allocated(b)
                                for b in fresh)):
                    released = self.allocator.free(blocks)
                    if self._sanitizer is not None:
                        self._sanitizer.on_free(released, uid=req.uid)
                    self._free_window(wblocks, uid=req.uid)
                raise
        return booked

    # --------------------------------------- admission by the pool's timeline
    # INVARIANT: a seated stream never waits for a block and is never
    # preempted; the queue's head is never overtaken.  A seat takes the blocks
    # the prompt and the first decode write touch; every later block is
    # granted at the dispatch that first writes into it (`_grant_blocks`).
    # The head is seated only if, with it, the blocks all seated streams hold
    # never exceed the pool at any coming step, each running to its
    # `max_new_tokens` (`paged_kv.timeline_peak`).  By induction every seat
    # leaves a plan that fits, and what happens is never above the plan: every
    # seated row writes one token a dispatch, `max_new_tokens` is a hard bound,
    # and an eos, a deadline, a poisoned row or a drain only brings blocks
    # home earlier.  The one write past a stream's last token, the dead row-step
    # of a finish seen one dispatch late (docs/serving.md#one-step-in-flight),
    # lands at `prompt + max_new_tokens - 1` at the latest: inside the blocks
    # `_life_blocks(total)` planned, in the last step the timeline holds the
    # stream for, so `_grant_blocks(ahead=True)` may grant it its last block.
    # Nothing is preempted, recomputed or reordered: a request gets the tokens
    # it gets alone (tests/test_serving_timeline.py).

    def _seat_blocks(self, prompt_len: int, total: int) -> int:
        """Blocks of the growing table a stream is seated with: what the
        prompt and the first decode write touch; where a seat is the
        stream's whole life (``_whole_life``), all ``total`` tokens' blocks."""
        if self._fold is not None:
            return int(self._fold.held(min(prompt_len + 1, total)))
        return pk.blocks_needed(
            total if self._whole_life else min(prompt_len + 1, total),
            self.config.block_size)

    def _plan(self, written: int, total: int, seat: int):
        """THE admission rule for the growing table's blocks: may a stream
        that has written ``written`` of at most ``total`` tokens be seated
        with ``seat`` blocks of its own?
        Returns a bound on the blocks the seated streams and this one will
        hold at any step — the peak of the pool's timeline, or the looser sum
        of their whole lives where even that fits — or None if it has to
        wait.  Pure host arithmetic over the mirrors."""
        free = self.allocator.free_blocks
        if self._whole_life:
            # what is checked out is the plan; with the radix cache armed an
            # eviction may still make room, which only the allocator knows
            if seat > free and self._prefix_index is None:
                return None
            return self.allocator.used_blocks + seat
        if seat > free:
            return None
        rows = self._timeline
        # a row in the unread step has written one token more than its mirror
        rows[0, :-1] = self._lengths
        rows[0, :-1] += self._unread is not None
        rows[:, -1] = written, total, seat
        # a block checked out by no seated stream is held for ever
        room = (self.num_blocks - 1 - self.allocator.used_blocks
                + int(self._held.sum()))
        bs = self.config.block_size
        if self._fold is None:
            # were every stream at its end at once, as a reservation for
            # life has it: where that fits (the slots bind, not the pool)
            # every step fits, and the rule stops at that sum, a row of it
            # and not a square
            lives = int(np.maximum(-(-rows[1] // bs), rows[2]).sum())
            if lives <= room:
                return lives
        # a folded stream's holding falls at every window's end: the sum
        # over the steps, read at the window ends and the finishes
        peak = pk.timeline_peak(*rows, bs, fold=self._fold)
        return peak if peak <= room else None

    def _head_plan(self, req: Request, shared: int = 0):
        """What seating ``req`` now takes — ``(seat blocks, window blocks,
        timeline peak)`` behind ``shared`` borrowed blocks — and the kinds
        of block it would have to wait for, ``(global, window)``: it may be
        seated iff neither."""
        T = len(req.tokens)
        total = T + req.max_new_tokens
        seat = self._seat_blocks(T, total) - shared
        peak = self._plan(T, total, seat)
        need_w = self._window_need(total)
        window_short = bool(need_w) and not self.window_allocator.can_alloc(
            need_w)
        return (seat, need_w, peak), (peak is None, window_short)

    def _window_need(self, total_tokens: int) -> int:
        """Window-kind blocks a stream of ``total_tokens`` reserves: what
        its own tokens fill, at most the ring; 0 for a model without window
        layers."""
        if not self.ring:
            return 0
        return min(pk.blocks_needed(total_tokens, self.config.block_size),
                   self.ring)

    def _alloc_window(self, n: int, uid=None) -> List[int]:
        """``n`` blocks of the window kind (the caller has asked
        ``can_alloc``); [] for a model without window layers."""
        if not n:
            return []
        wblocks = self.window_allocator.alloc(n)
        if self._window_sanitizer is not None:
            self._window_sanitizer.on_alloc(wblocks, uid=uid)
        return wblocks

    def _free_window(self, wblocks: List[int], uid=None):
        if not wblocks:
            return
        released = self.window_allocator.free(wblocks)
        if self._window_sanitizer is not None:
            self._window_sanitizer.on_free(released, uid=uid)

    def _deadline_unmeetable(self, req: Request) -> bool:
        """A queued request whose deadline has passed, or provably cannot
        be met (remaining budget < max_new x the measured step estimate)."""
        dl = self.results[req.uid]["deadline"]
        if dl is None:
            return False
        now = time.monotonic()
        est = self._step_estimate_s()
        return now >= dl or now + (req.max_new_tokens * est if est
                                   else 0.0) > dl

    def _admission_due(self) -> bool:
        """Would :meth:`_admit` shed or seat the queue's head now?  Asked
        while a step is unread, without touching anything (the next step
        then does not run ahead: :meth:`_behind_unread`).  With the prefix
        cache armed the head's need depends on what it shares and on what
        an eviction frees, so a free slot alone answers yes."""
        if not self.queue or self._draining:
            return False
        req = self.queue[0]
        if self._deadline_unmeetable(req):
            return True
        if all(s is not None for s in self._slots):
            return False
        if self._prefix_index is not None:
            return True
        return not any(self._head_plan(req)[1])

    def _prefix_match(self, req: Request) -> Optional[dict]:
        """Clamped radix lookup for one admission.  ``ns`` is capped at
        ``(T-1)//block_size``: the final prompt token (and everything the
        decode step will ever WRITE) must land in a PRIVATE block —
        writing a shared block would corrupt every co-tenant.  Returns
        None on a miss (or when the hit is below ``min_prefix_blocks``
        and there is no same-parent COW donor)."""
        if self._prefix_index is None:
            return None
        if len(self._prefix_index) == 0:
            return None
        c = self.config
        T = int(len(req.tokens))
        limit = (T - 1) // c.block_size
        m = self._prefix_index.match(req.tokens, c.block_size,
                                     limit_blocks=limit)
        ns = len(m["blocks"])
        donor = m["donor"]
        if ns >= self.prefix.min_prefix_blocks:
            return {"ns": ns, "blocks": m["blocks"], "keys": m["keys"],
                    "donor": donor}
        if ns == 0 and donor is not None:
            # root-level COW: no full block matched, but a cached first
            # block shares a leading run of tokens
            return {"ns": 0, "blocks": [], "keys": [], "donor": donor}
        # a sub-threshold chain cannot keep its donor (the donor's copy
        # is only correct ON TOP of the matched chain) — full miss
        return None

    def _alloc_blocks(self, n: int, uid=None) -> Optional[List[int]]:
        """Allocator front-end for admission/restore: on exhaustion,
        evict unreferenced prefix-cache entries (LRU, leaf-first) and
        retry once.  Eviction can never reclaim a block a live stream
        still references — the cache only releases refcount-1 entries."""
        blocks = self.allocator.alloc(n)
        if blocks is None and self._prefix_index is not None:
            shortfall = n - self.allocator.free_blocks
            evicted = self._prefix_index.evict(max(1, shortfall))
            if evicted:
                self._prefix_evicted_total += len(evicted)
                if self._sanitizer is not None:
                    self._sanitizer.on_unshare(evicted)
                    self._sanitizer.on_free(evicted)
                blocks = self.allocator.alloc(n)
        if blocks is not None and self._sanitizer is not None:
            self._sanitizer.on_alloc(blocks, uid=uid)
        return blocks

    def _step_estimate_s(self) -> Optional[float]:
        """PER-TOKEN wall estimate for predictive deadline shedding:
        the step EMA, clamped to the LAST measured step when that was
        faster (a step emits one token a row).  Fast-biased on purpose —
        a compile/deserialize-laden first step must not convince the
        gate that every deadline is hopeless; an underestimate only
        admits a request the per-step deadline check will still evict
        on time, while an overestimate sheds work the server could have
        finished."""
        if self._step_ema_s is None:
            return None
        est = self._step_ema_s
        if self._step_last_s is not None:
            est = min(est, self._step_last_s)
        return est

    def _start(self, slot: int, req: Request, blocks: List[int], new: int,
               share: Optional[dict] = None,
               wblocks: Optional[List[int]] = None):
        fault.site("serving.prefill")
        c = self.config
        wblocks = wblocks or []
        T = int(len(req.tokens))
        rec = self.results[req.uid]
        # a decode step is unread: the prefill goes to the device BEHIND it
        # (docs/serving.md#one-step-in-flight)
        under = self._unread is not None
        self._admits_under += under
        with self._spans.span("serving.prefill", uid=req.uid) as prefill:
            # queue wait ends the instant this request is seated
            rec["t_admit"] = prefill.t0
            if share is not None:
                prefill.attrs = {"prompt_len": T, "under_step": under,
                                 "shared_blocks": share["ns"]}
                self._start_shared(slot, req, blocks, new, share)
                return
            bucket = pk.blocks_needed(T, c.block_size) * c.block_size
            prefill.attrs = {"prompt_len": T, "bucket": bucket,
                             "under_step": under, **self._loop_attrs}
            with jax.set_mesh(self.engine.mesh):
                with self._spans.span("serving.prefill.dispatch"):
                    if self._fold is not None:
                        # a window at a time: the bucket is the tail's
                        read = self._prefill_folded(req, blocks, slot,
                                                    prefill)
                    else:
                        read = self._prefill_whole(req, blocks, wblocks,
                                                   slot, bucket, prefill)
                    read.copy_to_host_async()
            # the step in flight ends on the device before the prefill
            # begins, so reading it first costs no wait of its own; and it
            # is booked while the slot is still empty: a row of it that died
            # in this slot has its sample discarded, and the mirrors the
            # seat below writes are never advanced by it
            booked = self._book(self._unread) if under else None
            # the read syncs the prefill dispatch: the host waits here
            with self._spans.span("serving.prefill.readback"):
                first, bad = self._read_prefill(read, prefill)
        self._seat(slot, req, blocks, wblocks, new, first, bad, rec)
        return booked

    def _prefill_whole(self, req: Request, blocks: List[int],
                       wblocks: List[int], slot: int, bucket: int, prefill):
        """A prompt through its bucket's one executable; returns its read,
        still on the device."""
        c = self.config
        T = int(len(req.tokens))
        if self._recurrent:
            # what the recurrence walks and what it must not take in;
            # the dispatch below writes the slot's recurrent rows whole
            prefill.attrs.update(scan_tokens=T, pad_tokens=bucket - T)
            if hasattr(self.model, "prefill_attrs"):
                prefill.attrs.update(self.model.prefill_attrs(T))
            self._state_seats += 1
        toks = np.zeros((1, min(bucket, self.max_seq)), np.int32)
        toks[0, :T] = req.tokens
        nb_pre = bucket // c.block_size
        # the prompt's blocks of the growing table, then the ring
        # (scratch where a short stream holds no block)
        blk = np.zeros((nb_pre + self.ring,), np.int32)
        blk[:nb_pre] = blocks[:nb_pre]
        blk[nb_pre:nb_pre + len(wblocks)] = wblocks
        read, self.pool = self._prefill_fn(bucket)(*self._prefill_args(
            toks, jnp.asarray(blk), slot, T, req.seed, req.temperature,
            req.do_sample))
        return read

    def _seat(self, slot: int, req: Request, blocks: List[int],
              wblocks: List[int], new: int, first: int, bad: int, rec: dict):
        """What follows a prefill's read: the stream seated with its first
        token, or, where the prefill's logits were not finite, quarantined
        before it ever holds the slot."""
        c = self.config
        T = int(len(req.tokens))
        if bad:
            # quarantined AT prefill: the slot is never seated, the
            # sentinel token is never surfaced, and the blocks go back
            # scrubbed (prompt K/V of a poisoned forward may be
            # non-finite too)
            if self._sanitizer is not None:
                self._sanitizer.on_scrub(blocks, uid=req.uid)
            self._set_blocks(blocks, poison=False)
            released = self.allocator.free(blocks)
            if self._sanitizer is not None:
                self._sanitizer.on_free(released, uid=req.uid)
            self._scrub_window(wblocks, uid=req.uid)
            self._free_window(wblocks, uid=req.uid)
            logger.warning(
                f"serving: request {req.uid} QUARANTINED at prefill — "
                f"non-finite logits; typed '{POISONED}' result "
                f"(docs/serving.md#resilience)")
            self._finalize_unseated(req, POISONED,
                                  "non-finite prefill logits")
            self._check_breaker()
            return

        s = _Slot(req, blocks, T, new, wblocks=wblocks)
        if self._fold is not None:
            s.folded = T // self._fold.window
        s.out_tokens.append(first)
        s.hist.append(first)
        self._slots[slot] = s
        self._set_slot(slot, blocks, length=T, tok=first, seed=req.seed,
                       ngen=1, temp=req.temperature, flag=req.do_sample,
                       wblocks=s.wblocks, end=T + new)
        if self._sanitizer is not None:
            self._sanitizer.on_attach(req.uid, blocks)
        if self._window_sanitizer is not None:
            self._window_sanitizer.on_attach(req.uid, s.wblocks)
        rec["t_first"] = time.monotonic()
        rec["t_tokens"] = [rec["t_first"]]
        if new == 1 or first == c.eos_token_id:
            self._finish(slot)
        elif fault.poison_uid(req.uid):
            # logit_nan chaos fault: NaN this request's OWN blocks (an
            # eager host-side pool edit — the compiled step is untouched;
            # the poison rides the data, exactly like real KV corruption).
            # Only a slot that will actually decode is poisoned: a
            # request finishing at prefill frees its blocks above, and
            # they must go back clean.  A chaos-poisoned slot is NOT
            # published below — its NaN'd prompt blocks must never be
            # served to another tenant.
            if self._sanitizer is not None:
                self._sanitizer.on_quarantine(blocks, uid=req.uid)
            self._set_blocks(blocks, poison=True)
        elif self._prefix_index is not None:
            # publish the full PROMPT blocks immediately: decode writes
            # land strictly above the prompt, so these blocks are final
            # — and requests admitted in this SAME wave (co-batched)
            # can already share them, not just successive traffic
            self._prefix_insert(s)

    def _read_prefill(self, read, prefill):
        """The prefill's one buffer: ``(first token, poison flag)``; what
        the model's layers counted rides behind them and goes into the
        prefill's span (``routed_pairs`` of an expert model)."""
        read = np.asarray(read)
        prefill.attrs.update(zip(self._counter_names,
                                 (int(x) for x in read[2:])))
        return int(read[0]), int(read[1])

    # ------------------------------------------- a cache that folds itself
    def _prefill_folded(self, req: Request, blocks: List[int], slot: int,
                        prefill):
        """A prompt into a folded cache, a WINDOW AT A TIME
        (docs/serving.md#folded-cache): every whole window through one
        executable, which reads the summaries of the windows before it from
        the pool and leaves its own in the stream's next summary blocks; the
        tail, padded to its bucket, through the bucket's.  The only state a
        segment hands the next is the summary blocks it wrote.  Returns the
        last segment's read, still on the device (the first token is sampled
        there)."""
        c, fold = self.config, self._fold
        W, sb = fold.window, fold.summary_blocks
        full, tail = divmod(int(len(req.tokens)), W)
        bucket = pk.blocks_needed(tail, c.block_size) * c.block_size \
            if tail else 0
        prefill.attrs.update(bucket=bucket, windows=full)
        # the summary table every segment reads: scratch past what is folded
        table = np.zeros((self.model.summary_table_blocks(c.block_size),),
                         np.int32)
        for j in range(full + bool(tail)):
            whole = j < full
            own = blocks[j * sb:(j + 1) * sb] if whole else \
                blocks[full * sb:full * sb + bucket // c.block_size]
            piece = req.tokens[j * W:(j + 1) * W]
            toks = np.zeros((1, W if whole else bucket), np.int32)
            toks[0, :len(piece)] = piece
            read, self.pool = self._prefill_fn(
                toks.shape[1], window=whole)(*self._prefill_args(
                    toks, np.concatenate([table, own]).astype(np.int32),
                    j * W, len(piece), req.seed, req.temperature,
                    req.do_sample))
            if whole:
                table[j * sb:(j + 1) * sb] = own
        return read

    def _fold_ended_windows(self, active):
        """Fold the window of every row of ``active`` whose length has just
        reached a window's end: the window's exact rows into summary rows in
        fresh blocks (one small executable a row, the pool donated), its
        blocks back to the allocator, the row's table rewritten.  The new
        blocks are taken BEFORE the old come home: the step the admission
        sum charges ``summary_blocks`` more (``WindowFold.charge``)."""
        fold = self._fold
        rows = [i for i in active if self._slots[i] is not None
                and self._lengths[i] // fold.window > self._slots[i].folded]
        if not rows:
            return
        with self._spans.span("serving.fold") as span:
            for i in rows:
                s = self._slots[i]
                fresh = self.allocator.alloc(fold.summary_blocks)
                assert fresh is not None, (
                    f"the pool's timeline broke its promise: a fold needs "
                    f"{fold.summary_blocks} block(s) and the allocator has "
                    f"{self.allocator.free_blocks} free")
                src = s.blocks[-fold.window_blocks:]
                with jax.set_mesh(self.engine.mesh):
                    self.pool = self._foldfn(
                        self.engine.params, self.pool,
                        np.asarray(src, np.int32),
                        np.asarray(fresh, np.int32))
                # in place: the list is the slot's identity elsewhere
                s.blocks[-fold.window_blocks:] = fresh
                s.folded += 1
                if self._sanitizer is not None:
                    self._sanitizer.on_alloc(fresh, uid=s.req.uid)
                    self._sanitizer.on_detach(s.req.uid)
                    self._sanitizer.on_attach(s.req.uid, s.blocks)
                released = self.allocator.free(src)
                if self._sanitizer is not None:
                    self._sanitizer.on_free(released, uid=s.req.uid)
                self._held[i] = len(s.blocks)
                self._tables[i] = pk.SCRATCH_BLOCK
                self._tables[i, :len(s.blocks)] = s.blocks
            self._folded_total += len(rows)
            self._state_dirty = True
            span.attrs = {"windows": len(rows)}

    def _start_shared(self, slot: int, req: Request, blocks: List[int],
                      new: int, share: dict):
        """Seat a prefix-HIT request without running prefill.  The
        shared leading blocks already hold the prompt's K/V; the
        remaining prompt tail is INGESTED through the compiled decode
        step (teacher-forced: each step writes one prompt position's
        K/V and its sample is discarded) until the final prompt token,
        whose sample — at the same ``fold_in(seed, 0)`` index the
        prefill would have used — IS the first generated token.  TTFT
        therefore collapses to the new-suffix cost, and the output
        stream is token-identical to the unshared path."""
        c = self.config
        bs = c.block_size
        T = int(len(req.tokens))
        ns = share["ns"]
        prompt = [int(t) for t in np.asarray(req.tokens)]
        pos0 = ns * bs                  # first position without K/V yet
        donor = share["donor"]
        if donor is not None:
            # copy-on-write: a cached sibling block shares the leading
            # j tokens of our first DIVERGENT block — clone it into our
            # first private block and skip ingesting the copied run.
            # j is clamped so position T-1 is always re-ingested (its
            # decode step produces the first token's logits).
            db, j = donor
            j = min(int(j), T - 1 - pos0)
            if j > 0:
                self._copy_block(db, blocks[ns])
                self._prefix_cow_total += 1
                if self._sanitizer is not None:
                    self._sanitizer.on_cow(db, blocks[ns], uid=req.uid)
                pos0 += j
        s = _Slot(req, blocks, T, new)
        s.pending = prompt[pos0 + 1:]
        s.shared_blocks = ns
        s.shared_keys = list(share["keys"])
        self._slots[slot] = s
        # ngen 0: no token emitted yet
        self._set_slot(slot, blocks, length=pos0, tok=prompt[pos0],
                       seed=req.seed, ngen=0, temp=req.temperature,
                       flag=req.do_sample, end=T + new)
        if self._sanitizer is not None:
            self._sanitizer.on_attach(req.uid, blocks)
        self.results[req.uid]["t_tokens"] = []
        self._prefix_hits_total += 1
        self._prefix_shared_blocks_total += ns
        if fault.poison_uid(req.uid):
            # logit_nan chaos: poison only the PRIVATE blocks — the
            # shared prefix has co-tenants (and the cache) reading it
            priv = blocks[ns:]
            if self._sanitizer is not None:
                self._sanitizer.on_quarantine(priv, uid=req.uid)
            self._set_blocks(priv, poison=True)

    def _copy_block(self, src: int, dst: int):
        """Jitted whole-block clone for COW (every layer, K and V and
        the int8 scales).  A separate tiny executable — the decode step
        itself is untouched, so its jaxpr stays byte-identical with the
        cache armed."""
        self._settle()
        if self._blockcopy is None:
            def copier(pool, s, d):
                return {k: v.at[:, d].set(v[:, s]) for k, v in pool.items()}
            donate = (0,) if jax.default_backend() != "cpu" else ()
            self._blockcopy = jax.jit(copier, donate_argnums=donate)
        with jax.set_mesh(self.engine.mesh):
            self.pool = self._blockcopy(self.pool, jnp.int32(src),
                                        jnp.int32(dst))

    # ---------------------- KV snapshot/restore (docs/serving.md#kv-migration)
    def _snapshot_slot(self, slot: int) -> str:
        """Export one live slot's KV blocks + stream state as a committed
        snapshot image under ``stream_snapshot_dir(journal_dir, uid)``:
        stage ``image.npz``/``image.json``, manifest, publish rename
        (``checkpoint/atomic.py`` — a torn write is detectable, never
        restorable), then apply ``keep_n`` retention.  Entirely
        host-side: the compiled decode step never sees any of it."""
        from ..checkpoint import atomic
        self._settle()
        s = self._slots[slot]
        uid = s.req.uid
        ngen = int(self._ngen[slot])
        sdir = stream_snapshot_dir(self.config.journal_dir, uid)
        with jax.set_mesh(self.engine.mesh):
            image = pk.export_block_image(
                self.pool, s.blocks, quant_block=self._kv_quant_block)
        meta = {
            # atomic.py's newest-first ordering key: the decode position
            "global_steps": ngen,
            "stream": {
                "uid": int(uid),
                "prompt": [int(t) for t in np.asarray(s.req.tokens)],
                "out_tokens": [int(t) for t in s.out_tokens],
                "max_new_tokens": int(s.max_new),
                "seed": int(s.req.seed),
                "temperature": float(s.req.temperature),
                "do_sample": bool(s.req.do_sample),
                "num_blocks": len(s.blocks),
                "block_size": int(self.config.block_size),
                "kv_bits": int(self.config.kv_bits),
                # prefix sharing: the image is SELF-CONTAINED (every
                # block exported once, shared or not) — the count is
                # observability, not a restore dependency; the restorer
                # re-establishes sharing against its own LOCAL index
                "shared_blocks": int(s.shared_blocks)}}
        final = pk.save_block_image(sdir, f"snap-{ngen:06d}", image, meta)
        keep = self.kvs.keep_n if self.kvs is not None else 1
        atomic.rotate_checkpoints(sdir, keep, level="size")
        self._snap_last[slot] = ngen
        self._kv_snapshots_total += 1
        return final

    def _snapshot_slot_safe(self, slot: int):
        """Cadence wrapper: a failed snapshot must not take serving down
        — the stream simply stays recompute-only at migration time.  An
        :class:`fault.InjectedCrash` (a simulated kill, e.g. the
        ``kv_snapshot_torn`` site) propagates like the real thing."""
        try:
            self._snapshot_slot(slot)
        except Exception as e:
            logger.warning(
                f"serving: kv snapshot of uid {self._slots[slot].req.uid} "
                f"failed ({e}); stream stays recompute-only")

    def _delete_stream_snapshots(self, uid: int):
        """Retention's terminal half: a finished uid's images are dead
        weight — nothing ever restores a completed stream."""
        if not self.config.journal_dir:
            return
        sdir = stream_snapshot_dir(self.config.journal_dir, uid)
        if os.path.isdir(sdir):
            shutil.rmtree(sdir, ignore_errors=True)

    def _cleanup_snapshot_dirs(self):
        """``close()``'s retention half: drop every stream's images
        except those of still-pending uids (a drain timeout leaves their
        requests journaled in-flight, and a restart or a router handoff
        may still restore them).  Without this, nothing owns snapshot
        retention once the engine is gone."""
        if not self.config.journal_dir:
            return
        root = os.path.join(self.config.journal_dir, KV_SNAPSHOT_DIR)
        if not os.path.isdir(root):
            return
        keep = {int(u) for u, r in self.results.items()
                if r["outcome"] is None}
        for name in os.listdir(root):
            try:
                uid = int(name.split("-", 1)[1])
            except (IndexError, ValueError):
                continue         # not ours; never delete what we don't own
            if uid not in keep:
                shutil.rmtree(os.path.join(root, name), ignore_errors=True)
        try:
            os.rmdir(root)       # only when empty
        except OSError:  # dstpu: disable=DSTPU002 (non-empty root is the signal)
            pass

    def submit_restored(self, req: Request, snapshot_dir: str,
                        seat: Optional[dict] = None) -> dict:
        """Restore-first admission for a migrated stream: journal the
        request durably on THIS engine (its submit record lives on the
        dead replica's journal, not here), then try to seat it directly
        from ``snapshot_dir`` — a committed image of the dead replica's
        KV — so only the post-snapshot suffix re-decodes
        (token-identical: sampling is a pure function of
        ``(seed, token_index)``).  ANY restore defect — torn or corrupt
        image, wrong geometry, no free slot or blocks — degrades loudly
        to the plain recompute queue with a typed ``migration_fallback``
        monitor event.  The uid is never lost (journaled before the
        attempt) and never duplicated (either seated OR queued, never
        both).

        ``seat`` (disaggregation): the transfer queue's seat record —
        carries the prefill worker's claimed generation (the stale-
        handoff guard), first token, and prefix-cache block hashes the
        restore verifies before re-sharing.

        Returns ``{"uid", "restored", "restore_ms", "tokens_saved",
        "reason"}`` (``reason`` set on fallback)."""
        uid = self.submit(req, _requeue=True)
        if self.journal is not None:
            dl = (req.deadline_ms if req.deadline_ms is not None
                  else self.config.deadline_ms)
            self.journal.submit(req, deadline_ms=dl)
        t0 = time.perf_counter()
        reason, saved = None, 0
        try:
            saved = self._restore_stream(req, snapshot_dir, seat=seat)
            restored = True
        except (pk.BlockImageError, KVRestoreError) as e:
            restored, reason = False, str(e)
        ms = (time.perf_counter() - t0) * 1e3
        if restored:
            # submit() queued the request; the restore seated it
            # directly, so unqueue it — seated OR queued, never both
            assert self.queue and self.queue[-1] is req
            self.queue.pop()
            self._kv_migrated_total += 1
            self._kv_tokens_saved_total += saved
            self._kv_restore_ms.append(ms)
        else:
            self._kv_fallback_total += 1
            logger.warning(
                f"serving: KV restore of uid {uid} fell back to recompute "
                f"({reason}) — typed migration_fallback "
                "(docs/serving.md#kv-migration)")
            if self.monitor.armed:
                self.monitor.trace("migration_fallback", step=self._steps,
                                   uid=int(uid), reason=str(reason)[:200])
        if self.journal is not None:
            # informational for replay; the router's poll channel for
            # subprocess replicas (ProcessReplica tails it)
            self.journal.record("restore", uid=int(uid), restored=restored,
                                restore_ms=round(ms, 3), tokens_saved=saved)
            self.journal.flush()
        return {"uid": uid, "restored": restored,
                "restore_ms": round(ms, 3), "tokens_saved": saved,
                "reason": reason}

    def _warm_restore_path(self):
        """Compile-warm the block-image round-trip against the LIVE
        pool, once, right after the first decode step.  The import
        scatter's trace cache keys on the pool's sharding, and the
        first decode step replaces the init-time placement with the
        decode jit's output sharding — an init-time warm is invalidated
        by the very first step.  pad_to pins the scatter to one
        nb_max-wide shape, so this single round-trip covers every
        future restore regardless of stream depth (measured ~130-650 ms
        cold vs ~5 ms warm — latency that otherwise lands inside a
        crash handoff's restore window).  Block 0 is the scratch block,
        garbage by design, so rewriting it with its own (de)quantized
        image is inert."""
        with jax.set_mesh(self.engine.mesh):
            warm = pk.export_block_image(
                self.pool, [pk.SCRATCH_BLOCK],
                quant_block=self._kv_quant_block)
            self.pool = pk.import_block_image(
                self.pool, [pk.SCRATCH_BLOCK], warm, pad_to=self.nb_max)

    def _restore_stream(self, req: Request, snapshot_dir: str,
                        seat: Optional[dict] = None) -> int:
        """Seat ``req`` directly from a committed image: verify manifest
        + per-block digests, allocate fresh blocks, scatter the image
        into the pool, and resume decode at the snapshot's exact
        position.  Returns the recompute tokens saved (prompt prefill +
        already-emitted decode steps).  Raises
        :class:`KVRestoreError`/:class:`pk.BlockImageError` on any
        defect — :meth:`submit_restored` owns the fallback.  With a
        transfer ``seat`` the image must be at least as deep as the
        seat's claimed generation and agree on the first sampled token
        (the stale-handoff guard, satellite fix)."""
        self._settle()                  # a seat: no step stays unread
        # a survivor restores even when it doesn't snapshot itself
        kvs = self.kvs or KVSnapshotConfig()
        image, meta = pk.load_block_image(snapshot_dir, verify=kvs.verify)
        stream = (meta or {}).get("stream")
        if not stream:
            raise KVRestoreError(
                f"snapshot {snapshot_dir} carries no stream metadata")
        if int(stream["uid"]) != int(req.uid):
            raise KVRestoreError(
                f"snapshot is of uid {stream['uid']}, not {req.uid}")
        prompt = np.asarray(stream["prompt"], np.int32)
        if not np.array_equal(prompt, np.asarray(req.tokens, np.int32)):
            raise KVRestoreError(
                "snapshot prompt differs from the request being restored")
        out_tokens = [int(t) for t in stream["out_tokens"]]
        if not out_tokens:
            raise KVRestoreError("snapshot holds no emitted tokens")
        if seat:
            # stale-handoff guard (satellite fix): a transfer image whose
            # generation predates the seat record is an OLDER publish of
            # the same uid (a re-published entry superseded it) — seating
            # it would silently rewind the stream.  Fall back to
            # recompute (typed migration_fallback) instead.
            seat_gen = int(seat.get("gen", 0) or 0)
            if len(out_tokens) < seat_gen:
                raise KVRestoreError(
                    f"stale transfer image: image generation "
                    f"{len(out_tokens)} predates the seat record's "
                    f"gen {seat_gen} (stale-handoff guard)")
            first = seat.get("first_token")
            if first is not None and int(first) != out_tokens[0]:
                raise KVRestoreError(
                    f"transfer image's first token {out_tokens[0]} "
                    f"differs from the seat record's {int(first)} — "
                    f"image and seat are not the same publish")
        if int(stream["block_size"]) != self.config.block_size:
            raise KVRestoreError(
                f"snapshot block_size {stream['block_size']} != pool "
                f"{self.config.block_size}")
        new = int(req.max_new_tokens)
        nb = pk.blocks_needed(prompt.size + new, self.config.block_size)
        if int(stream["num_blocks"]) != nb:
            raise KVRestoreError(
                f"snapshot covers {stream['num_blocks']} block(s); this "
                f"request needs {nb}")
        free = [i for i, sl in enumerate(self._slots) if sl is None]
        if not free:
            raise KVRestoreError("no free slot for restore")
        # prefix sharing across migration: the image is self-contained,
        # but when the SURVIVOR's own radix index already holds the
        # prompt's leading blocks, re-establish sharing instead of
        # importing duplicate copies.  Restore may share every full
        # PROMPT block (decode resumes at >= prompt_len, so its writes
        # can never land in a shared block).  No local match degrades
        # LOUDLY to a full private import — never a torn refcount.
        ns = 0
        shared: List[int] = []
        resident: List[int] = []    # cache-resident prompt blocks the
        #                             import is about to DUPLICATE —
        #                             DSTPU317 evidence; empty on the
        #                             correct incref-and-share path
        if self._prefix_index is not None and len(self._prefix_index):
            m = self._prefix_index.match(prompt, self.config.block_size,
                                         limit_blocks=prompt.size
                                         // self.config.block_size)
            shared, ns = m["blocks"], len(m["blocks"])
            if ns and seat and seat.get("prefix_keys") is not None:
                # the seat's chained block hashes are a pure function of
                # the prompt tokens — the local radix chain MUST agree.
                # A disagreement means the seat (or the index) is
                # corrupt: refuse the share, import privately, and let
                # the sanitizer call the duplication out (DSTPU317).
                want = list(seat["prefix_keys"])[:ns]
                if list(m["keys"]) != want:
                    logger.warning(
                        f"serving: restore of uid {req.uid}: seat "
                        f"record's prefix keys disagree with the local "
                        f"radix chain over {ns} block(s) — refusing the "
                        f"share, importing privately")
                    resident, shared, ns = list(shared), [], 0
            if ns:
                logger.info(
                    f"serving: restore of uid {req.uid} re-established "
                    f"prefix sharing over {ns}/{nb} block(s)")
            else:
                logger.warning(
                    f"serving: restore of uid {req.uid} found no local "
                    f"prefix match — degrading to a full private import "
                    f"({nb} block(s) duplicated)")
        # an image covers the stream's whole life, so the seat takes it all:
        # the same rule as the queue's head, or the seated streams' growth
        # would be short of what the import took
        written = int(prompt.size) + len(out_tokens) - 1
        peak = self._plan(written, int(prompt.size) + new, nb - ns)
        fresh = (self._alloc_blocks(nb - ns, uid=req.uid)
                 if peak is not None else None)
        if fresh is None:
            raise KVRestoreError(
                f"the pool's timeline has no room for {nb - ns} block(s) "
                f"({self.allocator.free_blocks} free)")
        self._promised = peak
        if ns:
            self.allocator.incref(shared)
            self._prefix_shared_blocks_total += ns
        blocks = list(shared) + fresh
        slot = free[0]
        try:
            fault.site("serving.crash_during_restore")
            with jax.set_mesh(self.engine.mesh):
                if ns:
                    # import only the private tail of the image; the
                    # shared head's K/V is already resident (per-block
                    # digests still verify — they are per-block)
                    sub = dict(image,
                               k=image["k"][:, ns:], v=image["v"][:, ns:],
                               k_scale=image["k_scale"][:, ns:],
                               v_scale=image["v_scale"][:, ns:],
                               block_sha256=list(image["block_sha256"])[ns:])
                    self.pool = pk.import_block_image(
                        self.pool, fresh, sub, pad_to=self.nb_max)
                else:
                    self.pool = pk.import_block_image(
                        self.pool, blocks, image, pad_to=self.nb_max)
            s = _Slot(req, blocks, int(prompt.size), new)
            s.out_tokens = list(out_tokens)
            s.hist.extend(out_tokens)
            s.shared_blocks = ns
            # wire-precision KV (and a partially image-sourced stream)
            # never publishes into the prefix cache at finish
            s.wire_kv = True
            self._slots[slot] = s
            # decode resumes where the snapshot stopped: lengths trails
            # out_tokens by the one token whose KV the NEXT step writes
            # (_start's invariant), and sampling continues at
            # fold_in(seed, ngen) — token-identical to the dead replica's
            # stream by the determinism contract
            self._set_slot(
                slot, blocks,
                length=int(prompt.size) + len(out_tokens) - 1,
                tok=out_tokens[-1], seed=req.seed, ngen=len(out_tokens),
                temp=req.temperature, flag=req.do_sample,
                end=int(prompt.size) + new)
            if self._sanitizer is not None:
                self._sanitizer.on_attach(req.uid, blocks)
                # DSTPU317 (satellite fix): a restore that imports a
                # private copy of a prompt block the PrefixIndex already
                # holds is silent pool waste — the shadow sanitizer
                # makes it a lint failure
                self._sanitizer.on_import(fresh, uid=req.uid,
                                          resident=resident)
        except BaseException:
            # UNLIKE _admit's prefill edge, cleanup runs for
            # BaseException here too: a failed restore leaves the
            # SURVIVOR alive — it is the migration that died, not this
            # process — so the blocks must go home or this engine leaks
            # them for its whole remaining life (DSTPU312 at close).  A
            # real kill doesn't care either way: the allocator dies with
            # the process.  free() decrefs the shared borrow and
            # releases the fresh blocks EXACTLY once (guarded on the
            # fresh ids — the cache's own reference keeps shared blocks
            # allocated), so a mid-restore crash can never tear a
            # refcount.
            sl = self._slots[slot]
            if ((sl is None or sl.blocks is not blocks)
                    and all(self.allocator.is_allocated(b)
                            for b in fresh)):
                released = self.allocator.free(blocks)
                if self._sanitizer is not None:
                    self._sanitizer.on_free(released, uid=req.uid)
            raise
        self._snap_last[slot] = len(out_tokens)
        # the restored tokens all arrive with the image: one stamp
        rec = self.results[req.uid]
        rec["t_admit"] = rec["t_first"] = time.monotonic()
        rec["t_tokens"] = [rec["t_first"]] * len(out_tokens)
        if (len(out_tokens) >= new
                or out_tokens[-1] == self.config.eos_token_id):
            # a snapshot taken exactly at the stream's end (an
            # export_on_evict image can be): finish immediately instead
            # of decoding past the budget
            self._finish(slot)
        return int(prompt.size) + len(out_tokens)

    # ---------------------------------------------- prefill/decode handoff
    # (docs/serving.md#disaggregation) — everything below is host-side
    # file I/O over the TransferQueue; the compiled decode step never
    # sees any of it (--audit-step disagg proves jaxpr equality).

    def _seat_record(self, slot: int) -> dict:
        """The handoff's control-plane half: everything the decode
        worker needs to SEAT the stream without recomputing — the
        sampled first token, lengths, the RNG fold position (``gen``:
        sampling resumes at ``fold_in(seed, gen)``), and the prompt's
        chained prefix-block hashes so the decode side re-SHARES
        resident prefixes instead of re-importing them.  ``stream`` is
        the same block the restore path reads from any snapshot — a
        transfer entry IS a restorable image."""
        s = self._slots[slot]
        c = self.config
        dl = s.req.deadline_ms
        if dl is not None and dl == float("inf"):
            dl = "inf"          # the journal's JSON spelling
        return {
            "uid": int(s.req.uid),
            "gen": len(s.out_tokens),
            "first_token": int(s.out_tokens[0]),
            "prompt_len": int(s.prompt_len),
            "max_new_tokens": int(s.max_new),
            "seed": int(s.req.seed),
            "temperature": float(s.req.temperature),
            "do_sample": bool(s.req.do_sample),
            "deadline_ms": dl,
            "block_size": int(c.block_size),
            "kv_bits": int(c.kv_bits),
            "prefix_keys": pk.prefix_block_keys(s.req.tokens,
                                                c.block_size),
            "stream": {
                "uid": int(s.req.uid),
                "prompt": [int(t) for t in np.asarray(s.req.tokens)],
                "out_tokens": [int(t) for t in s.out_tokens],
                "max_new_tokens": int(s.max_new),
                "seed": int(s.req.seed),
                "temperature": float(s.req.temperature),
                "do_sample": bool(s.req.do_sample),
                "num_blocks": len(s.blocks),
                "block_size": int(c.block_size),
                "kv_bits": int(c.kv_bits),
                "shared_blocks": int(s.shared_blocks)}}

    def _publish_slot(self, slot: int) -> dict:
        """Export one prefill-finished slot's KV blocks as a block image,
        commit image + seat record on the transfer queue (one atomic
        publish), journal the handoff, and retire the slot with the
        typed ``TRANSFERRED`` outcome — the decode worker owns the
        stream now.  Raises to :meth:`_publish_transfers` on any
        refusal; the caller degrades the slot to local decode."""
        self._settle()
        s = self._slots[slot]
        uid = int(s.req.uid)
        gen = len(s.out_tokens)
        with jax.set_mesh(self.engine.mesh):
            image = pk.export_block_image(
                self.pool, s.blocks, quant_block=self._kv_quant_block)
        seat = self._seat_record(slot)
        pub = self._txq.publish(uid, gen, image, seat)
        self._transfers_total += 1
        self._transfer_bytes_total += int(pub["bytes"])
        self._transfer_pub_ms.append(float(pub["publish_ms"]))
        out = {"kind": "transfer", "uid": uid, "entry": pub["entry"],
               "gen": gen, "bytes": int(pub["bytes"]),
               "publish_ms": float(pub["publish_ms"]),
               "seat": {k: v for k, v in seat.items() if k != "stream"}}
        self._transfer_outbox[uid] = out
        if self.monitor.armed:
            # the handoff trace span: per-transfer bytes + publish
            # latency on the bus (docs/monitoring.md)
            self.monitor.trace("kv_transfer", step=self._steps, uid=uid,
                               gen=gen, bytes=out["bytes"],
                               publish_ms=out["publish_ms"],
                               entry=os.path.basename(pub["entry"]))
        if self.journal is not None:
            # the router's poll channel for subprocess replicas
            # (ProcessReplica tails it); flushes eagerly — the seat must
            # be durable before the TRANSFERRED finish retires the uid
            self.journal.transfer(uid, pub["entry"], gen, out["bytes"],
                                  out["publish_ms"], seat=out["seat"])
        self._finish(slot, outcome=TRANSFERRED)
        return out

    def _publish_transfers(self):
        """Prefill role: hand every prefill-finished slot off through the
        transfer queue.  A slot qualifies once its first token is
        sampled (``ngen >= 1``; a prefix-hit slot still ingesting has
        ``ngen == 0`` and publishes a later step) unless it is restored
        wire-KV (a stream seated HERE decodes here) or degrade-latched.
        Any refusal — backpressure, a publish defect, chaos poison —
        degrades that ONE stream to local mixed decode: the prefill
        worker never blocks and never drops.  Returns the number of
        streams handed off (the scheduler's progress evidence)."""
        from . import transfer as xfer
        published = 0
        for i, s in enumerate(self._slots):
            if (s is None or s.wire_kv or s.no_transfer
                    or int(self._ngen[i]) < 1):
                continue
            if fault.poison_uid(s.req.uid):
                # chaos-poisoned prefill output stays LOCAL: the next
                # decode step quarantines it here (typed POISONED) —
                # publishing known-poison would just move the quarantine
                # across the wire
                s.no_transfer = True
                continue
            try:
                self._publish_slot(i)
                published += 1
            except xfer.TransferBackpressureError as e:
                s.no_transfer = True
                self._transfer_backpressure_total += 1
                logger.warning(
                    f"serving: transfer of uid {s.req.uid} hit queue "
                    f"backpressure ({e}); degrading to local decode")
            except Exception as e:
                s.no_transfer = True
                logger.warning(
                    f"serving: transfer publish of uid {s.req.uid} "
                    f"failed ({e}); degrading to local decode")
        return published

    def pop_transfer(self, uid: int) -> Optional[dict]:
        """Take ownership of one published handoff record (``{"entry",
        "seat", "bytes", ...}``) — the router's poll channel for
        in-process replicas."""
        return self._transfer_outbox.pop(int(uid), None)

    def admit_next_transfer(self) -> Optional[dict]:
        """Decode role: exclusively claim the oldest committed queue
        entry and seat it through :meth:`submit_restored` (restore-
        first; ANY defect — torn image, stale seat, no capacity —
        degrades to the plain recompute queue with a typed
        ``migration_fallback``).  Returns ``submit_restored``'s dict
        (or a fallback-shaped one), None when nothing is pending."""
        if self._txq is None:
            return None
        claim = self._txq.claim()
        if claim is None:
            return None
        seat = claim.get("seat") or {}
        stream = seat.get("stream") or {}
        if not stream:
            # unreadable manifest: nothing to rebuild a Request from.
            # Drop the entry — the PRODUCER's journal still holds the
            # uid; zero-loss across the edge is the router's guarantee.
            logger.warning(
                f"serving: claimed transfer entry {claim['tag']} carries "
                f"no stream metadata; dropping it")
            self._txq.done(claim["entry"])
            return {"uid": seat.get("uid"), "restored": False,
                    "restore_ms": 0.0, "tokens_saved": 0,
                    "reason": "claimed entry carries no stream metadata"}
        dl = seat.get("deadline_ms")
        if dl == "inf":
            dl = float("inf")
        req = Request(tokens=np.asarray(stream["prompt"], np.int32),
                      max_new_tokens=int(stream["max_new_tokens"]),
                      temperature=float(stream.get("temperature", 1.0)),
                      do_sample=bool(stream.get("do_sample", False)),
                      seed=int(stream.get("seed", 0)),
                      uid=int(stream["uid"]), deadline_ms=dl)
        try:
            out = self.submit_restored(req, claim["entry"], seat=seat)
        except ValueError as e:
            # duplicate uid (a superseded re-publish of a stream this
            # engine already owns) or a request that no longer fits:
            # the entry is dead weight either way
            logger.warning(
                f"serving: claimed transfer entry {claim['tag']} "
                f"rejected ({e}); dropping it")
            self._txq.done(claim["entry"])
            return {"uid": req.uid, "restored": False, "restore_ms": 0.0,
                    "tokens_saved": 0, "reason": str(e)}
        self._txq.done(claim["entry"])
        return out

    def _admit_transfers(self):
        """Decode role: seat queued handoffs into free slots, one claim
        per free slot per step (admission-bounded, like ``_admit``).  A
        restore fallback lands its request on the recompute queue, which
        this same step's ``_admit`` picks up — degrade-to-mixed."""
        while any(sl is None for sl in self._slots):
            if self.admit_next_transfer() is None:
                return

    def _scrub_window(self, wblocks, uid=None):
        """Zero a stream's window-kind blocks before they go back (a
        poisoned forward's K/V may be non-finite in every layer)."""
        if not wblocks:
            return
        if self._window_sanitizer is not None:
            self._window_sanitizer.on_scrub(wblocks, uid=uid)
        self._set_blocks(wblocks, poison=False, window=True)

    def _set_blocks(self, blocks: List[int], poison: bool,
                    window: bool = False):
        """Pool edit over a block list, outside the decode step:
        ``poison=True`` NaN-fills the payload (int8 pools NaN the fp32
        scales — the int8 lanes cannot hold a NaN), ``poison=False``
        scrubs back to zeros/unit scales.  Scrubbing matters on eviction:
        a stale non-finite row would leak NaN into the block's NEXT
        tenant through the masked attention tail (0 · NaN = NaN), where
        stale *finite* garbage is harmless.

        Runs as ONE small jitted scatter with the pool donated (the
        decode step's in-place discipline — an eager ``.at[].set`` would
        materialize a full pool copy per quarantine event, transiently
        doubling a production pool's bytes).  The block list pads to
        ``nb_max`` by repeating its first id (duplicate scatter indices
        write the same value), so every request shape shares one
        executable.  ``window``: the blocks are of the window kind (the
        ``wk`` / ``wv`` leaves, padded to the ring)."""
        if self._blockset is None:
            quant = pk.is_quantized_pool(self.pool)

            def setter(pool, blk, val, window=False):
                if quant:
                    return dict(pool,
                                k_scale=pool["k_scale"].at[:, blk].set(val),
                                v_scale=pool["v_scale"].at[:, blk].set(val))
                return dict(pool, **{
                    name: pool[name].at[:, blk].set(
                        val.astype(pool[name].dtype))
                    for name in (pk.WINDOW if window
                                 else pk.payload_names(pool))})

            # cpu backend: donation would only warn (PR-4's copy-on-
            # donate note); device backends get the in-place update
            donate = (0,) if jax.default_backend() != "cpu" else ()
            self._blockset = jax.jit(setter, donate_argnums=donate,
                                     static_argnames=("window",))
        padded = np.full((self.ring if window else self.nb_max,), blocks[0],
                         np.int32)
        padded[:len(blocks)] = blocks
        val = jnp.float32(jnp.nan if poison else (1.0 if
                          pk.is_quantized_pool(self.pool) else 0.0))
        with jax.set_mesh(self.engine.mesh):
            self.pool = self._blockset(self.pool, jnp.asarray(padded), val,
                                       window=window)

    def _finish(self, slot: int, outcome: str = OK):
        s = self._slots[slot]
        if (self.kvs is not None and self.kvs.export_on_evict
                and outcome == DEADLINE and s.out_tokens):
            # on-evict export: a deadline eviction keeps its partial
            # tokens — one final image (while the blocks are still ours)
            # keeps the partial KV restorable too.  Every OTHER terminal
            # outcome deletes the stream's images below: nothing ever
            # restores a completed uid.
            self._snapshot_slot_safe(slot)
        if outcome == POISONED:
            # quarantine eviction: scrub the non-finite rows out of the
            # blocks BEFORE they return to the free list.  Only SOLE-
            # OWNER blocks are scrubbed — a shared prefix block has
            # live co-tenants (or the cache) reading it, and poison can
            # only ever land in private blocks (the decode step writes
            # nothing below the private boundary; attempting the shared
            # scrub anyway is exactly what DSTPU316 catches)
            scrub = [b for b in s.blocks
                     if self.allocator.refcount(b) == 1]
            if self._sanitizer is not None:
                self._sanitizer.on_scrub(scrub, uid=s.req.uid)
            if scrub:
                self._set_blocks(scrub, poison=False)
            self._scrub_window(s.wblocks, uid=s.req.uid)
        elif not s.wire_kv and self._prefix_index is not None:
            # publish this request's fully-WRITTEN prompt+output blocks
            # into the radix cache (the cache takes its own refcount)
            # BEFORE our release below — restored-from-image slots never
            # publish (their KV is wire-precision, not prefill output)
            self._prefix_insert(s)
        if self._sanitizer is not None:
            self._sanitizer.on_detach(s.req.uid)
        # free() decrefs; only ids that actually dropped to zero are
        # RELEASED (cache/co-tenant-held blocks stay live) — the shadow
        # sanitizer must see exactly the released set
        released = self.allocator.free(s.blocks)
        if self._sanitizer is not None:
            self._sanitizer.on_free(released, uid=s.req.uid)
        if self._window_sanitizer is not None:
            self._window_sanitizer.on_detach(s.req.uid)
        self._free_window(s.wblocks, uid=s.req.uid)
        rec = self.results[s.req.uid]
        rec["tokens"] = list(s.out_tokens)
        rec["outcome"] = outcome
        rec["t_done"] = time.monotonic()
        self._outcomes[outcome] += 1
        self._recent.append({"uid": s.req.uid, "outcome": outcome,
                             "generated": len(s.out_tokens),
                             "t": time.time()})
        if outcome == OK:
            self._completed_total += 1
        self._generated_total += len(s.out_tokens)
        if outcome in (OK, DEADLINE):
            # admitted-request latency accounting: completions AND
            # deadline evictions (their latency ≈ the deadline — the
            # bound the overload tests assert); queue sheds never ran
            self._lat_hist.add((rec["t_done"] - rec["t_submit"]) * 1e3)
            if rec["t_first"] is not None:
                self._ttft_hist.add(
                    (rec["t_first"] - rec["t_submit"]) * 1e3)
        self._record_request(s.req.uid, rec)
        if self.journal is not None:
            self.journal.finish(s.req.uid, outcome, rec["tokens"])
        if not (self.kvs is not None and self.kvs.export_on_evict
                and outcome == DEADLINE):
            # eos-evict (and every non-resumable outcome) owns deleting
            # the stream's on-disk images — the retention fix: before
            # this, nothing did
            self._delete_stream_snapshots(s.req.uid)
        self._slots[slot] = None
        self._snap_last[slot] = 0
        self._set_slot(slot)             # the cleared row

    def _prefix_insert(self, s: _Slot):
        """Publish one finishing request's fully-written KV blocks into
        the radix cache.  Block ``i`` is insertable iff every one of its
        positions has real K/V: the last emitted token's KV is never
        written (the step that would write it never ran), so the
        writable frontier is ``prompt_len + len(out) - 1``.  Leading
        shared blocks dedupe onto their existing entries; a same-content
        race with another tenant's freshly-published block dedupes too
        (our copy simply stays private and is released below)."""
        bs = self.config.block_size
        written = s.prompt_len + len(s.out_tokens) - 1
        toks = s.hist                    # prompt + emitted tokens
        parent = None
        newly: List[int] = []
        for i in range(written // bs):
            b = s.blocks[i]
            held_before = self._prefix_index.holds(b)
            key = self._prefix_index.insert(parent, toks[i * bs:(i + 1) * bs],
                                            b)
            if key is None:              # collision or capped — stop chain
                break
            if not held_before and self._prefix_index.holds(b):
                newly.append(b)
            parent = key
        if newly and self._sanitizer is not None:
            self._sanitizer.on_share(newly, uid=s.req.uid)

    def _evict_poisoned(self, slot: int):
        s = self._slots[slot]
        logger.warning(
            f"serving: request {s.req.uid} QUARANTINED — its decode "
            f"logits went non-finite; evicted with a typed '{POISONED}' "
            f"result, blocks scrubbed and returned "
            f"(docs/serving.md#resilience)")
        self._finish(slot, outcome=POISONED)
        self._check_breaker()

    def _check_breaker(self):
        """Trip to reject-all when the poison count in the recent-outcome
        window EXCEEDS ``poison_budget`` — one bad input is an eviction,
        a stream of them is an attack or a broken model, and the server
        must say so loudly instead of grinding through it."""
        if self._breaker_open:
            return
        recent = list(self._recent)
        poisoned = sum(1 for r in recent if r["outcome"] == POISONED)
        if poisoned <= self.config.poison_budget:
            return
        self._breaker_open = True
        dirpath = (self.config.forensic_dir or self.config.journal_dir
                   or os.getcwd())
        payload = {
            "event": "serving_forensics",
            "reason": f"poison rate: {poisoned} poisoned of "
                      f"{len(recent)} recent outcomes exceeds budget "
                      f"{self.config.poison_budget}",
            "time_unix": time.time(),
            "decode_steps": self._steps,
            "counters": dict(self._outcomes,
                             requeued=self._requeued_total),
            "policy": {"poison_budget": self.config.poison_budget,
                       "poison_window": self.config.poison_window,
                       "overload": self.config.overload,
                       "deadline_ms": self.config.deadline_ms},
            "recent": recent,
        }
        self._forensic_path = write_forensics(
            dirpath, f"serving_forensics_step{self._steps}.json", payload)
        logger.error(
            "serving circuit breaker TRIPPED: rejecting all new "
            f"submissions ({payload['reason']}); forensics: "
            f"{self._forensic_path}")
        mon = self.monitor
        if mon.armed:
            mon.counter("breaker_open", 1, step=self._steps)
            if self._forensic_path is not None:
                mon.artifact("serving_forensics", self._forensic_path,
                             step=self._steps,
                             reason=payload["reason"])
            mon.flush()

    def step(self) -> bool:
        """One scheduler iteration: admit from the queue, ONE fused
        decode dispatch for the whole batch, read and book ONE step's
        tokens (join/evict with quarantine + deadline enforcement), flush
        the journal.  While no slot changes, the step booked is the one
        the PREVIOUS call dispatched, and this call's runs on the device
        meanwhile (docs/serving.md#one-step-in-flight).  Returns False
        when there is nothing left to do: never while a step is unread."""
        if not self._preflight_done:
            self._preflight_gate()
        fault.site("serving.step")
        # the root span of this call (monitor/spans.py), recorded whether
        # or not a monitor is armed; its number is that of the step it books
        root = self._spans.open("serving.step", step=self._steps + 1)
        try:
            return self._step(root)
        except BaseException:
            # a step that dies after a dispatch leaves the device's copy
            # of the slot state ahead of the mirrors: the mirrors are the
            # truth, so whatever is unread is dropped (re-run, never
            # booked) and the mirrors are sent again
            self._unread = None
            self._state_dirty = True
            raise
        finally:
            self._spans.close(root)      # nothing, after an idle poll
            if self._startup_line_due and self._steps:
                self._log_startup()

    def _log_startup(self):
        """Once, when the first step that did anything has returned: where
        this process's time went before it could serve
        (docs/monitoring.md#start-up)."""
        from ..monitor import startup
        self._startup_line_due = False
        log_dist(f"ServingEngine {startup.line()}", ranks=[0])

    def _settles_every_step(self, active) -> bool:
        """Must the step dispatched for ``active`` be read before this call
        returns?  Where the next step is built from the host's own tokens
        (a prompt tail being ingested), where another party reads slot
        state between calls (a snapshot cadence, a transfer queue, a role)
        and while draining, the order stays dispatch, read, book: nothing
        is ever left in flight."""
        return (self.kvs is not None
                or self._txq is not None or self.role != "mixed"
                or self._draining
                or any(self._slots[i] is not None
                       and self._slots[i].pending is not None for i in active))

    def _behind_unread(self, unread: _Unread) -> Optional[str]:
        """What this call may send to the device BEHIND ``unread``, before
        reading it: the next decode ``"step"``, the queue head's
        ``"prefill"``, or nothing (``None``: settle, then dispatch).

        The next step runs ahead only on exactly the operands a read would
        leave: the resident state is clean and nothing is to be seated or
        shed.  A row that ends with the unread step (an eos, a poisoned row,
        a passed deadline, and the ``max_new_tokens`` the host could count)
        is seen one dispatch late: it computes one dead row-step inside its
        own blocks, and its slot is re-seated behind that step instead of
        after a drained device.  A step that no row would live through is
        not dispatched.

        An admission that is due runs with the step in flight
        (:meth:`_start` books it after the prefill's dispatch and before the
        seat).  What is armed for other readers settles first, as every
        admission did: whatever settles every step, and a prefix cache (a
        finish publishes blocks and an eviction frees them, which only the
        allocator knows).  So does a step that ends a row's window of a
        folded cache: it is folded, and the row's table rewritten, before
        anything else is dispatched or planned."""
        if self._settles_every_step(unread.active):
            return None
        if self._fold is not None and not (
                (self._lengths[unread.active] + 1) % self._fold.window).all():
            return None
        if self._admission_due():
            return "prefill" if self._prefix_index is None else None
        if self._state_dirty:
            return None
        slots = self._slots
        live = any(slots[i] is not None and len(slots[i].out_tokens) + 1
                   < slots[i].max_new for i in unread.active)
        return "step" if live else None

    def _settle(self):
        """Read and book the unread step, if there is one, outside
        :meth:`step`: every reader of slot state (``_decode_args``, a
        snapshot, a restore, a drain) calls this first, so that mirrors,
        token histories and the device's copy describe the same step."""
        if self._unread is None:
            return
        try:
            self._book(self._unread)
        except BaseException:
            self._unread = None
            self._state_dirty = True
            raise
        if self.journal is not None:
            self.journal.flush()

    def _step(self, root) -> bool:
        spans = self._spans
        mon = self.monitor
        mon.begin_step(root)
        unread = self._unread
        behind = self._behind_unread(unread) if unread is not None else None
        ahead = behind == "step"
        booked = None                    # (active, tokens emitted, stamp)
        if unread is not None and behind is None:
            # settle, then dispatch: a slot may change below
            booked = self._book(unread)
        active = ()
        published = 0
        if ahead:
            active = unread.active       # nothing to admit: no slot changes
        else:
            if self._txq is not None and self.role == "decode":
                # BEFORE _admit: a restore fallback re-queues its request,
                # and this same step's admission must pick it up (otherwise
                # the livelock guard below would see a queued request no
                # admission pass ever looked at)
                with spans.span("serving.kv_transfer"):
                    self._admit_transfers()
            with spans.span("serving.admit"):
                # with `unread` in flight, the first seat books it
                booked = self._admit() or booked
            if self._unread is not None:
                # ...and nothing was seated after all (the head was shed):
                # the order every step had before
                booked = self._book(unread)
            if self._txq is not None and self.role == "prefill":
                # AFTER _admit: slots seated by this step's prefill publish
                # immediately — the handoff adds zero decode-step latency
                with spans.span("serving.kv_transfer"):
                    published = self._publish_transfers()
            active = [i for i, s in enumerate(self._slots) if s is not None]
        if not active and booked is None:
            if self.queue and not self._draining and not published:
                # a prefill worker that just PUBLISHED its whole batch
                # made progress — empty slots + a queued backlog is its
                # steady state, not a livelock
                # livelock guard: requests are waiting, EVERY slot is
                # free, and admission still seated nothing — spinning a
                # hot no-op step() forever would hide the bug; raise
                # with the head's block math instead
                self._raise_stalled()
            # idle poll: nothing decoded — discard the bracket instead of
            # recording and emitting spans under a reused step number
            mon.abort_step()
            spans.discard(root)
            if self.journal is not None:
                self.journal.flush()
            return bool(self.queue)
        self._pool_attrs = self._pool_state(active)
        if active:
            new = self._dispatch(active, ahead)
            if ahead:
                booked = self._book(unread)
            elif booked is None and self._settles_every_step(active):
                booked = self._book(new)
                new = None
            self._unread = new
        if self.journal is not None:
            with spans.span("serving.journal"):
                # ONE buffered append per scheduler step (admits +
                # finishes); submits flushed eagerly at submit()
                self.journal.flush()
        if booked is None:
            # the first step after an idle stretch is dispatched and left
            # in flight: nothing was read, so nothing is reported under the
            # number of the step this call did not book
            mon.abort_step()
            root.attrs = {"n_active": len(active), "emitted": 0,
                          "t_tokens": None, **self._pool_attrs}
            return True
        n_active, emitted_step, now = booked
        self._monitor_finish(n_active, tokens=emitted_step)
        root.attrs = {"n_active": n_active, "emitted": emitted_step,
                      "t_tokens": now, **self._pool_attrs,
                      **self._route_attrs}
        return True

    def _pool_state(self, active) -> dict:
        """The pool as this call's dispatch finds it, for the step's span:
        blocks checked out and free (together the allocatable ones), tokens
        cached for the seated streams (the mirrors' count: one step behind
        while a step is unread), and whether the queue's head waits for
        BLOCKS: admission has just run or was not due, so a head still
        queued beside a free slot lacks only them (of which kind, the
        admission rule says: ``_head_plan``)."""
        waits = bool(self.queue and not self._draining
                     and len(active) < self.config.batch_slots)
        out = {"blocks_in_use": self.allocator.used_blocks,
               "blocks_free": self.allocator.free_blocks,
               "kv_tokens": int(self._lengths.sum()),
               "waits_for_blocks": waits,
               # the bound the admission rule held the seated streams to at
               # the last seat (`_plan`), the blocks this call's dispatch
               # grants to seated rows (`_dispatch` counts them) and the
               # tokens the allocatable blocks hold
               "blocks_promised": self._promised,
               "blocks_grown": 0,
               "kv_token_room": (self.num_blocks - 1)
               * self.config.block_size}
        if self._fold is not None:
            # the rows the tables hold, not the streams' lengths; how many
            # blocks are folded history and how many the current windows';
            # the windows folded, and the blocks their folds gave back,
            # since the last step's row
            fold = self._fold
            summary = fold.summary_blocks * sum(
                s.folded for s in self._slots if s is not None)
            folded = self._folded_total - self._folded_reported
            self._folded_reported = self._folded_total
            out.update(
                kv_tokens=int(fold.row(self._lengths).sum()),
                summary_blocks=summary,
                window_blocks=int(self._held.sum()) - summary,
                windows_folded=folded,
                # a window's blocks are the stream's alone: all come home
                blocks_released_by_fold=folded * fold.window_blocks)
        if self._recurrent:
            # the slots whose recurrent rows this dispatch advances (and
            # those it leaves), and the bytes of state that takes (read
            # and written)
            out["seated_slots"] = len(active)
            out["free_slots"] = self.config.batch_slots - len(active)
            if self._state_step_bytes is not None:
                out["state_bytes"] = len(active) * self._state_step_bytes
        if self.ring:
            # the second kind, and WHICH kind the head waits for; the tokens
            # a window layer can still read (each stream's last
            # `sliding_window`) beside `kv_tokens`, and the rest, which one
            # growing table for every layer would still hold
            window = self.model.config.sliding_window
            seen = int(np.minimum(self._lengths, window).sum())
            window_short = waits and not self.window_allocator.can_alloc(
                self._window_need(len(self.queue[0].tokens)
                                  + self.queue[0].max_new_tokens))
            out.update(
                window_blocks_in_use=self.window_allocator.used_blocks,
                window_blocks_free=self.window_allocator.free_blocks,
                window_kv_tokens=seen,
                window_capped_tokens=out["kv_tokens"] - seen,
                waits_for_window_blocks=window_short,
                # a head that waits lacks one kind or both: the rule's sum
                # is asked again only where the ring is short as well
                waits_for_global_blocks=waits and (
                    not window_short
                    or self._head_plan(self.queue[0])[1][0]))
        return out

    def _dispatch(self, active, ahead: bool) -> _Unread:
        """Call the decode executable for ``active`` and start its one
        buffer on the way down.  ``ahead``: the previous step is still
        unread, so the operands are its outputs as the device will leave
        them (JAX's asynchronous dispatch takes them as they are)."""
        spans = self._spans
        self._build_decode()
        with jax.set_mesh(self.engine.mesh):
            # the slot state goes up (only if a slot changed) on its own
            # bracket, so that "dispatch" is the call into the executable
            # alone
            with spans.span("serving.upload") as upload:
                upload.attrs = {"uploaded": self._state_dirty}
                self._pool_attrs["blocks_grown"] = self._ready_state(ahead)
                args = self._operands()
            self._reused_steps += not upload.attrs["uploaded"]
            self._ahead_steps += ahead
            res = self._resident    # operand order: [1] lengths, [2] toks,
            #                         [4] ngen come back advanced
            with spans.span("serving.dispatch") as dispatch:
                dispatch.attrs = {"ahead": ahead}
                read, self.pool, res[1], res[2], res[4] = self._decode(*args)
                # the one buffer the host needs starts down at once
                read.copy_to_host_async()
        if self._kv_warm_pending:
            self._kv_warm_pending = False
            self._warm_restore_path()
        return _Unread(read, active, upload.t0)

    def _book(self, step: _Unread):
        """Read a dispatched step's buffer and book its tokens into the
        slots, the mirrors and the result records (join/evict, quarantine,
        deadlines); it is unread no longer.  Returns ``(rows it was
        dispatched for, tokens emitted, their stamp)``."""
        spans = self._spans
        active = step.active
        self._unread = None
        # the host's wait for the device, alone on its bracket
        with spans.span("serving.readback") as readback:
            read = np.asarray(step.read)
            if self._counter_names:
                # what the model's layers counted in this step, behind the
                # columns every model sends
                n_cols = read.shape[1] - len(self._counter_names)
                self._route_attrs = dict(zip(
                    self._counter_names, (int(x) for x in read[0, n_cols:])))
                read = read[:, :n_cols]
            # a row's token | its non-finite flag
            out, nonfin = read[:, 0], read[:, 1] != 0
        # the value read above synced the dispatch: from the upload's start
        # to the read's end is a true decode-step cost, the predictive-
        # deadline EMA's input (the spans' own clock reads, no others).  A
        # step dispatched before the previous one was read began on the
        # device when that one ended: its cost is the time between the two
        # reads
        dt = readback.t1 - max(step.t0, self._t_read)
        self._t_read = readback.t1
        self._step_wall_hist.add(dt * 1e3)
        self._step_last_s = dt
        if self._step_ema_s is None:
            self._step_ema_s = dt
        elif dt < self._step_ema_s:
            # adapt DOWN fast: one compile-heavy outlier step decays
            # in a few iterations instead of poisoning the
            # predictive-deadline gate for a long tail
            self._step_ema_s = 0.5 * self._step_ema_s + 0.5 * dt
        else:
            self._step_ema_s = 0.7 * self._step_ema_s + 0.3 * dt
        self._steps += 1
        with spans.span("serving.bookkeeping"):
            c = self.config
            # one clock read stamps every token this step emitted
            now = self._token_stamp = time.monotonic()
            emitted_step = 0
            for i in active:
                s = self._slots[i]
                if s is None:
                    # a row that finished (eos, poison, deadline) while
                    # this step was already dispatched: its sample is
                    # discarded (docs/serving.md#one-step-in-flight)
                    continue
                if nonfin[i]:
                    # the sentinel token is NOT appended: the record keeps
                    # its pre-poison tokens
                    self._evict_poisoned(i)
                    continue
                if s.pending:
                    # prompt ingestion (prefix sharing): the step wrote one
                    # prompt position's K/V and its sample is DISCARDED.
                    # Advance stops one token short of the prompt end: the
                    # step where pending is empty has the final prompt
                    # token as its operand, and its sample (key
                    # fold_in(seed, 0), ngen still 0) IS the first
                    # generated token — the same index the prefill path
                    # samples, so outputs stay token-identical to the
                    # unshared path.  Not the in-graph advance (no sample
                    # is kept, ngen stands): the next dispatch re-sends the
                    # state
                    self._lengths[i] += 1
                    self._toks[i] = s.pending.pop(0)
                    self._state_dirty = True
                    dl = self.results[s.req.uid]["deadline"]
                    if dl is not None and now >= dl:
                        self._finish(i, outcome=DEADLINE)
                    continue
                tok = int(out[i])
                emitted_step += 1
                s.out_tokens.append(tok)
                s.hist.append(tok)
                rec = self.results[s.req.uid]
                rec["t_tokens"].append(now)
                if s.pending is not None:
                    # [] = the prompt tail's final step.  First token of a
                    # prefix-HIT request: TTFT stamps here (the plain path
                    # stamps it at prefill) — by construction one decode
                    # step after the suffix finished ingesting, i.e. the
                    # new-suffix cost
                    s.pending = None
                    if rec["t_first"] is None:
                        rec["t_first"] = now
                if len(s.out_tokens) >= s.max_new or tok == c.eos_token_id:
                    self._finish(i)
                    continue
                self._lengths[i] += 1
                self._ngen[i] += 1
                self._toks[i] = tok
                dl = rec["deadline"]
                if dl is not None and now >= dl:
                    # mid-decode deadline: evict with the partial tokens
                    # — the slot goes back to work that can still meet
                    # its budget
                    self._finish(i, outcome=DEADLINE)
                    continue
                if (self.kvs is not None
                        and int(self._ngen[i]) - int(self._snap_last[i])
                        >= self.kvs.every_tokens):
                    # periodic per-stream image at the configured token
                    # cadence (docs/serving.md#kv-migration) — host-side
                    # export + atomic commit; the compiled step above
                    # never changes
                    with spans.span("serving.kv_snapshot"):
                        self._snapshot_slot_safe(i)
        if self._fold is not None:
            self._fold_ended_windows(active)
        return len(active), emitted_step, now

    def _raise_stalled(self):
        c = self.config
        req: Request = self.queue[0]
        total = len(req.tokens) + req.max_new_tokens
        nb = self._life_blocks(total)
        seat = self._seat_blocks(len(req.tokens), total)
        # admission failure: the ledger dump makes the block math a
        # forensic artifact, not just an exception message
        path = self._memory_forensics(
            f"serving admission stalled: head uid {req.uid} needs {nb} "
            f"block(s) ({seat} at its seat), allocator has "
            f"{self.allocator.free_blocks} free")
        raise ServingStalledError(
            f"serving stalled: {len(self.queue)} request(s) queued, zero "
            f"slots active, and admission made no progress — head uid "
            f"{req.uid} needs {nb} block(s) over its life "
            f"(= ceil(({len(req.tokens)} prompt + {req.max_new_tokens} "
            f"new) / block_size {c.block_size}), {seat} of them at its "
            f"seat) but the allocator has "
            f"{self.allocator.free_blocks} free of "
            f"{self.num_blocks - 1} allocatable "
            f"({self.allocator.used_blocks} leaked or still held)"
            + (f", and {self._window_need(len(req.tokens) + req.max_new_tokens)}"
               f" window block(s) of which "
               f"{self.window_allocator.free_blocks} are free of "
               f"{self.window_num_blocks - 1}" if self.ring else "")
            + (f"; memory forensics: {path}" if path else ""))

    # decode steps between latency-percentile/hist emissions: quantile
    # walks are cheap (O(buckets)) but need not run per generated token
    _PERCENTILES_EVERY = 16

    def _monitor_finish(self, active_slots, tokens):
        """Per-decode-step telemetry: the serving stats (previously an
        export-only dict) re-routed through the bus in the one schema.
        Cheap counters ride every emitted step; the percentile gauges
        (a sort over the completion windows) ride a coarser cadence.
        ``tokens``: tokens emitted this step (a token a row that neither
        ended before the step was read nor ingests a prompt tail)."""
        mon = self.monitor
        # memory-ledger cadence: the monitor's `memory_interval` when it
        # carries one (config-built monitors; 0 = the documented off
        # switch), else the serving role default.  Independent of
        # monitor.interval thinning: the cadence is the documented one,
        # not the lcm.  Static terms latched — memory_ledger._static_terms.
        mem_every = mon.memory_interval
        if mem_every is None:
            mem_every = self._PERCENTILES_EVERY
        if (mon.armed and mon.bus is not None and mon.bus.sinks
                and mem_every and self._steps % mem_every == 0):
            from ..monitor import memory_ledger as mled
            mled.attribute_serving(self).emit(mon, step=self._steps)
        if not mon.armed:
            mon.end_step(self._steps, name="serving_step")
            return
        # scalars/counters are cheap host reads: pass them even on
        # thinned steps so the monitor's terminal flush (drain/close)
        # lands the run's FINAL state in the stream — `monitor.interval`
        # must not truncate what ds_fleet merges see
        scalars = {"active_slots": active_slots,
                   "queued": len(self.queue),
                   "completed_total": self._completed_total,
                   "generated_total": self._generated_total,
                   "free_blocks": self.allocator.free_blocks}
        # resilience outcomes as counters: the ds_top serving line and
        # any alerting pipeline read shed/deadline/poison pressure from
        # the one event stream (docs/monitoring.md).  The cumulative
        # completion/token totals ride as counters too — counters are
        # what ds_fleet SUMS across replicas (fleet.py), and the fleet's
        # completed count must equal the sum of the replicas' exactly
        counters = {"shed_total": self._outcomes[SHED],
                    "deadline_total": self._outcomes[DEADLINE],
                    "poisoned_total": self._outcomes[POISONED],
                    "requeued_total": self._requeued_total,
                    "breaker_open": int(self._breaker_open),
                    "completed_total": self._completed_total,
                    "generated_total": self._generated_total}
        if (self.kvs is not None or self._kv_migrated_total
                or self._kv_fallback_total):
            # KV migration counters (docs/serving.md#kv-migration):
            # summed fleet-wide by ds_fleet like every other counter
            counters["kv_snapshots_total"] = self._kv_snapshots_total
            counters["migrated_streams_total"] = self._kv_migrated_total
            counters["migration_fallbacks_total"] = self._kv_fallback_total
        gauges = {}
        if self._txq is not None:
            # disaggregation handoff telemetry (docs/serving.md
            # #disaggregation): per-edge bytes/latency plus the queue
            # depth the router's placement reads
            counters["kv_transfers_total"] = self._transfers_total
            counters["transfer_bytes_total"] = self._transfer_bytes_total
            counters["transfer_backpressure_total"] = \
                self._transfer_backpressure_total
            counters["transfer_claimed_total"] = self._txq.claimed_total
            scalars["transfer_queue_depth"] = self._txq.depth()
            if self._transfer_pub_ms:
                gauges["handoff_ms"] = round(
                    sum(self._transfer_pub_ms)
                    / len(self._transfer_pub_ms), 3)
        if self._prefix_index is not None:
            # prefix-sharing pressure (docs/serving.md#prefix-sharing):
            # hit rate of admissions against the radix cache, and the
            # fraction of logical blocks that are physically unique —
            # ds_bench_diff classifies prefix_hit_rate higher-better and
            # unique_block_frac lower-better
            counters["prefix_hits_total"] = self._prefix_hits_total
            counters["prefix_cow_total"] = self._prefix_cow_total
            counters["prefix_evicted_total"] = self._prefix_evicted_total
            gauges["prefix_hit_rate"] = round(
                self._prefix_hits_total
                / max(1, self._prefix_requests_total), 4)
            logical = self.allocator.logical_blocks
            gauges["unique_block_frac"] = round(
                self.allocator.used_blocks / max(1, logical), 4)
            scalars["shared_blocks"] = self.allocator.shared_blocks
            scalars["prefix_cached_blocks"] = \
                self._prefix_index.cached_blocks
        # windowed error rate from the outcome counters (the SLO
        # engine's error-budget series, docs/monitoring.md#slo-tracking):
        # bad/total over the terminal outcomes since the last EMISSION —
        # a cumulative ratio would dilute a fresh burn under a long
        # healthy history.  The baseline advances only on emitted steps:
        # a thinned step's gauge lands at most once (the terminal-flush
        # tail), so advancing the baseline there would silently drop its
        # outcomes from the error budget forever.
        term = sum(self._outcomes.values())
        bad = term - self._outcomes[OK]
        d_term = term - self._err_window_last[0]
        if d_term > 0:
            gauges["error_rate"] = round(
                (bad - self._err_window_last[1]) / d_term, 4)
        if not mon.should_emit(self._steps):
            mon.end_step(self._steps, scalars=scalars, gauges=gauges,
                         counters=counters, name="serving_step")
            return
        if d_term > 0:
            self._err_window_last = (term, bad)
        if self._steps % self._PERCENTILES_EVERY == 0:
            st = self.stats()
            if "latency_ms" in st:
                gauges["latency_p50_ms"] = st["latency_ms"]["p50"]
                gauges["latency_p99_ms"] = st["latency_ms"]["p99"]
                gauges["latency_p999_ms"] = st["latency_ms"]["p999"]
            if "ttft_ms" in st:
                gauges["ttft_p50_ms"] = st["ttft_ms"]["p50"]
            # the distributions themselves ride the bus as mergeable
            # schema-v2 hist events: replicas/restarts (and the item-3
            # router) aggregate them exactly (docs/monitoring.md)
            for hname, h in (("latency_ms", self._lat_hist),
                             ("ttft_ms", self._ttft_hist),
                             ("step_wall_ms", self._step_wall_hist)):
                if h:
                    mon.hist(hname, h, step=self._steps, unit="ms")
        self._emit_exe_cost(mon)
        mon.set_rates(tokens_per_step=tokens)
        mon.end_step(self._steps, scalars=scalars, gauges=gauges,
                     counters=counters, name="serving_step")

    # --------------------------------------------------- roofline attribution
    def _exe_cost_fields(self) -> Optional[dict]:
        """Price the LIVE decode executable for roofline attribution
        (analysis/roofline.py): XLA cost-analysis FLOPs + bytes
        accessed, the HLO wire census, the chip identity, and the paged
        path's gather-materialization bytes (modeled from the serving
        configuration — the exact traffic the ROADMAP-1 in-place kernel
        deletes).  None until a decode executable is live."""
        import jax as _jax
        from ..analysis.roofline import gather_materialization_bytes
        from ..monitor import gauges as mg
        if self._decode is None:
            return None
        if not getattr(self._decode, "_exes", None):
            # no live executable recorded (compile cache off -> CachedStep
            # passthrough): acquire one, once, exactly like the training
            # engine's pricing path (runtime/engine._monitor_step_stats)
            try:
                with jax.set_mesh(self.engine.mesh):
                    # only the operands' shapes are read: from inside a
                    # step's telemetry the step just dispatched stays unread
                    self._decode.executable(*(
                        self._operands() if self._unread is not None
                        else self._decode_args()))
            except Exception as e:
                logger.warning(f"serving: could not price the decode step "
                               f"({e}); roofline attribution unavailable")
                return None
        flops = mg.executable_flops(self._decode)
        hbm = mg.executable_bytes_accessed(self._decode)
        wire = mg.executable_wire_report(self._decode)
        mc = self.model.config
        c = self.config
        # impl-aware gather pricing: the kernel path reports 0 (the
        # bytes are GONE, not modeled-and-ignored); only the gather
        # fallback keeps the modeled term.  ds_explain names the impl.
        impl = self.model.paged_attention_impl()
        gather = gather_materialization_bytes(
            n_layer=mc.kv_layers, batch_slots=c.batch_slots,
            nb_max=self.nb_max, block_size=c.block_size,
            n_head=mc.n_head, head_dim=mc.head_dim,
            itemsize=(1 if c.kv_bits == 8 else jnp.dtype(
                getattr(self.model, "dtype", jnp.bfloat16)).itemsize),
            paged_impl=impl)
        if not (flops or hbm):
            return None
        return {"exe": "serving_step", "flops": flops, "hbm_bytes": hbm,
                "wire_bytes": wire.get("wire_bytes_per_step", 0),
                "gather_bytes": gather, "paged_impl": impl,
                "tokens_per_step": c.batch_slots,
                "device_kind": _jax.devices()[0].device_kind,
                "n_chips": len(_jax.devices())}

    def _emit_exe_cost(self, mon):
        """One `exe_cost` gauge per serving configuration — the
        ds_explain feed; priced once, constant per executable.  The
        attempt latches once a decode executable exists EVEN on a
        pricing failure (same executable → same outcome): a backend
        exposing no cost analysis must not re-run the HLO census — or
        re-try a failing AOT compile — on every monitored step."""
        if self._exe_cost_emitted or self._decode is None:
            return
        self._exe_cost_emitted = True
        fields = self._exe_cost_fields()
        if fields is None:
            return
        mon.gauge("exe_cost", float(fields["flops"]), step=self._steps,
                  **fields)

    def roofline_report(self) -> Optional[dict]:
        """The live engine's own roofline verdict (`ds_explain` without
        the stream round-trip — bench rungs embed this as
        ``extra.roofline``): the decode executable's priced costs
        against the chip table, with the measured step-wall histogram's
        p50 as the wall term.  None before any measured decode step."""
        from ..analysis.roofline import attribute
        fields = self._exe_cost_fields()
        if fields is None or not self._step_wall_hist:
            return None
        return attribute(
            wall_s=self._step_wall_hist.quantile(0.5) / 1e3,
            flops=fields["flops"], hbm_bytes=fields["hbm_bytes"],
            wire_bytes=fields["wire_bytes"],
            gather_bytes=fields["gather_bytes"],
            paged_impl=fields.get("paged_impl"),
            n_chips=fields["n_chips"])

    # ----------------------------------------------------------------- slo
    def slo_report(self) -> Optional[dict]:
        """The live SLO engine's roll-up verdict (``monitor/slo.py``;
        docs/monitoring.md#slo-tracking): per-objective error budgets +
        burn rates over the serving series this engine emits
        (``latency_p99_ms``/``ttft_p50_ms``/``error_rate``/
        ``tokens_per_sec``), plus the regression sentinel's trip count.
        What a bench rung embeds as ``extra.slo`` and the SLO-driven
        autotuner (ROADMAP #5) scores candidates by.  None unless the
        attached monitor carries a ``monitor.slo`` config."""
        return self.monitor.slo_verdict()

    # ------------------------------------------------------------ memory ledger
    def memory_ledger(self) -> dict:
        """One memory-ledger snapshot (``monitor/memory_ledger.py``):
        weights, the paged-KV pool with its in-use block split, decode +
        per-bucket prefill executables, compile-cache disk, measured
        gauges, and the explicit residual.  Host-side reads only — the
        compiled decode step is byte-identical ledger-on vs off
        (``--audit-step mem``)."""
        from ..monitor import memory_ledger as mled
        return mled.attribute_serving(self).snapshot()

    def _memory_forensics(self, reason, budget_bytes=None, extra=None):
        """Ledger + capacity-verdict dump for a memory-shaped failure
        (preflight over budget, admission stall).  Best-effort; returns
        the path or None and never masks the raise it accompanies.
        Needs an explicitly configured ``forensic_dir``/``journal_dir``
        — unlike the breaker (whose dump IS the event record), a
        memory dump must not litter the launch cwd of every
        mis-submitted request."""
        from ..monitor import memory_ledger as mled
        dirpath = self.config.forensic_dir or self.config.journal_dir
        if not dirpath:
            return None
        try:
            path = mled.oom_forensics(
                dirpath, self.memory_ledger(), reason=reason,
                budget_bytes=budget_bytes,
                filename=f"serving_memory_forensics_step"
                         f"{self._steps}.json", extra=extra)
        except Exception as e:
            logger.warning(f"serving memory forensics unavailable ({e})")
            return None
        mon = self.monitor
        if path and mon.armed:
            mon.artifact("memory_forensics", path, step=self._steps,
                         reason=str(reason)[:200])
            mon.flush()
        return path

    def run(self, requests=None, max_steps: int = 10 ** 6) -> Dict[int, dict]:
        """Submit ``requests`` (if given) and drive :meth:`step` until
        the queue drains and every slot completes.  Returns
        ``self.results`` (uid → tokens + stamps + outcome)."""
        for r in requests or ():
            self.submit(r)
        steps = 0
        while self.step():
            steps += 1
            if steps > max_steps:
                raise ServingStalledError(
                    f"serving run exceeded {max_steps} steps with work "
                    f"still pending ({len(self.queue)} queued, "
                    f"{sum(s is not None for s in self._slots)} active)")
        return self.results

    # ----------------------------------------------------------------- drain
    def drain(self, timeout_s: Optional[float] = None) -> dict:
        """Graceful shutdown: stop admission, let the ACTIVE slots finish
        (bounded by ``timeout_s``, default ``serving.drain_timeout_s``),
        and journal a clean-shutdown marker.  Queued-but-unseated
        requests are left journaled as pending — a restarted engine
        re-queues and serves them (:meth:`_recover`); WITHOUT a journal
        no restart will ever serve them, so they finalize as typed
        ``SHED`` results instead of staying in-flight forever.
        Idempotent; :meth:`close` drains first.  Returns a summary
        dict."""
        if timeout_s is None:
            timeout_s = self.config.drain_timeout_s
        self._settle()
        self._draining = True
        deadline = time.monotonic() + max(0.0, float(timeout_s))
        timed_out = False
        while any(s is not None for s in self._slots):
            if time.monotonic() >= deadline:
                timed_out = True
                break
            self.step()
        active = sum(s is not None for s in self._slots)
        summary = {"clean": not timed_out, "active": active,
                   "queued": len(self.queue)}
        if timed_out:
            logger.warning(
                f"serving drain timed out after {timeout_s}s with "
                f"{active} slot(s) still active — "
                + ("their requests stay journaled as in-flight (a "
                   "restart re-queues them)" if self.journal is not None
                   else "their requests finalize as typed 'shed' "
                        "results (no journal, no restart)"))
        mon = self.monitor
        if mon.armed:
            # final whole-run distributions: a run shorter than the
            # periodic cadence still leaves mergeable hist events in its
            # stream (what ds_explain / a restart merge reads)
            for hname, h in (("latency_ms", self._lat_hist),
                             ("ttft_ms", self._ttft_hist),
                             ("step_wall_ms", self._step_wall_hist)):
                if h:
                    mon.hist(hname, h, step=self._steps, unit="ms")
            self._emit_exe_cost(mon)
            mon.flush()
        if self.journal is not None:
            self.journal.shutdown(clean=not timed_out,
                                  pending=active + len(self.queue))
        else:
            # no journal = no restart will ever serve the leftovers:
            # give each — queued AND timed-out active — a typed terminal
            # outcome instead of an eternally in-flight record ("every
            # terminal outcome is typed" must hold on the default
            # configuration too; close() frees the pool right after)
            while self.queue:
                self._finalize_unseated(self.queue.popleft(), SHED,
                                        "drain without a journal")
            for i, s in enumerate(self._slots):
                if s is not None:
                    self._finish(i, outcome=SHED)
        log_dist(f"serving drained: {summary}", ranks=[0])
        return summary

    # ------------------------------------------------------------- reporting
    def pop_result(self, uid: int) -> dict:
        """Take ownership of a completed request's record (tokens +
        stamps) and drop it from ``results`` — the drain API a
        long-running server uses so records don't accumulate.  The
        latency aggregates behind :meth:`stats` are kept separately and
        survive the pop.  Raises KeyError for an unknown uid,
        RuntimeError for one still in flight."""
        rec = self.results[uid]
        if rec["t_done"] is None:
            raise RuntimeError(f"request {uid} is still in flight")
        if self._sanitizer is not None:
            self._sanitizer.on_serve(uid)
        return self.results.pop(uid)

    def reset_stats(self):
        """Zero the latency/throughput aggregates, the outcome counters
        and the recent-outcome ring, and drop completed records;
        in-flight requests and the breaker state are untouched (bench
        warmup hygiene — an OPEN breaker must survive a stats reset)."""
        for uid in [u for u, r in self.results.items()
                    if r["t_done"] is not None]:
            del self.results[uid]
        self._lat_hist = LogHistogram()
        self._ttft_hist = LogHistogram()
        self._step_wall_hist = LogHistogram()
        self._completed_total = 0
        self._generated_total = 0
        self._steps = 0
        self._reused_steps = 0
        self._state_uploads = 0
        self._ahead_steps = 0
        self._admits_under = 0
        self._grown_total = 0
        self._folded_total = self._folded_reported = 0
        self._state_seats = 0
        self._outcomes = {k: 0 for k in OUTCOMES}
        self._requeued_total = 0
        self._err_window_last = (0, 0)
        self._kv_snapshots_total = 0
        self._kv_migrated_total = 0
        self._kv_fallback_total = 0
        self._kv_tokens_saved_total = 0
        self._kv_restore_ms = []
        self._transfers_total = 0
        self._transfer_bytes_total = 0
        self._transfer_backpressure_total = 0
        self._transfer_pub_ms = []
        self._traces_emitted = 0
        # prefix-sharing counters reset; the CACHE itself is kept (warm
        # prefixes are the bench's measured state, not its warmup noise)
        self._prefix_requests_total = 0
        self._prefix_hits_total = 0
        self._prefix_shared_blocks_total = 0
        self._prefix_cow_total = 0
        self._prefix_evicted_total = 0
        self._recent = RingBuffer(max(1, int(self.config.poison_window)))

    def stats(self) -> dict:
        """Latency/throughput summary over completed requests: p50/p99/
        p999 submit→done and submit→first-token (ms), generated tokens.
        Percentiles come from the mergeable log-bucketed histograms
        (monitor/histogram.py) and cover EVERY completion since the last
        :meth:`reset_stats` — exact counts, ≤1% relative value error —
        not a truncated deque window."""
        out = {"completed": self._completed_total,
               "pending": len(self.queue) + sum(
                   s is not None for s in self._slots),
               "decode_steps": self._steps,
               # of those, the steps that sent no slot state up; and how
               # often the packed state went up (docs/serving.md#step-anatomy)
               "state_reused_steps": self._reused_steps,
               "state_uploads": self._state_uploads,
               # and the steps dispatched while the one before was still
               # unread (docs/serving.md#one-step-in-flight)
               "steps_ahead": self._ahead_steps,
               # and the prefills dispatched behind such a step
               "admits_under_step": self._admits_under,
               # blocks granted to seated rows at the dispatch that first
               # wrote into them (docs/serving.md#capacity-math--admission-control)
               "blocks_grown_total": self._grown_total,
               # where the cache folds: windows folded in decoding, and the
               # blocks their folds gave back (docs/serving.md#folded-cache)
               **({"windows_folded_total": self._folded_total,
                   "blocks_released_by_fold_total":
                   self._folded_total * self._fold.window_blocks}
                  if self._fold is not None else {}),
               "generated_tokens": self._generated_total,
               # what the donated pytree holds: K/V blocks, and for a
               # model with recurrent layers its per-slot rows and how
               # often a slot's rows were written whole
               "kv_pool_bytes": self._kv_pool_bytes,
               # what one token costs it, over how many layer-applications,
               # of how many loops over the layers
               "kv_bytes_per_token": self._kv_pool_bytes // (
                   self.num_blocks * self.config.block_size),
               **self._loop_attrs,
               # and what the model says of its own state (an expert
               # model's held experts, a latent pool's row)
               **(self.model.serving_stats(self.pool)
                  if hasattr(self.model, "serving_stats") else {}),
               # the second kind of block, for a model with window layers
               **({"window_num_blocks": self.window_num_blocks,
                   "window_ring_blocks": self.ring,
                   "window_pool_bytes": sum(
                       int(self.pool[n].nbytes) for n in pk.WINDOW)}
                  if self.ring else {}),
               "recurrent_state_bytes": self._recurrent_bytes,
               "state_seats": self._state_seats,
               "outcomes": dict(self._outcomes),
               "requeued": self._requeued_total,
               "breaker_open": self._breaker_open,
               "traces_emitted": self._traces_emitted,
               # None = the startup memory gate had nothing to compare
               # (disabled, no budget, or no executable analysis)
               "preflight": self._preflight}
        if self._lat_hist:
            p = self._lat_hist.percentiles()
            out["latency_ms"] = {
                "p50": round(p["p50"], 2), "p99": round(p["p99"], 2),
                "p999": round(p["p999"], 2), "max": round(p["max"], 2)}
        if self._ttft_hist:
            p = self._ttft_hist.percentiles()
            out["ttft_ms"] = {
                "p50": round(p["p50"], 2), "p99": round(p["p99"], 2),
                "p999": round(p["p999"], 2)}
        if self._sanitizer is not None:
            out["sanitizer"] = self._sanitizer.stats()
            if self._window_sanitizer is not None:
                out["window_sanitizer"] = self._window_sanitizer.stats()
        if (self.kvs is not None or self._kv_migrated_total
                or self._kv_fallback_total):
            kv = {"snapshots": self._kv_snapshots_total,
                  "migrated_streams": self._kv_migrated_total,
                  "migration_fallbacks": self._kv_fallback_total,
                  "recompute_tokens_saved": self._kv_tokens_saved_total}
            if self._kv_restore_ms:
                kv["restore_ms"] = {
                    "mean": round(sum(self._kv_restore_ms)
                                  / len(self._kv_restore_ms), 3),
                    "max": round(max(self._kv_restore_ms), 3)}
            if self.kvs is not None:
                kv["policy"] = self.kvs.describe()
            out["kv_snapshot"] = kv
        if self._txq is not None:
            tr = dict(self._txq.stats())
            tr["role"] = self.role
            tr["published_by_this_engine"] = self._transfers_total
            tr["published_bytes_by_this_engine"] = \
                self._transfer_bytes_total
            tr["backpressure_degraded"] = \
                self._transfer_backpressure_total
            if self._transfer_pub_ms:
                tr["handoff_ms"] = {
                    "mean": round(sum(self._transfer_pub_ms)
                                  / len(self._transfer_pub_ms), 3),
                    "max": round(max(self._transfer_pub_ms), 3)}
            out["transfer"] = tr
        if self._prefix_index is not None:
            out["prefix_cache"] = {
                "requests": self._prefix_requests_total,
                "requests_hit": self._prefix_hits_total,
                "hit_rate": round(
                    self._prefix_hits_total
                    / max(1, self._prefix_requests_total), 4),
                "shared_blocks_attached": self._prefix_shared_blocks_total,
                "cow_copies": self._prefix_cow_total,
                "evicted_blocks": self._prefix_evicted_total,
                "unique_blocks_in_use": self.allocator.used_blocks,
                "logical_blocks": self.allocator.logical_blocks,
                "index": self._prefix_index.stats(),
                "policy": self.prefix.describe()}
        return out

    def compile_report(self):
        return self.engine.compile_report()

    def close(self):
        """Graceful shutdown: :meth:`drain` (finish active slots, journal
        a clean shutdown), then drop live executables and the pool (bench
        hygiene — the same contract as ``DeepSpeedEngine.close``).  An
        engine the CALLER passed in (``engine=``) stays usable — only an
        internally built one is torn down.  Idempotent."""
        if self._closed:
            return
        self._closed = True
        try:
            # a drain failure (wedged backend, armed crash site) must not
            # leak the pool/executables/journal fd: teardown runs anyway
            self.drain()
            if self._prefix_index is not None:
                # the cache's references are deliberate, not leaks:
                # release them BEFORE the shadow leak check below
                dropped, released = self._prefix_index.clear()
                if self._sanitizer is not None:
                    self._sanitizer.on_unshare(dropped)
                    self._sanitizer.on_free(released)
            if self._sanitizer is not None:
                # after a clean drain every block must be home —
                # anything still allocated is a leak (DSTPU312)
                self._sanitizer.on_close()
                if self._window_sanitizer is not None:
                    self._window_sanitizer.on_close()
            # snapshot retention at teardown: finished uids' images go;
            # journaled still-pending uids keep theirs (a restart or a
            # router handoff may restore them)
            self._cleanup_snapshot_dirs()
        finally:
            try:
                if self.journal is not None:
                    self.journal.close()
            except OSError as e:
                logger.warning(f"serving: journal close failed ({e}); "
                               "continuing teardown")
            for fn in [self._decode, self._unpack, self._grow] + list(
                    self._prefills.values()):
                if fn is not None and hasattr(fn, "clear"):
                    fn.clear()
            self._decode = self._unpack = self._grow = self._resident = None
            self._unread = None
            self._prefills.clear()
            self._blockset = None
            self._blockcopy = None
            self.pool = None
            if self._owns_monitor:
                self.monitor.close()
            if self._owns_engine:
                self.engine.close()
