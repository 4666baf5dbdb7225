"""Replica router: zero-loss serving across replica failure, hang, and
straggle (docs/serving.md#replica-router).

PR-15 shipped the fleet *signal* (per-replica cadence/queue gauges,
leave-one-out straggler z-scores, SLO burn rates — ``monitor/fleet.py``);
this module is the *controller* that closes the loop: a front tier that
spreads traffic over N ``ServingEngine`` replicas and turns the
observability verdicts into placement and lifecycle actions.

Design (each piece reuses a proven subsystem rather than inventing one):

- **placement** — every queued request goes to the live replica with the
  lowest placement score: the router's own outstanding count for that
  replica plus the queue-depth/step-cadence signal read from the
  replica's OWN monitor stream (the same ``ReplicaView`` signals
  ``ds_fleet`` renders).  No second bookkeeping protocol: the telemetry
  the replicas already emit IS the load-balancing input.
- **health state machine** — per replica: ``healthy → suspect →
  (draining|dead)``.  A missed heartbeat makes a replica *suspect*
  (placement stops); re-probes back off with FULL jitter
  (``utils/retry.py`` — a fleet of routers re-probing a shared wedged
  replica must decorrelate); a fresh heartbeat heals it, heartbeat
  silence past ``dead_after_s`` (or process exit, or probe exhaustion)
  kills it.  The fleet straggler verdict and an SLO burn-rate breach
  DRAIN a replica — stop placing, let in-flight work finish — because a
  slow replica still holds answers; killing it would forfeit them.
  Draining recovers once the verdict clears for ``drain_clear_evals``
  consecutive evaluations.  ``dead`` is terminal.
- **crash handoff** — a dead replica's unfinished uids are recovered
  from its PR-10 request journal (``journal.replay`` — torn/foreign
  line counts surfaced, not logged-and-forgotten) and requeued onto
  siblings.  Sampling streams are pure functions of the request
  (``fold_in(PRNGKey(seed), token_index)`` — docs/serving.md), so the
  re-run is token-identical no matter which replica serves it or what
  it co-batches with.  Journaled finishes the router had not yet
  observed are adopted instead of recomputed.
- **exactly-once results** — the router's result table is set-once per
  uid: the FIRST terminal outcome wins, any later answer (a
  hung-but-alive replica that finally responds after its work was
  requeued) is counted as ``duplicates_suppressed`` and never served.
- **graceful degradation** — admission shed (``max_outstanding``) and
  deadline enforcement at the router itself, so a shrunken fleet
  degrades with typed ``SHED``/``DEADLINE`` outcomes on the monitor bus
  instead of unbounded queueing.
- **role pools (disaggregation)** — replicas may declare a serving
  role (``mixed`` / ``prefill`` / ``decode`` —
  docs/serving.md#disaggregation).  Fresh requests route to the
  healthy PREFILL pool by queue depth; a prefill worker's
  ``transferred`` outcome carries a committed transfer entry
  (``inference/transfer.py``) that the router seats onto the DECODE
  pool by free-block count through the same restore-first path the
  crash handoff uses.  An empty or unhealthy role pool degrades to
  mixed (then to any healthy replica) with a
  ``degraded_placements`` counter — never a stall.  The PR-16
  guarantees hold across the new edge: a prefill worker killed
  mid-transfer recovers through its journal AND its committed
  transfer entries (``transfer.find_transfer_entry``), set-once
  dedup suppresses the late copy.

Three replica shapes share the router logic: in-process engines
(:class:`LocalReplica` — unit tests, single-host serving), subprocess
workers speaking a directory protocol (:class:`ProcessReplica` +
:func:`replica_worker` — the chaos bench's real kill target), and
anything else implementing :class:`ReplicaHandle`.

CLI (``bin/ds_router``): observe mode — merge replica monitor streams
and render the health/placement table the live router would act on
(``--once``/``--json`` over committed fixtures is the tier-1 smoke);
``--worker spec.json`` runs one subprocess replica worker.
"""

import argparse
import dataclasses
import json
import os
import sys
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from .. import fault
from ..monitor.core import NullMonitor
from ..monitor.fleet import (FleetFollower, FleetView, ReplicaView,
                             STRAGGLER_ZMAX, STRAGGLER_MIN_EXCESS)
from ..utils.logging import logger
from ..utils.retry import RetryPolicy
from . import journal as jr
from . import transfer as xfer
from .serving import (Request, QueueFullError, ServingError,
                      OK, SHED, DEADLINE, stream_snapshot_dir)

# health states (docs/serving.md#replica-router)
HEALTHY = "healthy"
SUSPECT = "suspect"      # heartbeat missed: no placement, probing
DRAINING = "draining"    # straggler / SLO burn: no placement, work finishes
DEAD = "dead"            # terminal: journal replayed, work requeued

HEARTBEAT_FILE = "heartbeat.json"
INBOX_DIR = "inbox"
STOP_FILE = "stop"
READY_FILE = "ready"


@dataclasses.dataclass
class RouterConfig:
    """Router policy knobs (resolved policy printed by ``ds_report``)."""
    suspect_after_s: float = 2.0     # heartbeat age -> suspect
    dead_after_s: float = 6.0        # heartbeat age -> dead
    probe_retry: Optional[RetryPolicy] = None   # suspect re-probe backoff
    straggler_zmax: float = STRAGGLER_ZMAX
    straggler_min_excess: float = STRAGGLER_MIN_EXCESS
    drain_clear_evals: int = 3       # consecutive clean verdicts to heal
    slo_burn_drain: float = 10.0     # worst per-replica burn rate -> drain
    deadline_ms: Optional[float] = None   # router-level latency budget
    max_outstanding: int = 0         # admission shed bound (0 = unbounded)
    monitor_interval: int = 8        # emit router telemetry every N pumps
    # role override map name -> mixed|prefill|decode; unset names keep
    # the role the handle itself reports (docs/serving.md#disaggregation)
    roles: Optional[Dict[str, str]] = None

    def resolved_probe_retry(self) -> RetryPolicy:
        # FULL jitter (AWS-style): many routers probing one wedged
        # replica must decorrelate, exactly the thundering-herd case
        # utils/retry.py documents
        return self.probe_retry or RetryPolicy(
            max_attempts=6, base_delay_s=0.1, max_delay_s=2.0,
            jitter_mode="full")

    def describe(self) -> dict:
        pr = self.resolved_probe_retry()
        return {
            "suspect_after_s": self.suspect_after_s,
            "dead_after_s": self.dead_after_s,
            "probe_backoff": f"{pr.jitter_mode} jitter, "
                             f"base {pr.base_delay_s}s, "
                             f"max {pr.max_delay_s}s, "
                             f"{pr.max_attempts} attempts",
            "straggler_zmax": self.straggler_zmax,
            "straggler_min_excess": self.straggler_min_excess,
            "drain_clear_evals": self.drain_clear_evals,
            "slo_burn_drain": self.slo_burn_drain,
            "deadline_ms": self.deadline_ms,
            "max_outstanding": self.max_outstanding,
            "roles": dict(self.roles or {}),
        }


# --------------------------------------------------------------- handles
class ReplicaHandle:
    """One serving replica as the router sees it.  Implementations:
    :class:`LocalReplica` (in-process engine), :class:`ProcessReplica`
    (subprocess worker, directory protocol), test fakes."""

    name: str = "?"
    role: str = "mixed"          # mixed | prefill | decode

    def submit(self, req: Request, snapshot_dir: Optional[str] = None,
               seat: Optional[dict] = None):
        """Place one request on this replica (must journal it durably
        before acknowledging, where a journal exists).  When
        ``snapshot_dir`` names a committed KV block image of the stream
        (docs/serving.md#kv-migration), the replica should attempt
        restore-first admission (``ServingEngine.submit_restored``) and
        fall back to plain recompute on any image defect; ``seat`` is
        the transfer seat record (disaggregation) the restore path
        verifies the image against — the stale-handoff guard.
        In-process handles return the restore outcome dict
        synchronously; subprocess handles return ``None`` and report
        the outcome through their journal's ``restore`` record."""
        raise NotImplementedError

    def poll(self) -> List[dict]:
        """Newly finished results since the last poll:
        ``[{"uid", "outcome", "tokens"}, ...]``.  Passive — safe to call
        on a dead replica (late answers feed the dedup path)."""
        raise NotImplementedError

    def pump(self):
        """Advance in-process work (no-op for subprocess replicas)."""

    def heartbeat(self) -> Optional[float]:
        """Wall-clock stamp of the replica's last sign of life."""
        raise NotImplementedError

    def alive(self) -> bool:
        """Process-level liveness; True when unknowable."""
        return True

    @property
    def journal_dir(self) -> Optional[str]:
        return None

    def load(self) -> dict:
        """Best-effort {"queued": int, "active": int} placement signal."""
        return {}

    def stop(self):
        """Ask the replica to finish its work and shut down clean."""

    def close(self):
        """Release resources (hard: a dead subprocess gets terminated)."""


class LocalReplica(ReplicaHandle):
    """An in-process ``ServingEngine`` behind the handle interface.
    Heartbeat = the last time :meth:`pump` ran the engine (an in-process
    engine cannot silently die, but the interface stays uniform so the
    state machine is testable with frozen clocks)."""

    def __init__(self, name: str, engine, clock=time.time):
        self.name = name
        self.engine = engine
        self.role = getattr(engine, "role", "mixed")
        self._clock = clock
        self._hb = clock()
        self._submitted = set()

    def submit(self, req: Request, snapshot_dir: Optional[str] = None,
               seat: Optional[dict] = None):
        out = None
        if snapshot_dir is not None:
            out = self.engine.submit_restored(req, snapshot_dir, seat=seat)
        else:
            self.engine.submit(req)
        self._submitted.add(req.uid)
        return out

    def pump(self):
        self.engine.step()
        self._hb = self._clock()

    def poll(self) -> List[dict]:
        out = []
        for uid in sorted(self._submitted):
            rec = self.engine.results.get(uid)
            if rec is not None and rec["outcome"] is not None:
                rec = self.engine.pop_result(uid)
                self._submitted.discard(uid)
                if rec["outcome"] == xfer.TRANSFERRED:
                    # a prefill worker's terminal outcome is a HANDOFF,
                    # not an answer: surface the committed transfer
                    # entry + seat record so the router seats it on the
                    # decode pool
                    xres = self.engine.pop_transfer(uid) or {}
                    out.append({"kind": "transfer", "uid": uid,
                                "entry": xres.get("entry"),
                                "seat": xres.get("seat"),
                                "gen": xres.get("gen"),
                                "bytes": xres.get("bytes")})
                    continue
                out.append({"uid": uid, "outcome": rec["outcome"],
                            "tokens": rec["tokens"]})
        return out

    def heartbeat(self) -> Optional[float]:
        return self._hb

    @property
    def journal_dir(self) -> Optional[str]:
        return self.engine.config.journal_dir

    def load(self) -> dict:
        st = self.engine.stats()
        out = {"queued": len(self.engine.queue),
               "active": st["pending"] - len(self.engine.queue)}
        alloc = getattr(self.engine, "allocator", None)
        if alloc is not None:
            # the decode-pool seating signal: a restored stream lands
            # where the paged pool has the most room
            out["free_blocks"] = int(alloc.free_blocks)
        out["slots_free"] = max(
            0, int(self.engine.config.batch_slots) - out["active"])
        return out

    def stop(self):
        self.engine.drain()

    def close(self):
        self.engine.close()


class ProcessReplica(ReplicaHandle):
    """A subprocess replica worker (:func:`replica_worker`) behind a
    crash-safe directory protocol under ``root``:

    - ``inbox/req-<uid>.json`` — requests, written ATOMICALLY
      (tmp + rename) by the router; the worker submits to its engine
      (which journals the request durably) and only THEN unlinks, so a
      kill at any instant loses nothing: either the inbox file survives
      or the journal holds the submit;
    - ``journal/requests.jsonl`` — the results channel: the router
      incrementally tails the worker's own PR-10 journal for ``finish``
      records (complete lines only — torn tails wait for the next
      poll).  No second results protocol to keep crash-consistent;
    - ``heartbeat.json`` — touched every worker iteration; its mtime is
      the liveness signal (an IDLE engine emits no monitor events, so
      the event stream alone cannot prove liveness);
    - ``stop`` — graceful-shutdown request; ``ready`` — worker is up.
    """

    def __init__(self, name: str, root: str, proc=None, role: str = "mixed"):
        self.name = name
        self.root = root
        self.role = role             # must match the worker spec's role
        self.proc = proc             # subprocess.Popen | None
        self.inbox = os.path.join(root, INBOX_DIR)
        self._jdir = os.path.join(root, "journal")
        self._jpath = os.path.join(self._jdir, jr.JOURNAL_FILE)
        self._offset = 0             # journal tail position
        os.makedirs(self.inbox, exist_ok=True)

    def submit(self, req: Request, snapshot_dir: Optional[str] = None,
               seat: Optional[dict] = None):
        spec = {"uid": int(req.uid),
                "tokens": [int(t) for t in np.asarray(req.tokens).ravel()],
                "max_new_tokens": (None if req.max_new_tokens is None
                                   else int(req.max_new_tokens)),
                "temperature": float(req.temperature),
                "do_sample": bool(req.do_sample),
                "seed": int(req.seed)}
        if snapshot_dir is not None:
            # restore-first hint: the worker attempts submit_restored
            # and reports the outcome via its journal's restore record
            spec["snapshot_dir"] = snapshot_dir
        if seat is not None:
            spec["seat"] = seat      # stale-handoff guard input
        path = os.path.join(self.inbox, f"req-{int(req.uid):08d}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(spec, f)  # dstpu: disable=DSTPU104
        os.replace(tmp, path)        # atomic: the worker never sees a torn file

    def poll(self) -> List[dict]:
        if not os.path.isfile(self._jpath):
            return []
        out = []
        with open(self._jpath, "rb") as f:
            f.seek(self._offset)
            chunk = f.read()
        # complete lines only: a torn tail stays for the next poll
        end = chunk.rfind(b"\n")
        if end < 0:
            return []
        self._offset += end + 1
        for line in chunk[:end].split(b"\n"):
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue             # foreign matter; replay() will count it
            if rec.get("kind") == "finish":
                if rec.get("outcome") == xfer.TRANSFERRED:
                    # the transfer record (journaled just before this
                    # finish) carries the handoff; surfacing the finish
                    # too would double-seat the uid
                    continue
                out.append({"uid": int(rec["uid"]),
                            "outcome": rec.get("outcome"),
                            "tokens": rec.get("tokens")})
            elif rec.get("kind") == "transfer":
                # a prefill worker published this stream's block image:
                # hand the committed entry + seat record to the router
                out.append({"kind": "transfer", "uid": int(rec["uid"]),
                            "entry": rec.get("entry"),
                            "seat": rec.get("seat"),
                            "gen": rec.get("gen"),
                            "bytes": rec.get("bytes")})
            elif rec.get("kind") == "restore":
                # restore-first outcome report from submit_restored —
                # the router's migration counters feed on these
                out.append({"kind": "restore", "uid": int(rec["uid"]),
                            "restored": bool(rec.get("restored")),
                            "restore_ms": rec.get("restore_ms", 0.0),
                            "tokens_saved": rec.get("tokens_saved", 0)})
        return out

    def heartbeat(self) -> Optional[float]:
        try:
            return os.path.getmtime(os.path.join(self.root, HEARTBEAT_FILE))
        except OSError:
            return None

    def alive(self) -> bool:
        return self.proc is None or self.proc.poll() is None

    @property
    def journal_dir(self) -> Optional[str]:
        return self._jdir

    def stop(self):
        open(os.path.join(self.root, STOP_FILE), "w").close()

    def close(self):
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()


# ---------------------------------------------------------------- router
class _ReplicaState:
    """Router-side lifecycle record for one replica."""

    def __init__(self, handle: ReplicaHandle):
        self.handle = handle
        self.state = HEALTHY
        self.role = getattr(handle, "role", "mixed")
        self.since = 0.0
        self.reason = ""
        self.probe_attempt = 0
        self.next_probe_t = 0.0
        self.clear_evals = 0
        self.assigned = set()        # uids outstanding on this replica


class ReplicaRouter:
    """The replica front tier (module docstring).  Single-threaded like
    the serving scheduler: callers drive :meth:`pump` (or :meth:`run` /
    :meth:`drain`)."""

    def __init__(self, replicas: List[ReplicaHandle], config=None,
                 monitor=None, stream_sources=None, clock=time.time):
        names = [r.name for r in replicas]
        assert len(names) == len(set(names)), \
            f"replica names must be unique, got {names}"
        self.config = config or RouterConfig()
        self.monitor = monitor or NullMonitor()
        self._clock = clock
        self._probe = self.config.resolved_probe_retry()
        self._replicas: Dict[str, _ReplicaState] = {
            r.name: _ReplicaState(r) for r in replicas}
        now = clock()
        roles = dict(self.config.roles or {})
        for st in self._replicas.values():
            st.since = now
            st.role = roles.get(st.handle.name, st.role)
            if st.role not in xfer.ROLES:
                raise ValueError(
                    f"replica {st.handle.name!r}: role {st.role!r} not in "
                    f"{xfer.ROLES} (docs/serving.md#disaggregation)")
        # per-replica monitor streams: the placement/straggler signal.
        # dict name->run_dir, or a list aligned with `replicas`.
        self._fleet: Optional[FleetFollower] = None
        self._view_by_source: Dict[str, str] = {}
        if stream_sources:
            if not isinstance(stream_sources, dict):
                stream_sources = dict(zip(names, stream_sources))
            self._fleet = FleetFollower(list(stream_sources.values()))
            self._view_by_source = {src: name for name, src
                                    in stream_sources.items()}
        self.queue = deque()         # unplaced Requests
        self.results: Dict[int, dict] = {}
        self._next_uid = 0
        self._pumps = 0
        self._submitted_total = 0
        self._routed_total = 0
        self._requeued_total = 0
        self._duplicates_suppressed = 0
        self._unknown_results = 0
        self._torn_recovered = 0
        self._foreign_recovered = 0
        self._adopted_finishes = 0
        self._outcomes = {OK: 0, SHED: 0, DEADLINE: 0}
        # KV migration (docs/serving.md#kv-migration): restore-first
        # handoff outcome counters — ds_bench_diff gates on these
        self._migrated_streams = 0
        self._migrated_uids: List[int] = []
        self._migration_fallbacks = 0
        self._recompute_tokens_saved = 0
        self._restore_ms: List[float] = []
        self._handoff_ms: List[float] = []
        # disaggregation (docs/serving.md#disaggregation): prefill ->
        # decode seatings across the transfer-queue edge
        self._transfers_seated = 0
        self._transfer_seat_fallbacks = 0
        self._degraded_placements = 0
        self._seated_entries: Dict[int, str] = {}
        self._pending_seats = deque()    # (origin name, transfer res)
        self._drain_events: List[dict] = []
        self._dead_events: List[dict] = []

    # ------------------------------------------------------------ submit
    def submit(self, req: Request) -> int:
        """Accept one request at the front tier.  Admission shed
        (``max_outstanding``) and the router deadline produce TYPED
        outcomes in the result table — degraded service stays
        observable, it never becomes an exception storm."""
        if req.uid is None:
            req.uid = self._next_uid
        self._next_uid = max(self._next_uid, int(req.uid)) + 1
        uid = int(req.uid)
        if uid in self.results:
            raise ValueError(f"uid {uid} already submitted to the router")
        now = self._clock()
        rec = {"uid": uid, "request": req, "outcome": None, "tokens": None,
               "t_submit": now, "t_done": None, "replica": None,
               "deadline": (now + self.config.deadline_ms / 1e3
                            if self.config.deadline_ms is not None
                            else None)}
        self.results[uid] = rec
        self._submitted_total += 1
        if self.config.max_outstanding and \
                self._outstanding() >= self.config.max_outstanding:
            self._finalize(rec, SHED, None, "router admission shed")
            return uid
        self.queue.append(req)
        return uid

    def _outstanding(self) -> int:
        return (len(self.queue) + len(self._pending_seats)
                + sum(len(st.assigned) for st in self._replicas.values()))

    # -------------------------------------------------------------- pump
    def pump(self) -> bool:
        """One router iteration: heartbeat/health transitions, fleet
        verdict, dead-replica handoff, placement, replica pumps, result
        collection, telemetry.  Returns True while work is outstanding."""
        now = self._clock()
        self._pumps += 1
        self._check_heartbeats(now)
        self._check_fleet_verdicts(now)
        for st in list(self._replicas.values()):
            if st.state == DEAD and st.assigned:
                self._handoff(st, now)
        if self._pending_seats:
            # transfers deferred while every decode target was slot-full:
            # retry before placement so a freed slot admits THIS pump
            pend, self._pending_seats = self._pending_seats, deque()
            for name, res in pend:
                origin = self._replicas.get(name)
                if origin is not None:
                    self._seat_transfer(origin, res)
        self._place(now)
        for st in self._replicas.values():
            if st.state != DEAD:
                st.handle.pump()
        self._collect(now)
        self._emit(now)
        return bool(self._outstanding())

    # ---------------------------------------------------- state machine
    def _set_state(self, st: _ReplicaState, state: str, now, reason=""):
        if st.state == state:
            return
        logger.warning(f"router: replica {st.handle.name!r} "
                       f"{st.state} -> {state}"
                       + (f" ({reason})" if reason else ""))
        if self.monitor.armed:
            self.monitor.counter(f"router_{state}_transitions", 1)
        st.state = state
        st.since = now
        st.reason = reason
        if state == DRAINING:
            st.clear_evals = 0
            self._drain_events.append(
                {"replica": st.handle.name, "reason": reason, "t": now})
        if state == SUSPECT:
            st.probe_attempt = 0
            st.next_probe_t = now   # first probe immediately
        if state == DEAD:
            self._dead_events.append(
                {"replica": st.handle.name, "reason": reason, "t": now})
            self._handoff(st, now)

    def _check_heartbeats(self, now):
        cfg = self.config
        for st in self._replicas.values():
            if st.state == DEAD:
                continue
            if not st.handle.alive():
                self._set_state(st, DEAD, now, "process exit")
                continue
            hb = st.handle.heartbeat()
            age = None if hb is None else now - hb
            if st.state in (HEALTHY, DRAINING):
                if age is not None and age > cfg.suspect_after_s:
                    self._set_state(st, SUSPECT, now,
                                    f"heartbeat {age:.2f}s old")
            elif st.state == SUSPECT:
                if now < st.next_probe_t:
                    continue         # between backoff probes
                st.probe_attempt += 1
                if age is not None and age <= cfg.suspect_after_s:
                    self._set_state(st, HEALTHY, now, "heartbeat recovered")
                elif age is None or age > cfg.dead_after_s or \
                        st.probe_attempt >= self._probe.max_attempts:
                    self._set_state(
                        st, DEAD, now,
                        "no heartbeat" if age is None else
                        f"heartbeat {age:.2f}s old after "
                        f"{st.probe_attempt} probe(s)")
                else:
                    # full-jitter backoff between probes: a fleet of
                    # routers must not re-probe a wedged replica in
                    # lockstep
                    st.next_probe_t = now + self._probe.delay(
                        st.probe_attempt - 1)

    def _check_fleet_verdicts(self, now):
        if self._fleet is None:
            return
        self._fleet.poll()
        live_views = []
        for view in self._fleet.views:
            name = self._replica_for_view(view)
            if name is not None and self._replicas[name].state != DEAD:
                live_views.append(view)
        # verdict over LIVE replicas only: a dead replica's frozen
        # history must not mask (or become) the straggler
        verdict = FleetView(live_views).straggler(
            zmax=self.config.straggler_zmax,
            min_excess=self.config.straggler_min_excess)
        named = verdict.get("straggler")
        burns = {v.label: max((max(f.get("burn_fast", 0),
                                   f.get("burn_slow", 0))
                               for f in v.slo.values()), default=0.0)
                 for v in live_views}
        for view in live_views:
            name = self._replica_for_view(view)
            st = self._replicas[name]
            is_named = (view.label == named
                        or st.handle.name == named)
            burned = burns.get(view.label, 0.0) >= self.config.slo_burn_drain
            if st.state == HEALTHY and (is_named or burned):
                reason = (f"straggler verdict ({verdict.get('series')})"
                          if is_named else
                          f"slo burn {burns[view.label]:.1f} >= "
                          f"{self.config.slo_burn_drain}")
                self._set_state(st, DRAINING, now, reason)
            elif st.state == DRAINING:
                if is_named or burned:
                    st.clear_evals = 0
                else:
                    st.clear_evals += 1
                    if st.clear_evals >= self.config.drain_clear_evals:
                        self._set_state(st, HEALTHY, now, "verdict cleared")

    def _replica_for_view(self, view: ReplicaView) -> Optional[str]:
        name = self._view_by_source.get(view.source)
        if name is not None:
            return name
        return view.label if view.label in self._replicas else None

    # ----------------------------------------------------------- handoff
    def _find_stream_snapshot(self, jd: str, uid: int) -> Optional[str]:
        """Newest manifest-valid KV snapshot of ``uid`` on the dead
        replica's journal, or None.  No snapshot directory at all is the
        silent common case (snapshots off, or cadence never reached);
        a directory holding NO valid image — every tag torn or corrupt
        — is the loud case: a typed ``migration_fallback`` event fires
        and the stream recomputes."""
        sdir = stream_snapshot_dir(jd, uid)
        if not os.path.isdir(sdir):
            return None
        from ..checkpoint import atomic
        tag = atomic.find_latest_valid(sdir)
        if tag is None:
            self._migration_fallbacks += 1
            logger.warning(
                f"router: uid {uid} has snapshot images under {sdir} but "
                "none is manifest-valid (torn/corrupt) — falling back to "
                "recompute (typed migration_fallback)")
            if self.monitor.armed:
                self.monitor.trace("migration_fallback", step=self._pumps,
                                   uid=int(uid),
                                   reason="no manifest-valid snapshot")
            return None
        return os.path.join(sdir, tag)

    def _note_restore_outcome(self, out: dict):
        """Fold one restore-first outcome (synchronous dict from a
        LocalReplica, journal ``restore`` record from a worker) into the
        migration counters.  An engine-side fallback already emitted its
        typed event on the replica's own monitor stream — the router
        only counts it."""
        if out.get("uid") is not None:
            # the seated image has been consumed (restored or rejected)
            xfer.drop_entry(self._seated_entries.pop(int(out["uid"]), None))
        if out.get("restored"):
            self._migrated_streams += 1
            if out.get("uid") is not None:
                self._migrated_uids.append(int(out["uid"]))
            self._restore_ms.append(float(out.get("restore_ms") or 0.0))
            self._recompute_tokens_saved += int(out.get("tokens_saved") or 0)
        else:
            self._migration_fallbacks += 1

    def _handoff(self, st: _ReplicaState, now):
        """Recover a dead replica's unfinished work, restore-first:
        adopt journaled finishes the router had not observed yet, then
        for each remaining uid try to seat its newest manifest-valid KV
        snapshot on a healthy sibling (``submit_restored`` — only the
        post-snapshot suffix re-decodes, token-identical by the
        sampling-stream contract); anything without a usable image — or
        whose placement is refused — falls back to the plain requeue
        path (same Request, fresh deadline budget, full recompute).
        Either way: never a lost uid, never a duplicated one."""
        t0 = time.perf_counter()
        # drain the results channel one last time (answers that landed
        # before death must not be recomputed)
        for res in st.handle.poll():
            self._record_result(st, res)
        jd = st.handle.journal_dir
        if jd:
            state = jr.replay(jd)
            self._torn_recovered += state["torn_lines"]
            self._foreign_recovered += state["foreign_lines"]
            for uid, rec in state["finished"].items():
                mine = self.results.get(int(uid))
                if mine is None or mine["outcome"] is not None:
                    continue
                if rec.get("outcome") == xfer.TRANSFERRED:
                    # journaled as handed off, not served: seat from
                    # the committed transfer entry (found below from
                    # the journal dir) instead of adopting the partial
                    # prefill-side tokens as an answer
                    self._seat_transfer(st, {"uid": int(uid)})
                    continue
                self._adopted_finishes += 1
                self._record_result(st, {
                    "uid": int(uid), "outcome": rec.get("outcome"),
                    "tokens": rec.get("tokens")})
        requeued = migrated = 0
        targets, _ = self._role_pool("decode", exclude=st)
        for uid in sorted(st.assigned):
            rec = self.results.get(uid)
            if rec is None or rec["outcome"] is not None:
                continue
            rec["replica"] = None
            if rec["deadline"] is not None and \
                    self.config.deadline_ms is not None:
                # a re-run deserves a fresh budget (the same re-arm the
                # journal-recovery path applies — serving.py Request)
                rec["deadline"] = now + self.config.deadline_ms / 1e3
            # restore-first, newest evidence first: a committed
            # transfer entry (the prefill worker died mid-handoff — the
            # image + seat record survive the process) beats a cadence
            # snapshot beats recompute
            snap = seat = None
            if jd:
                snap = xfer.find_transfer_entry(jd, uid)
                if snap is not None:
                    seat = self._read_transfer_seat(snap)
                else:
                    snap = self._find_stream_snapshot(jd, uid)
            if snap is not None and targets:
                target = min(targets, key=self._placement_score)
                try:
                    out = target.handle.submit(rec["request"],
                                               snapshot_dir=snap,
                                               seat=seat)
                except (QueueFullError, ValueError, ServingError) as e:
                    logger.warning(
                        f"router: restore placement of uid {uid} on "
                        f"{target.handle.name!r} refused ({e}) — "
                        "requeueing for recompute")
                else:
                    rec["replica"] = target.handle.name
                    target.assigned.add(uid)
                    self._routed_total += 1
                    migrated += 1
                    if out is not None:      # in-process: outcome now;
                        self._note_restore_outcome(out)
                    continue                 # workers report via journal
            self.queue.append(rec["request"])
            requeued += 1
        st.assigned.clear()
        self._requeued_total += requeued
        ms = (time.perf_counter() - t0) * 1e3
        self._handoff_ms.append(ms)
        if self.monitor.armed:
            self.monitor.counter("router_requeued_total",
                                 self._requeued_total)
            self.monitor.gauge("router_handoff_requeue_ms", ms)
        logger.warning(
            f"router: handoff from dead replica {st.handle.name!r}: "
            f"placed {migrated} stream(s) restore-first, requeued "
            f"{requeued} uid(s) for recompute in {ms:.1f}ms"
            + (f", torn_lines={self._torn_recovered}"
               if self._torn_recovered else ""))

    # --------------------------------------------------------- placement
    def _placement_score(self, st: _ReplicaState) -> float:
        """Lower = better.  The router's own outstanding count, plus the
        replica's self-reported load, scaled by the stream's observed
        step cadence (a slower replica's slot-second buys fewer
        tokens)."""
        score = float(len(st.assigned))
        load = st.handle.load()
        score = max(score, float(load.get("queued", 0)
                                 + load.get("active", 0)))
        view = self._view_for(st)
        if view is not None:
            if view.queue_depths:
                score = max(score, float(view.queue_depths[-1]))
            cadence = view.step_cadence_ms()
            if cadence:
                score *= 1.0 + cadence / 1e3
        return score

    def _view_for(self, st: _ReplicaState) -> Optional[ReplicaView]:
        if self._fleet is None:
            return None
        for view in self._fleet.views:
            if self._replica_for_view(view) == st.handle.name:
                return view
        return None

    def _role_pool(self, want: str, exclude=None):
        """Healthy placement pool for a role with the degrade chain
        ``want -> mixed -> any healthy``.  Returns ``(targets,
        degraded)`` — degraded is True when the fleet HAS ``want``-role
        replicas but none is currently placeable (empty/unhealthy role
        pool), i.e. the router is knowingly degrading to mixed rather
        than stalling the request."""
        healthy = [st for st in self._replicas.values()
                   if st.state == HEALTHY and st is not exclude]
        pool = [st for st in healthy if st.role == want]
        if pool:
            return pool, False
        configured = any(st.role == want for st in self._replicas.values())
        mixed = [st for st in healthy if st.role == "mixed"]
        return (mixed or healthy), configured

    def _place(self, now):
        # fresh requests go to the PREFILL pool (the mixed pool when no
        # prefill role exists — byte-identical to the pre-role router)
        targets, degraded = self._role_pool("prefill")
        while self.queue:
            req = self.queue[0]
            rec = self.results[int(req.uid)]
            if rec["deadline"] is not None and now > rec["deadline"]:
                self.queue.popleft()
                self._finalize(rec, DEADLINE, None,
                               "router deadline while queued")
                continue
            if not targets:
                return               # nothing placeable: keep queued
            st = min(targets, key=self._placement_score)
            try:
                st.handle.submit(req)
            except QueueFullError:
                return               # replica back-pressure: retry later
            except (ValueError, ServingError) as e:
                self.queue.popleft()
                self._finalize(rec, SHED, None, f"rejected: {e}")
                continue
            self.queue.popleft()
            rec["replica"] = st.handle.name
            st.assigned.add(int(req.uid))
            self._routed_total += 1
            if degraded:
                self._degraded_placements += 1

    # ----------------------------------------------------------- results
    def _collect(self, now):
        # poll EVERY replica, dead ones included: a hung replica that
        # answers after its work was requeued exercises the dedup path,
        # not a crash
        for st in self._replicas.values():
            for res in st.handle.poll():
                self._record_result(st, res)

    def _record_result(self, st: _ReplicaState, res: dict):
        if res.get("kind") == "restore":
            # a worker's restore-first outcome report, not a finish
            self._note_restore_outcome(res)
            return
        if res.get("kind") == "transfer" or \
                res.get("outcome") == xfer.TRANSFERRED:
            # a prefill worker's handoff, not an answer: seat the
            # committed block image onto the decode pool
            self._seat_transfer(st, res)
            return
        uid = int(res["uid"])
        rec = self.results.get(uid)
        if rec is None:
            self._unknown_results += 1   # e.g. a worker's warmup request
            return
        st.assigned.discard(uid)
        if rec["outcome"] is not None:
            # set-once: the first terminal outcome won; this late answer
            # (hung replica, double recovery) must never double-serve
            self._duplicates_suppressed += 1
            if self.monitor.armed:
                self.monitor.counter("router_duplicates_suppressed_total",
                                     self._duplicates_suppressed)
            return
        # the uid may have been requeued and be sitting in the router
        # queue or on a sibling — the answer arrived anyway, take it
        for other in self._replicas.values():
            other.assigned.discard(uid)
        self._drop_queued(uid)
        self._finalize(rec, res["outcome"], res["tokens"],
                       f"served by {st.handle.name}")

    def _seat_transfer(self, st: _ReplicaState, res: dict):
        """Seat one prefill->decode handoff: the stream's committed
        transfer entry restores onto the decode replica with the most
        free blocks (degrade chain: decode -> mixed -> any healthy);
        anything unseatable — entry GC'd/torn, every target refuses —
        requeues for plain recompute.  Set-once dedup holds: a late
        transfer for a uid that already resolved (or was re-placed
        after its publisher was presumed dead) is suppressed, never
        double-served."""
        uid = int(res["uid"])
        rec = self.results.get(uid)
        if rec is None:
            self._unknown_results += 1   # e.g. a worker's warmup stream
            return
        st.assigned.discard(uid)
        if rec["outcome"] is not None or \
                rec["replica"] not in (None, st.handle.name):
            # resolved, or already recovered onto another replica: the
            # image is a stale copy of work someone else now owns
            self._duplicates_suppressed += 1
            if self.monitor.armed:
                self.monitor.counter("router_duplicates_suppressed_total",
                                     self._duplicates_suppressed)
            return
        self._drop_queued(uid)           # it may have been requeued
        entry = res.get("entry")
        if (not entry or not os.path.isdir(entry)) and \
                st.handle.journal_dir:
            # the outbox record was lost (crash between publish and
            # journal flush) but the committed entry survives on disk
            entry = xfer.find_transfer_entry(st.handle.journal_dir, uid)
        seat = res.get("seat") or self._read_transfer_seat(entry)
        if rec["deadline"] is not None and self._clock() > rec["deadline"]:
            xfer.drop_entry(entry)
            self._finalize(rec, DEADLINE, None,
                           "router deadline while seating transfer")
            return
        targets, degraded = self._role_pool("decode", exclude=st)
        if entry and os.path.isdir(entry) and targets:
            ready = [t for t in targets if self._has_free_slot(t)]
            if not ready:
                # every decode target is momentarily slot-full: seating
                # now would make submit_restored burn the image on a
                # recompute fallback — defer to the next pump instead
                self._pending_seats.append((st.handle.name, res))
                return
            target = max(ready, key=self._seat_score)
            try:
                out = target.handle.submit(rec["request"],
                                           snapshot_dir=entry, seat=seat)
            except (QueueFullError, ValueError, ServingError) as e:
                logger.warning(
                    f"router: transfer seating of uid {uid} on "
                    f"{target.handle.name!r} refused ({e}) — requeueing "
                    "for recompute")
            else:
                rec["replica"] = target.handle.name
                target.assigned.add(uid)
                self._routed_total += 1
                self._transfers_seated += 1
                if degraded:
                    self._degraded_placements += 1
                if self.monitor.armed:
                    self.monitor.trace(
                        "kv_transfer_seat", step=self._pumps, uid=uid,
                        source=st.handle.name, target=target.handle.name,
                        bytes=int(res.get("bytes") or 0))
                if out is not None:
                    # in-process: the image was consumed synchronously
                    # — drop the entry so the publisher's queue depth
                    # (its backpressure signal) reflects reality
                    self._note_restore_outcome(out)
                    xfer.drop_entry(entry)
                else:
                    # subprocess target reads the image later: drop the
                    # entry when its restore/finish record arrives
                    self._seated_entries[uid] = entry
                return
        self._transfer_seat_fallbacks += 1
        xfer.drop_entry(entry)           # unseatable: dead weight
        rec["replica"] = None
        self.queue.append(rec["request"])
        self._requeued_total += 1

    def _has_free_slot(self, st: _ReplicaState) -> bool:
        free = st.handle.load().get("slots_free")
        return True if free is None else int(free) > 0

    def _seat_score(self, st: _ReplicaState) -> float:
        """Higher = better decode seat: free paged-KV blocks first
        (a restored stream needs pool room NOW), least-loaded as the
        tie-break."""
        free = float(st.handle.load().get("free_blocks", 0))
        return free - 1e-3 * self._placement_score(st)

    def _read_transfer_seat(self, entry) -> Optional[dict]:
        if not entry:
            return None
        from ..checkpoint import atomic
        try:
            man = atomic.read_manifest(entry)
            return dict((man.get("meta") or {}).get("seat") or {}) or None
        except Exception:
            return None

    def _drop_queued(self, uid: int):
        for i, req in enumerate(self.queue):
            if int(req.uid) == uid:
                del self.queue[i]
                return

    def _finalize(self, rec: dict, outcome: str, tokens, why: str):
        rec["outcome"] = outcome
        rec["tokens"] = tokens
        rec["t_done"] = self._clock()
        rec.pop("request", None)     # the spec is no longer needed
        xfer.drop_entry(self._seated_entries.pop(int(rec["uid"]), None))
        self._outcomes[outcome] = self._outcomes.get(outcome, 0) + 1

    # --------------------------------------------------------- telemetry
    def _emit(self, now):
        if not self.monitor.armed or \
                self._pumps % max(1, self.config.monitor_interval):
            return
        states = {HEALTHY: 0, SUSPECT: 0, DRAINING: 0, DEAD: 0}
        for st in self._replicas.values():
            states[st.state] += 1
        self.monitor.begin_step()
        self.monitor.end_step(
            self._pumps,
            scalars={"queued": len(self.queue),
                     "outstanding": self._outstanding(),
                     "replicas_healthy": states[HEALTHY],
                     "replicas_draining": states[DRAINING],
                     "replicas_dead": states[DEAD]},
            counters={"router_routed_total": self._routed_total,
                      "router_requeued_total": self._requeued_total,
                      "router_duplicates_suppressed_total":
                          self._duplicates_suppressed,
                      "router_completed_total": self._outcomes.get(OK, 0),
                      "router_shed_total": self._outcomes.get(SHED, 0),
                      "router_deadline_total":
                          self._outcomes.get(DEADLINE, 0),
                      "router_migrated_streams_total":
                          self._migrated_streams,
                      "router_migration_fallbacks_total":
                          self._migration_fallbacks,
                      "router_transfers_seated_total":
                          self._transfers_seated,
                      "router_degraded_placements_total":
                          self._degraded_placements})

    # ------------------------------------------------------------- drive
    def run(self, requests=None, timeout_s: Optional[float] = None):
        """Submit ``requests`` (optional) and pump until every accepted
        uid is terminal, the fleet is entirely dead, or ``timeout_s``
        elapses.  Returns the result table."""
        for req in (requests or []):
            self.submit(req)
        t0 = time.monotonic()
        while self._outstanding():
            self.pump()
            if all(st.state == DEAD for st in self._replicas.values()):
                logger.warning("router: every replica is dead; "
                               f"{self._outstanding()} request(s) stranded")
                break
            if timeout_s is not None and time.monotonic() - t0 > timeout_s:
                logger.warning(f"router: run timed out after {timeout_s}s "
                               f"with {self._outstanding()} outstanding")
                break
        return self.results

    def drain(self, timeout_s: Optional[float] = None) -> dict:
        """Stop admission, pump until outstanding work resolves (or the
        timeout), and report ``{"resolved", "lost"}`` — ``lost`` is the
        zero-loss acceptance number: uids that never reached a terminal
        outcome."""
        out = self.run(timeout_s=timeout_s)
        lost = sum(1 for r in out.values() if r["outcome"] is None)
        return {"resolved": len(out) - lost, "lost": lost}

    def pop_result(self, uid: int) -> dict:
        """Take ownership of a terminal result (KeyError when unknown,
        RuntimeError while still in flight) — the set-once table plus
        this pop is the exactly-once serve contract."""
        rec = self.results[uid]
        if rec["outcome"] is None:
            raise RuntimeError(f"request {uid} is still in flight")
        return self.results.pop(uid)

    def close(self):
        for st in self._replicas.values():
            try:
                if st.state != DEAD:
                    st.handle.stop()
            except Exception:
                pass
            try:
                st.handle.close()
            except Exception:
                pass
        if self.monitor.armed:
            self.monitor.flush()

    # ------------------------------------------------------------- stats
    def states(self) -> Dict[str, dict]:
        return {name: {"state": st.state, "role": st.role,
                       "since": st.since, "reason": st.reason,
                       "assigned": len(st.assigned)}
                for name, st in self._replicas.items()}

    def stats(self) -> dict:
        lost = sum(1 for r in self.results.values()
                   if r["outcome"] is None) - self._outstanding()
        return {
            "submitted": self._submitted_total,
            "routed_total": self._routed_total,
            "outcomes": dict(self._outcomes),
            "requeued_total": self._requeued_total,
            "duplicates_suppressed": self._duplicates_suppressed,
            "unknown_results": self._unknown_results,
            "adopted_finishes": self._adopted_finishes,
            "torn_lines_recovered": self._torn_recovered,
            "foreign_lines_recovered": self._foreign_recovered,
            "handoff_requeue_ms": [round(v, 3) for v in self._handoff_ms],
            "migrated_streams": self._migrated_streams,
            "migrated_uids": list(self._migrated_uids),
            "migration_fallbacks": self._migration_fallbacks,
            "transfers_seated": self._transfers_seated,
            "transfer_seat_fallbacks": self._transfer_seat_fallbacks,
            "degraded_placements": self._degraded_placements,
            "recompute_tokens_saved": self._recompute_tokens_saved,
            "restore_ms": [round(v, 3) for v in self._restore_ms],
            "drain_events": list(self._drain_events),
            "dead_events": list(self._dead_events),
            "replicas": self.states(),
            "queued": len(self.queue),
            "lost": max(0, lost),
        }


# ----------------------------------------------------------- worker loop
def replica_worker(spec: dict):
    """One subprocess serving replica speaking the
    :class:`ProcessReplica` directory protocol (run via
    ``python -m deepspeed_tpu.inference.router --worker spec.json`` or
    ``bin/ds_router --worker``).

    Per iteration: touch the heartbeat, visit the replica fault sites
    (``serving.replica_hang_step`` / ``serving.replica_crash_step`` —
    an armed ``DSTPU_FAULT=crash_at=serving.replica_crash_step@N`` kills
    the worker at iteration N, mid-traffic, with no clean shutdown),
    consume the inbox (engine submit — durable in the journal — THEN
    unlink), run one scheduler step.  A ``stop`` file plus an idle
    engine exits through drain/close, which journals the clean-shutdown
    record."""
    import jax
    import jax.numpy as jnp
    from ..models.gpt2 import GPT2, GPT2Config
    from ..monitor import Monitor
    from .serving import ServingConfig, ServingEngine

    root = spec["root"]
    name = spec.get("name") or os.path.basename(os.path.normpath(root))
    inbox = os.path.join(root, INBOX_DIR)
    os.makedirs(inbox, exist_ok=True)
    hb_path = os.path.join(root, HEARTBEAT_FILE)
    stop_path = os.path.join(root, STOP_FILE)

    def touch_hb():
        tmp = hb_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"t": time.time(), "pid": os.getpid()}, f)  # dstpu: disable=DSTPU104
        os.replace(tmp, hb_path)

    mcfg = spec.get("model") or {}
    cfg = GPT2Config(vocab_size=mcfg.get("vocab_size", 256),
                     max_seq=mcfg.get("max_seq", 96),
                     n_embd=mcfg.get("n_embd", 64),
                     n_layer=mcfg.get("n_layer", 4),
                     n_head=mcfg.get("n_head", 4),
                     embd_pdrop=0.0, attn_pdrop=0.0, resid_pdrop=0.0,
                     attention_impl="jnp")
    model = GPT2(cfg, dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0))
    mon = Monitor(run_dir=os.path.join(root, "monitor"), sinks=("jsonl",),
                  role="serving", run_id=name, slo=spec.get("slo"))
    srv = ServingEngine(
        model=model, params=params, monitor=mon,
        compile_cache=spec.get("cache_dir"),
        config=ServingConfig(
            batch_slots=spec.get("batch_slots", 2),
            block_size=spec.get("block_size", 8),
            max_new_tokens=spec.get("max_new_tokens", 16),
            journal_dir=os.path.join(root, "journal"),
            kv_bits=spec.get("kv_bits", 16),
            kv_snapshot=spec.get("kv_snapshot"),
            role=spec.get("role", "mixed"),
            transfer=spec.get("transfer"),
            preflight=False))
    throttle_s = spec.get("throttle_ms", 0) / 1e3
    try:
        if spec.get("warm", True):
            # compile outside the traffic window: the router must observe scheduling cadence,
            # not a one-off XLA compile pretending to be a straggler.
            # The warmup uid is far outside router space; the router
            # counts its journal record as `unknown_results`.
            # warm_prompt_len must bucket like the REAL traffic: a cold
            # prefill executable compiles MID-LOOP otherwise, stalling
            # the heartbeat long enough to be declared dead
            wlen = int(spec.get("warm_prompt_len", 4))
            srv.run([Request(tokens=np.arange(wlen) % cfg.vocab_size,
                             max_new_tokens=2, seed=10 ** 6,
                             uid=10 ** 9)])
            if srv._txq is not None and srv.role == "prefill":
                # a prefill worker PUBLISHES its warmup stream — drop
                # the entry so no decode sibling serves a phantom uid
                claim = srv._txq.claim(uid=10 ** 9)
                if claim is not None:
                    srv._txq.done(claim["entry"])
            srv.reset_stats()
        touch_hb()
        open(os.path.join(root, READY_FILE), "w").close()
        while True:
            touch_hb()
            fault.site("serving.replica_hang_step")
            fault.site("serving.replica_crash_step")
            for fn in sorted(os.listdir(inbox)):
                if not fn.endswith(".json"):
                    continue
                path = os.path.join(inbox, fn)
                with open(path) as f:
                    rspec = json.load(f)
                req = Request(
                    tokens=np.asarray(rspec["tokens"], np.int32),
                    max_new_tokens=rspec.get("max_new_tokens"),
                    temperature=rspec.get("temperature", 1.0),
                    do_sample=rspec.get("do_sample", False),
                    seed=rspec.get("seed", 0), uid=rspec["uid"])
                snap = rspec.get("snapshot_dir")
                if snap:
                    # restore-first migration: seat the dead sibling's
                    # KV image (or fall back to recompute inside);
                    # journals the submit durably either way
                    srv.submit_restored(req, snap,
                                        seat=rspec.get("seat"))
                else:
                    srv.submit(req)  # journaled durably ...
                os.unlink(path)      # ... BEFORE the inbox entry dies
            progressed = srv.step()
            if throttle_s:
                time.sleep(throttle_s)
            if not progressed:
                if os.path.exists(stop_path):
                    break
                time.sleep(0.005)
        srv.drain()                  # journals the clean-shutdown record
    finally:
        srv.close()
        mon.close()


# ----------------------------------------------------------- observe CLI
def observe_states(view: FleetView, config: RouterConfig,
                   now: Optional[float] = None) -> List[dict]:
    """Health table over monitor streams alone (no handles): what the
    live router's state machine would conclude from the same evidence.
    ``now`` defaults to the newest event stamp across the fleet, so a
    COMMITTED fixture renders the same table forever (the tier-1
    smoke's determinism)."""
    if now is None:
        stamps = [r.last_t for r in view.replicas if r.last_t is not None]
        now = max(stamps) if stamps else time.time()
    verdict = view.straggler(zmax=config.straggler_zmax,
                             min_excess=config.straggler_min_excess)
    out = []
    for r in view.replicas:
        age = None if r.last_t is None else now - r.last_t
        if age is None or age > config.dead_after_s:
            state, why = DEAD, (f"last event {age:.1f}s ago" if age
                                else "no events")
        elif age > config.suspect_after_s:
            state, why = SUSPECT, f"last event {age:.1f}s ago"
        elif r.label == verdict.get("straggler"):
            state, why = DRAINING, \
                f"straggler verdict ({verdict.get('series')})"
        else:
            state, why = HEALTHY, ""
        out.append({"replica": r.label, "state": state, "why": why,
                    "event_age_s": None if age is None else round(age, 3),
                    "last_step": r.last_step,
                    "step_cadence_ms": r.step_cadence_ms(),
                    "queue_depth": r.signal("queue_depth")})
    return out


def render_router(view: FleetView, config: RouterConfig,
                  now: Optional[float] = None) -> str:
    """One observe-mode frame as a string (pure: unit-testable)."""
    rows = observe_states(view, config, now=now)
    lines = [f"ds_router — {len(rows)} replica(s) "
             f"(suspect>{config.suspect_after_s}s, "
             f"dead>{config.dead_after_s}s)",
             "-" * 78,
             f"{'replica':>16} {'state':>9} {'step':>7} {'cadence':>9} "
             f"{'queued':>7} {'age_s':>7}  why"]
    def fmt(v, nd=1):
        return "-" if v is None else (f"{v:.{nd}f}"
                                      if isinstance(v, float) else str(v))

    for r in rows:
        lines.append(
            f"{r['replica'][-16:]:>16} {r['state']:>9} "
            f"{fmt(r['last_step']):>7} {fmt(r['step_cadence_ms']):>9} "
            f"{fmt(r['queue_depth']):>7} {fmt(r['event_age_s']):>7}  "
            f"{r['why']}")
    lines.append("-" * 78)
    placeable = sum(1 for r in rows if r["state"] == HEALTHY)
    lines.append(f"placeable: {placeable}/{len(rows)} replica(s)")
    verdict = view.straggler(zmax=config.straggler_zmax,
                             min_excess=config.straggler_min_excess)
    if verdict["straggler"] is not None:
        lines.append(
            f"DRAIN (not kill): {verdict['straggler']} — "
            f"{verdict.get('series_label')} {verdict.get('value')} vs "
            f"fleet {verdict.get('fleet_mean_others')} "
            f"(z={verdict.get('zscore')})")
    return "\n".join(lines)


def main(argv=None):
    if argv is None:
        argv = sys.argv[1:]
    if "--worker" in argv:
        spec_path = argv[argv.index("--worker") + 1]
        with open(spec_path) as f:
            replica_worker(json.load(f))
        return 0
    ap = argparse.ArgumentParser(
        prog="ds_router",
        description="replica router observe mode: merge replica monitor "
                    "streams and render the health/placement table "
                    "(docs/serving.md#replica-router)")
    ap.add_argument("runs", nargs="+",
                    help="per-replica monitor run dirs (or events.jsonl "
                         "paths)")
    ap.add_argument("--once", action="store_true",
                    help="render one frame and exit")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable table on stdout (implies "
                         "--once)")
    ap.add_argument("--interval", type=float, default=2.0)
    ap.add_argument("--suspect-after", type=float,
                    default=RouterConfig.suspect_after_s)
    ap.add_argument("--dead-after", type=float,
                    default=RouterConfig.dead_after_s)
    args = ap.parse_args(argv)
    config = RouterConfig(suspect_after_s=args.suspect_after,
                          dead_after_s=args.dead_after)
    from ..monitor.sinks import resolve_stream
    missing = [r for r in args.runs
               if not os.path.exists(resolve_stream(r))]
    if missing:
        if args.as_json:
            # contractual CLI stdout (the ds_fleet idiom), not runtime
            # metrics leakage
            print(json.dumps({"error": "no event stream",  # dstpu: disable=DSTPU104
                              "missing": missing}))
        else:
            print(f"ds_router: no event stream under {missing}")  # dstpu: disable=DSTPU104
        return 1
    follower = FleetFollower(args.runs)
    try:
        while True:
            view = follower.poll()
            # committed fixtures are static: age everything relative to
            # the newest stamp in --once/--json mode, wall-clock live
            now = None if (args.once or args.as_json) else time.time()
            if args.as_json:
                rows = observe_states(view, config, now=now)
                print(json.dumps(  # dstpu: disable=DSTPU104
                    {"replicas": rows,
                     "straggler": view.straggler(
                         zmax=config.straggler_zmax,
                         min_excess=config.straggler_min_excess),
                     "policy": config.describe()},
                    sort_keys=True, default=str))
                return 0
            frame = render_router(view, config, now=now)
            if args.once:
                print(frame)  # dstpu: disable=DSTPU104
                return 0
            sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
            sys.stdout.flush()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
