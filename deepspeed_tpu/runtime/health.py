"""Training health guardian: on-device divergence sentinels + host escalation.

Production training on preemptible TPU fleets needs a defense ladder against
*numerical* faults, not just infrastructure ones (docs/health-monitor.md):

    detect -> skip -> rewind to last good checkpoint -> replay past the
    poison window -> abort with forensics

The pieces here split cleanly across the device/host boundary:

- **Device sentinels** (:class:`HealthState`, :func:`tree_nonfinite`,
  :func:`update_ema`): cheap scalar metrics computed INSIDE the jitted train
  step — global non-finite flags over loss/grads/params, and an EMA
  loss-spike z-score carried in ``TrainState``.  Pure ``jnp`` ops, no host
  callbacks, so the DSTPU201/DSTPU204 audits (``deepspeed_tpu/analysis``)
  stay clean and state donation stays honored.  The engine combines the
  sentinels into one ``skip`` flag and gates the parameter/optimizer update
  branchlessly (``jnp.where``) — the generalization of the fp16 loss-scaler
  skip-step to the bf16/fp32 paths, where a single NaN gradient would
  otherwise be written irrecoverably into the params.

- **Host monitor** (:class:`HealthMonitor`): reads the per-step sentinel
  scalars (every ``check_interval`` steps — each read is one device sync)
  and implements the configurable escalation policy of the ``health_check``
  config block: a run of ``consecutive_skip_budget`` skipped steps triggers
  an in-process ``engine.rewind()`` (manifest-verified checkpoint reload +
  data-stream fast-forward past the poison window); ``rewind_limit``
  exhaustion triggers ``on_exhausted`` (``abort`` with a forensic JSON dump,
  or ``warn``).

Nothing in this module runs under ``jit`` except the pure functions the
engine traces into its step.
"""

import json
import os
import time
from typing import Any, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..monitor.ring import RingBuffer
from ..utils.logging import logger, log_dist


class TrainingHealthError(RuntimeError):
    """Raised when the escalation ladder is exhausted (``on_exhausted:
    "abort"``) — training cannot make progress without intervention.  The
    forensic dump path is carried in ``.forensic_path`` when one was
    written."""

    def __init__(self, message, forensic_path=None):
        super().__init__(message)
        self.forensic_path = forensic_path


# ---------------------------------------------------------------------------
# device-side sentinels (traced into the jitted step; pure jnp only)
# ---------------------------------------------------------------------------

class HealthState(NamedTuple):
    """Device-resident EMA statistics of the training loss (all scalars,
    carried in ``TrainState`` and donated with it each step)."""
    ema_loss: jnp.ndarray   # f32 — EMA of the (finite) loss
    ema_sq: jnp.ndarray     # f32 — EMA of the squared (finite) loss
    count: jnp.ndarray      # i32 — finite-loss observations absorbed


def init_state() -> HealthState:
    return HealthState(ema_loss=jnp.float32(0.0), ema_sq=jnp.float32(0.0),
                       count=jnp.int32(0))


def tree_nonfinite(tree) -> jnp.ndarray:
    """Global any-non-finite flag over the inexact leaves of a pytree.

    The generalization of ``fp16.loss_scaler.has_overflow`` to arbitrary
    state trees (params, grads): integer/bool leaves are skipped, an empty
    tree is finite.  One scalar per leaf, OR-reduced — XLA fuses the whole
    scan into the step for free under SPMD (sharded leaves reduce
    cross-device automatically).
    """
    with jax.named_scope("sentinel"):
        leaves = [l for l in jax.tree_util.tree_leaves(tree)
                  if jnp.issubdtype(jnp.result_type(l), jnp.inexact)]
        if not leaves:
            return jnp.asarray(False)
        out = jnp.asarray(False)
        for l in leaves:
            out = jnp.logical_or(out, jnp.logical_not(jnp.all(jnp.isfinite(l))))
        return out


def rows_nonfinite(x, axis=-1) -> jnp.ndarray:
    """Per-row any-non-finite flag: the batched sibling of
    :func:`tree_nonfinite`, reduced over ``axis`` only.

    The serving quarantine uses it on the decode logits — one bool per
    batch slot, computed INSIDE the compiled step (pure ``jnp``, no host
    callback), so a poisoned request is detected in-graph and its
    sampling branchlessly forced to a sentinel while neighbors' rows are
    untouched (docs/serving.md#resilience)."""
    with jax.named_scope("sentinel"):
        return jnp.logical_not(jnp.all(jnp.isfinite(x), axis=axis))


def update_ema(state: HealthState, loss, *, window: int,
               zmax: float = 0.0, warmup: Optional[int] = None):
    """One EMA tick + loss-spike z-score, branchless.

    Returns ``(new_state, z, spike)``:

    - ``z``: the current loss's z-score against the PRIOR EMA mean/variance
      (0 while fewer than ``warmup`` finite losses have been absorbed, and
      0 for a non-finite loss — the non-finite sentinel owns that case);
    - ``spike``: ``z > zmax`` (always False when ``zmax <= 0``);
    - EMA absorbs only finite, non-spike losses, so a sustained poison
      window cannot drag the baseline toward itself and mask later spikes.
    """
    if warmup is None:
        warmup = max(4, int(window) // 4)
    alpha = jnp.float32(2.0 / (float(window) + 1.0))
    loss = jnp.asarray(loss, jnp.float32)
    finite = jnp.isfinite(loss)

    var = jnp.maximum(state.ema_sq - state.ema_loss * state.ema_loss, 0.0)
    # relative epsilon: a perfectly flat loss history must not turn the
    # first 1e-7 wiggle into an "infinite" z
    std = jnp.sqrt(var) + 1e-6 * (1.0 + jnp.abs(state.ema_loss))
    warmed = state.count >= jnp.int32(warmup)
    z = jnp.where(finite & warmed, (loss - state.ema_loss) / std, 0.0)
    if zmax > 0.0:
        spike = z > jnp.float32(zmax)
    else:
        spike = jnp.asarray(False)

    absorb = finite & jnp.logical_not(spike)
    l_eff = jnp.where(absorb, loss, state.ema_loss)
    first = state.count == 0
    new_ema = jnp.where(first, l_eff,
                        state.ema_loss + alpha * (l_eff - state.ema_loss))
    new_sq = jnp.where(first, l_eff * l_eff,
                       state.ema_sq + alpha * (l_eff * l_eff - state.ema_sq))
    new_state = HealthState(
        ema_loss=jnp.where(absorb, new_ema, state.ema_loss),
        ema_sq=jnp.where(absorb, new_sq, state.ema_sq),
        count=state.count + absorb.astype(jnp.int32))
    return new_state, z, spike


# ---------------------------------------------------------------------------
# host-side EMA twin (plain floats, same formula as update_ema)
# ---------------------------------------------------------------------------

class HostEma:
    """Host-side twin of the device EMA sentinel, for paths whose step
    metrics are host values already: the streamed-offload runner's in-line
    spike skip, and the monitor's fallback z-score when a step carries no
    device ``health_z``."""

    def __init__(self, window, zmax):
        self.window = int(window)
        self.zmax = float(zmax)
        self.reset()

    def reset(self):
        self._ema = 0.0
        self._sq = 0.0
        self._count = 0

    def update(self, loss):
        """One tick; returns ``(z, spike)`` with the same warmup /
        spike-exclusion semantics as :func:`update_ema`."""
        loss = float(loss)
        warmup = max(4, self.window // 4)
        alpha = 2.0 / (self.window + 1.0)
        finite = np.isfinite(loss)
        var = max(self._sq - self._ema * self._ema, 0.0)
        std = var ** 0.5 + 1e-6 * (1.0 + abs(self._ema))
        z = ((loss - self._ema) / std
             if finite and self._count >= warmup else 0.0)
        spike = self.zmax > 0 and z > self.zmax
        if finite and not spike:
            if self._count == 0:
                self._ema, self._sq = loss, loss * loss
            else:
                self._ema += alpha * (loss - self._ema)
                self._sq += alpha * (loss * loss - self._sq)
            self._count += 1
        return z, spike


# ---------------------------------------------------------------------------
# forensic-dump plumbing shared with the serving circuit breaker
# ---------------------------------------------------------------------------

def json_safe(obj):
    """Non-finite floats -> strings: the whole point of a forensic dump is
    the NaN/Inf values, and bare ``NaN``/``Infinity`` tokens (Python's
    default) are not RFC-8259 JSON — jq / JSON.parse / monitoring
    pipelines would reject the artifact.  Shared by the training
    guardian's dump and the serving circuit breaker's."""
    if isinstance(obj, float) and not np.isfinite(obj):
        return repr(obj)              # 'nan' | 'inf' | '-inf'
    if isinstance(obj, dict):
        return {k: json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [json_safe(v) for v in obj]
    return obj


def write_forensics(dirpath, filename, payload):
    """Atomically write a forensic JSON artifact (write-temp + replace);
    best-effort — returns the path, or None on failure (a dump failure
    must never mask the abort/trip it accompanies)."""
    path = os.path.join(dirpath, filename)
    try:
        os.makedirs(dirpath, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            # the forensic ARTIFACT itself; announced on the monitor bus
            # by the caller as an `artifact` event
            json.dump(json_safe(payload),  # dstpu: disable=DSTPU104
                      f, indent=2, allow_nan=False)
        os.replace(tmp, path)
    except (OSError, TypeError, ValueError) as e:
        # TypeError/ValueError: a payload value json couldn't serialize
        # (e.g. a numpy scalar a caller smuggled in as a uid) — a dump
        # failure must never mask the abort/trip it accompanies
        logger.warning(f"could not write forensic dump to {path}: {e}")
        return None
    return path


# ---------------------------------------------------------------------------
# host-side monitor: the escalation ladder
# ---------------------------------------------------------------------------

_METRIC_KEYS = ("loss", "grad_norm", "skip", "health_z", "loss_spike",
                "nonfinite_grads", "nonfinite_loss", "nonfinite_params")


class HealthMonitor:
    """Host-side escalation policy over the device sentinels.

    ``observe()`` stashes each step's sentinel scalars as device references
    and TRAILS the device by ``check_interval`` steps: an entry is synced
    (one ``float()``/``bool()`` host read each) only once ``check_interval``
    newer steps have been dispatched.  At the default interval of 1 this
    reads step *t-1* right after step *t* was dispatched — the read blocks
    only on work the device has already moved past, so the engine's
    async-dispatch overlap survives the guardian.  Escalation latency is
    bounded by the same ``check_interval``.  Decisions come back as an
    action string the engine executes: ``"ok"`` | ``"rewind"`` |
    ``"abort"``.

    The streamed-offload path computes no device EMA (its metrics are
    host-side already); the monitor then maintains a :class:`HostEma`
    twin so the z-score telemetry and spike accounting exist on every
    path.

    The forensic step history is a ``monitor.ring.RingBuffer`` (the same
    bounded-ring class behind the telemetry bus's in-memory sink), and
    when the engine runs with an armed monitor the guardian's events —
    rewinds, forensic dumps — are ALSO announced on the bus (``bus=``),
    so the escalation record shows up in the one telemetry stream
    instead of only in scattered log lines.
    """

    def __init__(self, cfg, bus=None):
        self.cfg = cfg
        self.bus = bus
        self.history = RingBuffer(int(cfg.history))
        self.consecutive_skips = 0
        self.total_skips = 0
        self.total_spikes = 0
        self.rewinds = 0           # process-lifetime total (telemetry)
        self.episode_rewinds = 0   # rewinds in the CURRENT poison episode;
        # an episode ends when a clean step is applied after a rewind, and
        # rewind_limit bounds rewinds per episode (see _decide)
        self.clean_since_rewind = 0
        self.last_bad_stream_step = None
        self.last_step = None
        self._pending = []
        # fallback z for metrics that carry no "health_z" — every engine
        # path provides one (device sentinels, or the streamed runner's
        # own HostEma), so this fires only for externally-driven monitors
        self._hema = HostEma(cfg.spike_window, cfg.spike_zmax)

    # ---------------------------------------------------------------- intake
    def observe(self, step_no, stream_step, metrics) -> str:
        """Record one finished step.  Device scalars are kept as references;
        entries older than the ``check_interval`` lag window are synced and
        processed now."""
        m = {k: metrics[k] for k in _METRIC_KEYS if k in metrics}
        self._pending.append((step_no, stream_step, m))
        lag = max(1, int(self.cfg.check_interval))
        if len(self._pending) <= lag:
            return "ok"
        ready, self._pending = self._pending[:-lag], self._pending[-lag:]
        return self._process(ready)

    def flush(self) -> str:
        """Sync + process EVERYTHING pending, lag included; returns the
        escalation action.  Called by the engine at checkpoint saves and
        before a forensic dump (observe() drains steadily in between);
        with the lag at N, up to N final steps can still be unprocessed
        if the process exits without either boundary."""
        ready, self._pending = self._pending, []
        return self._process(ready)

    def _process(self, entries) -> str:
        action = "ok"
        for step_no, stream_step, m in entries:
            rec = self._ingest(step_no, stream_step, m)
            act = self._decide(rec)
            if act != "ok":
                action = act
        return action

    def _ingest(self, step_no, stream_step, m):
        loss = float(m.get("loss", np.nan))
        gnorm = float(m["grad_norm"]) if "grad_norm" in m else None
        if "skip" in m:
            skip = bool(m["skip"])
        else:
            skip = not np.isfinite(loss)
        if "health_z" in m:
            z = float(m["health_z"])
            spike = bool(m.get("loss_spike", False))
        else:
            z, spike = self._hema.update(loss)
        rec = {"step": step_no, "stream_step": stream_step, "loss": loss,
               "grad_norm": gnorm, "z": round(z, 4), "skip": skip,
               "spike": spike}
        for k in ("nonfinite_grads", "nonfinite_loss", "nonfinite_params"):
            if k in m:
                rec[k] = bool(m[k])
        self.history.append(rec)
        self.last_step = step_no
        if skip:
            self.consecutive_skips += 1
            self.total_skips += 1
            if stream_step is not None:
                self.last_bad_stream_step = stream_step
        else:
            self.consecutive_skips = 0
            self.clean_since_rewind += 1
            # a clean APPLIED step after a rewind closes the poison
            # episode: the rewind budget re-arms for the next one
            self.episode_rewinds = 0
        if spike:
            self.total_spikes += 1
            if not skip:
                why = ("health_check.skip_on_spike is off"
                       if not self.cfg.skip_on_spike
                       else "this step's path applied it before the spike "
                            "was classified")
                logger.warning(
                    "health: loss spike at step %s (loss=%.6g z=%.2f > "
                    "zmax=%.2f); step applied (%s)",
                    step_no, loss, z, self.cfg.spike_zmax, why)
        return rec

    # -------------------------------------------------------------- decision
    def _decide(self, rec) -> str:
        budget = int(self.cfg.consecutive_skip_budget)
        if budget <= 0 or self.consecutive_skips < budget:
            return "ok"
        if self.episode_rewinds < int(self.cfg.rewind_limit):
            return "rewind"
        if self.cfg.on_exhausted == "warn":
            logger.warning(
                "health: skip budget exhausted (%d consecutive) and the "
                "episode's rewind limit (%d) spent; on_exhausted=warn — "
                "counters reset, training continues UNPROTECTED against "
                "this fault",
                self.consecutive_skips, self.cfg.rewind_limit)
            self.consecutive_skips = 0
            return "ok"
        return "abort"

    # ------------------------------------------------------------ transitions
    def record_rewind(self, tag=None):
        """Called by the engine after a successful in-process rewind."""
        self.rewinds += 1
        self.episode_rewinds += 1
        self.consecutive_skips = 0
        self.clean_since_rewind = 0
        self._hema.reset()
        log_dist("health rewind engaged: " + json.dumps({
            "event": "health_rewind", "rewind": self.rewinds,
            "episode_rewind": self.episode_rewinds,
            "limit": int(self.cfg.rewind_limit), "restored_tag": tag,
            "replayed_past_stream_step": self.last_bad_stream_step}),
            ranks=[0])
        if self.bus is not None:
            self.bus.counter(
                "health_rewind", self.rewinds, step=self.last_step,
                episode_rewind=self.episode_rewinds,
                restored_tag=tag,
                replayed_past_stream_step=self.last_bad_stream_step)

    def on_checkpoint_load(self):
        """A checkpoint load supersedes the observed run: the consecutive
        counter and host EMA describe discarded steps.  Rewind/skip totals
        persist — they are the process-lifetime escalation record."""
        self.consecutive_skips = 0
        self._pending = []
        self._hema.reset()

    # ------------------------------------------------------------- forensics
    def counters(self):
        return {"consecutive_skips": self.consecutive_skips,
                "total_skips": self.total_skips,
                "total_spikes": self.total_spikes,
                "rewinds": self.rewinds,
                "episode_rewinds": self.episode_rewinds,
                "last_bad_stream_step": self.last_bad_stream_step}

    # alias kept for existing callers; implementation is the module-level
    # json_safe (shared with the serving circuit breaker's dump)
    _json_safe = staticmethod(json_safe)

    def forensic_dump(self, dirpath, reason, last_good_tag=None):
        """Write the forensic JSON (ring-buffer history + counters + policy)
        atomically; returns the path.  Best-effort: a dump failure must not
        mask the abort it accompanies."""
        payload = {
            "event": "health_forensics",
            "reason": reason,
            "time_unix": time.time(),
            "step": self.last_step,
            "last_good_tag": last_good_tag,
            "counters": self.counters(),
            "policy": {
                "skip_nonfinite": bool(self.cfg.skip_nonfinite),
                "spike_window": int(self.cfg.spike_window),
                "spike_zmax": float(self.cfg.spike_zmax),
                "skip_on_spike": bool(self.cfg.skip_on_spike),
                "consecutive_skip_budget":
                    int(self.cfg.consecutive_skip_budget),
                "rewind_limit": int(self.cfg.rewind_limit),
                "on_exhausted": self.cfg.on_exhausted,
                "check_interval": int(self.cfg.check_interval),
            },
            "history": list(self.history),
        }
        step = self.last_step if self.last_step is not None else 0
        path = write_forensics(dirpath, f"health_forensics_step{step}.json",
                               payload)
        if path is None:
            return None
        logger.warning("health forensics written: " + json.dumps({
            "event": "health_forensics_written", "path": path,
            "reason": reason}))
        if self.bus is not None:
            self.bus.artifact("health_forensics", path,
                              step=self.last_step, reason=reason)
            self.bus.flush()
        return path
