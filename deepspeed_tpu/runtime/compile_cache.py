"""Persistent compiled-step cache with AOT warm-start.

The reference DeepSpeed amortizes kernel build cost once per install
(``op_builder/`` JIT compiles + prebuilt wheels); this XLA port instead
paid full tracing+compilation on EVERY process start — ~50s of
engine-ready time per benchmark run, per CI test worker, per auto-resume and
per rewind-and-replay.  This module makes that a cached cost:

- every jitted entry point (the fused ``_train_step``, the offload
  ``_grad_only_step``, eval steps, the pipe-engine schedule step, the
  ``param_stream`` per-layer programs, the inference prefill/decode
  steps) is dispatched through a :class:`CachedStep` wrapper;
- on first use the wrapper lowers the function (cheap tracing), builds a
  content-addressed key, and either DESERIALIZES a previously compiled
  executable (``jax.experimental.serialize_executable`` — donation
  aliasing is baked into the serialized artifact, so DSTPU204 holds for
  warm starts too) or compiles and writes the entry;
- entries are committed with the PR-1 atomic stage/manifest/rename
  protocol (``checkpoint/atomic.py``): SHA-256-manifested payloads, one
  publishing ``os.rename`` — a corrupt, truncated or unpicklable entry
  is a MISS that falls back to a fresh compile, never a crash.

Cache key anatomy (see docs/compile-cache.md) — everything that legally
invalidates an executable:

- jax/jaxlib versions, backend, device kind and the executable's own
  device assignment (the ids it runs on, not the host's device count);
- the entry point's name and the engine's config slice (dtype, zero
  stage, gas, grad-accum dtype, clipping, scaler + health flags, mesh
  axes, offload devices — passed in by the caller as ``key_extra``);
- per-argument abstract avals (shape/dtype/weak_type) and shardings;
- the donation spec;
- the DSTPU205 recompile-hazard fingerprint (the weak-typed-scalar
  argument surface of the PR-2 auditor; the baked-constant hazard class
  is covered by the lowering hash below — a closure-captured constant
  changes the StableHLO text);
- a SHA-256 of the lowered StableHLO itself — the belt-and-braces term
  that also captures remat policy, sharding constraints, and any model
  code change.

Placement (docs/compile-cache.md): :func:`cache_root` is the one
directory every compile cache of this program lives under —
``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else
``<checkout>/.compile_cache``.  JAX's own persistent cache
(:func:`use_persistent_cache`) takes the root itself; this store, when
merely switched on (``DSTPU_COMPILE_CACHE=1``), takes ``<root>/aot``.
"""

import hashlib
import json
import os
import pickle
import shutil
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from ..checkpoint import atomic
from ..monitor import scope_maps, spans
from ..utils.logging import logger, log_dist

PAYLOAD_FILE = "payload.bin"
KEY_FILE = "key_anatomy.json"
SCOPES_FILE = "device_scopes.json"
STATS_FILE = "last_run_stats.json"
FORMAT_VERSION = 1
ENV_DIR = "DSTPU_COMPILE_CACHE"
_ENV_OFF = ("0", "off", "false", "no", "disabled")
_ENV_ON = ("1", "on", "true", "yes", "enabled")
JAX_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_MAX_EVENTS = 64

# process-wide counters aggregated across every CompileCache instance —
# the pytest terminal summary and ds_report read these to show the
# cold-vs-warm trend of a whole run
GLOBAL_STATS = {"hits": 0, "misses": 0, "corrupt": 0, "puts": 0,
                "put_errors": 0, "lower_ms": 0.0, "compile_ms": 0.0,
                "deserialize_ms": 0.0}


def reset_global_stats():
    for k in GLOBAL_STATS:
        GLOBAL_STATS[k] = 0.0 if k.endswith("_ms") else 0


def cache_root():
    """The directory every compile cache of this program lives under:
    ``$JAX_COMPILATION_CACHE_DIR`` when the environment places it (an
    operator, CI, the chip driver), else ``<checkout>/.compile_cache``.
    A FIXED path either way — the path is part of JAX's cache key, so a
    directory that moves between runs (tempfile, pid, timestamp) never
    hits."""
    return os.environ.get(JAX_ENV_DIR) or os.path.join(_CHECKOUT,
                                                       ".compile_cache")


def aot_dir():
    """This store's place under :func:`cache_root`."""
    return os.path.join(cache_root(), "aot")


def use_persistent_cache():
    """Point JAX's own persistent compilation cache at :func:`cache_root`
    (the value JAX already read from the environment, when set) and
    return the directory.  Everything jitted afterwards — the engines'
    steps, init, casts — is found again by the next process that
    resolves the same root."""
    root = cache_root()
    jax.config.update("jax_compilation_cache_dir", root)
    return root


def resolve_env_dir():
    """The env-configured store dir, or None (incl. explicit-off
    values).  ``DSTPU_COMPILE_CACHE=1`` switches the store on under
    :func:`cache_root`; any other value is an explicit directory."""
    v = os.environ.get(ENV_DIR, "").strip()
    if not v or v.lower() in _ENV_OFF:
        return None
    if v.lower() in _ENV_ON:
        return aot_dir()
    return v


def env_disabled():
    """True when the env var explicitly turns the cache OFF (overrides a
    config-provided dir — the operator's kill switch)."""
    v = os.environ.get(ENV_DIR, "").strip()
    return bool(v) and v.lower() in _ENV_OFF


# --------------------------------------------------------------------- keys
def _leaf_sig(leaf):
    """(shape, dtype, weak_type) — the per-dispatch signature term.  No
    string formatting of shardings here: this runs on EVERY call."""
    aval = getattr(leaf, "aval", None)
    if aval is not None:
        return (tuple(getattr(aval, "shape", ())),
                str(getattr(aval, "dtype", "")),
                bool(getattr(aval, "weak_type", False)))
    if isinstance(leaf, (bool, int, float, complex)):
        # Python scalars are weak-typed by definition — the DSTPU205
        # hazard class; they key separately from explicit-dtype arrays
        return ("pyscalar", type(leaf).__name__, True)
    a = np.asarray(leaf)
    return (tuple(a.shape), str(a.dtype), False)


def _leaf_fingerprint(leaf):
    """_leaf_sig + the sharding repr — the once-per-signature key term."""
    sharding = getattr(leaf, "sharding", None)
    return _leaf_sig(leaf) + (str(sharding) if sharding is not None
                              else None,)


def args_signature(args, kwargs=None):
    """Hashable structural signature of a call: treedef + per-leaf
    (shape, dtype, weak_type).  One executable per signature."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    return (treedef, tuple(map(_leaf_sig, leaves)))


def _being_traced():
    """True while any jax trace is in progress (jax.make_jaxpr, an outer
    jit).  One thread-local read — no per-leaf scan on the hot path; a
    tracer can only reach us while a trace is live."""
    return not jax.core.trace_ctx.is_top_level()


def build_key_material(name, args, lowered, key_extra=None, kwargs=None):
    """The documented key anatomy (docs/compile-cache.md), or None when
    program identity cannot be established (then nothing is cached)."""
    import jaxlib
    leaves = jax.tree_util.tree_leaves((args, kwargs or {}))
    fps = [_leaf_fingerprint(l) for l in leaves]
    # DSTPU205 fingerprint, argument half: weak-typed scalar positions
    # (a Python int/float leaked into the step).  The closure-constant
    # half of DSTPU205 is covered by lowering_sha256 — baked consts are
    # dense attributes in the StableHLO text.
    weak_scalars = [i for i, (shape, _, weak, _) in enumerate(fps)
                    if weak and shape in ((), "pyscalar")]
    try:
        low_text = lowered.as_text()
    except Exception as e:  # lowering dialects vary across jax versions
        # WITHOUT the program hash, two lowerings that differ only in
        # content (a baked constant, a remat policy, model code) would
        # collide on avals+config and a warm start would dispatch a
        # stale executable — refuse to key at all: the caller compiles
        # fresh and skips the cache for this entry point
        logger.warning(f"compile cache: lowered.as_text failed ({e}); "
                       f"NOT caching {name} (program identity unavailable)")
        return None
    devices = _execution_devices(lowered)
    material = {
        "v": FORMAT_VERSION,
        "name": name,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        # the executable's own device assignment: a program compiled for
        # jax.devices()[:4] and one for all eight lower to the same text
        "devices": {"kind": devices[0].device_kind,
                    "ids": [d.id for d in devices]},
        "args": [list(map(str, fp)) for fp in fps],
        "dstpu205_weak_scalars": weak_scalars,
        "config": key_extra or {},
        "lowering_sha256": hashlib.sha256(low_text.encode()).hexdigest(),
        # the entry holds the executable's map of device scopes, and scopes
        # are ``op_name`` metadata the lowering's hash does not see: an
        # entry written under other rules (or by a commit that kept no
        # map) is not this program's
        "device_scopes": scope_maps.VERSION,
    }
    return material


def _execution_devices(lowered):
    """The devices ``lowered`` was lowered for, in assignment order —
    what its compiled executable must be loaded over.  (jax 0.9.0 keeps
    the assignment on the MeshComputation; ``jax.stages.Lowered`` has no
    public reader for it.)"""
    return list(lowered._lowering._device_list)


def key_from_material(material):
    blob = json.dumps(material, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()


# -------------------------------------------------------------------- cache
class CompileCache:
    """Content-addressed on-disk store of serialized compiled executables.

    Entry layout: ``<dir>/<key>/{payload.bin, key_anatomy.json,
    device_scopes.json, manifest.json}``, committed via the atomic stage/manifest/rename
    protocol and validated (SHA-256) on every read.  ``readonly=True``
    serves a shared CI cache: reads verify and deserialize, but nothing
    is written, touched, or evicted.
    """

    def __init__(self, dir, max_entries=0, readonly=False):
        self.dir = dir
        self.max_entries = int(max_entries or 0)
        self.readonly = bool(readonly)
        self.stats = {k: (0.0 if k.endswith("_ms") else 0)
                      for k in GLOBAL_STATS}
        self.events = []
        if not self.readonly:
            os.makedirs(self.dir, exist_ok=True)
            # age-guarded sweep: unlike a checkpoint dir, a compile cache
            # is SHARED BY DESIGN (CI workers, concurrent engines) — a
            # young `.tmp` may be another process's in-flight put, not a
            # killed writer's leftover
            atomic.clean_stale_staging(
                self.dir, min_age_s=atomic.LOAD_STAGING_MIN_AGE_S)

    # -------------------------------------------------------------- storage
    def _entry_dir(self, key):
        return os.path.join(self.dir, key)

    def get(self, key):
        """Verified payload bytes, or None.  A torn/corrupt entry is
        removed (unless readonly) and reported as a miss."""
        path = self._entry_dir(key)
        if not os.path.isdir(path):
            return None
        ok, problems = atomic.verify_checkpoint(path, level="full")
        if not ok:
            self._count("corrupt")
            logger.warning(
                "compile cache: entry %s failed validation (%s); "
                "falling back to a fresh compile" % (key[:16], problems))
            self.invalidate(key)
            return None
        try:
            with open(os.path.join(path, PAYLOAD_FILE), "rb") as f:
                payload = f.read()
        except OSError as e:
            self._count("corrupt")
            logger.warning(f"compile cache: entry {key[:16]} unreadable "
                           f"({e}); falling back to a fresh compile")
            self.invalidate(key)
            return None
        self._touch(path)
        return payload

    def scopes(self, key):
        """``(module, {instruction: scope})`` as stored beside entry
        ``key``'s payload when it was built (``SCOPES_FILE``), or None for
        an entry that holds none (an older store's)."""
        try:
            with open(os.path.join(self._entry_dir(key), SCOPES_FILE)) as f:
                got = json.load(f)
            return got["module"], got["instructions"]
        except (OSError, ValueError, KeyError):
            return None

    def put(self, key, payload, meta=None, scopes=None):
        """Atomically commit an entry; returns True on success.  Failures
        (disk full, permissions, races) degrade to not-cached.  ``scopes``:
        the executable's ``(module, {instruction: scope})``, kept as
        ``SCOPES_FILE`` so that a warm start reads it and renders no HLO.

        Staging is PER-PROCESS (``<key>.<pid>.tmp``): the cache is shared
        by design, and two workers compiling the same program must not
        clobber each other's in-flight staging (the same-content entry
        either writer commits is valid — first rename wins)."""
        if self.readonly:
            return False
        staged = atomic.stage_path(self.dir, f"{key}.{os.getpid()}")
        final = self._entry_dir(key)
        try:
            if os.path.isdir(staged):        # leftover of our own killed run
                shutil.rmtree(staged, ignore_errors=True)
            os.makedirs(staged)
            with open(os.path.join(staged, PAYLOAD_FILE), "wb") as f:
                f.write(payload)
            with open(os.path.join(staged, KEY_FILE), "w") as f:
                # key anatomy beside the payload, not a metric stream
                json.dump(meta or {}, f, indent=2,  # dstpu: disable=DSTPU104
                          sort_keys=True, default=str)
            if scopes is not None:
                with open(os.path.join(staged, SCOPES_FILE), "w") as f:
                    json.dump(  # dstpu: disable=DSTPU104
                        {"module": scopes[0], "instructions": scopes[1]}, f,
                        separators=(",", ":"))
            atomic.write_manifest(staged, meta={
                "key": key, "format_version": FORMAT_VERSION,
                "payload_bytes": len(payload)})
            try:
                os.rename(staged, final)
            except OSError:
                if not os.path.isdir(final):
                    raise
                # a concurrent writer committed the same key first; its
                # entry is equivalent — drop ours
                shutil.rmtree(staged, ignore_errors=True)
            atomic.fsync_dir(self.dir)
        except OSError as e:
            shutil.rmtree(staged, ignore_errors=True)
            self._count("put_errors")
            logger.warning(f"compile cache: could not write entry "
                           f"{key[:16]} ({e}); executable stays in-memory "
                           "only for this process")
            return False
        self._count("puts")
        self._evict_lru()
        return True

    def invalidate(self, key):
        if self.readonly:
            return
        try:
            shutil.rmtree(self._entry_dir(key))
        except OSError as e:
            logger.warning(f"compile cache: could not remove invalid entry "
                           f"{key[:16]}: {e}")

    def _touch(self, path):
        """LRU recency marker (entry-dir mtime).  Readonly caches skip it."""
        if self.readonly:
            return
        try:
            os.utime(path, None)
        except OSError as e:
            logger.debug(f"compile cache: utime failed on {path}: {e}")

    def entries(self):
        """Committed entries as (key, bytes, mtime), oldest first."""
        out = []
        if not os.path.isdir(self.dir):
            return out
        for name in os.listdir(self.dir):
            full = os.path.join(self.dir, name)
            if not os.path.isdir(full) or \
                    name.endswith(atomic.STAGE_SUFFIX) or \
                    name.endswith(".replaced"):
                continue
            if not os.path.isfile(os.path.join(full, PAYLOAD_FILE)):
                continue
            try:
                size = os.path.getsize(os.path.join(full, PAYLOAD_FILE))
                mtime = os.path.getmtime(full)
            except OSError:
                continue     # entry vanished mid-scan (concurrent evict)
            out.append((name, size, mtime))
        out.sort(key=lambda t: t[2])
        return out

    def _evict_lru(self):
        if self.readonly or self.max_entries < 1:
            return
        ent = self.entries()
        excess = len(ent) - self.max_entries
        for key, _, _ in ent[:max(excess, 0)]:
            self.invalidate(key)
            logger.info(f"compile cache: evicted LRU entry {key[:16]} "
                        f"(max_entries={self.max_entries})")

    # ------------------------------------------------------------ accounting
    def _count(self, k, ms=None):
        self.stats[k] += 1 if ms is None else ms
        GLOBAL_STATS[k] += 1 if ms is None else ms

    def record_event(self, name, key, source, ms, payload_bytes=0):
        self.events.append({"name": name, "key": key[:16], "source": source,
                            "ms": round(ms, 1),
                            "payload_bytes": payload_bytes})
        del self.events[:-_MAX_EVENTS]
        self.write_last_run_stats()

    def write_last_run_stats(self):
        """Small JSON beside the entries so ``ds_report`` can show the
        last run's hit/miss counters without importing jax state."""
        if self.readonly:
            return
        try:
            atomic.atomic_write_text(
                os.path.join(self.dir, STATS_FILE),
                json.dumps({"pid": os.getpid(), "ts": time.time(),
                            "stats": self.stats,
                            "events": self.events[-16:]}, indent=2))
        except OSError as e:
            logger.debug(f"compile cache: stats write failed: {e}")

    def report(self):
        ent = self.entries()
        return {
            "enabled": True,
            "dir": self.dir,
            "readonly": self.readonly,
            "max_entries": self.max_entries,
            "entries": len(ent),
            "total_bytes": sum(s for _, s, _ in ent),
            **{k: (round(v, 1) if isinstance(v, float) else v)
               for k, v in self.stats.items()},
            "events": list(self.events),
        }


# ``jit_fn.lower`` called beneath one interpreter frame of 2^17 slots (1 MiB):
# every executable of a ``CachedStep`` is lowered through it.  CPython 3.12
# keeps interpreter frames in chunks of 16 KiB and maps a chunk, and unmaps
# it again, each time the call depth crosses a chunk's edge.  A JAX trace
# goes tens of frames up and down for every operation it stages, so it
# crosses edges all the time, and in a process with many threads every
# unmapping is dear: on the chip's host each of Jamba's 23 executables took
# 2.6 s to lower in 16 KiB chunks and 0.8 s beneath this frame
# (``setup.trace_lower_s`` 54.9 s against 16.0: PERF.md section 6, PR 58).
# Where a trace's innermost calls lay ON an edge, which the sizes of all the
# frames above them decided, it was worse again (PR 29: 5.4 s an executable
# from one caller's frame 248 bytes smaller), and five modules kept their
# frames the size they had.  A frame that does not fit in what is left of a
# chunk gets a new one, the smallest 16 KiB x 2^k that holds it and 1,000
# slots more (``push_chunk``, Python/pystate.c): for 2^17 slots ONE chunk of
# 2 MiB, of which this frame takes the first and everything under ``lower``
# lies in the second, with no edge to cross whatever the callers above or the
# model below look like.  Pages it does not touch are never faulted in, and
# the chunk is unmapped when the frame returns.  The size is a constant of
# the interpreter's, not an option: where chunks are sized otherwise the
# frame is merely large, and a trace deeper than that 1 MiB goes on in 16 KiB
# chunks as all did before.  A step with no cache attached (``__call__``'s
# passthroughs) is traced by ``jax.jit`` where it is called: those calls are
# the steady state's too, which a chunk mapped a call would tax.  Held by
# ``tests/test_compile_cache.py::test_lowering_takes_as_long_from_any_depth``.
def _lower_at_own_depth(jit_fn, args, kwargs):
    return jit_fn.lower(*args, **kwargs)


_lower_at_own_depth.__code__ = _lower_at_own_depth.__code__.replace(
    co_stacksize=1 << 17)


# ----------------------------------------------------------- the AOT wrapper
class CachedStep:
    """Dispatch wrapper for one jitted entry point.

    Call-compatible with the wrapped ``jax.jit`` function (including
    donation and tracing through ``jax.make_jaxpr``); exposes ``lower``
    for the auditor/profiler.  With a cache attached, the first call per
    argument signature lowers the function, resolves the content key, and
    either deserializes the stored executable (warm start) or compiles
    and stores it; subsequent calls dispatch straight into the compiled
    executable.  Without a cache it is a transparent passthrough.
    """

    def __init__(self, name, jit_fn, cache=None, key_extra=None,
                 donate_argnums=(), describe=None):
        self.name = name
        self._jit = jit_fn
        self.cache = cache
        self.key_extra = key_extra or {}
        self.donate_argnums = tuple(donate_argnums)
        self._exes = {}        # args_signature -> (Compiled, key, source)
        # ``describe(compiled, args) -> dict``: read once from each
        # acquired executable, kept as ``described`` (the newest) and
        # as attributes of its compile.build / compile.load span
        self._describe = describe
        self.described = None

    # jax.jit API surface used elsewhere in the repo
    def lower(self, *args, **kwargs):
        return _lower_at_own_depth(self._jit, args, kwargs)

    def clear(self):
        """Drop live executables (frees their device programs)."""
        self._exes.clear()

    def live_executable(self, *args, **kwargs):
        """The already-acquired Compiled for these avals, or None.  Used
        by the auditor to check THE executable that is dispatching —
        including a deserialized (warm-started) one."""
        hit = self._exes.get(args_signature(args, kwargs))
        return hit[0] if hit else None

    def executable(self, *args, **kwargs):
        """Acquire (cache-or-compile) without calling.  Never consumes
        donated buffers.  Works with no cache attached (plain AOT
        compile) — the bench memory preflight path."""
        sig = args_signature(args, kwargs)
        hit = self._exes.get(sig)
        if hit is None:
            hit = self._acquire(args, kwargs, sig)
        return hit[0]

    def keys(self):
        """Content keys of every acquired signature (test hook)."""
        return [k for _, k, _ in self._exes.values()]

    def __call__(self, *args, **kwargs):
        if _being_traced():
            # being traced (jax.make_jaxpr / an outer jit): stage the
            # underlying jit call, never the dispatch machinery
            return self._jit(*args, **kwargs)
        if self.cache is None and not self._exes:
            return self._jit(*args, **kwargs)
        hit = None
        if len(self._exes) == 1:
            # steady-state fast path: nearly every wrapper only ever sees
            # one signature, so skip the per-call pytree flatten + sig
            # build.  Safe optimistically: Compiled.call validates avals
            # BEFORE executing (donated buffers are not consumed on a
            # mismatch), so a new shape/dtype surfaces as TypeError and
            # falls through to the full acquire below.  (A SHARDING
            # mismatch is a ValueError on jax 0.9.0 and propagates: the
            # signature does not key on shardings, so it is the caller's
            # error, not a new program.)
            (hit,) = self._exes.values()
            try:
                return self._dispatch(hit, args, kwargs)
            except TypeError:
                hit = None
        sig = args_signature(args, kwargs)
        hit = self._exes.get(sig)
        if hit is None:
            if self.cache is None:
                return self._jit(*args, **kwargs)
            hit = self._acquire(args, kwargs, sig)
        return self._dispatch(hit, args, kwargs)

    def _dispatch(self, hit, args, kwargs):
        exe, _, source = hit
        if source == "cache" and self.donate_argnums and \
                jax.default_backend() == "cpu":
            # DESERIALIZED executables on this jaxlib donate
            # UNCONDITIONALLY (must-alias semantics), where normal jit
            # dispatch — and, measured, a freshly `lowered.compile()`d
            # Compiled — backs off to a copy when a zero-copy host view
            # of the buffer is alive (np.asarray of a CPU jax array is
            # such a view; without this the view mutates in place
            # mid-step, the exact corruption jax's own compilation cache
            # shows on this container, tests/conftest.py).  Restore
            # copy-on-donate semantics by donating a COPY on backends
            # with zero-copy host views; device-backed arrays (TPU) have
            # none, so real donation is preserved where the memory win
            # matters.
            args = list(args)
            for i in self.donate_argnums:
                if i < len(args):
                    args[i] = jax.tree_util.tree_map(
                        lambda l: (jnp.copy(l) if isinstance(l, jax.Array)
                                   else l), args[i])
            args = tuple(args)
        return exe(*args, **kwargs)

    # ----------------------------------------------------------- internals
    def _acquire(self, args, kwargs, sig):
        # the compile.* spans nest under whatever step called for the
        # executable, which is how an in-window recompile names its step;
        # lower_ms / compile_ms / deserialize_ms are their durations.
        rec = spans.recorder()
        with rec.setup_span("compile.lower", attrs={"fn": self.name}) as span:
            lowered = _lower_at_own_depth(self._jit, args, kwargs)
        lower_ms = (span.t1 - span.t0) * 1000
        _split_lower(rec, span)
        _note_grouped_products(span)
        cache = self.cache
        material = None
        if cache is not None:
            cache._count("lower_ms", lower_ms)
            material = self._key_material(args, lowered, kwargs)
        if material is not None:
            key = key_from_material(material)
            exe = self._try_deserialize(cache, key,
                                        _execution_devices(lowered), args)
            if exe is not None:
                hit = (exe, key, "cache")
                self._exes[sig] = hit
                return hit
        else:
            key = "<uncached>"
        with rec.setup_span("compile.build", attrs={
                "fn": self.name, "source": "compile"}) as span:
            compiled = lowered.compile()
            if material is not None:
                self._try_serialize(cache, key, compiled, material)
            self._note(span, compiled, args)
        compile_ms = (span.t1 - span.t0) * 1000      # compile + serialise
        if material is not None:
            cache._count("misses")
            cache._count("compile_ms", compile_ms)
            cache.record_event(self.name, key, "compile", compile_ms)
        hit = (compiled, key, "compile")
        self._exes[sig] = hit
        return hit

    def _key_material(self, args, lowered, kwargs):
        """:func:`build_key_material` under its own span, ``compile.key``
        (the lowered text rendered and hashed)."""
        with spans.recorder().setup_span("compile.key",
                                         attrs={"fn": self.name}):
            return build_key_material(self.name, args, lowered,
                                      self.key_extra, kwargs=kwargs)

    def _note(self, span, exe, args):
        if self._describe is None:
            return
        try:
            self.described = self._describe(exe, args)
        except Exception as e:       # a reading, never a reason to fail
            logger.warning(f"{self.name}: executable not described ({e})")
            return
        span.attrs = {**span.attrs, **self.described}

    def _try_deserialize(self, cache, key, devices, args=()):
        rec = spans.recorder()
        with rec.setup_span("compile.load", attrs={
                "fn": self.name, "source": "cache"}) as span:
            payload = cache.get(key)
            if payload is None:
                rec.discard(span)    # nothing stored: a lookup, not a load
                return None
            from jax.experimental import serialize_executable as se
            try:
                ser, in_tree, out_tree = pickle.loads(payload)
                # the loader binds to EVERY local device unless told which:
                # an executable for a sub-mesh must go back onto its own
                exe = se.deserialize_and_load(ser, in_tree, out_tree,
                                              execution_devices=devices)
            except Exception as e:
                # unpicklable/incompatible payload (jaxlib drift the version
                # key missed, foreign-topology artifact): a miss, not a crash
                cache._count("corrupt")
                cache.invalidate(key)
                logger.warning(f"compile cache: could not deserialize entry "
                               f"{key[:16]} ({type(e).__name__}: {e}); "
                               "falling back to a fresh compile")
                return None
            _note_device_scopes(self.name, exe, cache.scopes(key),
                                loaded=True)
            self._note(span, exe, args)
        ms = (span.t1 - span.t0) * 1000              # read + deserialise
        cache._count("hits")
        cache._count("deserialize_ms", ms)
        cache.record_event(self.name, key, "cache", ms, len(payload))
        log_dist(f"compile cache HIT {self.name} [{key[:12]}] "
                 f"({ms:.0f} ms deserialize)", ranks=[0])
        return exe

    def _try_serialize(self, cache, key, compiled, material):
        from jax.experimental import serialize_executable as se
        scopes = _note_device_scopes(self.name, compiled)
        try:
            ser, in_tree, out_tree = se.serialize(compiled)
            payload = pickle.dumps((ser, in_tree, out_tree))
        except Exception as e:
            # e.g. a treedef holding a test-local class pickle refuses;
            # the executable still runs, it just is not persisted
            cache._count("put_errors")
            logger.warning(f"compile cache: could not serialize "
                           f"{self.name} ({type(e).__name__}: {e}); "
                           "entry not persisted")
            return
        cache.put(key, payload, meta=material, scopes=scopes)


def _note_device_scopes(name, exe, stored=None, loaded=False):
    """Hand ``monitor.device_scopes()`` the executable's map from device
    instruction to ``jax.named_scope``.  Read off the HLO text where the
    executable was BUILT; a load (``loaded``) hands on what its entry
    ``stored`` and renders nothing, and where the entry holds none (an
    older store's) the text is read when the maps are first asked for.  Returns
    the map, or None where it could not be read: a reading, never a reason
    to fail."""
    def read():
        from ..analysis.hlo_scopes import scope_map
        try:
            return scope_map(exe.as_text(), scope_maps.VOCABULARY,
                             scope_maps.CONTAINERS)
        except Exception as e:
            logger.warning(f"{name}: device scopes not read ({e})")
            return None

    if loaded and stored is None:
        scope_maps.note_later(read)
        return None
    got = stored or read()
    if got is not None:
        scope_maps.note(*got)
    return got


def _split_lower(rec, span):
    """``trace_s`` / ``mlir_s`` on a closed ``compile.lower`` span: the
    seconds of it JAX reported as tracing the function and as lowering the
    jaxpr to MLIR (the recorder's ``jax.trace`` / ``jax.lower`` rows inside
    it).  Left out where JAX reported neither."""
    inside = [r for r in rec.setup_rows()[0]
              if r.name in ("jax.trace", "jax.lower")
              and r.t_start >= span.t0 and r.t_end <= span.t1]
    parts = spans.innermost_seconds(inside, span.t0, span.t1,
                                    lambda r: r.name)
    if "jax.trace" in parts:
        span.attrs["trace_s"] = parts["jax.trace"]
    if "jax.lower" in parts:
        span.attrs["mlir_s"] = parts["jax.lower"]


def _note_grouped_products(span):
    """``grouped_products`` on a closed ``compile.lower`` span: the routed
    experts' grouped products that were traced inside it, each by kernel,
    tiles and shapes (``moe/dropless.products_traced``), so that a run says
    which kernel and plan an executable holds without a device trace.  Left
    out where none was traced (a program that never imported the expert
    layer imports nothing here)."""
    dropless = sys.modules.get("deepspeed_tpu.moe.dropless")
    products = dropless and dropless.products_traced(span.t0, span.t1)
    if products:
        span.attrs["grouped_products"] = products


def executable_memory_analysis(exe):
    """One shared reading of an executable's ``memory_analysis()`` for
    every preflight gate (train engine, serving, bench): byte-count dict
    with ``peak_bytes`` approximating execution-time live memory
    (arguments + outputs − donated aliases + temps + program), or None
    when the backend exposes no analysis.  Backend quirks (list-wrapped
    results, missing fields) are handled HERE so the gates cannot
    drift."""
    try:
        ma = exe.memory_analysis()
    except Exception as e:
        logger.warning(f"memory preflight unavailable: {e}")
        return None
    if isinstance(ma, (list, tuple)):
        ma = ma[0] if ma else None
    if ma is None:
        return None
    g = lambda k: int(getattr(ma, k, 0) or 0)
    out = {
        "argument_bytes": g("argument_size_in_bytes"),
        "output_bytes": g("output_size_in_bytes"),
        "temp_bytes": g("temp_size_in_bytes"),
        "alias_bytes": g("alias_size_in_bytes"),
        "generated_code_bytes": g("generated_code_size_in_bytes"),
    }
    out["peak_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                         - out["alias_bytes"] + out["temp_bytes"]
                         + out["generated_code_bytes"])
    return out


def wrap_step(name, fn, cache=None, key_extra=None, donate_argnums=(),
              describe=None):
    """jit + CachedStep in one place — the factory every engine's
    ``_wrap_step`` delegates to, so dispatch-policy changes land once."""
    return CachedStep(name, jax.jit(fn, donate_argnums=donate_argnums),
                      cache=cache, key_extra=key_extra,
                      donate_argnums=donate_argnums, describe=describe)


def report(cache):
    """Engine-facing compile report: the cache's report, or the disabled
    marker when no cache is attached."""
    if cache is None:
        return {"enabled": False}
    return cache.report()


# ------------------------------------------------------------- construction
def from_config(cfg):
    """Build the engine's CompileCache from its parsed ``compile_cache``
    config block (None when disabled / no directory resolved)."""
    if cfg is None or not cfg.enabled or not cfg.dir:
        return None
    return CompileCache(cfg.dir, max_entries=cfg.max_entries,
                        readonly=cfg.readonly)


def from_dir(dir=None, max_entries=0, readonly=False):
    """Cache from an explicit dir, or the env default (None if neither)."""
    if env_disabled():
        return None
    dir = dir or resolve_env_dir()
    if not dir:
        return None
    return CompileCache(dir, max_entries=max_entries, readonly=readonly)


def disk_report(dir=None):
    """What ``ds_report`` prints: entry count, bytes, last-run counters.
    Read-only — safe on a cache owned by another (live) process."""
    dir = dir or resolve_env_dir()
    if not dir:
        return {"configured": False}
    out = {"configured": True, "dir": dir, "exists": os.path.isdir(dir)}
    if not out["exists"]:
        return out
    n, total = 0, 0
    for name in os.listdir(dir):
        if name.endswith(atomic.STAGE_SUFFIX) or name.endswith(".replaced"):
            continue     # in-flight/stale staging is not a committed entry
        payload = os.path.join(dir, name, PAYLOAD_FILE)
        if os.path.isfile(payload):
            n += 1
            try:
                total += os.path.getsize(payload)
            except OSError:  # dstpu: disable=DSTPU002
                pass  # entry evicted mid-scan; the count stays best-effort
    out["entries"] = n
    out["total_bytes"] = total
    try:
        with open(os.path.join(dir, STATS_FILE)) as f:
            out["last_run"] = json.load(f)
    except (OSError, ValueError):
        out["last_run"] = None
    return out
