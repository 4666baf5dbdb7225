"""CLI: ``python -m deepspeed_tpu.analysis [paths] [--rules ...] [--json]``.

Default invocation lints the installed ``deepspeed_tpu`` package tree
(plus any extra paths given) and exits nonzero on unsuppressed
error-severity findings — the tier-1 suite runs exactly this and gates
on a clean repo.  ``--audit-step`` additionally builds tiny in-memory
engines (z1/z2/z3, bf16) and runs the jaxpr auditor on their real
compiled train steps.
"""

import argparse
import json
import os
import sys

from . import counts_by_severity, lint_paths, select_rules


def _default_paths():
    import deepspeed_tpu
    return [os.path.dirname(os.path.abspath(deepspeed_tpu.__file__))]


class _MLP:
    """Tiny bf16 MLP the built-in audit stages train (CPU works)."""

    def init(self, rng):
        import jax
        import jax.numpy as jnp
        k1, k2 = jax.random.split(rng)
        return {"w1": jax.random.normal(k1, (16, 32), jnp.float32),
                "w2": jax.random.normal(k2, (32, 16), jnp.float32)}

    def loss(self, params, batch, rng):
        import jax.numpy as jnp
        x, y = batch
        h = jnp.maximum(x.astype(jnp.bfloat16) @ params["w1"], 0)
        p = (h @ params["w2"]).astype(jnp.float32)
        return jnp.mean(jnp.square(p - y))


def _audit_builtin_steps(stages):
    """Jaxpr-audit a tiny bf16 MLP engine's compiled step per ZeRO stage
    on whatever devices this process sees (CPU works).

    Each stage is built TWICE through a throwaway compile cache: a cold
    engine populates it, then a WARM-STARTED engine — whose step is the
    deserialized executable — is the one audited.  That makes DSTPU204
    (donation declared vs honored via ``input_output_alias``) hold for
    AOT warm starts, not just fresh compiles (docs/compile-cache.md)."""
    import shutil
    import tempfile
    import numpy as np
    import deepspeed_tpu as ds
    from .findings import Finding
    from .jaxpr_audit import audit_engine

    findings = []
    data = (np.ones((8, 16), np.float32), np.ones((8, 16), np.float32))
    dataset = [(data[0][i], data[1][i]) for i in range(8)]
    # each stage spec pins its own compression policy; an inherited env
    # override would veto the `3q` variant's explicit enabled=true (or
    # silently compress the plain stages)
    os.environ.pop("DSTPU_COMMS_COMPRESSION", None)
    # a throwaway store, deliberately cold: the audit compiles each stage
    # once and warm-starts it once (CPU-only; the chip path's caches live
    # under compile_cache.cache_root(), a fixed directory)
    cache_dir = tempfile.mkdtemp(prefix="dstpu-audit-cc-")
    try:
        for spec in stages:
            if str(spec) == "decode":
                findings.extend(_audit_decode_step())
                continue
            if str(spec) == "serving-resilience":
                findings.extend(_audit_serving_resilience())
                continue
            if str(spec) == "serving-lifecycle":
                findings.extend(_audit_serving_lifecycle())
                continue
            if str(spec) == "paged-attn":
                findings.extend(_audit_paged_attention())
                continue
            if str(spec) == "tracing":
                findings.extend(_audit_tracing())
                continue
            if str(spec) == "elastic":
                findings.extend(_audit_elastic_resume())
                continue
            if str(spec) == "moe":
                findings.extend(_audit_moe_step())
                continue
            if str(spec) == "monitor":
                findings.extend(_audit_monitor_step(cache_dir))
                continue
            if str(spec) == "mem":
                findings.extend(_audit_mem_step(cache_dir))
                continue
            if str(spec) == "slo":
                findings.extend(_audit_slo_step(cache_dir))
                continue
            compressed = str(spec).endswith("q")
            stage = int(str(spec).rstrip("q"))
            cfg = {"train_micro_batch_size_per_gpu": 4,
                   "gradient_accumulation_steps": 1,
                   "steps_per_print": 10 ** 9,
                   "bf16": {"enabled": True},
                   "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                   "zero_optimization": {"stage": stage},
                   "compile_cache": {"dir": cache_dir}}
            if compressed:
                # quantized-collectives variant (docs/comms-compression.md):
                # fsdp absorbs the devices so qwZ/qgZ engage whenever this
                # process sees more than one; the audit then additionally
                # gates the census against the engine's declared
                # CommsBudget (wire-byte accounting, DSTPU203)
                cfg["mesh"] = {"axes": {"fsdp": -1, "data": 1}}
                cfg["zero_optimization"][
                    "stage3_param_persistence_threshold"] = 0
                cfg["comms_compression"] = {"enabled": True,
                                            "min_tensor_bytes": 0,
                                            "block_size": 4}
            cold, _, _, _ = ds.initialize(config=cfg, model=_MLP(),
                                          training_data=dataset)
            cache_on = cold.compile_report().get("enabled", False)
            warm_started = False
            if cache_on:
                cold.train_batch()  # compiles + persists the executable
                cold.close()
                engine, _, _, _ = ds.initialize(config=cfg, model=_MLP(),
                                                training_data=dataset)
                engine.train_batch()   # deserializes (or the finding below)
                rep = engine.compile_report()
                warm_started = bool(rep.get("hits"))
                if not warm_started:
                    findings.append(Finding(
                        "DSTPU200", "warning",
                        f"--audit-step z{stage}: warm start did not hit "
                        "the compile cache (hits=0); auditing a fresh "
                        "executable instead of a deserialized one",
                        eqn_path="warm-start",
                        extra={"zero_stage": stage,
                               "compile_report": {k: rep.get(k) for k in
                                                  ("hits", "misses",
                                                   "corrupt",
                                                   "put_errors")}}))
            else:
                # operator kill switch (DSTPU_COMPILE_CACHE=0): audit the
                # cold engine directly — disabling the cache is a choice,
                # not a finding
                engine = cold
            budget = engine.comms_budget() if compressed else None
            report = audit_engine(engine, comms_budget=budget)
            if compressed and budget is not None:
                from .comms import wire_report
                wr = wire_report([c for c in report.census
                                  if c.level == "hlo"])
                if wr["quantized_wire_bytes"] == 0:
                    findings.append(Finding(
                        "DSTPU200", "warning",
                        f"--audit-step z{stage}q: compression routes were "
                        "active but the compiled step moved no quantized "
                        "collective payload",
                        eqn_path="comms-compression",
                        extra={"wire_report": {k: wr[k] for k in
                                               ("wire_bytes",
                                                "quantized_wire_bytes")}}))
            for f in report.findings:
                f.extra = dict(f.extra, zero_stage=stage,
                               compressed=compressed,
                               warm_started=warm_started)
            findings.extend(report.findings)
            engine.close()
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    return findings


def _audit_decode_step():
    """Jaxpr-audit the serving layer's fused paged decode step (and the
    InferenceEngine's fused token-scan decode loop) on a tiny GPT-2:
    zero host callbacks (DSTPU201), donation declared-vs-honored on the
    KV pool/cache (DSTPU204), and no weak-scalar recompile hazards
    (DSTPU205) — the serving hot loop must stay a single clean
    executable (docs/serving.md).  The serving step is audited with the
    prefix cache ARMED (docs/serving.md#prefix-sharing): sharing is
    pure host-side block bookkeeping, so the armed decode jaxpr must be
    byte-identical to the cache-off trace."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from .findings import Finding
    from .jaxpr_audit import audit_fn
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
    from deepspeed_tpu.inference import (InferenceEngine, ServingEngine,
                                         ServingConfig, Request)

    cfg = GPT2Config(vocab_size=64, max_seq=32, n_embd=32, n_layer=2,
                     n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    model = GPT2(cfg, dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0))
    findings = []
    for kv_bits in (16, 8):
        scfg = dict(batch_slots=2, block_size=8, kv_bits=kv_bits,
                    max_new_tokens=4, preflight=False)
        plain = ServingEngine(model=model, params=params,
                              config=ServingConfig(**scfg))
        plain._build_decode()
        plain_jaxpr = str(jax.make_jaxpr(plain._decode)(
            *plain._decode_args()))
        plain.close()
        srv = ServingEngine(
            model=model, params=params,
            config=ServingConfig(prefix_cache=True, **scfg))
        srv._build_decode()
        if str(jax.make_jaxpr(srv._decode)(
                *srv._decode_args())) != plain_jaxpr:
            findings.append(Finding(
                "DSTPU201", "error",
                "--audit-step decode: arming serving.prefix_cache "
                f"CHANGED the traced decode step (kv_bits={kv_bits}) — "
                "sharing must stay host-side block bookkeeping, never "
                "program content", eqn_path="serving/jaxpr-equality"))
        # a shared-prefix pair warms the executables audit_fn will
        # inspect AND takes a real radix-cache hit, so the step audited
        # below is the one that served shared blocks
        srv.run([Request(tokens=np.arange(12), max_new_tokens=2, uid=1),
                 Request(tokens=np.concatenate(
                     [np.arange(8), np.array([33, 34, 35, 36])]),
                     max_new_tokens=2, uid=2)])
        if not srv.stats()["prefix_cache"]["requests_hit"]:
            findings.append(Finding(
                "DSTPU200", "warning",
                "--audit-step decode: the shared-prefix pair produced "
                f"no radix-cache hit (kv_bits={kv_bits}) — the audited "
                "step never exercised sharing",
                eqn_path="serving/prefix-cache"))
        report = audit_fn(srv._decode, *srv._decode_args(),
                          donate_argnums=(1,), mesh=srv.engine.mesh)
        for f in report.findings:
            f.extra = dict(f.extra, audit="serving-decode",
                           kv_bits=kv_bits)
        findings.extend(report.findings)
        srv.close()
    # the generate() fused token scan (prefill + ONE scan executable)
    eng = InferenceEngine(model, params=params)
    eng.generate(np.zeros((1, 4), np.int32), max_new_tokens=4)
    loop = next(iter(eng._decode_loops.values()))
    cache = model.init_cache(1, 8)
    last = jnp.zeros((1, cfg.vocab_size), jnp.float32)
    report = audit_fn(loop, eng.params, last, cache,
                      jax.random.PRNGKey(0), jnp.float32(1.0),
                      donate_argnums=(2,), mesh=eng.mesh)
    for f in report.findings:
        # the decode loop DISCARDS the final cache (tokens are the only
        # output), so jax cannot alias the donated cache to an output —
        # a known, documented non-aliasing, not a regression (DSTPU204
        # flags declared-but-unhonored donation)
        if f.rule == "DSTPU204":
            continue
        f.extra = dict(f.extra, audit="generate-decode-loop")
        findings.append(f)
    eng.close()
    return findings


def _audit_serving_resilience():
    """--audit-step serving-resilience: the quarantine-sentinel-armed
    serving decode step (docs/serving.md#resilience) must stay one clean
    executable — zero host callbacks (DSTPU201) with the pool donation
    honored (DSTPU204) — and the ``logit_nan`` chaos fault must leave
    the TRACED program byte-identical (the poison rides the pool data;
    the PR-3 jaxpr-equality discipline applied to the serving step).
    Functionally, a poisoned request must come back quarantined while
    its neighbor completes."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from .findings import Finding
    from .jaxpr_audit import audit_fn
    from deepspeed_tpu import fault
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
    from deepspeed_tpu.inference import (ServingEngine, ServingConfig,
                                         Request, POISONED, OK)

    cfg = GPT2Config(vocab_size=64, max_seq=32, n_embd=32, n_layer=2,
                     n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    model = GPT2(cfg, dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0))
    scfg = dict(batch_slots=2, block_size=8, max_new_tokens=4,
                preflight=False)
    findings = []

    def jaxpr_text(srv):
        srv._build_decode()
        return str(jax.make_jaxpr(srv._decode)(*srv._decode_args()))

    fault.reset()
    try:
        clean = ServingEngine(model=model, params=params,
                              config=ServingConfig(**scfg))
        clean_jaxpr = jaxpr_text(clean)
        # audit the sentinel-armed step itself: no host callbacks, pool
        # donation honored through the quarantine sentinel's extra output
        clean.run([Request(tokens=np.arange(5), max_new_tokens=2)])
        report = audit_fn(clean._decode, *clean._decode_args(),
                          donate_argnums=(1,), mesh=clean.engine.mesh)
        for f in report.findings:
            f.extra = dict(f.extra, audit="serving-resilience")
        findings.extend(report.findings)
        clean.close()

        fault.configure(logit_nan=7)
        armed = ServingEngine(model=model, params=params,
                              config=ServingConfig(**scfg))
        if jaxpr_text(armed) != clean_jaxpr:
            findings.append(Finding(
                "DSTPU201", "error",
                "--audit-step serving-resilience: arming the logit_nan "
                "fault CHANGED the traced decode step (jaxpr armed != "
                "disarmed) — the poison must ride the pool data, never "
                "the program", eqn_path="serving/jaxpr-equality"))
        res = armed.run([Request(tokens=np.arange(5), uid=7),
                         Request(tokens=np.arange(6), uid=8)])
        if res[7]["outcome"] != POISONED or res[8]["outcome"] != OK:
            findings.append(Finding(
                "DSTPU200", "warning",
                "--audit-step serving-resilience: the poisoned request "
                f"was not quarantined (outcomes: uid7="
                f"{res[7]['outcome']}, uid8={res[8]['outcome']})",
                eqn_path="serving/quarantine"))
        armed.close()
    finally:
        fault.reset()
    return findings


def _audit_serving_lifecycle():
    """--audit-step serving-lifecycle: the three lifecycle layers
    (docs/static-analysis.md#lifecycle) proven against live engines:

    - **jaxpr parity** — twin tiny serving engines, shadow sanitizer
      armed vs off, must trace byte-identical decode steps AND produce
      token-identical results (the sanitizer is host-side bookkeeping,
      never program content);
    - **detector integrity** — every DSTPU31x violation class, driven
      synthetically against a :class:`ShadowSanitizer`, must be caught
      (a sanitizer that misses a seeded double-free proves nothing
      about a clean run);
    - **interleaving sweeps** — the full 720-ordering
      :func:`~.interleave.crash_handoff_scenario` permutation sweep
      over the real router, the 720-ordering
      :func:`~.interleave.disagg_handoff_scenario` prefill→decode
      handoff sweep (publish/announce/torn-publish/crash racing), and
      the 720-ordering :func:`~.interleave.prefix_sharing_scenario`
      refcount sweep over the real allocator + radix cache, must all
      report zero violations."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from .findings import Finding
    from . import sanitize
    from .interleave import (explore, disagg_handoff_scenario,
                             prefix_sharing_scenario)
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
    from deepspeed_tpu.inference import (ServingEngine, ServingConfig,
                                         Request)

    findings = []

    # ---- detector integrity: every class must fire ------------------
    def seeded(code, drive):
        san = sanitize.ShadowSanitizer(8, halt=False)
        drive(san)
        got = [f.rule for f in san.findings]
        if code not in got:
            findings.append(Finding(
                "DSTPU200", "error",
                f"--audit-step serving-lifecycle: the shadow sanitizer "
                f"MISSED a seeded {code} violation (got {got}) — the "
                f"armed run's clean verdict below proves nothing",
                eqn_path=f"sanitize/detector/{code}"))

    seeded(sanitize.DOUBLE_FREE,
           lambda s: (s.on_alloc([3]), s.on_free([3]), s.on_free([3])))
    seeded(sanitize.USE_AFTER_FREE,
           lambda s: s.on_attach(1, [3]))
    seeded(sanitize.LEAK_AT_CLOSE,
           lambda s: (s.on_alloc([3]), s.on_close()))
    seeded(sanitize.SCRATCH_WRITE,
           lambda s: (s.on_alloc([3]), s.on_attach(1, [0, 3])))
    seeded(sanitize.DOUBLE_SERVE,
           lambda s: (s.on_serve(5), s.on_serve(5)))
    seeded(sanitize.SCRUB_REFERENCED,
           lambda s: (s.on_alloc([3]), s.on_attach(1, [3]),
                      s.on_scrub([3], uid=2)))
    seeded(sanitize.SCRUB_SHARED,
           lambda s: (s.on_alloc([3]), s.on_share([3]),
                      s.on_scrub([3], uid=1)))
    seeded(sanitize.DOUBLE_IMPORT,
           lambda s: (s.on_alloc([2, 3]),
                      s.on_import([3], uid=1, resident=[2])))

    # ---- jaxpr parity + token identity: armed vs off ----------------
    cfg = GPT2Config(vocab_size=64, max_seq=32, n_embd=32, n_layer=2,
                     n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    model = GPT2(cfg, dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0))
    scfg = dict(batch_slots=2, block_size=8, max_new_tokens=4,
                preflight=False)

    def run(sanitize_on):
        srv = ServingEngine(
            model=model, params=params,
            config=ServingConfig(sanitize=sanitize_on, **scfg))
        res = srv.run([Request(tokens=np.arange(5), max_new_tokens=3,
                               uid=1),
                       Request(tokens=np.arange(6) % 3, max_new_tokens=2,
                               uid=2)])
        srv._build_decode()
        jx = str(jax.make_jaxpr(srv._decode)(*srv._decode_args()))
        stats = srv.stats()
        srv.close()
        return res, jx, stats

    res_off, jx_off, _ = run(False)
    res_on, jx_on, stats_on = run(True)
    if jx_on != jx_off:
        findings.append(Finding(
            "DSTPU201", "error",
            "--audit-step serving-lifecycle: arming the shadow "
            "sanitizer CHANGED the traced decode step (jaxpr armed != "
            "off) — the shadow table must stay host-side bookkeeping",
            eqn_path="sanitize/jaxpr-equality"))
    for uid in (1, 2):
        if res_on[uid]["tokens"] != res_off[uid]["tokens"]:
            findings.append(Finding(
                "DSTPU201", "error",
                f"--audit-step serving-lifecycle: uid {uid} tokens "
                f"differ armed vs off — the sanitizer perturbed the "
                f"computation", eqn_path="sanitize/token-identity"))
    # roles armed (docs/serving.md#disaggregation): the whole handoff
    # is host-side file I/O — a decode-role worker with the transfer
    # queue armed must trace the SAME decode step as the mixed engine
    import tempfile
    with tempfile.TemporaryDirectory(prefix="dstpu-disagg-") as td:
        srv = ServingEngine(
            model=model, params=params,
            config=ServingConfig(role="decode",
                                 transfer={"dir": td}, **scfg))
        srv._build_decode()
        jx_role = str(jax.make_jaxpr(srv._decode)(*srv._decode_args()))
        srv.close()
    if jx_role != jx_off:
        findings.append(Finding(
            "DSTPU201", "error",
            "--audit-step serving-lifecycle: arming serving.role/"
            "transfer CHANGED the traced decode step (jaxpr decode-"
            "role != mixed) — the transfer plane must stay host-side "
            "file I/O", eqn_path="transfer/jaxpr-equality"))
    san_stats = stats_on.get("sanitizer") or {}
    if san_stats.get("findings", 0):
        findings.append(Finding(
            "DSTPU200", "error",
            f"--audit-step serving-lifecycle: the armed clean run "
            f"raised {san_stats['findings']} sanitizer finding(s)",
            eqn_path="sanitize/clean-run", extra={"stats": san_stats}))
    if not san_stats.get("checks", 0):
        findings.append(Finding(
            "DSTPU200", "error",
            "--audit-step serving-lifecycle: the armed run performed "
            "ZERO sanitizer checks — the hooks are not wired",
            eqn_path="sanitize/clean-run"))

    # ---- interleaving sweeps ----------------------------------------
    for report in (explore(), explore(disagg_handoff_scenario()),
                   explore(prefix_sharing_scenario())):
        if not report["ok"]:
            findings.extend(report["findings"])
        if report["explored"] != report["total_permutations"]:
            findings.append(Finding(
                "DSTPU200", "error",
                f"--audit-step serving-lifecycle: "
                f"{report['scenario']} interleave sweep covered "
                f"{report['explored']}/{report['total_permutations']} "
                f"orderings — the sweep must be exhaustive",
                eqn_path="interleave/coverage"))
    return findings


def _audit_tracing():
    """--audit-step tracing: request-scoped tracing armed at
    ``trace_sample_rate=1.0`` (docs/monitoring.md#request-tracing) must
    leave the serving decode step byte-identical — tracing is host-side
    bookkeeping, never program content.  Gates: armed-vs-disarmed jaxpr
    equality, zero host callbacks (DSTPU201) and pool donation honored
    (DSTPU204) on the armed step, and the armed run must emit parseable
    ``trace`` events with monotone non-overlapping spans."""
    import shutil
    import tempfile
    import numpy as np
    import jax
    import jax.numpy as jnp
    from .findings import Finding
    from .jaxpr_audit import audit_fn
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
    from deepspeed_tpu.inference import (ServingEngine, ServingConfig,
                                         Request)
    from deepspeed_tpu.monitor import Monitor, parse_line
    from deepspeed_tpu.monitor.sinks import EVENTS_FILE

    cfg = GPT2Config(vocab_size=64, max_seq=32, n_embd=32, n_layer=2,
                     n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    model = GPT2(cfg, dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0))
    scfg = dict(batch_slots=2, block_size=8, max_new_tokens=4,
                preflight=False)
    findings = []

    def jaxpr_text(srv):
        srv._build_decode()
        return str(jax.make_jaxpr(srv._decode)(*srv._decode_args()))

    clean = ServingEngine(model=model, params=params,
                          config=ServingConfig(**scfg))
    clean_jaxpr = jaxpr_text(clean)
    clean.close()

    run_dir = tempfile.mkdtemp(prefix="dstpu-audit-tracing-")
    try:
        armed = ServingEngine(
            model=model, params=params,
            monitor=Monitor(run_dir=run_dir, role="serving"),
            config=ServingConfig(trace_sample_rate=1.0, **scfg))
        if jaxpr_text(armed) != clean_jaxpr:
            findings.append(Finding(
                "DSTPU201", "error",
                "--audit-step tracing: arming trace_sample_rate=1.0 "
                "CHANGED the traced decode step (jaxpr armed != "
                "disarmed) — tracing must stay host-side bookkeeping",
                eqn_path="tracing/jaxpr-equality"))
        armed.run([Request(tokens=np.arange(5), max_new_tokens=3),
                   Request(tokens=np.arange(6), max_new_tokens=2)])
        report = audit_fn(armed._decode, *armed._decode_args(),
                          donate_argnums=(1,), mesh=armed.engine.mesh)
        for f in report.findings:
            f.extra = dict(f.extra, audit="tracing")
        findings.extend(report.findings)
        armed.close()
        traces = []
        stream_ok = True
        try:
            with open(os.path.join(run_dir, EVENTS_FILE)) as fh:
                for line in fh:
                    if line.strip():
                        e = parse_line(line)
                        if e.kind == "trace":
                            traces.append(e)
        except (OSError, ValueError) as e:
            stream_ok = False
            findings.append(Finding(
                "DSTPU104", "error",
                f"--audit-step tracing: armed event stream did not "
                f"parse ({e})", eqn_path="tracing/stream"))
        if stream_ok and not traces:
            findings.append(Finding(
                "DSTPU104", "error",
                "--audit-step tracing: the armed run emitted no `trace` "
                "events at trace_sample_rate=1.0",
                eqn_path="tracing/stream"))
        for e in traces:
            prev = 0.0
            for s in e.fields.get("spans") or ():
                if s["start_ms"] < prev - 1e-6:
                    findings.append(Finding(
                        "DSTPU104", "error",
                        f"--audit-step tracing: request "
                        f"{e.fields.get('uid')} spans overlap/regress "
                        f"({s['name']} starts {s['start_ms']}ms before "
                        f"the previous span ended at {prev}ms)",
                        eqn_path="tracing/spans"))
                prev = max(prev, s["start_ms"] + s["dur_ms"])
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    return findings


def _kv_gather_eqns(closed_jaxpr, block_size, n_head, head_dim):
    """Gathered-K/V-materialization census: every ``gather`` equation
    (anywhere in the program, scan bodies included) whose output is a
    per-slot block-list materialization — rank >= 4 with trailing dims
    ``(block_size, n_head * head_dim)``, the exact shape
    ``paged_kv.gather_kv``'s table gather produces over the pool's
    merged-head layout.  (The kernel path's int8 SCALE gather has a
    narrower minor dim and is not K/V payload.)  The in-place
    kernel's decode step must contain ZERO of these; the gather
    fallback's must contain them (the detector is sanity-checked
    against the fallback so an upstream lowering change cannot silently
    blind it)."""
    from .jaxpr_audit import iter_eqns
    hits = []
    sig = (int(block_size), int(n_head) * int(head_dim))
    for eqn, path in iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "gather":
            continue
        for ov in eqn.outvars:
            shape = tuple(getattr(ov.aval, "shape", ()))
            if len(shape) >= 4 and shape[-2:] == sig:
                hits.append((path, shape))
    return hits


def _audit_paged_attention():
    """--audit-step paged-attn: the in-place paged-attention kernel
    decode step (docs/serving.md#paged-attention-kernel) must be one
    clean executable — zero host callbacks (DSTPU201), pool donation
    honored (DSTPU204) — with **no gathered K/V materialization in the
    jaxpr** (the census above; the gather-fallback twin must trip the
    same census, proving the detector sees what the kernel deleted)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from .findings import Finding
    from .jaxpr_audit import audit_fn
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
    from deepspeed_tpu.inference import (ServingEngine, ServingConfig,
                                         Request)

    bs, H = 8, 4
    params_cache = {}

    def build(paged_impl, kv_bits=16):
        cfg = GPT2Config(vocab_size=64, max_seq=32, n_embd=32, n_layer=2,
                         n_head=H, embd_pdrop=0.0, attn_pdrop=0.0,
                         resid_pdrop=0.0, attention_impl="jnp",
                         paged_attention_impl=paged_impl)
        model = GPT2(cfg, dtype=jnp.bfloat16)
        if "p" not in params_cache:
            params_cache["p"] = model.init(jax.random.PRNGKey(0))
        return ServingEngine(
            model=model, params=params_cache["p"],
            config=ServingConfig(batch_slots=2, block_size=bs,
                                 kv_bits=kv_bits, max_new_tokens=6,
                                 preflight=False))

    findings = []
    hd = 32 // H

    # kernel decode step, 16-bit and int8 pools: clean audit + the
    # zero-gather census
    for kv_bits in (16, 8):
        srv = build("kernel", kv_bits=kv_bits)
        srv.run([Request(tokens=np.arange(5), max_new_tokens=2)])
        report = audit_fn(srv._decode, *srv._decode_args(),
                          donate_argnums=(1,), mesh=srv.engine.mesh)
        for f in report.findings:
            f.extra = dict(f.extra, audit="paged-attn", kv_bits=kv_bits)
        findings.extend(report.findings)
        jaxpr = jax.make_jaxpr(srv._decode)(*srv._decode_args())
        hits = _kv_gather_eqns(jaxpr, bs, H, hd)
        if hits:
            findings.append(Finding(
                "DSTPU206", "error",
                f"--audit-step paged-attn: the kernel decode step "
                f"(kv{kv_bits}) still materializes gathered K/V "
                f"({len(hits)} gather eqn(s), e.g. {hits[0][1]} at "
                f"{hits[0][0]}) — the in-place kernel must read pool "
                f"blocks without a dense per-slot copy",
                eqn_path="paged-attn/zero-gather"))
        srv.close()

    # detector sanity: the gather fallback MUST trip the census
    srv_g = build("gather")
    srv_g._build_decode()
    jaxpr_g = jax.make_jaxpr(srv_g._decode)(*srv_g._decode_args())
    if not _kv_gather_eqns(jaxpr_g, bs, H, hd):
        findings.append(Finding(
            "DSTPU206", "error",
            "--audit-step paged-attn: the gather-fallback twin shows NO "
            "gathered K/V materialization — the census detector is "
            "blind and the kernel's zero-gather verdict above proves "
            "nothing", eqn_path="paged-attn/census-sanity"))
    srv_g.close()
    return findings


def _audit_moe_step():
    """--audit-step moe: jaxpr-audit the quantized expert-parallel
    dispatch (docs/comms-compression.md, moe route) on a data×expert
    mesh: the compiled step must run zero host callbacks (DSTPU201)
    with every donation honored (DSTPU204), its census must move the
    dispatch/combine payload as int8 with replica groups > 1 on the
    expert phase (the two-level split), fit the engine's declared
    CommsBudget — and that budget must be TIGHT: the full-width twin's
    census has to violate it."""
    import numpy as np
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel.mesh import make_mesh
    from .findings import Finding
    from .fixtures import MoEProbeModel
    from .jaxpr_audit import audit_engine
    from .comms import wire_report, check_budget

    n = jax.device_count()
    if n < 4 or n % 2:
        return [Finding(
            "DSTPU200", "warning",
            f"--audit-step moe needs an even device count >= 4 for the "
            f"data×expert mesh (got {n}); skipped", eqn_path="moe-dispatch")]
    mesh = make_mesh({"data": 2, "expert": n // 2})
    rng = np.random.default_rng(0)
    # big enough that the expert exchange dominates the budget floors:
    # the tightness check below needs the full-width dispatch's 4x-wider
    # payload to clear the int8 ceiling by a margin, not a whisker
    dim = 128
    data = [(rng.normal(size=(dim,)).astype(np.float32),
             rng.normal(size=(dim,)).astype(np.float32)) for _ in range(512)]
    base = {"train_micro_batch_size_per_gpu": 64,
            "gradient_accumulation_steps": 1,
            "steps_per_print": 10 ** 9,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1}}

    def build(comp):
        cfg = dict(base)
        if comp:
            cfg["comms_compression"] = {
                "enabled": True, "min_tensor_bytes": 0,
                "routes": ["moe"], "moe": {"bits": 8, "block_size": 64}}
        e, _, _, _ = ds.initialize(config=cfg, model=MoEProbeModel(dim, n),
                                   training_data=data, mesh=mesh)
        e.train_batch()      # cold trace records the moe wire expectation
        return e

    findings = []
    full = build(False)
    full_census = [c for c in audit_engine(full).census if c.level == "hlo"]
    full.close()

    engine = build(True)
    if not engine._router.moe_active:
        engine.close()
        return [Finding("DSTPU200", "warning",
                        "--audit-step moe: the moe route did not activate "
                        "on this mesh", eqn_path="moe-dispatch",
                        extra={"policy": engine._router.describe()})]
    budget = engine.comms_budget()
    report = audit_engine(engine, comms_budget=budget)
    hlo = [c for c in report.census if c.level == "hlo"]
    wr = wire_report(hlo)
    quant = [c for c in hlo if c.quantized]
    if not quant:
        findings.append(Finding(
            "DSTPU200", "warning",
            "--audit-step moe: expert dispatch moved no int8 payload",
            eqn_path="moe-dispatch",
            extra={"by_kind": wr["by_kind"]}))
    if quant and not any(c.groups > 1 for c in quant):
        findings.append(Finding(
            "DSTPU200", "warning",
            "--audit-step moe: no quantized collective ran with replica "
            "groups > 1 (two-level phase missing on the data×expert mesh)",
            eqn_path="moe-dispatch",
            extra={"groups": [c.groups for c in quant]}))
    if budget is None or not check_budget(full_census, budget):
        findings.append(Finding(
            "DSTPU200", "warning",
            "--audit-step moe: the declared budget is loose — the "
            "full-width twin's census fits it",
            eqn_path="moe-dispatch",
            extra={"budget_declared": budget is not None}))
    for f in report.findings:
        f.extra = dict(f.extra, audit="moe-dispatch")
    findings.extend(report.findings)
    engine.close()
    return findings


def _audit_monitor_step(cache_dir):
    """--audit-step monitor: prove that an ARMED monitor leaves the
    compiled train step clean (docs/monitoring.md).  Twin tiny engines
    — monitor off and monitor on (jsonl+ring sinks into a tmp dir) —
    must produce byte-identical ``_train_step`` jaxprs (the PR-3
    equality gate), the armed engine's compiled step must show zero
    DSTPU201 host callbacks, and the stream it wrote must parse line by
    line under the versioned schema."""
    import shutil
    import tempfile
    import numpy as np
    import deepspeed_tpu as ds
    from deepspeed_tpu.monitor import parse_line
    from deepspeed_tpu.monitor.sinks import EVENTS_FILE
    from .findings import Finding
    from .jaxpr_audit import audit_engine, train_step_jaxpr_text

    data = (np.ones((8, 16), np.float32), np.ones((8, 16), np.float32))
    dataset = [(data[0][i], data[1][i]) for i in range(8)]
    mon_dir = tempfile.mkdtemp(prefix="dstpu-audit-mon-")
    findings = []

    def build(mon_cfg):
        cfg = {"train_micro_batch_size_per_gpu": 4,
               "gradient_accumulation_steps": 1,
               "steps_per_print": 10 ** 9,
               "bf16": {"enabled": True},
               "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
               "zero_optimization": {"stage": 2},
               "compile_cache": {"dir": cache_dir}}
        if mon_cfg:
            cfg["monitor"] = mon_cfg
        return ds.initialize(config=cfg, model=_MLP(),
                             training_data=dataset)[0]

    try:
        off = build(None)
        armed = build({"enabled": True, "dir": mon_dir,
                       "sinks": ["jsonl", "ring"], "interval": 1})

        if train_step_jaxpr_text(off) != train_step_jaxpr_text(armed):
            findings.append(Finding(
                "DSTPU201", "error",
                "--audit-step monitor: the armed monitor CHANGED the "
                "traced train step (jaxpr monitor-on != monitor-off) — "
                "instrumentation leaked into the compiled program",
                eqn_path="monitor/jaxpr-equality"))
        off.close()

        armed.train_batch()
        armed.train_batch()
        report = audit_engine(armed)
        for f in report.findings:
            f.extra = dict(f.extra, audit="monitor-armed")
        findings.extend(report.findings)
        armed.monitor.flush()
        stream = os.path.join(mon_dir, EVENTS_FILE)
        try:
            events = [parse_line(ln) for ln in open(stream)
                      if ln.strip()]
        except Exception as e:
            events = None
            findings.append(Finding(
                "DSTPU200", "warning",
                f"--audit-step monitor: event stream did not parse ({e})",
                eqn_path="monitor/stream"))
        if events is not None:
            kinds = {e.kind for e in events}
            missing = {"step", "span"} - kinds
            if missing:
                findings.append(Finding(
                    "DSTPU200", "warning",
                    f"--audit-step monitor: armed run emitted no "
                    f"{sorted(missing)} events (got {sorted(kinds)})",
                    eqn_path="monitor/stream"))
        armed.close()
    finally:
        shutil.rmtree(mon_dir, ignore_errors=True)
    return findings


def _audit_mem_step(cache_dir):
    """--audit-step mem: the memory ledger must stay host-side
    bookkeeping (docs/monitoring.md#memory-explainability).  Gates:

    - twin tiny TRAIN engines — ledger armed (``monitor.memory_interval
      = 1``) vs monitor off — produce byte-identical ``_train_step``
      jaxprs, and the armed engine's compiled step shows zero DSTPU201
      host callbacks;
    - twin SERVING engines — armed vs disarmed — produce byte-identical
      decode-step jaxprs;
    - both armed streams carry parseable schema-v3 ``mem`` events whose
      attribution names the expected subsystems (params / master /
      moments on the train side, the paged-KV pool on the serving side)
      and whose residual fields are present."""
    import shutil
    import tempfile
    import numpy as np
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.monitor import Monitor, parse_line
    from deepspeed_tpu.monitor.sinks import EVENTS_FILE
    from .findings import Finding
    from .jaxpr_audit import audit_engine, train_step_jaxpr_text

    findings = []

    def read_mems(run_dir, what):
        mems = []
        try:
            with open(os.path.join(run_dir, EVENTS_FILE)) as fh:
                for line in fh:
                    if line.strip():
                        e = parse_line(line)
                        if e.kind == "mem":
                            mems.append(e)
        except (OSError, ValueError) as e:
            findings.append(Finding(
                "DSTPU104", "error",
                f"--audit-step mem: {what} event stream did not parse "
                f"({e})", eqn_path="mem/stream"))
            return None
        if not mems:
            findings.append(Finding(
                "DSTPU104", "error",
                f"--audit-step mem: the armed {what} run emitted no "
                "`mem` events", eqn_path="mem/stream"))
        return mems

    # ---- train twin --------------------------------------------------
    data = (np.ones((8, 16), np.float32), np.ones((8, 16), np.float32))
    dataset = [(data[0][i], data[1][i]) for i in range(8)]
    mon_dir = tempfile.mkdtemp(prefix="dstpu-audit-mem-")

    def build(mon_cfg):
        cfg = {"train_micro_batch_size_per_gpu": 4,
               "gradient_accumulation_steps": 1,
               "steps_per_print": 10 ** 9,
               "bf16": {"enabled": True},
               "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
               "zero_optimization": {"stage": 2},
               "compile_cache": {"dir": cache_dir}}
        if mon_cfg:
            cfg["monitor"] = mon_cfg
        return ds.initialize(config=cfg, model=_MLP(),
                             training_data=dataset)[0]

    try:
        off = build(None)
        armed = build({"enabled": True, "dir": mon_dir,
                       "sinks": ["jsonl"], "interval": 1,
                       "memory_interval": 1})
        if train_step_jaxpr_text(off) != train_step_jaxpr_text(armed):
            findings.append(Finding(
                "DSTPU201", "error",
                "--audit-step mem: arming the memory ledger CHANGED the "
                "traced train step (jaxpr ledger-on != ledger-off) — "
                "attribution leaked into the compiled program",
                eqn_path="mem/jaxpr-equality"))
        off.close()
        armed.train_batch()
        armed.train_batch()
        report = audit_engine(armed)
        for f in report.findings:
            f.extra = dict(f.extra, audit="mem-armed")
        findings.extend(report.findings)
        armed.monitor.flush()
        mems = read_mems(mon_dir, "train")
        if mems:
            fields = mems[-1].fields
            hbm = fields.get("hbm") or {}
            missing = {"params", "master_fp32", "opt_moments"} - set(hbm)
            if missing:
                findings.append(Finding(
                    "DSTPU104", "error",
                    f"--audit-step mem: train ledger attribution is "
                    f"missing {sorted(missing)} (got {sorted(hbm)})",
                    eqn_path="mem/attribution"))
            if "host_residual_bytes" not in fields:
                findings.append(Finding(
                    "DSTPU104", "warning",
                    "--audit-step mem: no host residual in the train "
                    "ledger (host RSS unreadable?)",
                    eqn_path="mem/residual"))
        armed.close()
    finally:
        shutil.rmtree(mon_dir, ignore_errors=True)

    # ---- serving twin ------------------------------------------------
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
    from deepspeed_tpu.inference import (ServingEngine, ServingConfig,
                                         Request)
    cfg = GPT2Config(vocab_size=64, max_seq=32, n_embd=32, n_layer=2,
                     n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    model = GPT2(cfg, dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0))
    scfg = dict(batch_slots=2, block_size=8, max_new_tokens=4,
                preflight=False)

    def decode_jaxpr(srv):
        srv._build_decode()
        return str(jax.make_jaxpr(srv._decode)(*srv._decode_args()))

    clean = ServingEngine(model=model, params=params,
                          config=ServingConfig(**scfg))
    clean_jaxpr = decode_jaxpr(clean)
    clean.close()
    run_dir = tempfile.mkdtemp(prefix="dstpu-audit-mem-srv-")
    try:
        armed = ServingEngine(
            model=model, params=params,
            monitor=Monitor(run_dir=run_dir, role="serving"),
            config=ServingConfig(**scfg))
        if decode_jaxpr(armed) != clean_jaxpr:
            findings.append(Finding(
                "DSTPU201", "error",
                "--audit-step mem: arming the monitor+ledger CHANGED "
                "the traced decode step (jaxpr armed != disarmed)",
                eqn_path="mem/jaxpr-equality"))
        # enough decode steps to cross the serving ledger cadence
        armed.run([Request(tokens=np.arange(4), max_new_tokens=18,
                           uid=u) for u in range(2)])
        armed.close()
        mems = read_mems(run_dir, "serving")
        if mems:
            hbm = mems[-1].fields.get("hbm") or {}
            if "paged_kv_pool" not in hbm:
                findings.append(Finding(
                    "DSTPU104", "error",
                    f"--audit-step mem: serving ledger attribution is "
                    f"missing the paged_kv_pool (got {sorted(hbm)})",
                    eqn_path="mem/attribution"))
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    return findings


def _audit_slo_step(cache_dir):
    """--audit-step slo: the SLO engine must stay host-side stream
    consumption (docs/monitoring.md#slo-tracking).  Gates:

    - twin tiny TRAIN engines — ``monitor.slo`` armed (objectives over
      tokens/s + MFU, the training floors) vs monitor off — produce
      byte-identical ``_train_step`` jaxprs, and the armed engine's
      compiled step shows zero DSTPU201 host callbacks;
    - twin SERVING engines — armed (p99/error-rate objectives) vs
      disarmed — produce byte-identical decode-step jaxprs;
    - the armed streams parse and carry schema-v4 ``slo`` events;
    - the burn-rate semantics hold on synthetic streams: a sustained
      p99 breach trips the fast+slow alert, a single transient spike
      trips nothing."""
    import shutil
    import tempfile
    import numpy as np
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.monitor import (Event, Monitor, SLOConfig,
                                       SLOEvaluator, parse_line)
    from deepspeed_tpu.monitor.sinks import EVENTS_FILE
    from .findings import Finding
    from .jaxpr_audit import audit_engine, train_step_jaxpr_text

    findings = []

    # ---- synthetic burn-rate semantics (pure host math) --------------
    cfg = SLOConfig.from_value({
        "objectives": [{"name": "p99", "series": "latency_p99_ms",
                        "max": 500.0, "target": 0.99}],
        "fast_window": 10, "slow_window": 100,
        "fast_burn": 10.0, "slow_burn": 10.0, "sentinel": False})

    def drive(values):
        ev = SLOEvaluator(cfg)
        alerts = []
        for i, v in enumerate(values):
            for e in ev.feed(Event(kind="gauge", name="latency_p99_ms",
                                   t=float(i), step=i, value=v)):
                if e.kind == "alert" and e.fields.get("state") == "trip":
                    alerts.append(i)
        return alerts

    sustained = drive([100.0] * 50 + [900.0] * 50)
    if not sustained:
        findings.append(Finding(
            "DSTPU104", "error",
            "--audit-step slo: a sustained p99 breach did not trip the "
            "fast+slow burn-rate alert", eqn_path="slo/burn-rate"))
    transient = drive([100.0] * 50 + [900.0] + [100.0] * 100)
    if transient:
        findings.append(Finding(
            "DSTPU104", "error",
            f"--audit-step slo: a single transient spike PAGED (trips at "
            f"observations {transient}) — the slow window must absorb it",
            eqn_path="slo/burn-rate"))

    # ---- train twin --------------------------------------------------
    data = (np.ones((8, 16), np.float32), np.ones((8, 16), np.float32))
    dataset = [(data[0][i], data[1][i]) for i in range(8)]
    mon_dir = tempfile.mkdtemp(prefix="dstpu-audit-slo-")

    def build(mon_cfg):
        cfg = {"train_micro_batch_size_per_gpu": 4,
               "gradient_accumulation_steps": 1,
               "steps_per_print": 10 ** 9,
               "bf16": {"enabled": True},
               "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
               "zero_optimization": {"stage": 2},
               "compile_cache": {"dir": cache_dir}}
        if mon_cfg:
            cfg["monitor"] = mon_cfg
        return ds.initialize(config=cfg, model=_MLP(),
                             training_data=dataset)[0]

    slo_block = {"objectives": [
        {"name": "throughput", "series": "tokens_per_sec", "min": 1e-9},
        {"name": "mfu_floor", "series": "mfu", "min": 1e-12,
         "target": 0.9}]}

    def read_kinds(run_dir, what):
        try:
            with open(os.path.join(run_dir, EVENTS_FILE)) as fh:
                return {parse_line(ln).kind for ln in fh if ln.strip()}
        except (OSError, ValueError) as e:
            findings.append(Finding(
                "DSTPU104", "error",
                f"--audit-step slo: {what} event stream did not parse "
                f"({e})", eqn_path="slo/stream"))
            return None

    try:
        off = build(None)
        armed = build({"enabled": True, "dir": mon_dir,
                       "sinks": ["jsonl"], "interval": 1,
                       "slo": slo_block})
        if train_step_jaxpr_text(off) != train_step_jaxpr_text(armed):
            findings.append(Finding(
                "DSTPU201", "error",
                "--audit-step slo: arming the SLO engine CHANGED the "
                "traced train step (jaxpr slo-on != slo-off) — "
                "objective evaluation leaked into the compiled program",
                eqn_path="slo/jaxpr-equality"))
        off.close()
        armed.train_batch()
        armed.train_batch()
        report = audit_engine(armed)
        for f in report.findings:
            f.extra = dict(f.extra, audit="slo-armed")
        findings.extend(report.findings)
        armed.close()             # terminal flush emits the slo verdicts
        kinds = read_kinds(mon_dir, "train")
        if kinds is not None and "slo" not in kinds:
            findings.append(Finding(
                "DSTPU104", "error",
                f"--audit-step slo: the armed train run emitted no `slo` "
                f"events (got {sorted(kinds)})", eqn_path="slo/stream"))
    finally:
        shutil.rmtree(mon_dir, ignore_errors=True)

    # ---- serving twin ------------------------------------------------
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
    from deepspeed_tpu.inference import (ServingEngine, ServingConfig,
                                         Request)
    gcfg = GPT2Config(vocab_size=64, max_seq=32, n_embd=32, n_layer=2,
                      n_head=4, embd_pdrop=0.0, attn_pdrop=0.0,
                      resid_pdrop=0.0, attention_impl="jnp")
    model = GPT2(gcfg, dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0))
    scfg = dict(batch_slots=2, block_size=8, max_new_tokens=4,
                preflight=False)

    def decode_jaxpr(srv):
        srv._build_decode()
        return str(jax.make_jaxpr(srv._decode)(*srv._decode_args()))

    clean = ServingEngine(model=model, params=params,
                          config=ServingConfig(**scfg))
    clean_jaxpr = decode_jaxpr(clean)
    clean.close()
    run_dir = tempfile.mkdtemp(prefix="dstpu-audit-slo-srv-")
    try:
        mon = Monitor(run_dir=run_dir, role="serving",
                      slo={"objectives": [
                          {"name": "p99", "series": "latency_p99_ms",
                           "max": 1e9},
                          {"name": "errors", "series": "error_rate",
                           "max": 0.5}]})
        armed = ServingEngine(model=model, params=params, monitor=mon,
                              config=ServingConfig(**scfg))
        if decode_jaxpr(armed) != clean_jaxpr:
            findings.append(Finding(
                "DSTPU201", "error",
                "--audit-step slo: arming the monitor+SLO engine "
                "CHANGED the traced decode step (jaxpr armed != "
                "disarmed)", eqn_path="slo/jaxpr-equality"))
        armed.run([Request(tokens=np.arange(4), max_new_tokens=8,
                           uid=u) for u in range(2)])
        verdict = armed.slo_report()
        if not verdict or verdict.get("objectives_total") != 2:
            findings.append(Finding(
                "DSTPU104", "error",
                f"--audit-step slo: ServingEngine.slo_report() did not "
                f"carry the armed objectives (got {verdict})",
                eqn_path="slo/report"))
        armed.close()
        mon.close()
        kinds = read_kinds(run_dir, "serving")
        if kinds is not None and "slo" not in kinds:
            findings.append(Finding(
                "DSTPU104", "error",
                f"--audit-step slo: the armed serving run emitted no "
                f"`slo` events (got {sorted(kinds)})",
                eqn_path="slo/stream"))
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    return findings


def _audit_elastic_resume():
    """--audit-step elastic: audit the FIRST compiled step after an elastic
    reshard-on-resize (docs/elasticity.md) — a ZeRO-2 elastic engine saves
    on the full device set, a second engine auto-resumes on HALF of it, and
    the resumed engine's train step must show zero host callbacks
    (DSTPU201) and every declared donation honored on the NEW mesh
    (DSTPU204)."""
    import shutil
    import tempfile
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel.mesh import make_mesh
    from .findings import Finding
    from .jaxpr_audit import audit_engine

    # both n and n//2 must be schedulable by the fixed elastic block below
    # (micro [2,4], max 16 -> valid world sizes {1,2,4,8})
    n = jax.device_count()
    if n not in (2, 4, 8):
        return [Finding(
            "DSTPU200", "warning",
            f"--audit-step elastic needs a device count in (2,4,8) so the "
            f"built-in elastic schedule covers both the full and the "
            f"halved mesh (got {n}); skipped",
            eqn_path="elastic-resume")]

    import numpy as np
    data = (np.ones((32, 16), np.float32), np.ones((32, 16), np.float32))
    dataset = [(data[0][i], data[1][i]) for i in range(32)]
    cfg = {"steps_per_print": 10 ** 9,
           "bf16": {"enabled": True},
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": 2},
           "elasticity": {"enabled": True, "max_train_batch_size": 16,
                          "micro_batch_sizes": [2, 4], "min_gpus": 1,
                          "max_gpus": 64, "version": 0.1}}
    findings = []
    ckpt_dir = tempfile.mkdtemp(prefix="dstpu-audit-elastic-")
    try:
        a, _, _, _ = ds.initialize(config=dict(cfg), model=_MLP(),
                                   training_data=dataset,
                                   mesh=make_mesh({"data": n}))
        a.train_batch()
        a.save_checkpoint(ckpt_dir)
        a.close()

        half = n // 2
        cfg_b = dict(cfg, checkpoint={"dir": ckpt_dir, "auto_resume": True})
        b, _, _, _ = ds.initialize(
            config=cfg_b, model=_MLP(), training_data=dataset,
            mesh=make_mesh({"data": half}, devices=jax.devices()[:half]))
        if b.global_steps != 1:
            findings.append(Finding(
                "DSTPU200", "warning",
                f"--audit-step elastic: resume on {half} devices did not "
                f"restore the checkpointed step (global_steps="
                f"{b.global_steps})", eqn_path="elastic-resume"))
        report = audit_engine(b)
        for f in report.findings:
            f.extra = dict(f.extra, audit="elastic-resume",
                           from_world=n, to_world=half)
        findings.extend(report.findings)
        b.close()
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    return findings


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.analysis",
        description="jaxpr auditor + tracing-safety lint")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: the deepspeed_tpu "
                         "package)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids to run (default: all)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output on stdout")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--strict", action="store_true",
                    help="warnings also fail the run")
    ap.add_argument("--audit-step", default=None, metavar="STAGES",
                    help="also jaxpr-audit built-in tiny engines, e.g. "
                         "--audit-step 1,2,3 (compiles; needs jax). A "
                         "'q' suffix (e.g. 3q) audits the quantized-"
                         "collectives variant and additionally gates the "
                         "census against the engine's declared CommsBudget; "
                         "'decode' audits the serving layer's fused paged "
                         "decode step + generate()'s fused token scan; "
                         "'serving-resilience' audits the quarantine-"
                         "sentinel-armed serving step (zero host "
                         "callbacks, donation honored, logit_nan fault "
                         "jaxpr-identical; docs/serving.md#resilience); "
                         "'serving-lifecycle' proves the lifecycle "
                         "layers: shadow-sanitizer armed vs off jaxpr "
                         "AND token identity, every DSTPU31x violation "
                         "class caught on seeded violations, and the "
                         "full 720-ordering crash-handoff interleaving "
                         "sweep reports zero lost/duplicated uids "
                         "(docs/static-analysis.md#lifecycle); "
                         "'paged-attn' audits the in-place paged-"
                         "attention kernel decode step (zero host "
                         "callbacks, pool donation honored, NO gathered "
                         "K/V materialization in the jaxpr — census "
                         "sanity-checked against the gather fallback; "
                         "docs/serving.md); "
                         "'elastic' audits the first resharded step after "
                         "an elastic resume on half the devices "
                         "(docs/elasticity.md); 'moe' audits the quantized "
                         "expert-parallel dispatch on a data×expert mesh "
                         "(int8 on the wire, two-level replica groups, "
                         "tight budget); 'monitor' proves an ARMED "
                         "telemetry monitor leaves the compiled step "
                         "byte-identical and host-callback-free while "
                         "its JSONL stream parses (docs/monitoring.md); "
                         "'tracing' proves request-scoped tracing at "
                         "trace_sample_rate=1.0 leaves the serving "
                         "decode step jaxpr-identical (zero host "
                         "callbacks, donation honored) while emitting "
                         "parseable trace events with monotone spans "
                         "(docs/monitoring.md#request-tracing); 'mem' "
                         "proves the memory ledger leaves BOTH the "
                         "compiled train step and the serving decode "
                         "step byte-identical ledger-on vs off while "
                         "its schema-v3 `mem` events parse and name "
                         "the expected subsystems "
                         "(docs/monitoring.md#memory-explainability); "
                         "'slo' proves the SLO engine leaves BOTH "
                         "compiled steps byte-identical armed vs off, "
                         "emits parseable schema-v4 `slo` events, and "
                         "honors the multi-window burn-rate semantics "
                         "on synthetic streams (sustained breach trips, "
                         "transient spike does not; "
                         "docs/monitoring.md#slo-tracking)")
    args = ap.parse_args(argv)

    # findings are the stdout payload (the tier-1 gate parses --json);
    # engine/mesh INFO chatter must not interleave
    from ..utils.logging import route_logs_to_stderr
    route_logs_to_stderr()

    rules = select_rules(args.rules.split(",") if args.rules else None)
    if args.list_rules:
        for rule in sorted(rules, key=lambda r: r.id):
            print(f"{rule.id}  {rule.name:28s} [{rule.severity}] "
                  f"{rule.description}")
        return 0

    paths = args.paths or _default_paths()
    root = os.getcwd()
    findings = lint_paths(paths, rules=rules, root=root)
    if args.audit_step:
        stages = [s.strip() for s in args.audit_step.split(",")]
        findings.extend(_audit_builtin_steps(stages))

    counts = counts_by_severity(findings)
    failing = counts["error"] + (counts["warning"] if args.strict else 0)
    if args.as_json:
        print(json.dumps({"version": 1,
                          "rules": sorted(r.id for r in rules),
                          "findings": [f.to_dict() for f in findings],
                          "counts": counts,
                          "ok": failing == 0}))
    else:
        for f in findings:
            print(str(f))
        total = len(findings)
        print(f"{total} finding(s): " +
              ", ".join(f"{counts[s]} {s}" for s in ("error", "warning",
                                                     "info")))
    return 1 if failing else 0


if __name__ == "__main__":
    sys.exit(main())
