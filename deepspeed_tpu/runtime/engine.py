"""DeepSpeedEngine: the train-loop wrapper, re-designed TPU-native.

Parity: reference ``deepspeed/runtime/engine.py:168`` (``DeepSpeedEngine``).
The reference wraps a torch ``nn.Module`` and exposes imperative
``forward/backward/step``; behavior (ZeRO stage, precision, optimizer,
schedule) is driven by the JSON config.  This engine keeps the config surface
and the API names, but the hot path is ONE jitted SPMD train step:

  - grad accumulation  = ``lax.scan`` over the microbatch axis
    (reference: per-micro-batch backward + bucketed hook reduction,
    ``engine.py:1684``)
  - DP grad averaging  = mean over the globally-sharded batch; XLA inserts the
    all-reduce (reference ``allreduce_gradients`` ``engine.py:1663``)
  - ZeRO 1/2/3         = sharding placement of master/opt/grads/params over
    the ``fsdp`` mesh axis (see ``runtime/zero/partition.py``)
  - fp16 loss scaling  = branchless skip-step with on-device scaler state
    (reference ``_take_model_step`` overflow path, ``engine.py:1819-1871``)
  - checkpoint save/load with the reference's directory layout
    (``engine.py:2797 save_checkpoint``, ``:2467 load_checkpoint``)

The imperative ``forward()/backward()/step()`` trio is provided as a
compatibility shim that stages microbatches and executes the fused step at the
gradient-accumulation boundary.
"""

import json
import os
import time
from contextlib import contextmanager
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .config import DeepSpeedConfig
from . import constants as C
from . import health as hmod
from .fp16 import loss_scaler as ls
from .lr_schedules import get_lr_scheduler
from .dataloader import DeepSpeedDataLoader, RepeatingLoader
from .utils import (DummyOptim, clip_by_global_norm, global_norm, tree_cast,
                    see_memory_usage)
from .zero import partition as zpart
from ..ops.adam.fused_adam import FusedAdam, FusedAdamW
from ..ops.lamb.fused_lamb import FusedLamb
from ..parallel import mesh as M
from ..utils.logging import logger, log_dist
from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer

from ..checkpoint.constants import MODEL_FILE, OPTIM_FILE


class TrainState(NamedTuple):
    """Device-resident training state (one pytree, donated each step)."""
    global_steps: jnp.ndarray      # i32 — optimizer boundaries seen (incl. skipped)
    optimizer_steps: jnp.ndarray   # i32 — actual optimizer steps (Adam bias corr.)
    skipped_steps: jnp.ndarray     # i32 — overflow/health-skipped steps
    params: Any                    # compute-dtype params (sharded per ZeRO stage)
    master: Any                    # fp32 master params (None when training fp32)
    opt_state: Any
    scale: Any                     # LossScaleState (None unless fp16)
    health: Any = None             # health.HealthState (None when guardian off)
    comm_error: Any = None         # qgZ per-shard error feedback (None unless
    #                                comms_compression grads route is active)


def _resolve_model(model, loss_fn, params, apply_fn, rng_seed,
                   init_on_host=False):
    """Accept either a model object (``.init``/``.loss``[/``.apply``]) or an
    explicit (loss_fn, params) pair."""
    tp_specs = None
    if model is not None:
        if loss_fn is None:
            assert hasattr(model, "loss"), \
                "model must expose .loss(params, batch, rng) or pass loss_fn="
            loss_fn = model.loss
        if params is None:
            assert hasattr(model, "init"), "model must expose .init(rng) -> params"
            # jit the WHOLE init: eager per-leaf RNG ops are one device
            # dispatch each, vs one compile + one dispatch jitted.
            # init_on_host (offload): create params on the HOST CPU backend —
            # the fp32 master then builds from local memory (no multi-GB d2h)
            # and only the 16-bit image crosses to the device.
            trace_errors = (jax.errors.TracerArrayConversionError,
                            jax.errors.TracerBoolConversionError,
                            jax.errors.TracerIntegerConversionError,
                            jax.errors.ConcretizationTypeError,
                            jax.errors.UnexpectedTracerError)
            try:
                if init_on_host:
                    with jax.default_device(jax.devices("cpu")[0]):
                        params = jax.jit(model.init)(
                            jax.random.PRNGKey(rng_seed))
                else:
                    params = jax.jit(model.init)(jax.random.PRNGKey(rng_seed))
            except trace_errors:
                # init closures that resist tracing (python-side state):
                # fall back to eager — but KEEP the host placement, or an
                # offload-sized model's init lands on (and OOMs) the device.
                # Any other error propagates; swallowing it here used to
                # hide real init bugs behind a minutes-slow eager retry.
                logger.warning("model.init does not trace (python-side "
                               "state?); falling back to eager init")
                if init_on_host:
                    with jax.default_device(jax.devices("cpu")[0]):
                        params = model.init(jax.random.PRNGKey(rng_seed))
                else:
                    params = model.init(jax.random.PRNGKey(rng_seed))
        if apply_fn is None and hasattr(model, "apply"):
            apply_fn = model.apply
        tp_specs = getattr(model, "partition_specs", None)
        if callable(tp_specs):
            tp_specs = tp_specs(params)
    assert loss_fn is not None and params is not None, \
        "Provide either model= (with .init/.loss) or loss_fn= and params="
    return loss_fn, params, apply_fn, tp_specs


class DeepSpeedEngine:
    """Config-driven training engine over a jitted SPMD step."""

    # The fused SPMD step's ZeRO wire routes through the collective
    # router (qwZ/qgZ); PipelineEngine schedules its own collectives and
    # opts out (the pipe route is accepted-but-full-width for now).
    _supports_comms_compression = True

    def __init__(self, model=None, optimizer=None, config=None, config_params=None,
                 training_data=None, lr_scheduler=None, mesh=None, collate_fn=None,
                 loss_fn=None, params=None, apply_fn=None, rng_seed=0, mpu=None,
                 dist_init_required=None, dont_change_device=False, elastic=None,
                 monitor=None):
        config = config if config is not None else config_params
        assert config is not None, "DeepSpeed requires --deepspeed_config to specify configuration file"

        # ---- mesh first (config batch math needs dp world size) ----------
        if mesh is None:
            from .config_utils import load_config_dict
            raw = load_config_dict(config)
            mesh = M.make_mesh(raw.get(C.MESH, {}).get("axes", None))
            config = raw
        self.mesh = mesh
        self.mesh_ctx = M.MeshContext(mesh)
        self.config = DeepSpeedConfig(config, world_size=self.mesh_ctx.dp_world_size,
                                      elastic=elastic)

        # ---- unified runtime telemetry (monitor/; docs/monitoring.md) -----
        # Event bus + monitor-side spans/gauges/counters.  The `monitor`
        # kwarg outranks env DSTPU_MONITOR outranks the config block
        # (the --elastic/--health-check precedence pattern).  All
        # instrumentation is host-side: an armed monitor leaves the
        # compiled step byte-identical (--audit-step monitor).
        from ..monitor import core as moncore
        self.monitor = moncore.from_config(
            self.config.monitor_config, override_enabled=monitor,
            retry=self.config.io_retry_config.policy(), role="train")
        if not self.monitor.armed and self.config.wall_clock_breakdown:
            # wall_clock_breakdown alone still needs measured spans: arm a
            # bus-less monitor (no sinks, nothing written) so the span
            # recorder feeds the named-timer breakdown log
            self.monitor = moncore.Monitor(run_dir=None, sinks=())
        from ..monitor import spans as monspans
        self._spans = monspans.recorder()  # records armed or not
        self._startup_line_due = True
        self._mon_tokens_per_step = None   # lazy: first stacked batch
        self._mon_step_stats = None        # lazy: per-program flops/wire
        self._mon_example = None           # (batch, rng) for one-time pricing

        self.zero_stage = self.config.zero_optimization_stage
        self.compute_dtype = {"bfloat16": jnp.bfloat16, "float16": jnp.float16,
                              "float32": jnp.float32}[self.config.precision_dtype]
        self.fp16_enabled = self.config.fp16.enabled
        self.bfloat16_enabled = self.config.bf16.enabled

        # ---- training health guardian (runtime/health.py) ----------------
        # On-device divergence sentinels + branchless skip-step for EVERY
        # precision (the fp16 scaler covers only fp16 overflow; a NaN/Inf
        # gradient under bf16 — the TPU default — would otherwise be
        # written irrecoverably into params), plus the host-side
        # skip -> rewind -> abort escalation ladder.
        self._health_cfg = self.config.health_check
        self._health_enabled = self._health_cfg.enabled
        self.health_monitor = (
            hmod.HealthMonitor(self._health_cfg,
                               bus=(self.monitor.bus if self.monitor.armed
                                    else None))
            if self._health_enabled else None)
        self._stream_step = 0        # monotonic data-stream batch index
        self._last_batch_index = None  # stream index of the running step
        # True while _stream_step and the live iterator agree (fresh engine,
        # or a load that restored the loader state); loading a pre-guardian
        # checkpoint loses the correspondence and disables fast-forward
        self._stream_pos_known = True
        self._ff_stride = 1          # same-episode rewind fast-forward stride
        self._last_ckpt_dir = self.config.checkpoint_config.dir

        # ---- persistent compiled-step cache (runtime/compile_cache.py) ----
        # AOT warm-start: every jitted entry point below dispatches through
        # a CachedStep, so a process restart (benchmark run, CI worker,
        # auto-resume, rewind-and-replay) deserializes yesterday's
        # executable instead of re-paying ~50s of XLA compilation.
        from . import compile_cache as ccache
        self.compile_cache = ccache.from_config(
            self.config.compile_cache_config)
        self._cc_key_slice = self._cache_key_slice()

        # ---- model ---------------------------------------------------------
        self.module = model
        if (self.zero_stage >= 3 and self.mesh_ctx.fsdp_size > 1
                and getattr(getattr(model, "config", None),
                            "unroll_layers", False)):
            log_dist(
                "unroll_layers with ZeRO-3 nearly doubles live memory: the "
                "unrolled program gathers layers less incrementally than the "
                "scanned one (measured 1.8x temp bytes on the fsdp mesh). "
                "Prefer the scanned layer loop (unroll_layers=False) at "
                "stage 3.", ranks=[0])
        # mirrors the _offload construction condition below: an eval-only
        # engine (DummyOptim) or a client-object optimizer never builds the
        # host tier, so its params must NOT be committed to the CPU backend
        offload_wanted = (self.config.zero_config.offload_optimizer_device()
                          in ("cpu", "nvme")
                          and optimizer is None
                          and self.config.optimizer_name is not None)
        # ---- ZeRO-3 parameter offload (streamed layer blocks) ------------
        # reference: stage3.py:656 _configure_offloading + the param tier of
        # swap_tensor/ — params live on host/NVMe, so offload_param REQUIRES
        # the host optimizer tier (there is nowhere on-device to keep a
        # master) and the decomposed-forward contract from the model.
        param_stream_wanted = (
            self.config.zero_config.offload_param_device() in ("cpu", "nvme"))
        if param_stream_wanted:
            if self.zero_stage != 3:
                raise ValueError(
                    "zero_optimization.offload_param requires stage 3 "
                    f"(got stage {self.zero_stage})")
            if not offload_wanted:
                raise ValueError(
                    "offload_param requires offload_optimizer (cpu or nvme) "
                    "with a config-specified Adam/AdamW: streamed parameters "
                    "have no device-resident master for an in-device "
                    "optimizer to update")
            if self.fp16_enabled:
                raise ValueError(
                    "offload_param does not support fp16 dynamic loss "
                    "scaling; use bf16 (TPU-native) or fp32")
            if not callable(getattr(model, "stream_fns", None)):
                raise ValueError(
                    "offload_param needs a model exposing stream_fns() "
                    "(decomposed embed/block/head forward) — GPT2 and "
                    "compatible families provide it")
            if self.mesh.size > 1:
                raise ValueError(
                    "offload_param streaming is single-chip scale-up "
                    "machinery; on a multi-chip mesh use ZeRO-3 sharding "
                    "(params shard over the fsdp axis) without offload_param")
        self._param_stream = None
        if param_stream_wanted and params is None and \
                self.config.zero_config.offload_param.fast_init:
            # host numpy init: the jitted XLA-CPU init costs minutes and
            # ~3x the tree in transient RAM at multi-billion params
            if not callable(getattr(model, "init_numpy", None)):
                raise ValueError(
                    "offload_param.fast_init requires the model to expose "
                    "init_numpy(seed) (a host-RAM init twin)")
            params = model.init_numpy(rng_seed)
        with self._spans.setup_span("setup.params_init"):
            (self._loss_fn, params0, self._apply_fn,
             self._tp_specs) = _resolve_model(
                model, loss_fn, params, apply_fn, rng_seed,
                init_on_host=offload_wanted)
            # one jitted cast, not one dispatch per leaf; under offload the
            # cast runs ON THE HOST backend — the default-device jit would
            # silently haul the tree to the accelerator
            f32 = lambda t: tree_cast(t, jnp.float32)
            if all(np.dtype(l.dtype) == np.float32
                   for l in jax.tree_util.tree_leaves(params0)):
                pass  # already fp32: skip the cast (a copy of the whole
                # tree — prohibitive transient RAM at beyond-HBM param counts)
            elif offload_wanted:
                with jax.default_device(jax.devices("cpu")[0]):
                    params0 = jax.jit(f32)(params0)
            else:
                params0 = jax.jit(f32)(params0)

        # ---- quantized-collectives router (runtime/comm/) ----------------
        # Per-route wire policy: qwZ int8 param gathers, qgZ error-fed
        # int8 grad reduction, 1-bit optimizer transport.  Default-off
        # policy => the router degrades to plain sharding constraints.
        from .comm.collective_router import CollectiveRouter
        self._router = CollectiveRouter(
            self.config.comms_compression, self.mesh, self.mesh_ctx,
            self.zero_stage,
            supports_zero_routes=self._supports_comms_compression)
        self._onebit_transport = None
        # quantized expert-parallel dispatch (moe route): the wire is
        # process-global so moe/layer.py finds it at trace time; install
        # it now AND before every step dispatch (_install_moe_wire) so a
        # retrace under THIS engine never sees another engine's policy
        self._moe_wire = self._router.moe_wire()
        self._install_moe_wire()
        if self._router.weights_active or self._router.grads_active \
                or self._router.moe_active:
            log_dist("comms_compression active: "
                     f"{self._router.describe()}", ranks=[0])

        # ---- optimizer -----------------------------------------------------
        self.optimizer = self._configure_optimizer(optimizer)
        # ---- lr scheduler --------------------------------------------------
        self.lr_scheduler = self._configure_lr_scheduler(lr_scheduler)

        # ---- shardings (ZeRO stages as placement; partition.py) -----------
        fsdp = self.mesh_ctx.fsdp_size
        self._param_specs = zpart.param_specs(
            params0, self.zero_stage, fsdp,
            persistence_threshold=self.config.zero_config.param_persistence_threshold,
            tp_specs=self._tp_specs)
        self._master_specs = zpart.master_specs(params0, self.zero_stage, fsdp,
                                                tp_specs=self._tp_specs)
        self._grad_specs = zpart.grad_specs(params0, self.zero_stage, fsdp,
                                            tp_specs=self._tp_specs)
        self._param_sh = zpart.to_named(self._param_specs, self.mesh)
        self._master_sh = zpart.to_named(self._master_specs, self.mesh)
        self._repl_sh = NamedSharding(self.mesh, P())

        # shape → master spec map: optimizer-state leaves that are param-shaped
        # (Adam moments etc.) inherit the master sharding.
        self._shape_spec_cache = {}
        for p, sp in zip(jax.tree_util.tree_leaves(params0),
                         jax.tree_util.tree_leaves(
                             self._master_specs, is_leaf=lambda x: isinstance(x, P))):
            self._shape_spec_cache.setdefault(np.shape(p), sp)

        # ---- host offload tier (ZeRO-Offload / -Infinity optimizer) -------
        # reference: stage_1_and_2.py cpu_offload path + stage3 swap tier
        self._offload = None
        offload_device = self.config.zero_config.offload_optimizer_device()
        if offload_device in ("cpu", "nvme") and not isinstance(self.optimizer, DummyOptim):
            if optimizer is not None:
                raise ValueError(
                    "offload_optimizer requires a config-specified Adam/AdamW "
                    "(the host tier runs its own fused step; a client "
                    "optimizer object cannot be offloaded)")
            name = self.config.optimizer_name or C.ADAM_OPTIMIZER
            assert name in (C.ADAM_OPTIMIZER, C.ADAMW_OPTIMIZER), \
                f"offload_optimizer requires Adam/AdamW (got {name!r}; " \
                "reference parity: DeepSpeedCPUAdam)"
            from .zero.offload_engine import HostOffloadOptimizer
            if param_stream_wanted:
                # layer-major flat layout: each streamed layer is one
                # contiguous host segment (zero-copy h2d views, contiguous
                # grad landing).  consume_params frees the init tree leaf
                # by leaf — at beyond-HBM scale the init tree, master and
                # moments cannot coexist in host RAM.
                from .zero import param_stream as ps
                stacked_key = model.stream_fns()["stacked_key"]
                stream_tree = ps.to_stream_tree(params0, stacked_key)
                # the per-layer slices copied the stacked leaves — free the
                # stacks now (nonblock leaves are SHARED with the stream
                # tree and get consumed by the host optimizer build)
                for leaf in jax.tree_util.tree_leaves(params0[stacked_key]):
                    if hasattr(leaf, "delete"):
                        leaf.delete()
                params0 = None
                self._offload = HostOffloadOptimizer(
                    stream_tree, self.config.zero_config,
                    self.config.aio_config, optimizer_name=name,
                    optimizer_params=self.config.optimizer_params,
                    compute_dtype_name=self.config.precision_dtype,
                    consume_params=True,
                    payload_in_ram=(self.config.zero_config
                                    .offload_param_device() == "cpu"),
                    retry=self.config.io_retry_config.policy())
                del stream_tree
                # init tree freed — NOW allocate grad buffer + RAM image
                self._offload.alloc_buffers()
                self._param_stream = ps.ParamStreamRunner(
                    model, self._offload, self.mesh, self.compute_dtype,
                    gas=self.config.gradient_accumulation_steps,
                    grad_clip=self.config.gradient_clipping,
                    zero_config=self.config.zero_config,
                    aio_config=self.config.aio_config,
                    retry=self.config.io_retry_config.policy(),
                    skip_nonfinite=(self._health_enabled
                                    and self._health_cfg.skip_nonfinite),
                    spike=((self._health_cfg.spike_window,
                            self._health_cfg.spike_zmax,
                            self._health_cfg.skip_on_spike)
                           if self._health_enabled else None),
                    compile_cache=self.compile_cache,
                    cache_key_extra=self._cc_key_slice,
                    comms_compression=self.config.comms_compression)
            else:
                self._offload = HostOffloadOptimizer(
                    params0, self.config.zero_config, self.config.aio_config,
                    optimizer_name=name,
                    optimizer_params=self.config.optimizer_params,
                    compute_dtype_name=self.config.precision_dtype,
                    retry=self.config.io_retry_config.policy())
        # one-step delayed parameter update (ZeRO-Offload DPU): device step
        # k+1 overlaps the host optimizer+transfers for step k
        off_cfg = self.config.zero_config.offload_optimizer
        self._dpu = (self._offload is not None and off_cfg is not None
                     and off_cfg.delayed_param_update)
        self._dpu_warmup = (off_cfg.delayed_param_update_warmup
                            if self._dpu else 0)
        self._pending_offload = None   # (grads, metrics) awaiting host apply
        self._pending_row_drop_checks = []   # device drop counters, read on
        # reporting steps only (no per-step host sync)
        self._jit_scatter_params = None   # flat h2d → param tree (lazy)
        self._scatter_nchunks = 0
        from .zero.wire import H2DUploader
        self._h2d = H2DUploader()

        # ---- sparse embedding gradients (reference engine.py:2227
        # sparse_allreduce_no_retain) -----------------------------------------
        # In-SPMD, gradient reduction is XLA's (sharding constraints), so the
        # wire where sparsity pays is the offload d2h transfer: declared
        # embedding leaves cross as (row indices, row values) instead of the
        # dense (vocab, dim) tensor.  Opt-in via the model's
        # ``sparse_grad_paths()`` — correctness requires the leaf to be used
        # ONLY as a lookup table (a tied LM head makes its grad dense).
        self._sparse_grad_paths = ()
        if self.config.sparse_gradients_enabled:
            declared = getattr(self.module, "sparse_grad_paths", None)
            if callable(declared):
                self._sparse_grad_paths = tuple(tuple(p) for p in declared())
            if not self._sparse_grad_paths:
                log_dist("sparse_gradients enabled but the model declares no "
                         "sparse_grad_paths(); gradients stay dense", ranks=[0])
            elif self._offload is None:
                log_dist("sparse_gradients: in-SPMD reduction is handled by "
                         "XLA sharding; the sparse wire format applies to the "
                         "offload d2h path only", ranks=[0])

        # ---- initial device state -----------------------------------------
        with self._spans.setup_span("setup.state_place") as placed:
            self.state = self._init_state(params0)
            placed.attrs = {"bytes": sum(
                int(getattr(leaf, "nbytes", 0))
                for leaf in jax.tree_util.tree_leaves(self.state))}
        self._needs_master = self.compute_dtype != jnp.float32

        # ---- data ----------------------------------------------------------
        self.training_dataloader = None
        self._data_iterator = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data,
                                                         collate_fn=collate_fn)
            self._data_iterator = iter(RepeatingLoader(self.training_dataloader))

        # ---- compiled steps -------------------------------------------------
        # CachedStep wrappers: call-compatible with the jitted functions
        # (donation, .lower for the auditor/profiler) but warm-startable
        # from the persistent compile cache
        self._jit_train_step = self._wrap_step(
            "train_step", self._train_step, donate_argnums=(0,),
            describe=self._describe_step)
        self._jit_grad_step = self._wrap_step(
            "grad_only_step", self._grad_only_step,
            describe=self._describe_step)
        self._jit_eval = None

        # ---- curriculum learning / PLD ------------------------------------
        # (reference: engine injects curriculum_seqlen, engine.py:1596-1602;
        # PLD theta passed into model fwd, progressive_layer_drop.py)
        self.curriculum_scheduler = None
        if self.config.curriculum.enabled:
            from .data_pipeline.curriculum_scheduler import CurriculumScheduler
            self.curriculum_scheduler = CurriculumScheduler(self.config.curriculum.params)
        self.progressive_layer_drop = None
        if self.config.progressive_layer_drop.enabled:
            from .progressive_layer_drop import ProgressiveLayerDrop
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=self.config.progressive_layer_drop.theta,
                gamma=self.config.progressive_layer_drop.gamma)

        # ---- misc parity state ---------------------------------------------
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size(),
            steps_per_output=self.config.steps_per_print,
            bus=self.monitor.bus if self.monitor.armed else None)
        self.micro_steps = 0
        self._global_steps_host = 0
        self._base_rng = jax.random.PRNGKey(rng_seed)
        self._pending_microbatches = []   # forward/backward/step shim buffer
        self._last_metrics = {}
        self.loaded_checkpoint_tag = None
        self.global_samples = 0
        if self.config.tensorboard.enabled:
            self._setup_tensorboard()
        # ---- memory ledger (monitor/memory_ledger.py) ---------------------
        # Host RSS HWM bracketed per wall-clock phase (init /
        # first-compile / steady-step) + periodic `mem` events; the
        # attribution is host-side reads only — the compiled step is
        # byte-identical ledger-on vs off (--audit-step mem).
        from ..monitor import memory_ledger as mled
        self._rss_phases = mled.RssPhases()
        self._rss_phases.mark(mled.PHASE_INIT)
        self._mem_interval = self.config.monitor_config.memory_interval
        self._oom_dumped = False
        if self.config.memory_breakdown:
            see_memory_usage("Engine initialized", force=True,
                             bus=self.monitor.bus if self.monitor.armed
                             else None)
        if self.config.prescale_gradients or \
                self.config.gradient_predivide_factor != 1.0:
            # reference: sum-allreduce with pre/post division to control
            # overflow (engine.py allreduce_gradients). Here the loss is a
            # mean over the GLOBAL batch, so XLA's reduction is already the
            # average — prescaling is implicit and numerically equivalent.
            log_dist("prescale_gradients/gradient_predivide_factor: XLA "
                     "mean-reduction already averages gradients; keys accepted "
                     "as no-ops", ranks=[0])
        log_dist(f"DeepSpeedEngine ready: zero_stage={self.zero_stage} "
                 f"dtype={self.config.precision_dtype} mesh={dict(self.mesh.shape)} "
                 f"micro_batch={self.train_micro_batch_size_per_gpu()} "
                 f"gas={self.gradient_accumulation_steps()}", ranks=[0])

    # ------------------------------------------------------------------ config
    def _configure_optimizer(self, client_optimizer):
        """Parity: reference ``engine.py:1079 _configure_optimizer`` /
        ``:1153 _configure_basic_optimizer`` (config name → optimizer)."""
        if client_optimizer is not None:
            assert hasattr(client_optimizer, "init") and hasattr(client_optimizer, "update"), \
                "client optimizer must expose .init(params) and .update(...)"
            if hasattr(client_optimizer, "set_world_size"):
                client_optimizer.set_world_size(self.mesh_ctx.dp_world_size)
            return client_optimizer
        name = self.config.optimizer_name
        if name is None:
            return DummyOptim()
        p = dict(self.config.optimizer_params or {})
        p.pop("torch_adam", None)  # accepted in reference configs; no-op here
        if name == C.ADAMW_OPTIMIZER:
            p.pop("adam_w_mode", None)  # implied by the optimizer type
        if name in (C.ADAM_OPTIMIZER,):
            opt = FusedAdam(**p)
        elif name == C.ADAMW_OPTIMIZER:
            opt = FusedAdamW(**p)
        elif name == C.LAMB_OPTIMIZER:
            opt = FusedLamb(**p)
        elif name == C.ONEBIT_ADAM_OPTIMIZER:
            from .fp16.onebit.adam import OnebitAdam
            opt = OnebitAdam(**p)
            # route the 1-bit compressed allreduce over the REAL dp mesh
            # axis (per-rank error feedback inside shard_map) — without
            # this, compressed_allreduce runs in its degenerate local
            # mode and is dead code from the engine's perspective
            self._onebit_transport = self._router.onebit_comm()
            if self._onebit_transport is not None:
                opt.set_comm(self._onebit_transport)
        elif name == C.ONEBIT_LAMB_OPTIMIZER:
            from .fp16.onebit.lamb import OnebitLamb
            opt = OnebitLamb(**p)
        elif name == C.ZERO_ONE_ADAM_OPTIMIZER:
            from .fp16.onebit.zoadam import ZeroOneAdam
            opt = ZeroOneAdam(**p)
        elif name == C.ADAGRAD_OPTIMIZER:
            from ..ops.adagrad.cpu_adagrad import DeepSpeedCPUAdagrad
            opt = DeepSpeedCPUAdagrad(**p)
        elif name == C.SGD_OPTIMIZER:
            from ..ops.sgd import SGD
            opt = SGD(**p)
        else:
            raise ValueError(f"Unknown optimizer type {name!r}")
        if hasattr(opt, "set_world_size"):
            opt.set_world_size(self.mesh_ctx.dp_world_size)
        return opt

    def _configure_lr_scheduler(self, client_scheduler):
        """Parity: reference ``engine.py:780``."""
        if client_scheduler is not None:
            return client_scheduler
        if self.config.scheduler_name is not None:
            return get_lr_scheduler(self.config.scheduler_name,
                                    self.config.scheduler_params,
                                    optimizer=self.optimizer)
        return None

    def _lr_at(self, step):
        """Traced lr as a function of the global step counter."""
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "lr_fn"):
            return self.lr_scheduler.lr_fn(step)
        return jnp.asarray(getattr(self.optimizer, "lr", 0.0), jnp.float32)

    # ------------------------------------------------------------------- state
    def _init_state(self, params0):
        dtype = self.compute_dtype
        needs_master = dtype != jnp.float32

        if self._param_stream is not None:
            # streamed params: nothing model-sized lives on the device;
            # the runner owns the nonblock tree and the host owns the rest.
            # Health sentinels for this path are host-side (the runner's
            # metrics are host values already), so no device HealthState.
            self._scaler = None       # fp16 rejected for streamed mode
            z = lambda: jax.device_put(jnp.asarray(0, jnp.int32),
                                       self._repl_sh)
            return TrainState(global_steps=z(), optimizer_steps=z(),
                              skipped_steps=z(), params=None, master=None,
                              opt_state=None, scale=None, health=None)

        # one jitted cast: in the offload path ON THE HOST backend (only the
        # 16-bit image then crosses the wire, placed in a second step);
        # otherwise fused straight into the target sharding
        if self._offload is not None:
            with jax.default_device(jax.devices("cpu")[0]):
                p16 = jax.jit(lambda t: tree_cast(t, dtype))(params0)
            params = jax.device_put(p16, self._param_sh)
        else:
            params = jax.jit(lambda t: tree_cast(t, dtype),
                             out_shardings=self._param_sh)(params0)

        if self._offload is not None:
            # fp32 master + optimizer state live on the HOST (or NVMe); the
            # device holds only the compute-dtype params
            scale = None
            if self.fp16_enabled:
                scaler = ls.create_loss_scaler(self.config.fp16)
                self._scaler = scaler
                scale = jax.device_put(scaler.state, self._repl_sh)
            else:
                self._scaler = None
            z = lambda: jax.device_put(jnp.asarray(0, jnp.int32), self._repl_sh)
            return TrainState(global_steps=z(), optimizer_steps=z(),
                              skipped_steps=z(), params=params, master=None,
                              opt_state=None, scale=scale,
                              health=self._init_health_device(),
                              comm_error=self._init_comm_error(params))

        master = jax.device_put(params0, self._master_sh) if needs_master else None

        # opt state created under jit with its shardings DECLARED, so it
        # materializes directly sharded: moments are zeros with no data
        # dependence on the sharded base, and left to propagation they
        # come out replicated — every device holding the whole of both
        # moments is what a model sized for the mesh cannot afford
        base = master if needs_master else params

        def mk_opt(p):
            return self.optimizer.init(p)
        opt_state = jax.jit(mk_opt, out_shardings=self._opt_shardings(
            jax.eval_shape(mk_opt, base)))(base)

        scale = None
        if self.fp16_enabled:
            scaler = ls.create_loss_scaler(self.config.fp16)
            self._scaler = scaler
            scale = jax.device_put(scaler.state, self._repl_sh)
        else:
            self._scaler = None

        z = lambda: jax.device_put(jnp.asarray(0, jnp.int32), self._repl_sh)
        return TrainState(global_steps=z(), optimizer_steps=z(), skipped_steps=z(),
                          params=params, master=master, opt_state=opt_state,
                          scale=scale, health=self._init_health_device(),
                          comm_error=self._init_comm_error(base))

    def _init_health_device(self):
        """Fresh (replicated) device HealthState, or None when the guardian
        is off.  Also the post-load reset: a restored run must not inherit
        the EMA statistics of the poisoned steps it just discarded."""
        if not self._health_enabled:
            return None
        return jax.device_put(hmod.init_state(), self._repl_sh)

    def _init_comm_error(self, base_like):
        """Fresh qgZ error-feedback state (``TrainState.comm_error``), or
        None when the grads compression route is inactive."""
        if base_like is None or not self._router.grads_active:
            return None
        return self._router.init_error_feedback(base_like, self._grad_specs)

    def _opt_shardings(self, opt_state):
        """Optimizer-state leaves that are param-shaped inherit the master
        sharding; anything else (scalars, counters) is replicated.  The
        1-bit transport's per-rank error buffers (leading ``(D, ...)``
        axis) shard over the dp axis — replicating them would cost a
        world-size multiple of the padded model."""
        onebit_fields = ("worker_error", "server_error")
        axis = (self._onebit_transport.axis
                if self._onebit_transport is not None else None)

        def sh_for(path, leaf):
            if axis is not None and any(
                    getattr(e, "name", getattr(e, "key", None))
                    in onebit_fields for e in path):
                return NamedSharding(self.mesh, P(axis))
            # leaves may be abstract (eval_shape) — read .shape, not data
            spec = self._shape_spec_cache.get(
                tuple(getattr(leaf, "shape", ())))
            return NamedSharding(self.mesh, spec if spec is not None else P())
        return jax.tree_util.tree_map_with_path(sh_for, opt_state)

    # ----------------------------------------------------- compile cache/AOT
    def _cache_key_slice(self):
        """The config slice of the compile-cache key: everything OUTSIDE
        the traced program that legally invalidates an executable (the
        lowering hash covers the program itself — docs/compile-cache.md)."""
        cfg = self.config
        h = self._health_cfg
        return {
            "engine": type(self).__name__,
            "zero_stage": self.zero_stage,
            "dtype": cfg.precision_dtype,
            "gas": cfg.gradient_accumulation_steps,
            "grad_accum_dtype": cfg.grad_accum_dtype,
            "gradient_clipping": cfg.gradient_clipping,
            "mesh": dict(self.mesh.shape),
            "fp16": ({"initial_scale_power": cfg.fp16.initial_scale_power,
                      "loss_scale": cfg.fp16.loss_scale,
                      "loss_scale_window": cfg.fp16.loss_scale_window,
                      "hysteresis": cfg.fp16.hysteresis,
                      "min_loss_scale": cfg.fp16.min_loss_scale}
                     if self.fp16_enabled else None),
            "health": {"enabled": h.enabled,
                       "skip_nonfinite": h.skip_nonfinite,
                       "spike_window": h.spike_window,
                       "spike_zmax": h.spike_zmax,
                       "skip_on_spike": h.skip_on_spike},
            "offload_optimizer": cfg.zero_config.offload_optimizer_device(),
            "offload_param": cfg.zero_config.offload_param_device(),
            "sparse_gradients": cfg.sparse_gradients_enabled,
            # the wire policy changes the traced program (quantize ops,
            # partial-grad layout) — part of the executable's identity
            "comms_compression": cfg.comms_compression.describe(),
        }

    def _wrap_step(self, name, fn, donate_argnums=(), describe=None):
        """jit + CachedStep: the engine's dispatch path for a compiled
        entry point (AOT warm-start when the compile cache is on)."""
        from . import compile_cache as ccache
        return ccache.wrap_step(
            f"{type(self).__name__}.{name}", fn,
            cache=self.compile_cache, key_extra=self._cc_key_slice,
            donate_argnums=donate_argnums, describe=describe)

    def _describe_step(self, exe, args):
        """Two censuses of a compiled step's HLO, each entry times the
        trip counts of the loops it sits in.  What one call moves between
        devices (``analysis/comms.step_collectives``), with the bytes of
        collectives whose payload has no parameter's shape, the sign that
        the partitioner moves activations where ZeRO-3 should move
        weights; and ``custom_calls``, the Pallas kernels one call runs
        by the scope they sit in (a flash block under a remat policy that
        saves ``attn_out`` reads 2 x layers under ``attention``, forward
        and fused backward, 3 x under any other).  Beside them
        ``flash_tiles``, read off the trace and not off the HLO: the tile
        plans of the step's distinct dense flash calls (a scanned layer's
        two count once), as tiles visited / masked / in the square;
        visited < square where the causal tile walk engages; and the
        distinct backward calls by form, ``bwd_fused`` / ``bwd_split``
        (a step that fell back to two backward kernels shows here)."""
        from ..analysis.comms import step_collectives
        from ..analysis.jaxpr_audit import census_from_hlo_text, \
            custom_calls_from_hlo_text
        from ..ops.transformer.flash_attention import tile_census
        state = args[0]
        shapes = {np.shape(leaf) for leaf in jax.tree_util.tree_leaves(
            state.master if state.master is not None else state.params)}
        text = exe.as_text()
        return {**step_collectives(census_from_hlo_text(text), shapes),
                "custom_calls": custom_calls_from_hlo_text(text),
                "flash_tiles": tile_census()}

    def compile_report(self):
        """Compile-cache status + per-entry hit/miss/compile-ms events
        for this engine's cache (surfaced by ds_report), and of each
        acquired step executable what it moves (``collectives``), the
        kernels it runs (``custom_calls``) and the tiles its flash calls
        visit (``flash_tiles``)."""
        from . import compile_cache as ccache
        report = ccache.report(self.compile_cache)
        steps = [w for w in (self._jit_train_step, self._jit_grad_step)
                 if w.described]
        beside = ("custom_calls", "flash_tiles")
        report["collectives"] = {
            w.name: {k: v for k, v in w.described.items()
                     if k not in beside} for w in steps}
        for key in beside:
            report[key] = {w.name: w.described[key] for w in steps}
        return report

    def _install_moe_wire(self):
        """Make THIS engine's quantized expert wire (or its absence) the
        process-global one ``moe/layer.py`` reads at trace time — called
        at init and before every step dispatch, so interleaved engines
        with different policies each retrace under their own."""
        from .comm import moe_wire as mw
        mw.set_active(self._moe_wire)

    def comms_budget(self):
        """Declared per-step wire ceiling for the compressed step's
        collective census (``analysis/comms.py CommsBudget``), computed
        from the compression policy — tight enough that the FULL-WIDTH
        step violates it.  None when no compression route is active or
        the engine streams params.  The moe route's component is
        trace-recorded, so budget-gated flows run one cold step first
        (docs/comms-compression.md)."""
        if self._param_stream is not None or self.state is None:
            return None
        if not (self._router.weights_active or self._router.grads_active
                or self._router.moe_active):
            return None
        base = (self.state.master if self.state.master is not None
                else self.state.params)
        return self._router.comms_budget(
            base, self._param_specs, self._grad_specs,
            np.dtype(self.compute_dtype).itemsize,
            moe_wire=self._moe_wire)

    def preflight_memory(self, batch, rng=None):
        """Peak-HBM preflight of the compiled step via the executable's
        ``memory_analysis()`` — available BEFORE any step executes (and
        nearly free when the compile cache is warm).  ``batch`` must be a
        stacked step batch (``_stack_microbatches`` output or matching
        shapes).  Returns byte counts with ``peak_bytes`` approximating
        execution-time live memory (arguments + outputs − donated
        aliases + temps + program), or None when the backend exposes no
        memory analysis (e.g. some CPU builds) or the engine streams
        params (``offload_param`` never materializes the model in HBM).

        Never consumes donated buffers — acquisition only lowers,
        deserializes or compiles."""
        if self._param_stream is not None:
            return None
        rng = rng if rng is not None else jax.random.fold_in(
            self._base_rng, 0)
        fn = (self._jit_grad_step if self._offload is not None
              else self._jit_train_step)
        with jax.set_mesh(self.mesh):
            exe = fn.executable(self.state, batch, rng)
        from .compile_cache import executable_memory_analysis
        return executable_memory_analysis(exe)

    def memory_ledger(self) -> dict:
        """One memory-ledger snapshot (``monitor/memory_ledger.py``):
        device HBM + host RSS attributed to named subsystems from the
        LIVE state (TrainState leaves, offload-tier buffers, H2D
        staging, NVMe swap pools, compiled programs, compile-cache
        disk), the measured gauges, the explicit residual, and the
        per-phase host-RSS high-water marks.  Host-side reads only."""
        from ..monitor import memory_ledger as mled
        return mled.attribute_engine(self).snapshot(
            phases=self._rss_phases)

    def _maybe_oom_forensics(self, exc):
        """RESOURCE_EXHAUSTED post-mortem (docs/monitoring.md
        #memory-explainability): dump the memory ledger + the capacity
        model's verdict — which subsystem blew the budget and which knob
        buys headroom — through the PR-3 ``write_forensics`` path, once,
        then let the original error propagate.  Only inspects; never
        swallows."""
        if self._oom_dumped or "RESOURCE_EXHAUSTED" not in str(exc):
            return
        self._oom_dumped = True
        from ..monitor import gauges as mg
        from ..monitor import memory_ledger as mled
        try:
            snap = self.memory_ledger()
            path = mled.oom_forensics(
                self._forensic_dir(), snap, reason=exc,
                budget_bytes=mg.hbm_limit_bytes(),
                filename=f"memory_forensics_step"
                         f"{self._global_steps_host}.json")
        except Exception as e:      # a dump failure must never mask the OOM
            logger.warning(f"memory forensics unavailable ({e})")
            return
        if path and self.monitor.armed:
            self.monitor.artifact("memory_forensics", path,
                                  step=self._global_steps_host)
            self.monitor.flush()

    def close(self):
        """Release device state, live compiled executables and staging
        buffers.  ``del engine`` alone does NOT free these (engines built
        one after another in a process leak them until a later one dies
        RESOURCE_EXHAUSTED); call ``close()`` between engine lifetimes
        sharing one process.  A pending delayed-param update is dropped,
        not applied — close is teardown, not a checkpoint boundary."""
        self._pending_offload = None
        self._pending_row_drop_checks = []
        self._data_iterator = None
        # release the global expert-wire slot iff this engine owns it
        from .comm import moe_wire as mw
        if mw.get_active() is not None and mw.get_active() is self._moe_wire:
            mw.set_active(None)
        self._moe_wire = None
        for wrapper in (self._jit_train_step, self._jit_grad_step,
                        self._jit_eval, self._jit_scatter_params):
            if hasattr(wrapper, "clear"):
                wrapper.clear()
        self._jit_eval = None
        self._jit_scatter_params = None
        self._h2d.close()
        state, self.state = self.state, None
        if state is not None:
            for leaf in jax.tree_util.tree_leaves(state):
                if hasattr(leaf, "delete") and hasattr(leaf, "is_deleted") \
                        and not leaf.is_deleted():
                    leaf.delete()
        ps, self._param_stream = self._param_stream, None
        if ps is not None:
            ps.close()
        self._offload = None
        if (self.monitor.armed and self.monitor.bus is not None
                and self.monitor.bus.sinks):
            # terminal hist flush: a run shorter than the timer's
            # emission cadence must still leave its whole-run step-time
            # distribution in the stream (what ds_fleet merges read)
            tt = getattr(self, "tput_timer", None)
            if tt is not None and getattr(tt, "step_time_hist", None):
                self.monitor.bus.hist("train_step_time_ms",
                                      tt.step_time_hist,
                                      step=self._global_steps_host,
                                      unit="ms")
        self.monitor.close()
        import gc
        gc.collect()

    # ------------------------------------------------------------- train step
    def _micro_loss_fn(self):
        """The ``(base_params, mb, r) -> (loss, aux)`` callable shared by
        the full-width ``_grad_fn`` and the qgZ partials path (the two
        must never drift): cast to the compute dtype, deliver params over
        the ZeRO-3 wire (quantized qwZ all-gather for routed leaves, the
        plain sharding constraint otherwise), then the model's OWN
        ``loss_with_metrics`` when the engine trains on the model's loss
        (MoE aux metrics, reference engine.py:1639) — a client ``loss_fn=``
        stays authoritative and is never silently displaced."""
        dtype = self.compute_dtype
        needs_master = dtype != jnp.float32
        own_loss = (getattr(self._loss_fn, "__self__", None)
                    is self.module
                    and getattr(self._loss_fn, "__name__", "") == "loss")
        lwm = (getattr(self.module, "loss_with_metrics", None)
               if own_loss else None)

        def fn(base_params, mb, r):
            p = tree_cast(base_params, dtype) if needs_master else base_params
            p = self._router.gather_params(p, self._param_specs)
            if lwm is not None:
                return lwm(p, mb, r)
            return self._loss_fn(p, mb, r), {}

        return fn

    @staticmethod
    def _acc_aux_fn(gas):
        """Aux-metric accumulation rule of the gas scan, shared by both
        gradient paths: losses/ratios average over microbatches; COUNTS
        (keys ending in "_dropped") sum — "tokens dropped this step" must
        mean the step's total, not a per-microbatch mean."""
        def acc_aux(acc_tree, aux_tree):
            return {k: acc_tree[k] + (v if k.endswith("_dropped")
                                      else v / gas)
                    for k, v in aux_tree.items()}
        return acc_aux

    def _grad_fn(self, base, batch, rng, cur_scale):
        """Gradient computation inside the jitted step.

        Default: scan over the gas microbatch axis accumulating fp32 grads
        (reference per-micro-batch backward + bucketed hook reduction,
        ``engine.py:1684``).  ``PipelineEngine`` overrides this with the
        pipelined forward/backward.  Returns ``(grads, scaled_loss_sum)``
        where ``scaled_loss_sum == mean_loss * cur_scale``.
        """
        if self._router.grads_active:
            # qgZ: gradients leave this function as per-dp-slice PARTIALS
            # (leading (D, ...) axis); _grads_and_metrics routes them
            # through the quantized reduction
            return self._grad_fn_partials(base, batch, rng, cur_scale)
        gas = self.gradient_accumulation_steps()
        loss_fn = self._micro_loss_fn()

        def micro_loss(base_params, mb, r):
            loss, aux = loss_fn(base_params, mb, r)
            return loss * cur_scale / gas, aux

        vgrad = jax.value_and_grad(micro_loss, has_aux=True)

        if gas == 1:
            # no accumulation loop: the scan wrapper would zero-init and
            # add-into a full fp32 grad tree (1.4GB at 350M) per step for
            # nothing
            mb = jax.tree_util.tree_map(lambda a: a[0], batch)
            (scaled_loss, aux), grads = vgrad(base, mb,
                                              jax.random.fold_in(rng, 0))
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)
            return grads, scaled_loss, aux

        acc_dtype = (jnp.bfloat16 if self.config.grad_accum_dtype == "bf16"
                     else jnp.float32)
        acc_aux = self._acc_aux_fn(gas)

        def body(carry, xs):
            gacc, lacc, aacc, idx = carry
            mb = xs
            r = jax.random.fold_in(rng, idx)
            (scaled_loss, aux), grads = vgrad(base, mb, r)
            grads = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(acc_dtype), gacc, grads)
            aacc = acc_aux(aacc, aux)
            return (grads, lacc + scaled_loss, aacc, idx + 1), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, acc_dtype), base)
        mb0 = jax.tree_util.tree_map(lambda a: a[0], batch)
        # zero-init the aux accumulator with the right structure
        aux_zeros = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(lambda b, m, r: micro_loss(b, m, r)[1],
                           base, mb0, rng))
        (grads, scaled_loss_sum, aux, _), _ = jax.lax.scan(
            body, (zeros, jnp.float32(0.0), aux_zeros, jnp.int32(0)), batch)
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads)
        return grads, scaled_loss_sum, aux

    def _grad_fn_partials(self, base, batch, rng, cur_scale):
        """qgZ gradient computation: PARTIAL gradients per data-parallel
        slice instead of XLA's implicit full-width reduction.

        The global microbatch reshapes to ``(D, micro_per_rank, ...)``
        (a shard-local reshape: sharding already splits axis 0 into D
        contiguous chunks) and a vmapped ``value_and_grad`` produces one
        gradient slice per dp rank — each device computes exactly the
        backward it computed before, but the cross-device sum is now OURS
        to schedule, so the reduction wire can move int8 with error
        feedback (``comm/quantized.py reduce_partials_quantized``).
        Returns ``(partial_grads (D, *shape), scaled_loss_sum, aux)``;
        also note the reduction now happens ONCE per step (after the gas
        scan) rather than per microbatch.

        Normalization: each slice loss is a mean over ``micro/D`` rows,
        so the per-slice loss is scaled by ``1/D`` here — the SUMMED
        partial gradients then equal the gradient of the global-batch
        mean exactly.  (Without it the summed partials are D× the
        full-width gradient — invisible under Adam, an effective-lr
        explosion under any scale-sensitive optimizer.)
        """
        gas = self.gradient_accumulation_steps()
        D = self.mesh_ctx.dp_world_size
        loss_fn = self._micro_loss_fn()
        lead = NamedSharding(self.mesh, P(M.BATCH_AXES))

        def slice_loss(base_params, mb, r):
            loss, aux = loss_fn(base_params, mb, r)
            return loss * cur_scale / (gas * D), aux

        vgrad = jax.vmap(jax.value_and_grad(slice_loss, has_aux=True),
                         in_axes=(None, 0, 0))

        def split_dp(mb):
            def r(a):
                a = jnp.reshape(a, (D, a.shape[0] // D) + a.shape[1:])
                return jax.lax.with_sharding_constraint(a, lead)
            return jax.tree_util.tree_map(r, mb)

        def one_micro(mb, r):
            rs = jax.random.split(r, D)
            with zpart.one_rank_rows():
                (sl, aux), pg = vgrad(base, split_dp(mb), rs)
            pg = jax.tree_util.tree_map(
                lambda g: jax.lax.with_sharding_constraint(g, lead), pg)
            # per-slice aux -> microbatch aux (counts sum, ratios average)
            aux = {k: (jnp.sum(v, axis=0) if k.endswith("_dropped")
                       else jnp.mean(v, axis=0)) for k, v in aux.items()}
            # per-slice losses carry 1/D, so the sum IS the scaled mean
            return pg, jnp.sum(sl), aux

        if gas == 1:
            mb = jax.tree_util.tree_map(lambda a: a[0], batch)
            pg, scaled_loss, aux = one_micro(mb, jax.random.fold_in(rng, 0))
            pg = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), pg)
            return pg, scaled_loss, aux

        acc_dtype = (jnp.bfloat16 if self.config.grad_accum_dtype == "bf16"
                     else jnp.float32)
        acc_aux = self._acc_aux_fn(gas)

        def body(carry, xs):
            gacc, lacc, aacc, idx = carry
            pg, sl, aux = one_micro(xs, jax.random.fold_in(rng, idx))
            gacc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(acc_dtype), gacc, pg)
            return (gacc, lacc + sl, acc_aux(aacc, aux), idx + 1), None

        zeros = jax.tree_util.tree_map(
            lambda p: jax.lax.with_sharding_constraint(
                jnp.zeros((D,) + p.shape, acc_dtype), lead), base)
        mb0 = jax.tree_util.tree_map(lambda a: a[0], batch)
        aux_zeros = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(lambda m, r: one_micro(m, r)[2], mb0, rng))
        (pg, scaled_loss_sum, aux, _), _ = jax.lax.scan(
            body, (zeros, jnp.float32(0.0), aux_zeros, jnp.int32(0)), batch)
        pg = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), pg)
        return pg, scaled_loss_sum, aux

    def _grads_and_metrics(self, state: TrainState, base, batch, rng):
        """Shared gradient post-processing contract, used by the fused
        in-device step AND the offload grad-only step: scan microbatches,
        unscale, overflow check, clip, constrain to ZeRO-2 sharding
        (reference clip order: unscale → clip → step,
        ``stage_1_and_2.py:1736 unscale_and_clip``).

        With the qgZ route active the grad function returns PARTIALS;
        overflow/non-finite sentinels run on the partials (quantization
        would launder an Inf into finite garbage) and the reduction goes
        through the router's error-fed int8 wire.  Returns
        ``(grads, overflow, lr, metrics, new_comm_error)`` — the last is
        None on the full-width path."""
        # a step's trace starts here: what _describe_step reads as
        # ``flash_tiles`` is this step's calls and nothing traced before
        from ..ops.transformer.flash_attention import reset_tile_census
        reset_tile_census()
        cur_scale = (state.scale.cur_scale if state.scale is not None
                     else jnp.float32(1.0))
        out = self._grad_fn(base, batch, rng, cur_scale)
        # uniform (grads, loss, aux) contract; a 2-tuple from a legacy
        # client override still unpacks
        grads, scaled_loss_sum, aux = out if len(out) == 3 else (*out, {})
        # unscale (fp16); loss for reporting is the true mean loss
        grads = jax.tree_util.tree_map(lambda g: g / cur_scale, grads)
        loss = scaled_loss_sum / cur_scale
        overflow = (ls.has_overflow(grads) if self.fp16_enabled
                    else jnp.asarray(False))
        new_ef = None
        wire_nf = None
        if self._router.grads_active:
            # non-finite flags come from the RAW partials: the quantizer
            # sanitizes NaN/Inf to 0 (the int cast is undefined on them),
            # so without this a poisoned gradient would silently train as
            # zeros.  Re-injecting NaN into the reduced grads restores
            # full-width semantics exactly — the post-reduce sentinels
            # catch it when the guardian is armed, and with the guardian
            # OFF (numerics debugging) the NaN propagates visibly, as it
            # would on the lossless wire.  (fp16 needs no twin: its
            # overflow scan below already runs on the partials and the
            # scaler skip-step is unconditional.)
            wire_nf = (None if self.fp16_enabled
                       else hmod.tree_nonfinite(grads))
            grads, new_ef = self._router.reduce_grads(
                grads, state.comm_error, self._grad_specs)
            if wire_nf is not None:
                grads = jax.tree_util.tree_map(
                    lambda g: jnp.where(
                        wire_nf, jnp.full(g.shape, jnp.nan, g.dtype), g),
                    grads)
        if self.config.gradient_clipping > 0:
            with jax.named_scope("optimizer"):
                grads, gnorm = clip_by_global_norm(
                    grads, self.config.gradient_clipping)
        else:
            gnorm = global_norm(grads)
        # ZeRO-2: constrain grads to fsdp sharding → reduce-scatter
        grads = zpart.constrain(grads, self._grad_specs, self.mesh)
        lr = self._lr_at(state.global_steps)
        metrics = {"loss": loss, "grad_norm": gnorm, "overflow": overflow,
                   "lr": lr, "loss_scale": cur_scale}
        if wire_nf is not None:
            metrics["nonfinite_wire"] = wire_nf
        metrics.update(aux)
        return grads, overflow, lr, metrics, new_ef

    def _health_sentinels(self, state, loss, grads, overflow):
        """On-device divergence sentinels (traced into the step; pure jnp,
        no host callbacks — the DSTPU201 audit stays clean).

        Returns ``(skip, new_health, sentinel_metrics)`` where ``skip``
        gates the branchless skip-step.  For fp16 the grad flag reuses the
        scaler's overflow scan (one reduction, not two)."""
        cfg = self._health_cfg
        nf_grads = (overflow if self.fp16_enabled
                    else hmod.tree_nonfinite(grads))
        nf_loss = jnp.logical_not(jnp.isfinite(loss))
        new_health, z, spike = hmod.update_ema(
            state.health, loss, window=cfg.spike_window,
            zmax=cfg.spike_zmax)
        skip = overflow
        if cfg.skip_nonfinite:
            skip = skip | nf_grads | nf_loss
        if cfg.skip_on_spike:
            skip = skip | spike
        sm = {"nonfinite_grads": nf_grads, "nonfinite_loss": nf_loss,
              "health_z": z, "loss_spike": spike}
        return skip, new_health, sm

    def _train_step(self, state: TrainState, batch, rng):
        """One full optimizer step: scan over gas microbatches, reduce, update.

        ``batch`` leaves are shaped (gas, global_micro_batch, ...) with the
        second axis sharded over the batch axes (data, fsdp, expert).

        With the health guardian enabled (default), the fp16 scaler's
        branchless skip-step generalizes to EVERY precision: a step whose
        loss, gradients, or updated parameters are non-finite (or whose
        loss z-score spikes, when ``skip_on_spike`` is set) is a ``where``-
        selected no-op on params and optimizer state — no data-dependent
        control flow, donation honored, no host round-trip.
        """
        dtype = self.compute_dtype
        needs_master = dtype != jnp.float32
        base = state.master if needs_master else state.params

        grads, overflow, lr, metrics, new_ef = self._grads_and_metrics(
            state, base, batch, rng)
        if self._health_enabled:
            skip, new_health, sm = self._health_sentinels(
                state, metrics["loss"], grads, overflow)
            metrics.update(sm)
        else:
            skip, new_health = overflow, state.health
        with jax.named_scope("optimizer"):
            new_base, new_opt = self.optimizer.update(
                grads, state.opt_state, base, step=state.optimizer_steps + 1,
                lr=lr)
            new_base = zpart.constrain(
                new_base, self._master_specs if needs_master
                else self._param_specs, self.mesh)

        if self._health_enabled and self._health_cfg.skip_nonfinite:
            # optimizer-minted non-finites (e.g. an Inf moment) are caught
            # on the UPDATED base, before anything is committed
            nf_params = hmod.tree_nonfinite(new_base)
            skip = skip | nf_params
            metrics["nonfinite_params"] = nf_params

        gate = self.fp16_enabled or (
            self._health_enabled and (self._health_cfg.skip_nonfinite
                                      or self._health_cfg.skip_on_spike))
        if gate:
            # branchless skip-step: the unhealthy step is a no-op on
            # params/optimizer state (reference _take_model_step overflow
            # path, engine.py:1819-1871 — extended beyond fp16)
            sel = lambda new, old: jax.tree_util.tree_map(
                lambda n, o: jnp.where(skip, o, n), new, old)
            with jax.named_scope("optimizer"):
                new_base = sel(new_base, base)
                new_opt = sel(new_opt, state.opt_state)
            if new_ef is not None:
                # error feedback computed from a skipped step's garbage
                # gradients must not poison future compensation
                new_ef = sel(new_ef, state.comm_error)
        if self.fp16_enabled:
            # the loss scale reacts to OVERFLOW only — a health skip (loss
            # spike, optimizer NaN) is not a scale-is-too-big signal
            new_scale = ls.update_scale(
                state.scale, overflow, dynamic=self._scaler.dynamic,
                scale_factor=self._scaler.scale_factor,
                scale_window=self._scaler.scale_window,
                min_scale=self._scaler.min_scale,
                delayed_shift=self._scaler.delayed_shift,
                consecutive_hysteresis=self._scaler.consecutive_hysteresis)
        else:
            new_scale = state.scale

        if needs_master:
            with jax.named_scope("optimizer"):
                new_params = zpart.constrain(tree_cast(new_base, dtype),
                                             self._param_specs, self.mesh)
            new_master = new_base
        else:
            new_params = new_base
            new_master = None

        metrics["skip"] = skip
        skip_i = skip.astype(jnp.int32)
        new_state = TrainState(
            global_steps=state.global_steps + 1,
            optimizer_steps=state.optimizer_steps + (1 - skip_i),
            skipped_steps=state.skipped_steps + skip_i,
            params=new_params, master=new_master, opt_state=new_opt,
            scale=new_scale, health=new_health,
            comm_error=(new_ef if new_ef is not None
                        else state.comm_error))
        return new_state, metrics

    def _grad_only_step(self, state: TrainState, batch, rng):
        """Device half of the offload step: grads (unscaled, clipped, sharded)
        + metrics + the UPDATED loss-scale state; the optimizer update happens
        on the host (reference: backward populates the fp32 cpu partition,
        ``stage_1_and_2.py:1008-1160``).  Grads cross to the host in the
        16-bit compute dtype — the reference also moves 16-bit grads over
        PCIe and upcasts on the CPU (half the transfer bytes).

        The dynamic loss scale updates IN-GRAPH (eagerly), not host-side
        with the delayed param apply: under DPU the next step dispatches
        before the previous host apply, and a host-side scale update would
        reach it one step late — one overflow would then cost two skipped
        steps and two halvings.  In-graph, the halved scale flows to the
        next dispatch through device state with no host sync."""
        grads, overflow, _, metrics, new_ef = self._grads_and_metrics(
            state, state.params, batch, rng)
        if self._health_enabled:
            # the host half reads metrics["skip"] and makes the skipped
            # step a no-op on the host master/moments — the offload
            # spelling of the branchless skip-step.  (No nonfinite_params
            # sentinel here: the update happens on the host.)
            skip, new_health, sm = self._health_sentinels(
                state, metrics["loss"], grads, overflow)
            metrics.update(sm)
        else:
            skip, new_health = overflow, state.health
        metrics["skip"] = skip
        if new_ef is not None:
            # the error feedback advances in-graph (like scale/health);
            # a skipped step must leave it untouched
            new_ef = jax.tree_util.tree_map(
                lambda n, o: jnp.where(skip, o, n), new_ef,
                state.comm_error)
        if self.fp16_enabled:
            new_scale = ls.update_scale(
                state.scale, overflow, dynamic=self._scaler.dynamic,
                scale_factor=self._scaler.scale_factor,
                scale_window=self._scaler.scale_window,
                min_scale=self._scaler.min_scale,
                delayed_shift=self._scaler.delayed_shift,
                consecutive_hysteresis=self._scaler.consecutive_hysteresis)
        else:
            new_scale = state.scale
        if self.compute_dtype == jnp.bfloat16:
            # bf16 spans the fp32 exponent range so no new inf can appear
            # after the overflow check; fp16 (max 65504) must stay fp32 —
            # casting could mint inf that bypasses the skip-step logic
            grads = tree_cast(grads, jnp.bfloat16)
        if self._sparse_grad_paths:
            grads, rows_dropped = self._sparsify_grads(grads, batch)
            # surfaced so an under-declared sparse_grad_row_bound is an
            # ERROR (checked host-side in _host_offload_update), never a
            # silent truncation of embedding gradients
            metrics["sparse_rows_dropped"] = rows_dropped
        elif self.mesh.size == 1:
            # ONE flat buffer for the wire: a per-leaf d2h pays one
            # round-trip latency per leaf (~minutes per step for a
            # billion-param tree on a remote-attached chip); the in-graph
            # concatenate costs one HBM copy.  Single-device only — on a
            # mesh the concatenate would gather sharded grads whole.
            grads = jnp.concatenate(
                [g.reshape(-1) for g in jax.tree_util.tree_leaves(grads)])
        return grads, metrics, new_scale, new_health, new_ef

    def _sparsify_grads(self, grads, batch):
        """Replace declared embedding-grad leaves with row-sparse
        (indices, values) pairs for the d2h wire.

        The static row bound defaults to the TOTAL integer-id count in the
        batch — safe (a lookup touches at most one row per id) but counts
        non-lookup int leaves like labels too (2× buffers for
        (inputs, labels) batches).  A model can tighten it by declaring
        ``sparse_grad_row_bound(batch) -> int`` (count only the ids that
        actually feed its lookups).  Under-declaring would drop gradient
        rows, so the true nonzero-row count is checked per leaf and
        returned as ``rows_dropped`` — the engine raises on any nonzero
        value rather than corrupting embedding training silently."""
        from .sparse_tensor import SparseTensor
        bound_fn = getattr(self.module, "sparse_grad_row_bound", None)
        if callable(bound_fn):
            tokens = int(bound_fn(batch))
        else:
            tokens = sum(int(np.prod(l.shape)) for l in
                         jax.tree_util.tree_leaves(batch)
                         if jnp.issubdtype(jnp.asarray(l).dtype, jnp.integer))
        if tokens == 0:
            return grads, jnp.int32(0)
        dropped = [jnp.int32(0)]

        def replace(tree, path):
            key = path[0]
            sub = tree[key]
            if len(path) == 1:
                assert np.ndim(sub) == 2, \
                    f"sparse_grad_paths leaf {path} must be 2-D (rows, dim)"
                rows = sub.shape[0]
                if tokens >= rows:
                    return tree  # dense is smaller; keep it
                nz = jnp.any(sub != 0, axis=1)
                nz_rows = jnp.sum(nz.astype(jnp.int32))
                dropped[0] = dropped[0] + jnp.maximum(nz_rows - tokens, 0)
                st = SparseTensor.from_dense(sub, max_rows=tokens, nz=nz)
                out = dict(tree)
                out[key] = {"sparse_indices": st.indices,
                            "sparse_values": st.values}
                return out
            out = dict(tree)
            out[key] = replace(sub, path[1:])
            return out

        for path in self._sparse_grad_paths:
            grads = replace(grads, path)
        return grads, dropped[0]

    def _host_offload_update(self, grads, metrics):
        """Host half of the offload step: d2h grads → native fused Adam on
        the flat fp32 master (moments on host RAM or streamed from NVMe) →
        h2d of the 16-bit payload."""
        state = self.state
        # "skip" unifies fp16 overflow with the health guardian's
        # non-finite/spike sentinels (all device scalars computed in
        # _grad_only_step); the bool() read syncs, but this host path
        # synchronizes on the grads right below anyway
        if "skip" in metrics:
            overflow = bool(metrics["skip"])
        else:
            overflow = bool(metrics["overflow"]) if self.fp16_enabled else False
        ovf = jnp.asarray(int(overflow), jnp.int32)
        # NOTE: checked only on non-overflow steps — a NaN/inf grad step makes
        # every row "nonzero" through the NaN-propagating clip; that path must
        # reach the skip-step logic below, not die here.  The per-step
        # counters ACCUMULATE host-side (device scalars, no sync) and are
        # read only on reporting steps: int() forces a host-device sync,
        # which would shrink the DPU overlap window on every step, while
        # the accumulated check still catches a drop on ANY step of the
        # interval.
        if not overflow and "sparse_rows_dropped" in metrics:
            self._pending_row_drop_checks.append(
                metrics["sparse_rows_dropped"])
            # flush on reporting steps OR every 50 steps — steps_per_print
            # is often set huge to silence logs, which must not disable
            # the guard (or grow the pending list without bound).  Checkpoint
            # save, eval and state-dict export flush unconditionally
            # (_flush_row_drop_checks) so a short run or a mid-interval save
            # can never skip the check.
            if (self._global_steps_host + 1) % \
                    self.config.steps_per_print == 0 or \
                    len(self._pending_row_drop_checks) >= 50:
                self._flush_row_drop_checks()
        if not overflow:
            from .zero.offload_engine import FlatWireHandle
            t0 = time.time()
            if isinstance(grads, FlatWireHandle):
                # flat wire format: land the chunked d2h start_d2h began
                flat = self._offload.land_flat(grads)
            else:
                flat = self._offload.flatten_grads(grads)
            t1 = time.time()
            lr = float(metrics["lr"])
            self._offload.step(flat, int(state.optimizer_steps) + 1, lr)
            t2 = time.time()
            # h2d dispatch is async; its cost surfaces as next-step wait
            params = self._upload_offload_params()
            self._offload.last_host_times = {
                "grad_d2h_flatten_s": t1 - t0, "host_adam_s": t2 - t1}
        else:
            # the skipped step's grads are never landed; dropping the wire
            # handle (or tree) frees the device buffers
            params = state.params
        # scale/health already advanced in-graph by _grad_only_step (kept
        # as-is: under DPU `state` may carry newer values than this
        # pending step)
        self.state = TrainState(
            global_steps=state.global_steps + 1,
            optimizer_steps=state.optimizer_steps + (1 - ovf),
            skipped_steps=state.skipped_steps + ovf,
            params=params, master=None, opt_state=None, scale=state.scale,
            health=state.health, comm_error=state.comm_error)

    # ------------------------------------------------------------- public API
    def train_batch(self, data_iter=None):
        """Run one full training step (gas microbatches → one optimizer step).

        Parity: ``PipelineEngine.train_batch`` naming; for the non-pipeline
        engine this replaces the forward/backward/step trio with one call.
        """
        from .. import fault
        fault.site("engine.step")    # host-side only; never traced
        self._install_moe_wire()
        with self._step_root():
            return self._train_batch(data_iter)

    @contextmanager
    def _step_root(self):
        """The ``train.step`` root span of one optimizer step (recorded
        whether or not a monitor is armed; an armed monitor reads its
        ``span`` events from it) and the profiler's step marker, so XProf's
        step view works on a training capture."""
        step_no = self._global_steps_host + 1
        root = self._spans.open("train.step", step=step_no)
        self.monitor.begin_step(root)
        try:
            with jax.profiler.StepTraceAnnotation("train", step_num=step_no):
                yield
        finally:
            self._spans.close(root)
            if self._startup_line_due:
                self._log_startup()

    def _log_startup(self):
        """Once, when the first step has returned: where this process's
        time went before it could train (docs/monitoring.md#start-up)."""
        from ..monitor import startup
        self._startup_line_due = False
        log_dist(f"DeepSpeedEngine {startup.line()}", ranks=[0])

    def _train_batch(self, data_iter):
        from .. import fault
        it = data_iter if data_iter is not None else self._data_iterator
        assert it is not None, "train_batch needs training_data or a data_iter"
        if it is not self._data_iterator:
            # training is fed by an EXTERNAL iterator: the engine-owned
            # loader no longer tracks the real stream, so a rewind must
            # not "fast-forward" it (the warning path in rewind())
            self._stream_pos_known = False
        gas = self.gradient_accumulation_steps()
        with self._spans.span("train.data_fetch"):
            micro_batches = [next(it) for _ in range(gas)]
        # data-stream position of THIS step (monotonic; checkpointed with
        # the data-pipeline state, advanced by rewind's fast-forward) —
        # also the index the value-corruption fault sites key on, so an
        # injected grad_nan/loss_spike window rides the data deterministically
        self._last_batch_index = self._stream_step
        self._stream_step += 1
        if fault.is_enabled():
            micro_batches = [fault.corrupt_batch(mb, self._last_batch_index)
                             for mb in micro_batches]
        if self.curriculum_scheduler is not None:
            micro_batches = [self._apply_curriculum(mb) for mb in micro_batches]
        try:
            if self._param_stream is not None:
                return self._run_stream_step(micro_batches)
            batch = self._stack_microbatches(micro_batches)
            return self._run_fused_step(batch)
        except Exception as e:
            # an allocator OOM gets its post-mortem pre-written (ledger +
            # capacity verdict); the error itself always propagates
            self._maybe_oom_forensics(e)
            raise

    def _apply_curriculum(self, mb):
        """Crop token sequences to the scheduled difficulty (reference:
        ``curriculum_seqlen`` kwarg injection, ``engine.py:1596-1602``; here
        the seq axis itself is cropped — same tokens seen, shorter program)."""
        seqlen = self.curriculum_scheduler.update_difficulty(
            self._global_steps_host + 1)

        def crop(x):
            if hasattr(x, "ndim") and x.ndim >= 2 and x.shape[1] > seqlen:
                return x[:, :seqlen + 1] if np.issubdtype(
                    np.asarray(x).dtype, np.integer) else x[:, :seqlen]
            return x
        return jax.tree_util.tree_map(crop, mb)

    def curriculum_seqlen(self):
        if self.curriculum_scheduler is None:
            return None
        return self.curriculum_scheduler.get_current_difficulty()

    def _stack_microbatches(self, micro_batches):
        # spanned as one phase: host collation + the H2D placement (the
        # device_put dispatch; the DMA itself overlaps the step)
        with self._spans.span("train.h2d_upload"):
            batch = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                           *micro_batches)
            if self.monitor.armed and self._mon_tokens_per_step is None:
                from ..monitor import gauges as mg
                self._mon_tokens_per_step = mg.tokens_in_batch(batch)
            sh = jax.tree_util.tree_map(
                lambda x: NamedSharding(self.mesh, P(None, M.BATCH_AXES)),
                batch)
            return jax.device_put(batch, sh)

    def _run_fused_step(self, batch):
        self.tput_timer.start()
        rng = jax.random.fold_in(self._base_rng, self.micro_steps)
        # FLOPS profiler: profile the step program BEFORE the donated buffers
        # are consumed (reference: engine.py:1583-1588 profile_step bracket)
        if (self.config.flops_profiler.enabled
                and self._global_steps_host + 1 == self.config.flops_profiler.profile_step):
            self._profile_train_step(batch, rng)
        # trace with the mesh in context so bare-PartitionSpec sharding
        # constraints inside models (MoE expert axis, SP) bind to it
        if self.monitor.armed and self.monitor.bus.sinks \
                and self._mon_step_stats is None:
            self._mon_example = (batch, rng)   # freed once stats price
        self.monitor.trace_before_step(self._global_steps_host + 1)
        with jax.set_mesh(self.mesh):
            if self._offload is not None:
                with self._spans.span("train.dispatch"):
                    grads, metrics, new_scale, new_health, new_ef = \
                        self._jit_grad_step(self.state, batch, rng)
                # loss scale + health EMA + qgZ error feedback advance
                # eagerly (device-graph dependency): the NEXT dispatch
                # sees a post-overflow halving / updated loss baseline /
                # compensated error with no host sync
                self.state = self.state._replace(
                    scale=new_scale, health=new_health,
                    comm_error=(new_ef if new_ef is not None
                                else self.state.comm_error))
                # queue grad d2h behind the device compute (async copy
                # engine; overlaps the host work below).  For the flat
                # wire this swaps `grads` for a chunk handle — the
                # original flat array's buffer is then freed as soon as
                # the chunk slices are computed, instead of being pinned
                # through the DPU delay window.
                with self._spans.span("train.grad_d2h"):
                    grads = self._offload.start_d2h(grads)
                if self._dpu and self._global_steps_host >= self._dpu_warmup:
                    # DPU steady state: while the device computes THIS
                    # step's grads, the host applies the PREVIOUS step's —
                    # params are one step stale (ZeRO-Offload paper §DPU;
                    # the reference's overlap-centric design,
                    # docs/_posts/2021-03-08-zero3-offload.md:72)
                    if self._pending_offload is not None:
                        with self._spans.span("train.host_adam"):
                            self._host_offload_update(*self._pending_offload)
                    self._pending_offload = (grads, metrics)
                else:
                    with self._spans.span("train.host_adam"):
                        self._host_offload_update(grads, metrics)
            else:
                with self._spans.span("train.dispatch"):
                    self.state, metrics = self._jit_train_step(
                        self.state, batch, rng)
        return self._finish_step(metrics)

    def _run_stream_step(self, micro_batches):
        """ZeRO-3 param-offload step: the runner streams layer blocks
        through the device (``zero/param_stream.py``); the engine keeps
        counters/schedules/reporting identical to the fused path."""
        self.tput_timer.start()
        rng = jax.random.fold_in(self._base_rng, self.micro_steps)
        lr = float(self._lr_at(self.state.global_steps))
        if self.monitor.armed and self._mon_tokens_per_step is None:
            from ..monitor import gauges as mg
            self._mon_tokens_per_step = mg.tokens_in_batch(micro_batches)
        self.monitor.trace_before_step(self._global_steps_host + 1)
        with jax.set_mesh(self.mesh):
            # the runner's layer loop (streamed gathers, NVMe swaps, host
            # Adam) runs inside this bracket; its own phase timings land
            # as child spans in _monitor_finish when it reports them
            with self._spans.span("train.dispatch"):
                metrics = self._param_stream.train_step(
                    micro_batches, rng, lr=lr,
                    step_no=int(self.state.optimizer_steps) + 1)
        # the runner's skip-step (non-finite loss/grad-norm -> host Adam
        # not applied) reports through metrics["skip"]; counters mirror
        # the fused path's skipped-step accounting
        skip = bool(metrics.get("skip", False))
        one = jnp.asarray(1, jnp.int32)
        zero = jnp.asarray(0, jnp.int32)
        self.state = self.state._replace(
            global_steps=self.state.global_steps + one,
            optimizer_steps=self.state.optimizer_steps + (zero if skip
                                                          else one),
            skipped_steps=self.state.skipped_steps + (one if skip
                                                      else zero))
        return self._finish_step(metrics)

    def _finish_step(self, metrics):
        """Post-step bookkeeping shared by the fused and streamed paths."""
        self._last_metrics = metrics
        self.micro_steps += self.gradient_accumulation_steps()
        self.global_samples += self.train_batch_size()
        self._global_steps_host += 1
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "step"):
            self.lr_scheduler.step()
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self._global_steps_host)
        if self._scaler is not None and self.state.scale is not None:
            self._scaler.state = self.state.scale
        # host sync (float()/block) only on steps that actually report — keeps
        # the hot path async so input prep overlaps device compute
        step_no = self._global_steps_host
        # RSS HWM phase brackets (one getrusage read): init ended at
        # __init__, first-compile ends with step 1, steady re-marks at
        # the ledger cadence
        from ..monitor import memory_ledger as mled
        if step_no == 1:
            self._rss_phases.mark(mled.PHASE_FIRST_COMPILE)
        elif self._mem_interval and step_no % self._mem_interval == 0:
            self._rss_phases.mark_latest(mled.PHASE_STEADY)
        reporting = step_no % self.config.steps_per_print == 0
        if reporting:
            self._report_progress(step_no, metrics)
        self.tput_timer.stop(global_step=True,
                             sync_obj=metrics["loss"] if reporting else None)
        self._monitor_finish(step_no, metrics, reporting)
        if self.health_monitor is not None:
            # trails the device by health_check.check_interval steps (the
            # sentinel read then blocks only on already-finished work) and
            # may rewind (in-process) or abort (with forensics)
            self._health_observe(step_no, metrics)
        return metrics["loss"]

    # ------------------------------------------------------------- telemetry
    _MON_SCALAR_KEYS = ("loss", "lr", "grad_norm", "loss_scale", "skip",
                        "moe_aux_loss", "moe_tokens_dropped")

    def _monitor_finish(self, step_no, metrics, reporting):
        """Per-step telemetry emission (monitor/; docs/monitoring.md).

        Closes the step's root span and hands the monitor (a) the span
        tree measured around this step's dispatch path, (b) the step's
        scalar metrics as DEVICE REFERENCES — synced one step late by
        the monitor, never here — and (c) host-side gauges/counters
        (memory, compile-cache, health counters, per-step wire bytes).
        With ``wall_clock_breakdown`` the same spans feed the named-timer
        registry and its log line on reporting steps."""
        mon = self.monitor
        if not mon.armed:
            return
        scalars = gauges = counters = None
        if mon.should_emit(step_no):
            scalars = {k: metrics[k] for k in self._MON_SCALAR_KEYS
                       if k in metrics}
            gauges, counters = self._monitor_gauges_counters()
        spans = mon.end_step(step_no, scalars=scalars, gauges=gauges,
                             counters=counters)
        if (self._mem_interval and mon.bus is not None and mon.bus.sinks
                and step_no % self._mem_interval == 0):
            # the memory ledger's periodic `mem` event (host-side reads
            # only — the compiled step never sees this; --audit-step
            # mem).  Gated on live sinks but NOT on monitor.interval:
            # memory_interval alone sets this cadence, as documented —
            # an interval-thinned monitor must not push it to the lcm.
            from ..monitor import memory_ledger as mled
            mled.attribute_engine(self).emit(mon, step=step_no,
                                             phases=self._rss_phases)
        if self.config.wall_clock_breakdown and spans:
            for name, _, dur_s in spans:
                self.timers.record_span(name, dur_s)
            if reporting:
                self.timers.log(
                    sorted({name for name, _, _ in spans}),
                    memory_breakdown=self.config.memory_breakdown)

    def _monitor_gauges_counters(self):
        """Host-side gauge/counter payload for one emitted step: rate
        denominators (tokens, flops — set once, the monitor divides by
        measured wall), device memory (live stats, or the executable's
        ``memory_analysis()`` projection where the backend exposes
        none), compile-cache hit/miss, and health skip/rewind state."""
        from ..monitor import gauges as mg
        stats = self._monitor_step_stats()
        self.monitor.set_rates(
            tokens_per_step=self._mon_tokens_per_step or None,
            samples_per_step=self.train_batch_size(),
            flops_per_step=stats.get("flops"),
            peak_flops=stats.get("peak_flops"))
        gauges = {}
        mem = mg.device_memory()
        if mem:
            gauges.update(mem)
        elif stats.get("hbm_projected"):
            gauges["hbm_peak_projected"] = stats["hbm_projected"]
        if self.compile_cache is not None:
            gauges["compile_cache_hits"] = self.compile_cache.stats["hits"]
            gauges["compile_cache_misses"] = \
                self.compile_cache.stats["misses"]
        if self.health_monitor is not None:
            hc = self.health_monitor.counters()
            gauges["health_skipped_total"] = hc["total_skips"]
            gauges["health_rewinds"] = hc["rewinds"]
        return gauges, dict(stats.get("wire") or {})

    def _monitor_step_stats(self):
        """Per-program telemetry constants, priced from the DISPATCHING
        compiled step (no extra lowering/compile): XLA cost-analysis
        FLOPs (the flops-profiler reading — live MFU divides them by
        measured wall), the HLO collective census priced as wire
        bytes/step (``analysis/comms.py``), and the projected peak bytes.
        Cached per live-signature count: a retrace under a new batch
        shape (curriculum cropping) re-prices, so the gauges follow the
        program that is actually executing."""
        from ..monitor import gauges as mg
        fn = (self._jit_grad_step if self._offload is not None
              else self._jit_train_step)
        n_sigs = mg.live_signature_count(fn)
        if self._mon_step_stats is not None:
            cached_n, out = self._mon_step_stats
            if cached_n == n_sigs:
                return out
            self._mon_step_stats = None    # new program: re-price
        if not getattr(fn, "_exes", None) and self._mon_example is not None:
            # no live executable recorded (compile cache off -> CachedStep
            # passthrough): acquire one, once, so the per-program gauges
            # exist anyway.  One extra compile on monitored no-cache
            # engines — enabling the compile cache avoids it.
            example, self._mon_example = self._mon_example, None
            try:
                with jax.set_mesh(self.mesh):
                    fn.executable(self.state, *example)
            except Exception as e:
                logger.warning(f"monitor: could not price the compiled "
                               f"step ({e}); MFU/wire gauges unavailable")
        self._mon_example = None
        out = {}
        flops = mg.executable_flops(fn)
        if flops:
            out["flops"] = flops
            out["peak_flops"] = mg.peak_flops_per_chip() * self.mesh.size
        wire = mg.executable_wire_report(fn)
        if wire:
            out["wire"] = wire
        peak = mg.executable_peak_bytes(fn)
        if peak:
            out["hbm_projected"] = peak
        hbm_bytes = mg.executable_bytes_accessed(fn)
        if flops or hbm_bytes:
            # one `exe_cost` event per priced program: the ds_explain
            # (analysis/roofline.py) feed — XLA FLOPs + memory-traffic
            # bytes + census wire bytes + the producing chip, so an
            # offline stream carries everything the roofline needs
            self.monitor.gauge(
                "exe_cost", float(flops), exe="train_step", flops=flops,
                hbm_bytes=hbm_bytes,
                wire_bytes=(wire or {}).get("wire_bytes_per_step", 0),
                device_kind=self.mesh.devices.flat[0].device_kind,
                n_chips=self.mesh.size)
        n_sigs = mg.live_signature_count(fn)
        if n_sigs:
            # cache against the signature count: stable program = priced
            # once; a retrace invalidates (see the check above)
            self._mon_step_stats = (n_sigs, out)
        return out

    # ------------------------------------------------- health guardian (host)
    def _health_observe(self, step_no, metrics):
        """Feed the step's sentinels to the monitor and execute the action
        it escalates to (docs/health-monitor.md)."""
        action = self.health_monitor.observe(
            step_no, self._last_batch_index, metrics)
        if action == "rewind":
            self._health_rewind()
        elif action == "abort":
            self._health_abort("consecutive-skip budget exhausted and "
                               "rewind limit spent")

    def _health_abort(self, reason):
        # drain the monitor's lag window first: the newest steps —
        # including the ones that tripped the abort — must reach the
        # forensic history (their escalation verdict is moot now)
        self.health_monitor.flush()
        path = self.health_monitor.forensic_dump(
            self._forensic_dir(), reason,
            last_good_tag=self.loaded_checkpoint_tag)
        raise hmod.TrainingHealthError(
            f"training health: {reason}; "
            f"counters={self.health_monitor.counters()}"
            + (f"; forensics at {path}" if path else ""),
            forensic_path=path)

    def _forensic_dir(self):
        return (self._health_cfg.forensic_dir
                or self.config.checkpoint_config.dir
                or self._last_ckpt_dir or os.getcwd())

    def _health_rewind(self):
        """Monitor-driven escalation: in-process rewind to the newest valid
        checkpoint, then fast-forward the data stream past the last
        observed poison batch.  A rewind that cannot run (no checkpoint
        dir / no loadable tag) falls through to ``on_exhausted``.

        When a rewind's replay runs STRAIGHT back into skips (no clean
        step applied since the previous rewind — we are provably still
        inside the same poison window), the fast-forward stride doubles:
        a W-batch window is crossed in O(log W) rewinds instead of one
        skip-budget's width per rewind, at the cost of over-skipping at
        most W clean batches."""
        mon = self.health_monitor
        same_episode = mon.episode_rewinds > 0 and mon.clean_since_rewind == 0
        self._ff_stride = self._ff_stride * 2 if same_episode else 1
        target = mon.last_bad_stream_step
        if target is not None:
            target += self._ff_stride - 1
        try:
            self.rewind(replay_past=target)
        except Exception as e:
            # any ordinary failure (no dir, no valid tag, checkpoint IO
            # errors after retry exhaustion) ends the ladder here;
            # InjectedCrash/SIGKILL-like BaseExceptions still propagate
            if self._health_cfg.on_exhausted == "warn":
                logger.warning(f"health: rewind unavailable ({e}); "
                               "on_exhausted=warn — continuing without it")
                mon.consecutive_skips = 0
                return
            self._health_abort(f"rewind failed: {e}")
        mon.record_rewind(tag=self.loaded_checkpoint_tag)

    def rewind(self, load_dir=None, tag=None, replay_past=None):
        """In-process rewind-and-replay: reload the newest *valid* (manifest-
        verified) checkpoint without a process restart, then fast-forward
        the restored data stream past ``replay_past`` (a data-stream batch
        index, e.g. the last step poisoned by a bad batch) so replay
        resumes on clean data instead of re-feeding the poison window.

        Used by the health guardian's escalation ladder; also callable
        directly (operator-driven rollback)."""
        load_dir = load_dir or self._rewind_dir()
        if load_dir is None:
            raise ValueError(
                "rewind needs a checkpoint directory: set checkpoint.dir "
                "in the config or save/load a checkpoint first")
        path, _ = self.load_checkpoint(load_dir, tag=tag)
        if replay_past is not None:
            if self._data_iterator is None:
                logger.warning(
                    "rewind: no engine-owned data iterator to fast-forward "
                    "(external data_iter?); replay will re-feed the stream "
                    "from the checkpointed position")
            elif not self._stream_pos_known:
                logger.warning(
                    "rewind: data-stream position unknown (the checkpoint "
                    "carried no data-pipeline state); fast-forward skipped "
                    "— replay may re-feed already-seen batches")
            else:
                gas = self.gradient_accumulation_steps()
                skipped = max(replay_past - self._stream_step + 1, 0)
                loader = self.training_dataloader
                if (skipped and isinstance(self._data_iterator,
                                           RepeatingLoader)
                        and self._data_iterator.loader is loader
                        and hasattr(loader, "load_state_dict")):
                    # O(1) jump: advance the loader's (epoch, batch_index)
                    # arithmetic instead of collating every discarded batch
                    # (a W-step window at model-scale batch sizes would
                    # otherwise stall recovery on throwaway numpy stacking)
                    per_epoch = max(len(loader), 1)
                    sd = loader.state_dict()
                    pos = sd["epoch"] * per_epoch + sd["batch_index"] \
                        + skipped * gas
                    loader.load_state_dict({
                        "seed": sd["seed"], "epoch": pos // per_epoch,
                        "batch_index": pos % per_epoch})
                    self._data_iterator = iter(RepeatingLoader(loader))
                    self._stream_step += skipped
                else:
                    while self._stream_step <= replay_past:
                        for _ in range(gas):
                            next(self._data_iterator)
                        self._stream_step += 1
                log_dist("rewind fast-forward: " + json.dumps(
                    {"event": "health_fast_forward", "batches": skipped,
                     "resume_stream_step": self._stream_step}), ranks=[0])
        return path

    def _rewind_dir(self):
        return self.config.checkpoint_config.dir or self._last_ckpt_dir

    def _upload_offload_params(self):
        """Host master → device params as CHUNKED flat h2d transfers + a
        jitted concat/scatter (per-leaf device_put pays one round-trip
        latency per leaf; one monolithic transfer serializes the
        transport — ``zero/wire.py``).  Chunks are staged through
        reusable host buffers so the next host optimizer step can mutate
        the 16-bit payload while the previous upload is still in flight
        (the DPU overlap makes that race live otherwise).

        Single-device fast path only: on a multi-chip mesh the flat image
        would land whole on one device before resharding (OOM for models
        that only fit sharded) — there the per-leaf placement puts each
        leaf directly into its sharding."""
        if self._sparse_grad_paths or self.mesh.size > 1:
            # sparse wire keeps the tree format end-to-end.  Under DPU the
            # payload leaves are live views of the host 16-bit image, which
            # the NEXT host step mutates while this device_put may still be
            # reading — stage copies first (same race the flat branch
            # stages against).
            tree = self._offload.payload_tree()
            if self._dpu:
                # copy into ALTERNATING pre-faulted staging trees (a fresh
                # tree_map(np.array) would allocate + first-touch the full
                # payload every step; a single reused tree could itself be
                # overwritten while its upload is in flight — two buffers
                # give a full upload cycle of slack, and the grad landing
                # between reuses proves the older transfer completed)
                stages = getattr(self, "_tree_stages", None)
                if stages is None:
                    stages = self._tree_stages = [
                        jax.tree_util.tree_map(np.array, tree), None]
                    self._tree_stage_idx = 0
                idx = self._tree_stage_idx
                if stages[idx] is None:
                    stages[idx] = jax.tree_util.tree_map(np.array, tree)
                else:
                    jax.tree_util.tree_map(np.copyto, stages[idx], tree)
                self._tree_stage_idx = 1 - idx
                tree = stages[idx]
            return jax.device_put(tree, self._param_sh)
        with self._spans.span("train.param_h2d"):
            payload = self._offload.payload_flat()
            chunks = self._h2d.upload_flat(payload, stage=self._dpu)
        if self._jit_scatter_params is None or \
                self._scatter_nchunks != len(chunks):
            from .zero.wire import make_chunk_scatter
            self._scatter_nchunks = len(chunks)
            self._jit_scatter_params = make_chunk_scatter(
                self._offload.shapes, self._offload.treedef,
                int(chunks[0].shape[0]), len(chunks),
                out_shardings=self._param_sh)
        params = self._jit_scatter_params(*chunks)
        # staging buffers recycle once the scatter OUTPUT is ready (the
        # donated chunks' is_deleted cannot prove the h2d DMA finished)
        self._h2d.settle_on(jax.tree_util.tree_leaves(params)[0])
        return params

    def _flush_row_drop_checks(self):
        """Read the accumulated device drop counters (syncs) and raise if any
        sparse-gradient row was silently dropped since the last flush."""
        pending, self._pending_row_drop_checks = \
            self._pending_row_drop_checks, []
        n_dropped = sum(int(x) for x in pending)
        if n_dropped > 0:
            raise RuntimeError(
                f"sparse_grad_row_bound under-declared: {n_dropped} "
                "nonzero gradient row(s) exceeded the declared bound "
                "within the last reporting interval and were "
                "dropped; raise the bound (or remove "
                "sparse_grad_row_bound to use the safe default)")

    def _flush_offload(self):
        """Apply a pending delayed-param update so exported / evaluated
        parameters reflect every batch seen (DPU holds one step in flight).
        Also the unconditional flush point for the sparse row-drop guard:
        every state-export boundary (checkpoint save, eval, state_dict)
        routes through here, so corrupted-gradient errors cannot be skipped
        by run length or checkpoint timing."""
        self._flush_row_drop_checks()
        if self._pending_offload is not None:
            pending, self._pending_offload = self._pending_offload, None
            self._host_offload_update(*pending)
            # the just-applied in-flight step appended its own drop counter
            # (DPU holds one step back) — check it too before any export
            self._flush_row_drop_checks()

    def eval_batch(self, batch, rng=None):
        """Loss without gradient/update (jitted separately)."""
        self._install_moe_wire()
        self._flush_offload()
        if self._param_stream is not None:
            rng = rng if rng is not None else jax.random.PRNGKey(0)
            with jax.set_mesh(self.mesh):
                return self._param_stream.eval_loss(batch, rng)
        if self._jit_eval is None:
            def eval_fn(params, mb, r):
                return self._loss_fn(params, mb, r)
            self._jit_eval = self._wrap_step("eval_step", eval_fn)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        batch = self._device_batch(batch)
        with jax.set_mesh(self.mesh):
            return self._jit_eval(self.state.params, batch, rng)

    def _device_batch(self, batch):
        sh = jax.tree_util.tree_map(
            lambda x: NamedSharding(self.mesh, P(M.BATCH_AXES)), batch)
        return jax.device_put(batch, sh)

    # --- forward/backward/step compatibility shim -------------------------
    def forward(self, batch, rng=None):
        """Compatibility shim: computes the (eval) loss AND stages the batch
        for the fused step executed at the gas boundary in :meth:`step`."""
        self._staged_batch = batch
        return self.eval_batch(batch, rng)

    def backward(self, loss=None):
        """Compatibility shim: queue the staged microbatch.  The actual
        gradient computation happens fused inside :meth:`step` at the
        accumulation boundary (reference semantics: grads materialize during
        backward; here XLA fuses them into the optimizer step)."""
        assert getattr(self, "_staged_batch", None) is not None, \
            "call forward(batch) before backward()"
        self._pending_microbatches.append(self._staged_batch)
        self._staged_batch = None
        return loss

    def is_gradient_accumulation_boundary(self):
        """Parity: reference ``engine.py:1267``."""
        return len(self._pending_microbatches) >= self.gradient_accumulation_steps()

    def step(self):
        """Compatibility shim: at the gas boundary, run the fused train step
        over the queued microbatches."""
        if not self.is_gradient_accumulation_boundary():
            return None
        # a retrace here must see THIS engine's expert-wire policy, not
        # whichever engine dispatched last (same rule as train_batch)
        self._install_moe_wire()
        micro_batches, self._pending_microbatches = \
            self._pending_microbatches, []
        with self._step_root():
            if self._param_stream is not None:
                return self._run_stream_step(micro_batches)
            return self._run_fused_step(
                self._stack_microbatches(micro_batches))

    # ------------------------------------------------------------ data/loader
    def deepspeed_io(self, dataset, batch_size=None, route=None, data_sampler=None,
                     collate_fn=None, num_local_io_workers=None):
        """Build the config-driven loader (parity: reference ``engine.py:1493``).

        One process feeds the whole mesh, so the loader yields GLOBAL
        micro-batches of ``micro_batch × dp_world`` samples; the engine shards
        them over the (data, fsdp) axes on device_put.
        """
        if batch_size is None:
            batch_size = self.train_micro_batch_size_per_gpu() * self.mesh_ctx.dp_world_size
        return DeepSpeedDataLoader(dataset, batch_size=batch_size,
                                   collate_fn=collate_fn,
                                   drop_last=self.config.dataloader_drop_last)

    def _profile_train_step(self, batch, rng):
        """Print the FLOPS profile of the compiled train step (parity:
        reference flops-profiler engine integration, ``engine.py:1583-1588``)."""
        from ..profiling.flops_profiler.profiler import FlopsProfiler
        prof = FlopsProfiler(ds_engine=self)
        prof.start_profile()
        try:
            step_fn = (self._jit_grad_step if self._offload is not None
                       else self._jit_train_step)
            with jax.set_mesh(self.mesh):
                lowered = step_fn.lower(self.state, batch, rng)
                ca = lowered.compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            prof._flops = int(ca.get("flops", 0) or 0)
            prof._macs = prof._flops // 2
            prof._bytes = ca.get("bytes accessed")
            prof._duration = self.tput_timer.avg_step_time()
            if self.config.flops_profiler.detailed:
                # per-module tree via named_scope attribution (the model's
                # scopes; optimizer/infra ops stay at the root)
                from ..profiling.flops_profiler.profiler import module_tree
                raw_fn = (self._grad_only_step if self._offload is not None
                          else self._train_step)
                try:
                    with jax.set_mesh(self.mesh):
                        jaxpr = jax.make_jaxpr(raw_fn)(self.state, batch, rng)
                    prof._tree = module_tree(jaxpr)
                except Exception:
                    prof._tree = None
        except Exception as e:
            logger.warning(f"flops profiler cost analysis failed: {e}")
        prof.print_model_profile(
            profile_step=self.config.flops_profiler.profile_step,
            detailed=self.config.flops_profiler.detailed,
            output_file=self.config.flops_profiler.output_file)
        prof.end_profile()

    # ------------------------------------------------------------- reporting
    def _report_progress(self, step, metrics):
        lr = float(metrics["lr"])
        loss = float(metrics["loss"])
        msg = f"step={step}, loss={loss:.6f}, lr={lr:.3e}"
        if self.fp16_enabled:
            msg += (f", loss_scale={float(metrics['loss_scale']):.1f}"
                    f", skipped={int(self.state.skipped_steps)}")
        elif self._health_enabled and bool(metrics.get("skip", False)):
            msg += (f", SKIPPED (health sentinel; total "
                    f"{int(self.state.skipped_steps)})")
        if "moe_aux_loss" in metrics:
            msg += f", moe_aux={float(metrics['moe_aux_loss']):.4f}"
        log_dist(msg, ranks=[0])
        dropped = float(metrics.get("moe_tokens_dropped", 0.0))
        if dropped > 0:
            log_dist(f"WARNING: MoE dropped {dropped:.0f} token-slots this "
                     "step (capacity overflow) — raise capacity_factor / "
                     "max_capacity or enable drop-free gating "
                     "(drop_tokens=False)", ranks=[0])

    def _setup_tensorboard(self):
        """Tensorboard as a monitor-bus sink.

        The old path imported ``torch.utils.tensorboard`` — a torch
        dependency a JAX framework must not carry, and dead in any
        torch-less container.  Now ``tensorboard.enabled`` attaches a
        :class:`monitor.sinks.TensorboardSink` (tensorboardX / flax
        writer) to the engine's event bus, so the scalars it exports are
        the SAME step/gauge events every other sink sees; when no
        non-torch writer is importable it degrades to one warning
        (JSONL/CSV always work)."""
        from ..monitor import core as moncore
        from ..monitor.sinks import TensorboardSink, SinkUnavailable
        if not moncore._is_rank0():
            # same rank-0 gate Monitor.__init__ applies to export sinks:
            # every process writing the same tfevents dir would conflict
            return
        path = os.path.join(self.config.tensorboard.output_path or ".",
                            self.config.tensorboard.job_name)
        try:
            sink = TensorboardSink(path)
        except (SinkUnavailable, OSError) as e:
            logger.warning(f"tensorboard unavailable: {e}")
            return
        if not self.monitor.armed:
            # arm a bus-only monitor so the tensorboard sink has events
            # to consume; no file sinks, nothing else changes
            self.monitor = moncore.Monitor(run_dir=None, sinks=())
        self.monitor.bus.attach(sink)
        # a late-armed (tensorboard-only) monitor must reach the other
        # bus consumers built before it
        self.tput_timer.bus = self.monitor.bus
        if self.health_monitor is not None:
            self.health_monitor.bus = self.monitor.bus

    # ------------------------------------------------------------ properties
    @property
    def global_steps(self):
        return int(self.state.global_steps)

    @property
    def skipped_steps(self):
        return int(self.state.skipped_steps)

    def train_batch_size(self):
        return self.config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self.config.gradient_accumulation_steps

    def zero_optimization_stage(self):
        return self.zero_stage

    def loss_scale(self):
        if self.state.scale is None:
            return 1.0
        return float(self.state.scale.cur_scale)

    def get_lr(self):
        return [float(self._lr_at(self.state.global_steps))]

    def get_global_grad_norm(self):
        m = self._last_metrics.get("grad_norm")
        return float(m) if m is not None else None

    def module_state_dict(self):
        """Full (gathered) params as a host pytree of numpy arrays."""
        self._flush_offload()
        if self._param_stream is not None:
            return self._param_stream.full_params_host()
        return jax.tree_util.tree_map(np.asarray, self.state.params)

    # ----------------------------------------------------------- checkpoints
    def _get_ckpt_name(self, checkpoints_path, tag):
        return os.path.join(checkpoints_path, str(tag))

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True):
        """Parity: reference ``engine.py:2797``.  Layout:
        ``<dir>/<tag>/{model,optim}_states.msgpack`` + ``<dir>/latest``.
        Arrays are gathered to host; ZeRO-sharded state is saved in full so
        checkpoints reshard freely across mesh-size changes (the reference
        needs ``elastic_checkpoint`` machinery for this; here resharding is a
        device_put).

        Crash-consistent (docs/fault-tolerance.md): every file goes into a
        ``<tag>.tmp`` staging dir, a SHA-256 manifest is recorded, and the
        checkpoint is published by one ``os.rename``; the ``latest`` pointer
        is updated write-temp-then-rename only after commit.  A kill at any
        instant leaves either the previous checkpoint set intact or the new
        tag fully committed — never a torn tag that ``latest`` points at."""
        from ..checkpoint.serialization import save_tree
        from ..checkpoint import atomic
        from .. import fault
        self._flush_offload()
        if self.health_monitor is not None:
            # drain the monitor's lag window so the saved run's history is
            # complete; the returned action is intentionally discarded —
            # if the drained steps warrant escalation, the still-elevated
            # counters re-trigger it on the next training step, not from
            # inside a save
            self.health_monitor.flush()
        tag = tag or f"global_step{self.global_steps}"
        retry = self.config.io_retry_config.policy()
        fsync = self.config.checkpoint_config.fsync
        os.makedirs(save_dir, exist_ok=True)
        # drop staging leftovers of killed saves (any tag) and restore an
        # orphaned `.replaced` before staging anew
        atomic.clean_stale_staging(save_dir)
        path = atomic.stage_path(save_dir, tag)
        os.makedirs(path)

        engine_meta = {
            "global_steps": self.global_steps,
            "optimizer_steps": int(self.state.optimizer_steps),
            "skipped_steps": self.skipped_steps,
            "micro_steps": self.micro_steps,
            "global_samples": self.global_samples,
            "zero_stage": self.zero_stage,
            "dtype": self.config.precision_dtype,
            # elastic-resume record (docs/elasticity.md): the mesh this
            # state was partitioned on + the global batch it was trained
            # at, so a resume on a DIFFERENT mesh can verify the resize is
            # a pure re-partition (global batch preserved) and log the
            # re-layout instead of silently changing training semantics
            "mesh": {k: int(v) for k, v in dict(self.mesh.shape).items()},
            "dp_world_size": self.mesh_ctx.dp_world_size,
            "train_batch_size": self.train_batch_size(),
            "elasticity": self.config.elastic_record,
            "client_state": client_state or {},
            "lr_scheduler": (self.lr_scheduler.state_dict()
                             if self.lr_scheduler is not None and
                             hasattr(self.lr_scheduler, "state_dict") else None),
            # data-pipeline state: sampler (seed, epoch, batch index) +
            # monotonic stream position, so load/auto_resume/rewind resume
            # the EXACT batch stream (docs/health-monitor.md)
            "data_state": {
                "stream_step": self._stream_step,
                "loader": (self.training_dataloader.state_dict()
                           if self.training_dataloader is not None and
                           hasattr(self.training_dataloader, "state_dict")
                           else None),
            },
        }
        params_out = (self._param_stream.full_params_host()
                      if self._param_stream is not None
                      else self.state.params)
        # fsync deferred to commit_staged: one durability pass per file,
        # not two (the manifest hash reads the page cache either way)
        save_tree(os.path.join(path, MODEL_FILE),
                  {"params": params_out}, meta=engine_meta,
                  fsync=False, retry=retry)
        fault.site("ckpt.after_model_file")
        if self._offload is not None:
            # host-resident state saved in the SAME layout as the in-device
            # AdamState (param-shaped moment pytrees + full master pytree),
            # so offload/non-offload runs can load each other's checkpoints
            # and zero_to_fp32 consolidation works unchanged.  Streamed mode
            # converts its layer-major trees back to the stacked model tree.
            moments = self._offload.moments_tree()
            master = self._offload.master_tree()
            if self._param_stream is not None:
                from .zero.param_stream import from_stream_tree
                key = self._param_stream.sf["stacked_key"]
                moments = {k: from_stream_tree(v, key)
                           for k, v in moments.items()}
                master = from_stream_tree(master, key)
            optim_tree = {"opt_state": moments, "master": master}
        else:
            optim_tree = {"opt_state": self.state.opt_state}
            if self.state.master is not None:
                optim_tree["master"] = self.state.master
        if self.state.scale is not None:
            optim_tree["scale"] = self.state.scale
        if self.state.comm_error is not None:
            # qgZ error feedback: without it a resumed run would re-pay
            # the compensation warm-up (rewind-safe like `health`)
            optim_tree["comm_error"] = self.state.comm_error
        save_tree(os.path.join(path, OPTIM_FILE), optim_tree,
                  fsync=False, retry=retry)
        fault.site("ckpt.after_optim_file")

        # everything that belongs to the tag — recovery script and gathered
        # 16-bit weights included — is staged and manifested BEFORE commit
        self._copy_recovery_script(path)
        if self.config.zero_config.gather_16bit_weights_on_model_save:
            self.save_16bit_model(path, fsync=False, retry=retry)
        atomic.write_manifest(path, meta={
            "tag": tag,
            "global_steps": self.global_steps,
            "format_version": 1,
        })
        fault.site("ckpt.before_commit")
        with self.monitor.standalone_span("checkpoint_commit"):
            final = atomic.commit_staged(save_dir, tag, fsync=fsync)
        self.monitor.artifact("checkpoint", final, tag=tag,
                              global_steps=self.global_steps)
        fault.site("ckpt.after_commit")
        if save_latest:
            atomic.write_latest(save_dir, tag)
        keep_n = self.config.checkpoint_config.keep_n
        if keep_n:
            # rotation's newest-valid probe uses the cheap size level: the
            # retained tags were hash-verified at commit, and re-hashing
            # them all on every save would put O(keep_n · ckpt_bytes) of
            # SHA-256 on the training hot path
            atomic.rotate_checkpoints(save_dir, keep_n)
        self._last_ckpt_dir = save_dir   # rewind target of last resort
        log_dist(f"saved checkpoint {final}", ranks=[0])
        return True

    def _copy_recovery_script(self, save_path):
        """Drop zero_to_fp32.py beside the checkpoint so weights can be
        extracted without this framework installed (parity: reference
        ``engine.py:3095 _copy_recovery_script``)."""
        import shutil
        from ..utils import zero_to_fp32 as z2f
        src = z2f.__file__
        dst = os.path.join(save_path, "zero_to_fp32.py")
        try:
            shutil.copy2(src, dst)
            os.chmod(dst, 0o755)
        except OSError as e:
            logger.warning(f"could not copy recovery script: {e}")

    def save_16bit_model(self, save_dir, save_filename="model_16bit.msgpack",
                         fsync=True, retry=None):
        """Save the full (gathered) params in the 16-bit compute dtype
        (parity: reference ``engine.py:3194 save_16bit_model`` /
        ``_zero3_consolidated_16bit_state_dict`` :3118 — with sharded state
        the gather here is just the host transfer in ``save_tree``)."""
        from ..checkpoint.serialization import save_tree
        self._flush_offload()
        os.makedirs(save_dir, exist_ok=True)
        path = os.path.join(save_dir, save_filename)
        params_out = (self._param_stream.full_params_host()
                      if self._param_stream is not None
                      else self.state.params)
        save_tree(path, {"params": params_out},
                  meta={"dtype": self.config.precision_dtype},
                  fsync=fsync, retry=retry)
        log_dist(f"saved 16-bit model to {path}", ranks=[0])
        return True

    def _resolve_checkpoint_tag(self, load_dir, tag):
        """Validating, self-healing tag resolution (docs/fault-tolerance.md):

        - explicit ``tag``: manifest must verify, else raise
          ``CheckpointValidationError`` (the caller asked for *that* state);
        - ``latest`` pointer: verify; on mismatch, a missing pointer, or a
          pointer at a torn/uncommitted tag, fall back to the newest valid
          tag with one structured warning;
        - a tag without a manifest (pre-fault-tolerance layout) loads with a
          warning instead of failing — old checkpoints stay readable.
        """
        from ..checkpoint import atomic
        # restore an orphaned `.replaced` (killed same-tag re-commit) on
        # EVERY load path, not just auto_resume.  `.tmp` cleanup is age-
        # guarded here: a reader sharing a live trainer's dir must not
        # delete an in-flight save's staging dir (loads never need the
        # cleanup for correctness; the next save sweeps the garbage)
        atomic.clean_stale_staging(load_dir,
                                   min_age_s=atomic.LOAD_STAGING_MIN_AGE_S)
        verify = self.config.checkpoint_config.verify
        explicit = tag is not None
        problems = []
        if tag is None:
            tag = atomic.read_latest(load_dir)
            if tag is None:
                problems.append(f"no `latest` pointer in {load_dir}")
        if tag is not None:
            path = self._get_ckpt_name(load_dir, tag)
            # legacy = the manifest FILE is absent but state files are
            # there; an unparseable manifest is a torn checkpoint, not a
            # pre-fault-tolerance one
            if atomic.is_legacy_checkpoint(path):
                logger.warning(
                    f"checkpoint {path} has no manifest (pre-fault-tolerance "
                    f"layout); loading without integrity verification")
                return tag
            ok, tag_problems = atomic.verify_checkpoint(path, level=verify)
            if ok:
                return tag
            if explicit:
                raise atomic.CheckpointValidationError(
                    f"checkpoint {path} failed validation: {tag_problems}")
            problems.extend(tag_problems)
        fallback = atomic.find_latest_valid(
            load_dir, exclude=(tag,) if tag else (), level=verify)
        if fallback is None:
            # last resort: a pre-fault-tolerance tag the validity scan
            # cannot vouch for is still better than refusing restorable
            # state (manifested-but-invalid tags never land here — a
            # manifest file, even a corrupt one, means post-upgrade)
            legacy = [t for t in atomic.find_legacy_tags(load_dir)
                      if t != tag]
            if legacy:
                logger.warning("checkpoint fallback engaged: " + json.dumps({
                    "event": "checkpoint_fallback", "load_dir": load_dir,
                    "unusable_tag": tag, "problems": problems,
                    "fallback_tag": legacy[0], "legacy": True}))
                return legacy[0]
            raise FileNotFoundError(
                f"no loadable checkpoint in {load_dir}: {problems}")
        logger.warning("checkpoint fallback engaged: " + json.dumps({
            "event": "checkpoint_fallback", "load_dir": load_dir,
            "unusable_tag": tag, "problems": problems,
            "fallback_tag": fallback}))
        return fallback

    def _check_mesh_transition(self, meta):
        """Elastic resume-on-resize gate (docs/elasticity.md): compare the
        checkpoint's recorded mesh with the current one.

        - identical mesh: nothing to do (the common restart).
        - no record: pre-elastic checkpoint — reshard anyway (the on-disk
          form is full arrays), but warn that global-batch preservation
          cannot be verified.
        - different mesh: a reshard-on-resize event.  The resize is a pure
          re-partition only when the GLOBAL batch is preserved (ZeRO shard
          layout is a function of world size — arXiv 1910.02054 — but the
          optimizer trajectory is a function of the batch): with
          elasticity enabled a changed global batch means the elasticity
          block itself changed (it is a pure function of that block), so
          raise; without elasticity, warn loudly and continue.  The
          re-layout of the ZeRO placements is logged as one structured
          event (``relayout_report``).
        """
        cur_mesh = {k: int(v) for k, v in dict(self.mesh.shape).items()}
        saved_mesh = meta.get("mesh")
        if saved_mesh is None:
            logger.warning(
                "pre-elastic checkpoint: no mesh/batch record in the "
                f"checkpoint meta; resharding onto mesh {cur_mesh} "
                "proceeds, but global-batch preservation cannot be "
                "verified — if the device count changed, loss-curve "
                "continuity is not guaranteed (enable `elasticity` and "
                "re-save to make checkpoints resize-aware)")
            return
        saved_mesh = {k: int(v) for k, v in saved_mesh.items()}
        if saved_mesh == cur_mesh:
            return
        saved_tb = meta.get("train_batch_size")
        cur_tb = self.train_batch_size()
        event = {"event": "elastic_resume",
                 "from_mesh": saved_mesh, "to_mesh": cur_mesh,
                 "from_dp_world": meta.get("dp_world_size"),
                 "to_dp_world": self.mesh_ctx.dp_world_size,
                 "global_batch": {"from": saved_tb, "to": cur_tb,
                                  "preserved": saved_tb == cur_tb},
                 "elastic": bool(self.config.elasticity_enabled)}
        if saved_tb is not None and saved_tb != cur_tb:
            if self.config.elasticity_enabled:
                # the elastic final batch is a pure function of the
                # elasticity block — a mismatch means the block changed
                # between save and resume, which silently changes the
                # optimizer trajectory; refuse rather than drift
                from ..elasticity import ElasticityConfigError
                raise ElasticityConfigError(
                    f"elastic resume would change the global batch "
                    f"{saved_tb} -> {cur_tb}: the `elasticity` block does "
                    f"not match the one the checkpoint was trained with "
                    f"(saved record: {meta.get('elasticity')})")
            logger.warning(
                "resuming on a different mesh WITHOUT elasticity: the "
                f"global batch changes {saved_tb} -> {cur_tb}, which "
                "changes training semantics (lr schedule, convergence). "
                "Enable `elasticity` (or `deepspeed --elastic`) to pick a "
                "(micro_batch, gas) pair that preserves it.")
        old_fsdp = int(saved_mesh.get("fsdp", 1))
        new_fsdp = self.mesh_ctx.fsdp_size
        if self.state is not None and self.state.params is not None \
                and old_fsdp != new_fsdp:
            event["relayout"] = zpart.relayout_report(
                self.state.params, self.zero_stage, old_fsdp, new_fsdp,
                persistence_threshold=(self.config.zero_config
                                       .param_persistence_threshold),
                tp_specs=self._tp_specs)
        log_dist("elastic resume: " + json.dumps(event), ranks=[0])
        if self.monitor.armed:
            # the same record on the telemetry stream (one schema)
            self.monitor.counter(
                "elastic_resume", 1,
                from_mesh=json.dumps(saved_mesh),
                to_mesh=json.dumps(cur_mesh),
                global_batch_preserved=bool(saved_tb == cur_tb))

    def load_checkpoint(self, load_dir, tag=None, load_module_only=False,
                        load_optimizer_states=True, load_lr_scheduler_states=True):
        """Parity: reference ``engine.py:2467``. Returns (path, client_state).

        Loads only manifest-verified checkpoints; see
        ``_resolve_checkpoint_tag`` for the fallback policy."""
        from ..checkpoint.serialization import load_tree
        # a pending delayed update is superseded by the loaded state —
        # and so are its drop counters (they describe discarded steps)
        self._pending_offload = None
        self._pending_row_drop_checks = []
        tag = self._resolve_checkpoint_tag(load_dir, tag)
        path = self._get_ckpt_name(load_dir, tag)
        self.loaded_checkpoint_tag = tag
        retry = self.config.io_retry_config.policy()

        from ..checkpoint.serialization import reshard_put, restore_like
        model_tree, meta = load_tree(os.path.join(path, MODEL_FILE),
                                     with_meta=True, retry=retry)
        # elastic resume (docs/elasticity.md): validate a mesh change
        # BEFORE restoring anything — the checkpoint stores full (gathered)
        # arrays, so re-partitioning onto this mesh is the reshard_put
        # below, but the resize is only training-equivalent when the
        # global batch is preserved
        self._check_mesh_transition(meta)
        state = self.state
        if self._offload is None:
            # (offload path uploads once from the restored host master below)
            state = state._replace(params=reshard_put(
                model_tree["params"], self.state.params, self._param_sh))
        if state.master is not None:
            # keep the fp32 master coherent with the loaded params NOW; if
            # optimizer states are loaded below this is overwritten with the
            # checkpointed master, otherwise (load_module_only) the train step
            # would silently resume from the stale master.
            state = state._replace(master=reshard_put(
                model_tree["params"], state.master, self._master_sh,
                cast=np.float32))

        loaded_ef = None
        if self._offload is not None:
            # host tier: master/moments restored into the offload buffers;
            # the device payload is refreshed from the loaded master.
            # Streamed mode converts checkpoint (stacked) trees into its
            # layer-major layout first.
            if self._param_stream is not None:
                from .zero.param_stream import to_stream_tree
                skey = self._param_stream.sf["stacked_key"]
                conv = lambda t: (to_stream_tree(t, skey)
                                  if t is not None else None)
            else:
                conv = lambda t: t
            self._offload.load_state(master_tree=conv(model_tree["params"]))
            if load_optimizer_states and not load_module_only:
                optim_tree, _ = load_tree(os.path.join(path, OPTIM_FILE),
                                          with_meta=True, retry=retry)
                opt = optim_tree.get("opt_state", {})
                self._offload.load_state(
                    master_tree=conv(optim_tree.get("master")),
                    m=conv(opt.get("exp_avg")), v=conv(opt.get("exp_avg_sq")))
                if "scale" in optim_tree and state.scale is not None:
                    state = state._replace(scale=jax.device_put(
                        restore_like(state.scale, optim_tree["scale"]),
                        self._repl_sh))
                loaded_ef = optim_tree.get("comm_error")
            if self._param_stream is not None:
                self._param_stream.reload_from_host()
            else:
                state = state._replace(params=jax.device_put(
                    self._offload.payload_tree(), self._param_sh))
        elif load_optimizer_states and not load_module_only:
            optim_tree, _ = load_tree(os.path.join(path, OPTIM_FILE),
                                      with_meta=True, retry=retry)
            opt_state = reshard_put(optim_tree["opt_state"],
                                    self.state.opt_state,
                                    self._opt_shardings(self.state.opt_state))
            master = state.master
            if "master" in optim_tree and master is not None:
                master = reshard_put(optim_tree["master"], master,
                                     self._master_sh)
            scale = state.scale
            if "scale" in optim_tree and scale is not None:
                scale = jax.device_put(
                    restore_like(scale, optim_tree["scale"]), self._repl_sh)
            state = state._replace(opt_state=opt_state, master=master, scale=scale)
            loaded_ef = optim_tree.get("comm_error")

        if state.comm_error is not None:
            # qgZ error feedback: reset, then restore when the checkpoint
            # carries a matching state (a pre-compression checkpoint, or
            # one from a different mesh/policy, restarts compensation
            # from zero — EF is an accumulator, resetting is always safe)
            def _ef_leaf(cur, new):
                new = np.asarray(new)
                if new.shape != cur.shape:
                    raise ValueError(
                        f"comm_error leaf shape {new.shape} != {cur.shape}")
                return jax.device_put(new.astype(cur.dtype), cur.sharding)

            ef = jax.tree_util.tree_map(
                lambda cur: jax.device_put(
                    np.zeros(cur.shape, cur.dtype), cur.sharding),
                state.comm_error)
            if loaded_ef is not None:
                try:
                    ef = jax.tree_util.tree_map(
                        _ef_leaf, state.comm_error,
                        restore_like(state.comm_error, loaded_ef))
                except Exception as e:
                    logger.warning(
                        "checkpoint comm_error does not match the current "
                        f"compression policy/mesh ({e}); error feedback "
                        "reset to zero")
            state = state._replace(comm_error=ef)

        mk = lambda v: jax.device_put(jnp.asarray(v, jnp.int32), self._repl_sh)
        self._global_steps_host = int(meta["global_steps"])
        state = state._replace(global_steps=mk(meta["global_steps"]),
                               optimizer_steps=mk(meta["optimizer_steps"]),
                               skipped_steps=mk(meta["skipped_steps"]),
                               # fresh EMA: the loaded run must not inherit
                               # loss statistics of the steps just discarded
                               health=self._init_health_device()
                               if state.health is not None else None)
        self.state = state
        self.micro_steps = meta.get("micro_steps", 0)
        self.global_samples = meta.get("global_samples", 0)
        # data-pipeline state: restore the sampler position so replay
        # resumes the exact batch stream (pre-guardian checkpoints carry
        # none — the stream then restarts, as before)
        data_state = meta.get("data_state") or {}
        self._stream_step = int(data_state.get("stream_step", 0))
        self._last_batch_index = None
        if (data_state.get("loader") is not None
                and self.training_dataloader is not None
                and hasattr(self.training_dataloader, "load_state_dict")):
            exact = self.training_dataloader.load_state_dict(
                data_state["loader"])
            # rebuild the engine-owned iterator over the restored position
            self._data_iterator = iter(
                RepeatingLoader(self.training_dataloader))
            # a mesh resize changes the loader's global micro-batch; the
            # position converts through rows (loader state carries its
            # batch_size) and stays EXACT at optimizer-step boundaries —
            # only an off-boundary conversion (floored, rows replay)
            # degrades the stream position to unknown for fast-forward
            self._stream_pos_known = exact is not False
        else:
            # pre-guardian checkpoint (or no engine-owned loader): the live
            # iterator's position no longer matches _stream_step, so a
            # rewind must not fast-forward against it
            self._stream_pos_known = False
        if self.health_monitor is not None:
            self.health_monitor.on_checkpoint_load()
        if self._param_stream is not None:
            self._param_stream.reset_health_ema()
        self._last_ckpt_dir = load_dir
        if (load_lr_scheduler_states and self.lr_scheduler is not None
                and meta.get("lr_scheduler") is not None
                and hasattr(self.lr_scheduler, "load_state_dict")):
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        log_dist(f"loaded checkpoint {path} at global_step={meta['global_steps']}",
                 ranks=[0])
        return path, meta.get("client_state", {})
