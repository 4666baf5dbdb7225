"""deepspeed_tpu — TPU-native training framework with DeepSpeed's capabilities.

API facade parity: reference ``deepspeed/__init__.py`` —
``initialize`` (:51), ``init_inference`` (:221), ``add_config_arguments``
(:205), ``init_distributed``.  Built from scratch on JAX/XLA/Pallas; the
compute path is jitted SPMD over a named device mesh, not a port of the
reference's torch/CUDA machinery.
"""

import sys as _sys
import time as _time
_T_IMPORT = _time.monotonic()           # setup.import starts here
_JAX_PRELOADED = "jax" in _sys.modules

from .version import __version__
from .runtime.activation_checkpointing import checkpointing
from .runtime.engine import DeepSpeedEngine
from .runtime.config import DeepSpeedConfig
from .runtime.health import HealthMonitor, TrainingHealthError
from .runtime.lr_schedules import get_lr_scheduler
from .runtime import zero
from .utils.logging import logger, log_dist
from .monitor import spans as _spans


@_spans.in_setup_span("setup.engine_init", engine="train")
def initialize(args=None, model=None, optimizer=None, model_parameters=None,
               training_data=None, lr_scheduler=None, mpu=None,
               dist_init_required=None, collate_fn=None, config=None,
               config_params=None, mesh=None, loss_fn=None, params=None,
               apply_fn=None, rng_seed=0, auto_resume=None, elastic=None,
               monitor=None):
    """Initialize the engine. Returns ``(engine, optimizer, dataloader, lr_scheduler)``.

    Parity: reference ``deepspeed/__init__.py:51-151``.  ``args.deepspeed_config``
    is honored when ``config`` is not given.  If the model is a
    ``PipelineModule``, a ``PipelineEngine`` is built instead
    (reference ``__init__.py:119-143``).

    ``auto_resume=True`` (or config ``checkpoint.auto_resume``, or env
    ``DSTPU_AUTO_RESUME=1`` as set by ``deepspeed --auto-resume``) restarts
    the job from the newest *valid* checkpoint under ``checkpoint.dir`` when
    one exists — the restart path of a preempted TPU job
    (docs/fault-tolerance.md).  A missing or empty checkpoint dir is a
    normal cold start, not an error.

    ``elastic=True`` (or env ``DSTPU_ELASTIC=1`` as set by ``deepspeed
    --elastic``) turns the config's ``elasticity`` block on without editing
    the JSON: the (micro_batch, gas) pair is recomputed from the elastic
    schedule at THIS world size, so a preempted job relaunched on a
    different chip count keeps its global batch and ``auto_resume`` can
    re-partition the checkpoint onto the new mesh (docs/elasticity.md).
    Combined, ``--elastic --auto-resume`` is the full
    preemption-survival path.

    ``monitor=True`` (or env ``DSTPU_MONITOR=1`` as set by ``deepspeed
    --monitor``, or config ``monitor.enabled``) arms the unified runtime
    telemetry bus (``deepspeed_tpu/monitor``; docs/monitoring.md):
    per-step spans, MFU/memory gauges, wire-byte counters and trace
    capture streamed as JSONL for ``python -m deepspeed_tpu.monitor``
    (``ds_top``) to tail.  ``monitor=False`` forces it off against both.
    """
    if config is None and config_params is not None:
        config = config_params
    if config is None and args is not None and \
            getattr(args, "deepspeed_config", None) is not None:
        config = args.deepspeed_config
    assert config is not None, \
        "DeepSpeed requires --deepspeed_config to specify configuration file"

    try:
        from .runtime.pipe.module import PipelineModule
        is_pipe = isinstance(model, PipelineModule)
    except ImportError:
        is_pipe = False
    if is_pipe:
        from .runtime.pipe.engine import PipelineEngine
        engine = PipelineEngine(model=model, optimizer=optimizer, config=config,
                                training_data=training_data,
                                lr_scheduler=lr_scheduler, mesh=mesh,
                                collate_fn=collate_fn, rng_seed=rng_seed,
                                elastic=elastic, monitor=monitor)
    else:
        engine = DeepSpeedEngine(model=model, optimizer=optimizer, config=config,
                                 training_data=training_data,
                                 lr_scheduler=lr_scheduler, mesh=mesh,
                                 collate_fn=collate_fn, loss_fn=loss_fn,
                                 params=params, apply_fn=apply_fn,
                                 rng_seed=rng_seed, mpu=mpu,
                                 dist_init_required=dist_init_required,
                                 elastic=elastic, monitor=monitor)
    _maybe_auto_resume(engine, auto_resume)
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def _maybe_auto_resume(engine, auto_resume):
    """Resolve the auto-resume request (kwarg > env > config) and restart
    from the newest valid checkpoint in ``checkpoint.dir`` if any."""
    import os
    ckpt_cfg = engine.config.checkpoint_config
    if auto_resume is None:
        # precedence: kwarg > env (when set, can also DISABLE) > config
        env = os.environ.get("DSTPU_AUTO_RESUME")
        if env:
            auto_resume = env.lower() in ("1", "true", "yes")
        else:
            auto_resume = ckpt_cfg.auto_resume
    if not auto_resume:
        return
    load_dir = ckpt_cfg.dir
    if not load_dir:
        from .runtime.config import DeepSpeedConfigError
        raise DeepSpeedConfigError(
            "auto_resume needs checkpoint.dir in the config (where to look)")
    from .checkpoint import atomic
    atomic.clean_stale_staging(load_dir,
                               min_age_s=atomic.LOAD_STAGING_MIN_AGE_S)
    # cheap cold-start detection only; tag resolution + manifest
    # verification (and torn-tag fallback) happen inside load_checkpoint
    if not atomic.has_checkpoint(load_dir):
        log_dist(f"auto_resume: no checkpoint in {load_dir}; cold start",
                 ranks=[0])
        return
    path, _ = engine.load_checkpoint(load_dir)
    log_dist(f"auto_resume: restarted from {path}", ranks=[0])


def init_distributed(dist_backend=None, auto_mpi_discovery=True,
                     distributed_port=29500, verbose=True, timeout=None,
                     init_method=None):
    """Multi-host runtime init.

    Parity: reference ``deepspeed/utils/distributed.py:12``.  On TPU pods this
    is ``jax.distributed.initialize()`` (one process per host); single-host it
    is a no-op.  NCCL/MPI rendezvous is replaced by the TPU runtime's own
    coordination service.
    """
    import os
    import jax
    # JAX auto-discovers the coordinator on TPU pods (metadata service), SLURM,
    # and Open MPI; call initialize() whenever any multi-host signal is present.
    multi_host_signals = ("COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS",
                          "MEGASCALE_COORDINATOR_ADDRESS", "TPU_WORKER_HOSTNAMES",
                          "TPU_WORKER_ID", "SLURM_JOB_ID", "OMPI_COMM_WORLD_SIZE")
    if any(os.environ.get(k) for k in multi_host_signals):
        try:
            jax.distributed.initialize()
            log_dist(f"jax.distributed initialized: process "
                     f"{jax.process_index()}/{jax.process_count()}", ranks=[0])
        except Exception as e:  # already initialized or effectively single-host
            logger.debug(f"jax.distributed.initialize skipped: {e}")
    return None


def add_config_arguments(parser):
    """Add ``--deepspeed``/``--deepspeed_config`` args.

    Parity: reference ``deepspeed/__init__.py:205``.
    """
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag to indicate use)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the DeepSpeed JSON config file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help="Deprecated alias of --deepspeed")
    group.add_argument("--deepscale_config", default=None, type=str,
                       help="Deprecated alias of --deepspeed_config")
    group.add_argument("--local_rank", type=int, default=-1,
                       help="Accepted for launcher compatibility; unused on TPU "
                            "(one process drives all local chips)")
    return parser


def init_inference(model=None, **kwargs):
    """Build an InferenceEngine. Parity: reference ``deepspeed/__init__.py:221``."""
    from .inference.engine import InferenceEngine
    return InferenceEngine(model, **kwargs)


# the package's own import, top to bottom (monitor/startup.py: "import")
_spans.recorder().setup_record("setup.import", _T_IMPORT, _time.monotonic(),
                               attrs={"jax_preloaded": _JAX_PRELOADED})
