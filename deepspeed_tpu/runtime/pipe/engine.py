"""PipelineEngine: pipelined training as ONE jitted SPMD program.

Parity: reference ``deepspeed/runtime/pipe/engine.py`` — ``PipelineEngine``
(:46), ``train_batch`` (:302), ``_exec_schedule`` (:1368) dispatching
``_INSTRUCTION_MAP`` (:1355) over p2p send/recv (``pipe/p2p.py``).

TPU-native redesign: the reference interprets the instruction IR, issuing one
NCCL p2p per edge and one autograd call per micro-batch.  Here the ENTIRE
schedule — every tick of every stage — is a single ``lax.scan`` inside a
``shard_map`` over the ``pipe`` mesh axis:

- tick t, stage s computes micro-batch ``t - s`` (the IR's semantics,
  ``schedule.py``); total ticks = M + S - 1;
- stage-to-stage transfer = ``ppermute`` ring rotation (the p2p of
  ``pipe/p2p.py:48,69``), which XLA overlaps with compute over ICI;
- TRAINING runs the 1F1B timetable with a HAND-WRITTEN backward: each scan
  tick performs (at most) one forward micro-batch AND one backward
  micro-batch per stage.  Stage ``s`` forwards micro-batch ``f`` at tick
  ``f + s`` and backwards micro-batch ``b`` at tick ``b + 2S - 1 - s`` —
  the cotangent produced by stage ``s+1`` at tick ``t`` arrives at stage
  ``s`` exactly at tick ``t + 1``.  Saved state is a circular buffer of
  ``num_pipe_buffers = 2S`` boundary activations per stage (the reference's
  ``schedule.py:243 num_pipe_buffers`` bound), so live memory is **O(S),
  independent of M** — the 1F1B property the reference's ``TrainSchedule``
  (``schedule.py:182``) exists to provide.  Backward recomputes the stage
  body from the saved boundary input (1F1B + activation checkpointing);
- forward sends are ``ppermute`` ring rotations (the p2p of
  ``pipe/p2p.py:48,69``); backward cotangent sends are the reverse rotation
  (reference ``_exec_send_grads``/``_exec_recv_grads``); XLA overlaps both
  with compute over ICI;
- tied-weight gradient reduction (reference ``_exec_reduce_tied_grads`` :240)
  is a psum over 'pipe' of the prologue/epilogue cotangents (stage 0
  contributes the embedding-use grads, stage S-1 the head-use grads);
- the first-iteration tensor-shape handshake (``:836 _send_tensor_meta``)
  disappears — shapes are static under jit;
- loss aggregation from the last stage (``:552 _aggregate_total_loss``) is a
  masked psum.

EVALUATION (forward only) keeps the simpler all-forward scan
(``_pipeline_loss``), which needs no saved activations at all.
"""
# dstpu: disable-file=DSTPU102 (reviewed: the pipeline schedule IS the
# collective choreography -- ppermute ring order is the 1F1B timetable)

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ...utils.logging import log_dist
from ..engine import DeepSpeedEngine
from ..utils import tree_cast
from ..zero import partition as zpart
from .module import PipelineModule
from .schedule import TrainSchedule, InferenceSchedule


def _split_labels(batch):
    """(inputs, labels) from a stacked micro-batch pytree.

    Accepted shapes: ``(inputs, labels)`` tuples (reference pipeline data
    contract, ``pipe/engine.py:795 _exec_load_micro_batch``) or dicts with a
    ``'labels'`` key.  Anything else is rejected rather than silently trained
    with ``labels == inputs``.
    """
    if isinstance(batch, (tuple, list)) and len(batch) >= 2:
        return batch[0], batch[1]
    if isinstance(batch, dict) and "labels" in batch:
        inputs = {k: v for k, v in batch.items() if k != "labels"}
        if len(inputs) == 1:
            inputs = next(iter(inputs.values()))
        return inputs, batch["labels"]
    raise ValueError(
        "PipelineEngine batches must be (inputs, labels) tuples or dicts "
        f"with a 'labels' key; got {type(batch).__name__}")


class PipelineEngine(DeepSpeedEngine):
    """Config/mesh-driven pipeline-parallel engine.

    ``gradient_accumulation_steps`` doubles as the micro-batch count M
    (reference: ``train_batch() = micro_batches`` micro-steps,
    ``pipe/engine.py:302``).
    """

    # 1F1B schedules its own collectives (ppermute activations, per-tick
    # grad accumulation); the qwZ/qgZ wire rewrite does not apply — the
    # `pipe` comms_compression route is accepted-but-full-width
    # (docs/comms-compression.md)
    _supports_comms_compression = False

    def __init__(self, model=None, **kwargs):
        assert isinstance(model, PipelineModule), \
            "PipelineEngine requires a PipelineModule"
        super().__init__(model=model, loss_fn=self._no_flat_loss, **kwargs)
        S = self.mesh_ctx.pipe_size
        assert S == model.num_stages, \
            (f"mesh pipe axis ({S}) != PipelineModule.num_stages "
             f"({model.num_stages}); set config mesh.axes.pipe")
        self.num_stages = model.num_stages
        self.micro_batches = self.gradient_accumulation_steps()
        if self.config.grad_accum_dtype != "fp32":
            from ...utils.logging import logger
            logger.warning(
                "data_types.grad_accum_dtype is ignored by the pipeline "
                "engine: 1F1B accumulates per-tick gradients in fp32 (the "
                "bf16 option applies to the gas scan of the non-pipeline "
                "engine)")

    @staticmethod
    def _no_flat_loss(params, batch, rng):
        raise RuntimeError("PipelineEngine computes loss via the pipelined "
                           "schedule; flat loss_fn is unused")

    # ------------------------------------------------------------ schedules
    def train_schedule(self, stage_id=0):
        """The instruction-IR view of what the fused program executes."""
        return TrainSchedule(micro_batches=self.micro_batches,
                             stages=self.num_stages, stage_id=stage_id)

    def inference_schedule(self, stage_id=0):
        return InferenceSchedule(micro_batches=self.micro_batches,
                                 stages=self.num_stages, stage_id=stage_id)

    # ------------------------------------------------------------- gradients
    @property
    def num_pipe_buffers(self):
        """1F1B live-activation bound per stage (reference
        ``schedule.py:243``): independent of micro-batch count M."""
        return 2 * self.num_stages

    def _grad_fn(self, base, batch, rng, cur_scale):
        """Pipelined 1F1B forward/backward (replaces the gas scan).

        The cast (master→compute) and the sharding constraint are linear /
        identity maps, so gradients w.r.t. ``base`` equal the hand-computed
        gradients w.r.t. the casted params, cast back to fp32.

        Returns the base engine's (grads, scaled_loss, aux) contract, so
        the shared ``_train_step`` — including the health guardian's
        on-device sentinels and branchless skip-step — applies unchanged to
        the pipelined program: a NaN riding the ppermute ring propagates
        into the psum'd loss/grads, trips the non-finite sentinels, and the
        step is ``where``-selected to a no-op on every stage's params.
        """
        dtype = self.compute_dtype
        needs_master = dtype != jnp.float32
        p = tree_cast(base, dtype) if needs_master else base
        p = zpart.constrain(p, self._param_specs, self.mesh)
        scaled_loss, grads = self._pipeline_grads(p, batch, rng, cur_scale)
        return grads, scaled_loss, {}

    def _pipeline_grads(self, params, batch, rng, cur_scale):
        """Hand-scheduled 1F1B: returns ``(mean_loss * cur_scale, grads)``
        with fp32 grads structured like ``params``.

        Timetable (stage ``s`` of ``S``, micro-batch index in ``[0, M)``,
        ticks ``t in [0, M + 2S - 1)``):

        - forward of micro-batch ``f`` runs at tick ``t = f + s``;
        - backward of micro-batch ``b`` runs at tick ``t = b + 2S - 1 - s``;
        - both transfers are one-tick ppermutes, so activations/cotangents
          arrive exactly when consumed.

        A micro-batch's boundary input is held for ``2(S - s) - 1`` ticks in a
        ``2S``-slot circular buffer.  With
        ``activation_checkpoint_interval >= 1`` the stage body is recomputed
        from it in backward (activation checkpointing) — live activation
        memory is O(S·micro) where the reference's GPipe profile is
        O(M·micro).  With ``interval == 0`` (reference semantics: no
        checkpointing, ``runtime/pipe/engine.py:719`` runs backward on stored
        activations) the forward tick runs under ``jax.vjp`` and the
        *residuals* ride the same circular buffer — ``jax.vjp``'s pullback is
        a pytree, so its leaves scan-carry like any activation — trading
        O(S·micro·L) residual memory for a backward with no re-forward.
        """
        module = self.module
        S = self.num_stages
        B = self.num_pipe_buffers
        inputs, labels = _split_labels(batch)
        M = jax.tree_util.tree_leaves(inputs)[0].shape[0]
        T = M + 2 * S - 1
        interval = int(module.activation_checkpoint_interval)
        L = module.layers_per_stage

        def per_stage(stages_local, other_p, inp, lab, key):
            s = lax.axis_index("pipe")
            local = jax.tree_util.tree_map(lambda a: a[0], stages_local)
            is_last = s == S - 1

            def _vary_one(a):
                if "pipe" in jax.typeof(a).vma:
                    return a        # pcast rejects varying→varying
                return lax.pcast(a, ("pipe",), to="varying")
            varying = lambda v: jax.tree_util.tree_map(_vary_one, v)
            # CRITICAL: differentiate w.r.t. a pipe-VARYING view of the
            # replicated prologue/epilogue params.  vjp w.r.t. an invariant
            # input inserts an implicit psum over 'pipe' at the use site —
            # inside the per-stage conds below that psum would be executed by
            # only some stages (deadlock).  With a varying view the cotangent
            # stays local; the single explicit psum happens after the scan.
            other_v = varying(other_p)

            def load_mb(tree, f):
                return jax.tree_util.tree_map(lambda a: a[f], tree)

            # rngs depend only on (micro-batch, stage, layer-slot) — NEVER the
            # tick — so backward recompute sees identical dropout masks.
            def r_for(f, slot):
                return jax.random.fold_in(key, (f * S + s) * (L + 2) + slot)

            def stage_fwd(local_p, other_p2, x_recv, f):
                """Stage forward incl. prologue/input-select; differentiable
                w.r.t. (local_p, other_p2, x_recv).  The ``where`` masks the
                prologue's cotangent to stage 0 automatically."""
                x0 = module.prologue_apply(other_p2, load_mb(inp, f),
                                           rng=r_for(f, L))
                h = jnp.where(s == 0, x0, x_recv)

                def chunk(lo, hi):
                    def run(h2, f2):
                        for j in range(lo, hi):
                            h2 = module.slot_apply(j, local_p[j], h2,
                                                   r_for(f2, j))
                        return h2
                    return run

                step_sz = interval if interval > 0 else L
                for lo in range(0, L, step_sz):
                    c = chunk(lo, min(lo + step_sz, L))
                    if interval > 0:
                        c = jax.checkpoint(c)
                    h = c(h, f)
                return h

            def head_loss(other_p2, y, b):
                """Epilogue + loss on the last stage; scaled seed for the
                mean over M micro-batches."""
                out = module.epilogue_apply(other_p2, y, rng=r_for(b, L + 1))
                lb = load_mb(lab, b)
                loss = module.compute_loss(out, lb).astype(jnp.float32)
                return loss * (cur_scale / M)

            # shape/dtype protos (never executed on real data), typed as
            # pipe-varying so cond branches / scan carries agree (shard_map
            # vma typing)
            x_proto = jax.eval_shape(
                lambda op: module.prologue_apply(op, load_mb(inp, 0),
                                                 rng=r_for(0, L)), other_p)
            zero_x = varying(jnp.zeros(x_proto.shape, x_proto.dtype))
            zeros_local = varying(jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, jnp.float32), local))
            zeros_other = varying(jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, jnp.float32), other_p))
            zero_f32 = varying(jnp.float32(0.0))

            store_resid = interval == 0
            if store_resid:
                # One traced vjp OUTSIDE the scan gives the residual-leaf
                # protos AND — by tracer identity — which leaves are just the
                # tick-invariant parameters forwarded through (matmul saves W
                # itself): those must NOT be buffered per slot, or every
                # stage's weights would be materialized 2S times.  Only
                # genuinely per-micro-batch residuals (activations, gathered
                # inputs, rng-derived masks) ride the circular buffer; the
                # unmatched-is-buffered default keeps unknown leaves correct.
                _, _vf0 = jax.vjp(
                    lambda lp, op, xr: stage_fwd(lp, op, xr, jnp.int32(0)),
                    local, other_v, zero_x)
                _leaves0 = jax.tree_util.tree_leaves(_vf0)
                _inv_ids = {id(l) for l in
                            jax.tree_util.tree_leaves((local, other_v))}
                buffered_idx = tuple(i for i, l in enumerate(_leaves0)
                                     if id(l) not in _inv_ids)
                zero_res = tuple(
                    varying(jnp.zeros((B,) + jnp.shape(_leaves0[i]),
                                      jnp.result_type(_leaves0[i])))
                    for i in buffered_idx)
                # visibility: a residual computed FROM params (e.g. a dtype
                # cast) fails the tracer-identity match and silently rides
                # all 2S slots, multiplying stage-weight memory — log the
                # total buffered bytes so that shows up as a number, not a
                # mystery OOM
                _buf_bytes = sum(
                    B * int(np.prod(jnp.shape(_leaves0[i]) or (1,)))
                    * jnp.result_type(_leaves0[i]).itemsize  # noqa: E131
                    for i in buffered_idx)
                log_dist(
                    f"pipeline residual store: {len(buffered_idx)} leaves "
                    f"x {B} slots = {_buf_bytes / 1e6:.1f} MB per stage "
                    f"({len(_leaves0) - len(buffered_idx)} tick-invariant "
                    "leaves excluded)", ranks=[0])

            def tick(carry, t):
                # UNIFORM execution: every device runs the identical op
                # sequence every tick, with inactive work masked by `where`.
                # No `lax.cond` on stage-dependent predicates: the auto-axis
                # (data/tensor) collectives XLA inserts inside a branch would
                # then be executed by only some pipe stages — deadlock.
                if store_resid:
                    res_bufs, y_buf, y_send, g_send, gl, go, lacc = carry
                else:
                    buf, y_send, g_send, gl, go, lacc = carry
                # receives: activation from s-1 (down ring), cotangent from
                # s+1 (up ring) — both from the PREVIOUS tick's sends.
                down = [(i, (i + 1) % S) for i in range(S)]
                up = [((i + 1) % S, i) for i in range(S)]
                x_recv = lax.ppermute(y_send, "pipe", down)
                g_recv = lax.ppermute(g_send, "pipe", up)

                # ---------------- forward: micro-batch f = t - s ------------
                f = t - s
                f_act = (f >= 0) & (f < M)
                fc = jnp.clip(f, 0, M - 1)
                # OOB index B drops buffer writes on inactive ticks (no
                # full-buffer select)
                slot = jnp.where(f_act, fc % B, B)
                if store_resid:
                    # no-recompute mode: forward runs under vjp NOW and the
                    # pullback's per-micro-batch residual leaves ride the
                    # circular buffer to this micro-batch's backward tick
                    # (tick-invariant leaves — the weights — are reused from
                    # this tick's own vjp at backward, see buffered_idx)
                    y, vjp_f = jax.vjp(
                        lambda lp, op, xr: stage_fwd(lp, op, xr, fc),
                        local, other_v, x_recv)
                    leaves_f, res_def = jax.tree_util.tree_flatten(vjp_f)
                    res_bufs = tuple(
                        rb.at[slot].set(_vary_one(leaves_f[i]), mode="drop")
                        for rb, i in zip(res_bufs, buffered_idx))
                    y_buf = y_buf.at[slot].set(y, mode="drop")
                else:
                    y = stage_fwd(local, other_v, x_recv, fc)
                    # save the boundary input for the backward recompute
                    buf = buf.at[slot].set(x_recv, mode="drop")

                # ---------------- backward: micro-batch b = t-(2S-1)+s ------
                b = t - (2 * S - 1) + s
                b_act = (b >= 0) & (b < M)
                bc = jnp.clip(b, 0, M - 1)

                if store_resid:
                    leaves_b = list(leaves_f)   # invariant leaves: this tick's
                    for rb, i in zip(res_bufs, buffered_idx):
                        leaves_b[i] = rb[bc % B]
                    vjp_fn = jax.tree_util.tree_unflatten(res_def, leaves_b)
                    y_r = y_buf[bc % B]
                else:
                    x_saved = buf[bc % B]
                    y_r, vjp_fn = jax.vjp(
                        lambda lp, op, xr: stage_fwd(lp, op, xr, bc),
                        local, other_v, x_saved)
                # seed: last stage differentiates epilogue+loss; other stages
                # use the received cotangent.  The head runs on every stage
                # (masked) to keep the op sequence uniform.
                sl, (g_oe, g_y_last) = jax.value_and_grad(
                    head_loss, argnums=(0, 1))(other_v, y_r, bc)
                g_y = jnp.where(is_last, g_y_last.astype(y_r.dtype), g_recv)
                d_local, d_other, d_x = vjp_fn(g_y)

                mask = lambda z: jax.tree_util.tree_map(
                    lambda a: jnp.where(b_act, a.astype(jnp.float32), 0.0), z)
                gl = jax.tree_util.tree_map(jnp.add, gl, mask(d_local))
                go = jax.tree_util.tree_map(jnp.add, go, mask(d_other))
                go = jax.tree_util.tree_map(
                    lambda a, e: a + jnp.where(b_act & is_last,
                                               e.astype(jnp.float32), 0.0),
                    go, g_oe)
                lacc = lacc + jnp.where(b_act & is_last, sl, 0.0)
                # mask sends so bubble-tick garbage never reaches active ticks
                y_send_n = jnp.where(f_act, y, 0.0).astype(y.dtype)
                g_send_n = jnp.where(b_act, d_x, 0.0).astype(d_x.dtype)
                if store_resid:
                    return (res_bufs, y_buf, y_send_n, g_send_n,
                            gl, go, lacc), None
                return (buf, y_send_n, g_send_n, gl, go, lacc), None

            if store_resid:
                carry0 = (
                    zero_res,
                    varying(jnp.zeros((B,) + x_proto.shape, x_proto.dtype)),
                    zero_x,                          # y_send
                    zero_x,                          # g_send
                    zeros_local, zeros_other, zero_f32)
                (_, _, _, _, gl, go, lacc), _ = lax.scan(
                    tick, carry0, jnp.arange(T))
            else:
                carry0 = (
                    varying(jnp.zeros((B,) + x_proto.shape, x_proto.dtype)),
                    zero_x,                          # y_send
                    zero_x,                          # g_send
                    zeros_local, zeros_other, zero_f32)
                (_, _, _, gl, go, lacc), _ = lax.scan(
                    tick, carry0, jnp.arange(T))

            # stage grads: re-add the stage axis; shard_map concatenates over
            # 'pipe'.  Prologue/epilogue grads: psum reduces the per-stage
            # contributions (stage 0 / stage S-1; zeros elsewhere) — the
            # reference's tied-grad allreduce (pipe/module.py:419).
            gl = jax.tree_util.tree_map(lambda a: a[None], gl)
            go = lax.psum(go, "pipe")
            scaled_loss = lax.psum(jnp.where(is_last, lacc, 0.0), "pipe")
            return scaled_loss, gl, go

        fn = jax.shard_map(per_stage, mesh=self.mesh,
                           in_specs=(P("pipe"), P(), P(), P(), P()),
                           out_specs=(P(), P("pipe"), P()),
                           axis_names={"pipe"})
        stages = params["stages"]
        other = {k: v for k, v in params.items() if k != "stages"}
        scaled_loss, g_stages, g_other = fn(stages, other, inputs, labels, rng)
        grads = dict(g_other)
        grads["stages"] = g_stages
        return scaled_loss, grads

    # ------------------------------------------------------- fused pipeline
    def _pipeline_loss(self, params, batch, rng, train=True):
        """Mean loss over M micro-batches, computed by the collective
        pipeline.  ``batch`` leaves are (M, micro_batch, ...).

        ``train=False`` passes ``rng=None`` to every layer — the layer
        protocol's "deterministic" signal — so eval never runs dropout
        (reference ``eval_batch`` puts the module in eval mode,
        ``pipe/engine.py:382``)."""
        module = self.module
        S = self.num_stages
        inputs, labels = _split_labels(batch)
        M = jax.tree_util.tree_leaves(inputs)[0].shape[0]

        stages = params["stages"]
        other = {k: v for k, v in params.items() if k != "stages"}
        # remat every `interval` layers within the stage body (reference
        # ``pipeline.activation_checkpoint_interval``; 0 disables)
        interval = int(module.activation_checkpoint_interval)

        def per_stage(stages_local, other_p, inp, lab, key):
            s = lax.axis_index("pipe")
            local = jax.tree_util.tree_map(lambda a: a[0], stages_local)

            L = module.layers_per_stage
            def chunk_body(lo, hi):
                def run(h, t):
                    for j in range(lo, hi):
                        r = (jax.random.fold_in(key, (t * S + s) * 131 + j)
                             if train else None)
                        h = module.slot_apply(j, local[j], h, r)
                    return h
                return run

            chunks = []
            step_sz = interval if interval > 0 else L
            for lo in range(0, L, step_sz):
                c = chunk_body(lo, min(lo + step_sz, L))
                if interval > 0:
                    c = jax.checkpoint(c)
                chunks.append(c)

            def stage_body(x, t):
                for c in chunks:
                    x = c(x, t)
                return x

            def load_mb(t):
                return jax.tree_util.tree_map(lambda a: a[t], inp)

            x0_probe = module.prologue_apply(
                other_p, load_mb(0),
                rng=jax.random.fold_in(key, 7) if train else None)
            zero_h = jnp.zeros_like(x0_probe)

            def tick(carry, t):
                y_prev = carry
                # receive previous tick's output from stage s-1 (p2p recv)
                perm = [(i, (i + 1) % S) for i in range(S)]
                x_recv = lax.ppermute(y_prev, "pipe", perm)
                # first stage loads micro-batch t instead
                x0 = module.prologue_apply(
                    other_p, load_mb(jnp.clip(t, 0, M - 1)),
                    rng=jax.random.fold_in(key, t * 7 + 1) if train else None)
                x_in = jnp.where(s == 0, x0, x_recv)
                y = stage_body(x_in, t)
                return y, y

            # carry values become pipe-varying after the first ppermute;
            # mark the initial carry accordingly (shard_map vma typing)
            carry0 = lax.pcast(zero_h, ("pipe",), to="varying")
            _, ys = lax.scan(tick, carry0, jnp.arange(M + S - 1))

            # Epilogue + loss ONCE over the M completed micro-batches
            # (ticks S-1 … M+S-2 on the last stage), batched into a single
            # vmapped application instead of per-tick masked compute.
            ys_valid = ys[S - 1:]                       # (M, mb, ...)
            def one_loss(i, y):
                out = module.epilogue_apply(
                    other_p, y,
                    rng=jax.random.fold_in(key, i * 7 + 3) if train else None)
                lb = jax.tree_util.tree_map(lambda a: a[i], lab)
                return module.compute_loss(out, lb).astype(jnp.float32)
            losses = jax.vmap(one_loss)(jnp.arange(M), ys_valid)
            mean_loss = jnp.mean(losses)
            # aggregate from the last stage (reference _aggregate_total_loss)
            return lax.psum(jnp.where(s == S - 1, mean_loss, 0.0), "pipe")

        fn = jax.shard_map(per_stage, mesh=self.mesh,
                           in_specs=(P("pipe"), P(), P(), P(), P()),
                           out_specs=P(), axis_names={"pipe"})
        return fn(stages, other, inputs, labels, rng)

    # ------------------------------------------------------------------ eval
    def eval_batch(self, batch, rng=None):
        """Pipelined forward-only loss on ONE micro-batch ``(inputs, labels)``
        (promoted internally to a stack of one; pass pre-stacked batches
        through ``_pipeline_loss`` directly if needed)."""
        self._flush_offload()   # a pending DPU update must land first
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        if self._jit_eval is None:
            def eval_fn(params, b, r):
                return self._pipeline_loss(params, b, r, train=False)
            self._jit_eval = self._wrap_step("eval_step", eval_fn)
        # promote a single micro-batch to a stack of one
        batch = jax.tree_util.tree_map(lambda a: jnp.asarray(a)[None], batch)
        return self._jit_eval(self.state.params, batch, rng)

    # forward/backward shim is meaningless under a fused pipeline schedule
    def forward(self, *a, **k):
        raise NotImplementedError("PipelineEngine: use train_batch()/eval_batch() "
                                  "(reference PipelineEngine also forbids "
                                  "forward/backward, pipe/engine.py:46)")

    backward = forward
    step = forward
