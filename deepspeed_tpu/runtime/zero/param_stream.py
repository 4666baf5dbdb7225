"""ZeRO-3 parameter offload: params live on the HOST (or NVMe) and layer
blocks stream through the device during forward/backward.

Parity: the reference's ZeRO-3 offload / ZeRO-Infinity param tier —
``zero/stage3.py:656 _configure_offloading`` +
``zero/partition_parameters.py:555`` (``remote_device``) +
``swap_tensor/partitioned_param_swapper.py:37`` — the machinery behind
"13B trainable on one V100-32GB, 40B with NVMe"
(``docs/_posts/2020-09-09-ZeRO-Offload.md:9``,
``docs/_posts/2021-03-08-zero3-offload.md:49``).

TPU-native shape (NOT a hook translation): the reference intercepts
per-submodule fwd/bwd with gather/release hooks; here the model exposes
its forward DECOMPOSED (``model.stream_fns()``: embed / per-layer block /
head) and a Python-driven loop runs one jitted block program per layer:

  - the host optimizer's flat buffers are built over a LAYER-MAJOR tree
    (``{"layers": [per-layer dicts], "nonblock": {...}}``) so each
    layer's parameters and gradients are CONTIGUOUS flat segments —
    per-layer h2d uploads are zero-copy views of the 16-bit image and
    per-layer grad d2h lands with one contiguous accumulate;
  - forward streams layer l+1's params (chunked async ``device_put``,
    ``zero/wire.py``) while layer l's block computes — the double-
    buffered prefetch the reference's param coordinator does with CUDA
    streams;
  - backward IS the rematerialization: each layer's params stream in
    again (reverse order), ``jax.vjp`` re-runs the block forward, the
    layer's bf16 grads stream out chunked+async and accumulate into the
    host fp32 gradient buffer while the next layer's backward runs;
  - small "nonblock" params (embeddings, final LN) stay device-resident
    (the reference's ``param_persistence_threshold`` idea) with their
    grads accumulated on device and transferred once per step;
  - the host fused Adam then runs over the same flat buffers
    (``offload_engine.HostOffloadOptimizer``) — parameters are never
    materialized whole on the device, so trainable model size is bounded
    by HOST memory, not HBM.

With ``offload_param.device == "nvme"`` the 16-bit layer payloads live
in per-layer files serviced by the kernel-AIO op (no host-RAM image);
reads prefetch ahead of the layer loop.
"""

import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from . import wire
from ...utils.logging import logger, log_dist


def to_stream_tree(params, stacked_key):
    """Model tree (stacked blocks) -> layer-major stream tree."""
    blocks = params[stacked_key]
    L = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    layers = [jax.tree_util.tree_map(lambda a: a[l], blocks)
              for l in range(L)]
    nonblock = {k: v for k, v in params.items() if k != stacked_key}
    return {"layers": layers, "nonblock": nonblock}


def from_stream_tree(tree, stacked_key):
    """Layer-major stream tree -> model tree (stacked blocks).

    Used on the checkpoint boundary so streamed and monolithic runs can
    load each other's checkpoints unchanged."""
    layers = tree["layers"]
    blocks = jax.tree_util.tree_map(lambda *ls: np.stack(ls), *layers)
    out = dict(tree["nonblock"])
    out[stacked_key] = blocks
    return out


class ParamStreamRunner:
    """Drives the streamed train/eval step for one engine.

    The engine owns config parsing, LR schedules, counters and
    checkpoint I/O; this object owns the device loop and the layout
    bookkeeping between the host optimizer's flat buffers and the
    per-layer jitted programs.
    """

    def __init__(self, model, host_opt, mesh, compute_dtype, *,
                 gas, grad_clip, zero_config, aio_config, retry=None,
                 skip_nonfinite=True, spike=None, compile_cache=None,
                 cache_key_extra=None, comms_compression=None):
        assert mesh.size == 1, (
            "offload_param streaming is single-chip (scale-up) machinery; "
            "on a multi-chip mesh use ZeRO-3 sharding (stage 3 without "
            "offload_param) — params then shard over the fsdp axis")
        self.model = model
        self.host = host_opt
        self.mesh = mesh
        self.dtype = compute_dtype
        self.gas = int(gas)
        self.grad_clip = float(grad_clip or 0.0)
        # health guardian skip-step: a non-finite step must be a no-op on
        # the host master/moments (runtime/health.py; the streamed twin of
        # the engine's branchless in-graph skip).  ``spike`` is the
        # (window, zmax, skip_on_spike) tuple of the loss-spike sentinel —
        # this path has no device HealthState, so the EMA runs host-side
        # with the same formula (health.HostEma).
        self.skip_nonfinite = bool(skip_nonfinite)
        self._spike_ema = None
        self._skip_on_spike = False
        if spike is not None:
            from ..health import HostEma
            window, zmax, skip_on_spike = spike
            self._spike_ema = HostEma(window, zmax)
            self._skip_on_spike = bool(skip_on_spike)
        sf = model.stream_fns()
        self.sf = sf
        self.L = int(sf["n_layer"])
        self.local_flags = np.asarray(sf["local_flags"], bool)

        # ---- flat-layout bookkeeping (layer-major stream tree) -----------
        # host_opt was built over to_stream_tree(params); dict keys sort as
        # "layers" < "nonblock", so the flat buffer is
        #   [layer 0 | layer 1 | ... | layer L-1 | nonblock]
        # with every segment contiguous.  Verify by index rather than
        # assuming: unflattening leaf positions shows where each leaf sits.
        numel = host_opt.numel
        idx_tree = host_opt.treedef.unflatten(
            list(range(len(host_opt.shapes))))
        layer0 = idx_tree["layers"][0]
        layer_idx = jax.tree_util.tree_leaves(layer0)   # any nesting
        self.layer_shapes = [host_opt.shapes[i] for i in layer_idx]
        per_layer = sum(int(np.prod(s or (1,))) for s in self.layer_shapes)
        self.layer_bounds = []
        for l in range(self.L):
            ids = jax.tree_util.tree_leaves(idx_tree["layers"][l])
            lo = int(host_opt.offsets[min(ids)])
            self.layer_bounds.append((lo, lo + per_layer))
            assert lo == l * per_layer, "layer segments must tile the front"
        self.nb_lo = self.L * per_layer
        self.nb_hi = numel
        self.per_layer = per_layer
        self.layer_treedef = jax.tree_util.tree_structure(
            jax.tree_util.tree_map(lambda x: 0, layer0))
        nb_ids = jax.tree_util.tree_leaves(idx_tree["nonblock"])
        assert min(nb_ids, default=len(host_opt.shapes)) >= self.L * \
            len(layer_idx), "nonblock leaves must follow the layer segments"
        self._nb_shapes = [host_opt.shapes[i] for i in nb_ids]
        self._nonblock_treedef = jax.tree_util.tree_structure(
            jax.tree_util.tree_map(lambda x: 0, idx_tree["nonblock"]))

        # ---- NVMe param tier ---------------------------------------------
        off_p = zero_config.offload_param
        self.nvme = off_p is not None and off_p.device == "nvme"
        if self.nvme:
            from ..swap_tensor.partitioned_param_swapper import (
                AsyncPartitionedParameterSwapper)
            assert off_p.nvme_path, "offload_param.device=nvme needs nvme_path"
            itemsize = 2 if host_opt.out_dtype is not None else 4
            self.swapper = AsyncPartitionedParameterSwapper(
                aio_config, off_p.nvme_path,
                dtype=np.uint16 if itemsize == 2 else np.float32,
                buffer_count=max(4, int(off_p.buffer_count)),
                buffer_numel=per_layer, retry=retry)
            self._flush_layers_to_nvme(range(self.L))
            host_opt.drop_payload()
        else:
            self.swapper = None

        # ---- quantized layer wire (docs/comms-compression.md) ------------
        # qwZ for the h2d hop: the 16-bit layer payload crosses as a
        # block-quantized int8/int4 image + fp32 scales, dequantized
        # inside the jitted scatter (half / quarter the wire bytes — the
        # route that matters on a slow host<->device link).  The
        # fp32 master and host optimizer stay exact; only the COMPUTE
        # copy is lossy, exactly like the fused engine's qwZ gathers.
        # Quantized images are cached per host-payload version (one host
        # quantization pass per optimizer step, ~numel/2 extra host RAM);
        # excluded leaves ride a separate full-width image.  The NVMe
        # tier keeps the full-width wire (its payload lives on disk).
        cc = comms_compression
        self._quant = bool(
            cc is not None and cc.enabled and "param_stream" in cc.routes
            and cc.weights_bits is not None and not self.nvme
            and host_opt.out_dtype is not None)
        if self._quant:
            self._q_bits = int(cc.weights_bits)
            self._q_block = int(cc.block_size)
            if self._q_bits == 4 and self._q_block % 2:
                self._q_block += 1
            self._q_plan = self._build_quant_plan(cc)
            self._q_cache = {}
            self._payload_version = 0
            if self._q_plan["q_total"] == 0:
                self._quant = False     # policy excluded every layer leaf
        if self._quant:
            log_dist("param_stream comms_compression: layer wire "
                     f"int{self._q_bits} block={self._q_block} "
                     f"(q {self._q_plan['q_total']} / fw "
                     f"{self._q_plan['fw_total']} elems per layer)",
                     ranks=[0])

        # ---- device-resident nonblock params + jitted programs -----------
        self._h2d = wire.H2DUploader()
        self._jit_cache = {}
        # persistent compiled-step cache: the per-layer programs (embed /
        # block fwd+bwd / head / nonblock reductions) are the streamed
        # path's compile cost — L layers × two directions re-compiled on
        # every process start without it (runtime/compile_cache.py)
        self._compile_cache = compile_cache
        self._cache_key_extra = dict(cache_key_extra or {},
                                     n_layer=self.L, nvme=self.nvme)
        self._nonblock_dev = None
        self._upload_nonblock()
        self.last_times = {}

    # ------------------------------------------------------------- layout
    def _payload_seg(self, lo, hi):
        """16-bit (or fp32) host view of flat range [lo, hi)."""
        return self.host.payload_flat()[lo:hi]

    # ------------------------------------------- quantized layer wire
    def _build_quant_plan(self, cc):
        """Per-leaf wire plan for one layer block: quantized leaves get
        block-ALIGNED ranges of the int8 image (a shared block would mix
        a weight tail with e.g. an LN vector and ruin the block scale);
        excluded / sub-threshold leaves ride a full-width image."""
        from ..comm.collective_router import _path_str
        dummy = jax.tree_util.tree_unflatten(
            self.layer_treedef, list(range(len(self.layer_shapes))))
        paths = [p for p, _ in
                 jax.tree_util.tree_flatten_with_path(dummy)[0]]
        B = self._q_block
        entries, q_off, fw_off = [], 0, 0
        for path, shape in zip(paths, self.layer_shapes):
            n = int(np.prod(shape or (1,)))
            ps = _path_str(path)
            if n * 2 < cc.min_tensor_bytes or \
                    any(pat in ps for pat in cc.excluded):
                entries.append(("fw", fw_off, n))
                fw_off += n
            else:
                npad = ((n + B - 1) // B) * B
                entries.append(("q", q_off, n, npad))
                q_off += npad
        return {"entries": tuple(entries), "q_total": q_off,
                "fw_total": fw_off}

    def _wire_dtype_np(self):
        import ml_dtypes
        return (ml_dtypes.bfloat16 if self.host.out_dtype == "bfloat16"
                else np.float16)

    def _quant_images(self, l, lo, hi):
        """(q_img u8, scales f32, fw_img 16-bit) for layer ``l``, cached
        per host-payload version (one host quantization pass per applied
        optimizer step, not per fetch — fetches run L×gas×2 per step)."""
        hit = self._q_cache.get(l)
        if hit is not None and hit[0] == self._payload_version:
            return hit[1]
        from ..comm.quantized import quantize_flat_np
        seg16 = self._payload_seg(lo, hi)
        if seg16.dtype == np.uint16:
            seg16 = seg16.view(self._wire_dtype_np())
        pl = self._q_plan
        B = self._q_block
        pack = 2 if self._q_bits == 4 else 1
        q_img = np.empty(pl["q_total"] // pack, np.uint8)
        scales = np.empty(pl["q_total"] // B, np.float32)
        fw_img = np.empty(pl["fw_total"], seg16.dtype)
        off = 0
        for entry, shape in zip(pl["entries"], self.layer_shapes):
            n = int(np.prod(shape or (1,)))
            leaf = seg16[off:off + n]
            off += n
            if entry[0] == "fw":
                fw_img[entry[1]:entry[1] + n] = leaf
            else:
                _, qo, _, npad = entry
                q, s = quantize_flat_np(leaf, block_size=B,
                                        bits=self._q_bits)
                q_img[qo // pack:(qo + npad) // pack] = q
                scales[qo // B:(qo + npad) // B] = s
        imgs = (q_img, scales, fw_img)
        self._q_cache[l] = (self._payload_version, imgs)
        return imgs

    def _upload_layer_quantized(self, l, lo, hi):
        q_img, scales, fw_img = self._quant_images(l, lo, hi)
        B = self._q_block
        pack = 2 if self._q_bits == 4 else 1
        bpb = B // pack                     # packed bytes per block
        cb = max(bpb, (wire.DEFAULT_CHUNK_BYTES // bpb) * bpb)
        q_chunks = self._h2d.upload_flat(q_img, chunk_bytes=cb)
        fw_chunks = (self._h2d.upload_flat(fw_img) if fw_img.size else [])
        sc_dev = jax.device_put(scales)     # tiny; ref held by _q_cache
        key = ("layerq", len(q_chunks), len(fw_chunks))
        if key not in self._jit_cache:
            out_dtype = (jnp.bfloat16 if self.host.out_dtype == "bfloat16"
                         else jnp.float16)
            per_fw = (int(fw_chunks[0].shape[0]) if fw_chunks else 1)
            self._jit_cache[key] = wire.make_quantized_chunk_scatter(
                tuple(self.layer_shapes), self.layer_treedef,
                self._q_plan["entries"], int(q_chunks[0].shape[0]),
                len(q_chunks), per_fw, len(fw_chunks),
                bits=self._q_bits, block=B, out_dtype=out_dtype)
        tree = self._jit_cache[key](sc_dev, *q_chunks, *fw_chunks)
        self._h2d.settle_on(jax.tree_util.tree_leaves(tree)[0])
        return tree

    # ---------------------------------------------------------- NVMe tier
    def _flush_layers_to_nvme(self, layer_ids):
        enc = self.host.encode_range
        buf = np.empty(self.per_layer,
                       np.uint16 if self.host.out_dtype is not None
                       else np.float32)
        for l in layer_ids:
            lo, hi = self.layer_bounds[l]
            enc(lo, hi, buf)
            self.swapper.swap_out(l, buf)
        self.swapper.synchronize_writes()

    # ------------------------------------------------------------ uploads
    def _scatter_jit(self, name, shapes, nchunks, per):
        key = (name, nchunks)
        if key not in self._jit_cache:
            treedef = (self.layer_treedef if name == "layer"
                       else self._nonblock_treedef)
            self._jit_cache[key] = wire.make_chunk_scatter(
                shapes, treedef, per, nchunks)
        return self._jit_cache[key]

    def _upload_segment(self, seg16, name, shapes, stage=False):
        """Host flat 16-bit segment -> device pytree (chunked, async)."""
        if seg16.dtype == np.uint16:
            import ml_dtypes
            seg16 = seg16.view(ml_dtypes.bfloat16 if self.host.out_dtype ==
                               "bfloat16" else np.float16)
        chunks = self._h2d.upload_flat(seg16, stage=stage)
        per = int(chunks[0].shape[0])
        tree = self._scatter_jit(name, tuple(shapes), len(chunks),
                                 per)(*chunks)
        self._h2d.settle_on(jax.tree_util.tree_leaves(tree)[0])
        return tree

    def fetch_layer(self, l):
        """Start layer l's h2d; returns the device layer-param tree (the
        consuming jit waits on the transfers, so calling this one layer
        AHEAD gives double-buffered prefetch for free)."""
        if self.nvme:
            self.swapper.swap_in([l])
            seg = self.swapper.get_buffer(l)
            # staged: the swap buffer returns to the pool immediately (the
            # staging copy decouples it from the in-flight h2d DMA)
            tree = self._upload_segment(seg, "layer", self.layer_shapes,
                                        stage=True)
            self.swapper.release([l])
            return tree
        lo, hi = self.layer_bounds[l]
        if self._quant:
            return self._upload_layer_quantized(l, lo, hi)
        seg = self._payload_seg(lo, hi)
        return self._upload_segment(seg, "layer", self.layer_shapes)

    def prefetch_layer_nvme(self, l):
        """Begin the NVMe read for layer l (overlaps the current layer's
        compute; no-op on the cpu tier where fetch is a RAM view).  Skips
        (rather than fails) only on the one benign condition — no free pool
        buffer, in which case the blocking fetch_layer picks the read up —
        so genuine AIO errors surface HERE with their real context instead
        of resurfacing later mislabeled."""
        if self.nvme and 0 <= l < self.L:
            if self.swapper.available_swap_in_buffers() < 1:
                return                # pool busy; fetch_layer will block
            try:
                self.swapper.swap_in([l], async_op=True)
            except RuntimeError as e:
                # the availability check above races in-flight release/
                # acquire (swap_out's drain, a concurrent prefetch): the
                # pool can empty between check and acquire.  Same benign
                # condition as the guarded return — fall back to the
                # blocking fetch.  Anything else (AIO submit failures
                # arrive as their own error types) still raises.
                if "no free swap buffer" not in str(e):
                    raise
                logger.debug(
                    f"prefetch_layer_nvme({l}): swap buffer pool drained "
                    "between availability check and acquire; falling back "
                    "to the blocking fetch")

    def _upload_nonblock(self):
        nb_shapes = self._nb_shapes
        if self.nvme:
            buf = np.empty(self.nb_hi - self.nb_lo,
                           np.uint16 if self.host.out_dtype is not None
                           else np.float32)
            self.host.encode_range(self.nb_lo, self.nb_hi, buf)
            seg = buf
        else:
            seg = self._payload_seg(self.nb_lo, self.nb_hi)
        self._nonblock_dev = self._upload_segment(seg, "nonblock", nb_shapes)

    # ------------------------------------------------------- jitted pieces
    def _jits(self, deterministic):
        key = ("step", bool(deterministic))
        if key in self._jit_cache:
            return self._jit_cache[key]
        sf = self.sf
        dtype = self.dtype
        inv_gas = 1.0 / self.gas
        wire_dtype = (jnp.bfloat16 if self.host.out_dtype == "bfloat16"
                      else jnp.float32)

        def embed(nb, tokens, rng):
            return sf["embed"](nb, tokens, rng, deterministic)

        def block_fwd(p, x, rng, is_local):
            return sf["block"](p, x, rng, is_local, deterministic)

        def block_bwd(p, x, rng, is_local, dy):
            _, vjp = jax.vjp(
                lambda pp, xx: sf["block"](pp, xx, rng, is_local,
                                           deterministic), p, x)
            dp, dx = vjp(dy)
            leaves = jax.tree_util.tree_leaves(dp)
            dp_flat = jnp.concatenate(
                [l.astype(jnp.float32).reshape(-1) for l in leaves])
            return dx, (dp_flat * inv_gas).astype(wire_dtype)

        def head(nb, x, labels):
            def f(nb_, x_):
                return sf["head_loss"](nb_, x_, labels)
            loss, (d_nb, dx) = jax.value_and_grad(f, argnums=(0, 1))(nb, x)
            return loss, d_nb, dx

        def embed_bwd(nb, tokens, rng, dx):
            _, vjp = jax.vjp(lambda nb_: embed(nb_, tokens, rng), nb)
            (d_nb,) = vjp(dx)
            return d_nb

        def nb_add(a, b):
            return jax.tree_util.tree_map(
                lambda x, y: x.astype(jnp.float32) + y.astype(jnp.float32),
                a, b)

        def nb_flat(d_nb):
            leaves = jax.tree_util.tree_leaves(d_nb)
            flat = jnp.concatenate(
                [l.astype(jnp.float32).reshape(-1) for l in leaves])
            return (flat * inv_gas).astype(wire_dtype)

        def head_eval(nb, x, labels):
            return sf["head_loss"](nb, x, labels)

        from ..compile_cache import wrap_step

        def wrap(nm, fn, donate=()):
            return wrap_step(
                f"param_stream.{nm}", fn, cache=self._compile_cache,
                key_extra=dict(self._cache_key_extra,
                               deterministic=bool(deterministic)),
                donate_argnums=donate)

        out = {
            "embed": wrap("embed", embed),
            "block_fwd": wrap("block_fwd", block_fwd),
            "block_bwd": wrap("block_bwd", block_bwd, donate=(0, 4)),
            "head": wrap("head", head),
            "head_eval": wrap("head_eval", head_eval),
            "embed_bwd": wrap("embed_bwd", embed_bwd),
            "nb_add": wrap("nb_add", nb_add),
            "nb_flat": wrap("nb_flat", nb_flat),
            "layer_rngs": wrap("layer_rngs", sf["layer_rngs"]),
        }
        self._jit_cache[key] = out
        return out

    # ------------------------------------------------------------ training
    def train_step(self, micro_batches, rng, *, lr, step_no):
        """One optimizer step over ``gas`` microbatches.  Returns metrics."""
        J = self._jits(deterministic=False)
        host = self.host
        flat = host._flat32
        t0 = time.time()
        flat[:] = 0.0
        losses = []
        nb_grads = None
        t_dev = 0.0
        t_d2h = 0.0

        for mi, mb in enumerate(micro_batches):
            mb_rng = jax.random.fold_in(rng, mi)
            tokens, labels = self.sf["split_batch"](mb)
            tokens = jnp.asarray(tokens)
            labels = jnp.asarray(labels)
            rngs = J["layer_rngs"](mb_rng)

            # ---------- forward: stream layers up ----------
            td = time.time()
            x = J["embed"](self._nonblock_dev, tokens, mb_rng)
            self.prefetch_layer_nvme(0)
            xs = []
            p_next = self.fetch_layer(0)
            for l in range(self.L):
                p = p_next
                self.prefetch_layer_nvme(l + 1)
                xs.append(x)
                x = J["block_fwd"](p, x, rngs[l],
                                   jnp.asarray(self.local_flags[l]))
                # dispatch epoch BEFORE the next fetch: reading x proves
                # only uploads consumed by layers <= l completed — the
                # l+1 fetch below postdates that proof
                ep_proved = self._h2d.dispatch_epoch
                # prefetch next layer's params while this block computes
                p_next = (self.fetch_layer(l + 1) if l + 1 < self.L
                          else None)
                self._throttle(l, x, ep_proved)
            del p, p_next

            # ---------- head: loss + gradients ----------
            loss, d_nb, dx = J["head"](self._nonblock_dev, x, labels)
            losses.append(loss)

            # ---------- backward: stream layers down, grads out ----------
            self.prefetch_layer_nvme(self.L - 1)
            p_next = self.fetch_layer(self.L - 1)
            pending = None    # (handle, lo, hi, epoch) grad d2h in flight
            for l in range(self.L - 1, -1, -1):
                p = p_next
                self.prefetch_layer_nvme(l - 1)
                dx, dp_flat = J["block_bwd"](
                    p, xs[l], rngs[l], jnp.asarray(self.local_flags[l]), dx)
                # epoch proven once THIS layer's grads land (its bwd
                # consumed p's upload); the l-1 fetch below postdates it
                ep = self._h2d.dispatch_epoch
                p_next = self.fetch_layer(l - 1) if l > 0 else None
                handle = wire.d2h_flat_start(dp_flat)
                del dp_flat
                if pending is not None:
                    ph, plo, phi, pep = pending
                    t1 = time.time()
                    self._land_add(ph, plo, phi, flat)
                    t_d2h += time.time() - t1
                    # landing reads the bwd outputs — a barrier proving
                    # the param uploads dispatched up to that layer's
                    # bwd (epoch pep) completed; later fetches excluded
                    self._h2d.release_parked(pep)
                lo, hi = self.layer_bounds[l]
                pending = (handle, lo, hi, ep)
                xs[l] = None          # free the saved activation
            if pending is not None:
                ph, plo, phi, pep = pending
                t1 = time.time()
                self._land_add(ph, plo, phi, flat)
                t_d2h += time.time() - t1
                self._h2d.release_parked(pep)
            del p, p_next, xs

            # ---------- nonblock grads (device-accumulated) ----------
            d_nb_e = J["embed_bwd"](self._nonblock_dev, tokens, mb_rng, dx)
            d_nb = J["nb_add"](d_nb, d_nb_e)
            nb_grads = d_nb if nb_grads is None else J["nb_add"](nb_grads,
                                                                 d_nb)
            t_dev += time.time() - td

        # land nonblock grads: one chunked d2h into the nonblock segment
        t1 = time.time()
        nb_flat_dev = J["nb_flat"](nb_grads)
        self._land_add(wire.d2h_flat_start(nb_flat_dev),
                       self.nb_lo, self.nb_hi, flat)
        t_d2h += time.time() - t1
        del nb_grads, nb_flat_dev

        # ---------- clip + host Adam + payload refresh ----------
        t1 = time.time()
        gnorm = self._host_global_norm(flat)
        loss = float(np.mean([float(l) for l in losses]))
        # health-guardian skip-step, streamed spelling: the grads are
        # already host-side (the wire crossed either way), so the no-op is
        # simply not applying the host optimizer — master, moments, NVMe
        # image and the device payload all stay at the pre-step state
        z, spiked = (self._spike_ema.update(loss)
                     if self._spike_ema is not None else (0.0, False))
        skip = (self.skip_nonfinite and not (np.isfinite(gnorm)
                                             and np.isfinite(loss))) \
            or (self._skip_on_spike and spiked)
        if skip:
            logger.warning(
                f"param-stream step {step_no}: unhealthy sentinels "
                f"(loss={loss}, grad_norm={gnorm}, z={z:.2f}); host "
                "optimizer step SKIPPED — params/optimizer state untouched")
            t_adam = time.time() - t1
        else:
            if self.grad_clip > 0 and gnorm > self.grad_clip:
                np.multiply(flat, self.grad_clip / (gnorm + 1e-6), out=flat)
            host.step(flat, step_no, lr)
            t_adam = time.time() - t1
            if self.nvme:
                t2 = time.time()
                self._flush_layers_to_nvme(range(self.L))
                t_adam += time.time() - t2
            self._upload_nonblock()
            if self._quant:
                # payload changed: next fetch of each layer re-quantizes
                # (a SKIPPED step leaves the payload — and the cached
                # quantized images — untouched)
                self._payload_version += 1

        self.last_times = {
            "device_plus_wire_s": round(t_dev, 3),
            "grad_d2h_land_s": round(t_d2h, 3),
            "host_adam_s": round(t_adam, 3),
            "step_wall_s": round(time.time() - t0, 3),
        }
        metrics = {"loss": jnp.asarray(loss), "grad_norm": jnp.asarray(gnorm),
                   "overflow": jnp.asarray(False), "lr": jnp.asarray(lr),
                   "loss_scale": jnp.asarray(1.0), "skip": jnp.asarray(skip)}
        if self._spike_ema is not None:
            # carried so the monitor uses THIS ema (no double accounting)
            metrics["health_z"] = jnp.asarray(z)
            metrics["loss_spike"] = jnp.asarray(spiked)
        return metrics

    def close(self):
        """Engine shutdown: drop the jitted per-layer programs (and their
        live executables), the device nonblock tree, parked H2D staging
        buffers, and the NVMe swapper's pinned buffer pool.  ``del
        engine`` frees none of these, so engines built one after another
        in a process would leak them."""
        for entry in self._jit_cache.values():
            fns = entry.values() if isinstance(entry, dict) else (entry,)
            for fn in fns:
                if hasattr(fn, "clear"):
                    fn.clear()
        self._jit_cache.clear()
        self._nonblock_dev = None
        if self._quant:
            self._q_cache.clear()
        self._h2d.close()
        swapper, self.swapper = self.swapper, None
        if swapper is not None:
            try:
                swapper.synchronize_writes()
                swapper.synchronize_reads()
            except (OSError, RuntimeError) as e:
                logger.warning(f"param-stream close: AIO drain failed "
                               f"({e}); dropping buffers anyway")
            swapper.release(list(swapper._id_to_buffer))

    def reset_health_ema(self):
        """Post-checkpoint-load reset: the restored run must not inherit
        loss statistics of the steps it just discarded."""
        if self._spike_ema is not None:
            self._spike_ema.reset()

    @property
    def THROTTLE_EVERY(self):
        """Forward-loop sync cadence (layers); tighter = smaller in-flight
        upload window (host RAM) at the cost of more syncs — the
        max-params probe sets 2 via env to squeeze under the 125 GB
        host.  Read per-use so setting the env after import still works;
        clamped to >= 1 (0 would divide by zero in the layer loop)."""
        try:
            return max(1, int(os.environ.get("DS_TPU_STREAM_THROTTLE", "4")))
        except ValueError:
            logger.warning("DS_TPU_STREAM_THROTTLE is not an int; using 4")
            return 4

    @property
    def GC_AT_THROTTLE(self):
        return os.environ.get("DS_TPU_STREAM_GC", "0") == "1"

    def _throttle(self, l, x, proved_epoch=None):
        """Backpressure for the forward stream: without it the Python loop
        dispatches EVERY layer's upload before any compute finishes, and
        the runtime buffers up to the whole model's bytes in host RAM
        (observed: the 2.7B probe OOM'd a 125 GB host).  A tiny VALUE READ
        of the current activation every few layers bounds the in-flight
        window to ~THROTTLE_EVERY layers (``jax.block_until_ready`` does
        not actually wait on this remote-attached runtime — only a value
        read synchronizes)."""
        if (l + 1) % self.THROTTLE_EVERY == 0:
            np.asarray(jax.device_get(x[0, 0, 0]))
            # the value read above transitively proves every upload
            # consumed by layers <= l completed — recycle their staging
            # buffers (parked pairs never self-observe ready on this
            # runtime once their settle target is donated downstream).
            # proved_epoch was captured BEFORE the l+1 fetch dispatched,
            # so that fetch's pairs (settled, possibly deleted, DMA not
            # provably landed) stay parked until their own barrier.
            self._h2d.release_parked(proved_epoch)
            if self.GC_AT_THROTTLE:
                import gc
                gc.collect()      # drop cyclic refs pinning transfer state

    @staticmethod
    def _land_add(handle, lo, hi, flat):
        """Land a started chunked d2h and ACCUMULATE (+=) into the flat
        fp32 segment (upcasts 16-bit wire grads on the add)."""
        spans, parts = handle
        for (a, b), p in zip(spans, parts):
            seg = flat[lo + a:lo + b]
            seg += np.asarray(p, np.float32)

    @staticmethod
    def _host_global_norm(flat):
        # chunked np.dot: one pass, no temporary the size of the buffer
        total = 0.0
        step = 1 << 24
        for a in range(0, flat.shape[0], step):
            seg = flat[a:a + step]
            total += float(np.dot(seg, seg))
        return float(np.sqrt(total))

    # ------------------------------------------------------------ eval path
    def eval_loss(self, batch, rng):
        J = self._jits(deterministic=True)
        tokens, labels = self.sf["split_batch"](batch)
        tokens = jnp.asarray(tokens)
        labels = jnp.asarray(labels)
        rngs = J["layer_rngs"](rng)
        x = J["embed"](self._nonblock_dev, tokens, rng)
        self.prefetch_layer_nvme(0)
        p_next = self.fetch_layer(0)
        for l in range(self.L):
            p = p_next
            self.prefetch_layer_nvme(l + 1)
            x = J["block_fwd"](p, x, rngs[l],
                               jnp.asarray(self.local_flags[l]))
            ep_proved = self._h2d.dispatch_epoch
            p_next = self.fetch_layer(l + 1) if l + 1 < self.L else None
            self._throttle(l, x, ep_proved)
        return J["head_eval"](self._nonblock_dev, x, labels)

    # --------------------------------------------------------- checkpoints
    def full_params_host(self):
        """Model-tree (stacked) params from the host payload — numpy."""
        if self.nvme:
            tree = self._host_tree_from_master()
        else:
            tree = self.host.payload_tree()
        return from_stream_tree(tree, self.sf["stacked_key"])

    def _host_tree_from_master(self):
        # nvme mode has no RAM image; derive the compute-dtype tree from
        # the fp32 master (identical values to the on-disk payload)
        import jax.numpy as jnp
        master = self.host.master
        out16 = self.host.out_dtype
        leaves = []
        for off, s in zip(self.host.offsets, self.host.shapes):
            n = int(np.prod(s or (1,)))
            seg = master[off:off + n].reshape(s)
            if out16 == "bfloat16":
                seg = np.asarray(jnp.asarray(seg, jnp.bfloat16))
            elif out16 == "float16":
                seg = seg.astype(np.float16)
            leaves.append(seg)
        return self.host.treedef.unflatten(leaves)

    def reload_from_host(self):
        """After the engine restores the host master (checkpoint load),
        refresh the NVMe payload files and the device nonblock tree."""
        if self.nvme:
            self._flush_layers_to_nvme(range(self.L))
        self._upload_nonblock()
        if self._quant:
            self._payload_version += 1
