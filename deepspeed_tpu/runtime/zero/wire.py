"""Chunked host<->device wire for the offload tiers.

Parity role: the reference moves offload traffic through pinned CUDA
buffers with async copies overlapping compute
(``zero/stage_1_and_2.py:1008-1160`` pinned d2h grad buckets;
``swap_tensor/partitioned_param_swapper.py`` pinned swap buffers).  The
TPU-runtime analogue: one monolithic transfer serializes on a single
stream, while splitting the flat payload into ~64 MB chunks and issuing
every chunk's ``copy_to_host_async`` / ``device_put`` before consuming
any pipelines the transport (on PCIe the chunking is free and preserves
overlap with compute).

All offload wire traffic (grad d2h, param h2d, streamed layer blocks)
goes through these helpers so the chunking policy lives in one place.
"""

import numpy as np
import jax

# 64 MB: large enough to amortize per-transfer dispatch, small enough to
# pipeline (and to bound the staging copy used to avoid mutate-in-flight
# races on the h2d payload)
DEFAULT_CHUNK_BYTES = 64 << 20


def _chunk_bounds(n, itemsize, chunk_bytes):
    per = max(1, chunk_bytes // max(1, itemsize))
    bounds = list(range(0, n, per)) + [n]
    return [(bounds[i], bounds[i + 1]) for i in range(len(bounds) - 1)]


def d2h_flat_start(dev_flat, *, chunk_bytes=DEFAULT_CHUNK_BYTES):
    """Slice a flat device array into chunks and start EVERY chunk's async
    device-to-host copy.  Returns the (spans, parts) handle for
    :func:`d2h_flat_land`.  Starting all transfers before consuming any
    pipelines the transport; starting them right after the grad step is
    dispatched overlaps them with host work (DPU)."""
    n = int(dev_flat.shape[0])
    spans = _chunk_bounds(n, dev_flat.dtype.itemsize, chunk_bytes)
    parts = ([dev_flat] if len(spans) <= 1
             else [dev_flat[a:b] for a, b in spans])
    for p in parts:
        if hasattr(p, "copy_to_host_async"):
            p.copy_to_host_async()
    return spans, parts


def d2h_flat_land(handle, host_out):
    """Land started chunks into a preallocated host buffer (upcasts on
    copy: fp32 landing buffer for 16-bit grads, into pre-faulted memory)."""
    spans, parts = handle
    for (a, b), p in zip(spans, parts):
        host_out[a:b] = np.asarray(p)
    return host_out


def d2h_flat_into(dev_flat, host_out, *, chunk_bytes=DEFAULT_CHUNK_BYTES):
    """Start + land in one call (non-overlapped path)."""
    assert host_out.shape[0] == int(dev_flat.shape[0]), \
        (host_out.shape, dev_flat.shape)
    return d2h_flat_land(d2h_flat_start(dev_flat, chunk_bytes=chunk_bytes),
                         host_out)


def d2h_tree_start(tree):
    """Begin async d2h for every leaf of a pytree (non-blocking)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "copy_to_host_async"):
            leaf.copy_to_host_async()


def gather_span(parts, per, start, end):
    """Concatenate the flat range ``[start, end)`` out of equally-sized
    chunks (``per`` elements each, last chunk may be short) — no
    full-size concatenate of the whole buffer."""
    import jax.numpy as jnp
    pieces = []
    s = start
    while s < end:
        c = s // per
        base = c * per
        e = min(end, base + int(parts[c].shape[0]))
        pieces.append(parts[c][s - base:e - base])
        s = e
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


def make_chunk_scatter(shapes, treedef, per, nchunks, *, out_shardings=None):
    """Build the jitted chunks→pytree scatter shared by every h2d upload
    path: each leaf is sliced straight out of the chunk(s) covering it —
    no full-size concatenate (that would double peak HBM) and per-chunk
    donation stays usable (XLA reuses chunk memory for the leaf outputs).

    ``shapes``: leaf shapes in treedef order (leaves tile the flat buffer
    contiguously); ``per``: elements per chunk (all chunks but the last).
    """

    def scatter(*parts):
        leaves = []
        o = 0
        for s in shapes:
            n = int(np.prod(s or (1,)))
            leaves.append(gather_span(parts, per, o, o + n).reshape(s))
            o += n
        return jax.tree_util.tree_unflatten(treedef, leaves)

    kw = {"out_shardings": out_shardings} if out_shardings is not None else {}
    return jax.jit(scatter, donate_argnums=tuple(range(nchunks)), **kw)


def make_quantized_chunk_scatter(shapes, treedef, plan, per_q, nq,
                                 per_fw, nfw, *, bits, block,
                                 out_dtype):
    """Chunks→pytree scatter for the QUANTIZED layer wire
    (docs/comms-compression.md, the ``param_stream`` route): quantized
    leaves are sliced out of the int8 image chunks and dequantized
    per-leaf on device; excluded/full-width leaves come from the
    (possibly empty) full-width image.

    ``plan``: per-leaf ``("q", q_off, n, npad)`` or ``("fw", fw_off, n)``
    entries in treedef order — offsets in ELEMENTS of the respective
    image (quantized leaves are block-aligned so each leaf owns whole
    scale blocks; the int4 image packs two elements per byte).
    Call: ``scatter(scales, *q_chunks, *fw_chunks)`` (chunks donated).
    """
    from ..comm.quantized import dequantize_flat_jnp
    pack = 2 if bits == 4 else 1

    def scatter(scales, *parts):
        q_parts, fw_parts = parts[:nq], parts[nq:]
        leaves = []
        for entry, shape in zip(plan, shapes):
            if entry[0] == "fw":
                _, off, n = entry
                flat = gather_span(fw_parts, per_fw, off, off + n)
            else:
                _, off, n, npad = entry
                qflat = gather_span(q_parts, per_q, off // pack,
                                    (off + npad) // pack)
                sc = scales[off // block:(off + npad) // block]
                flat = dequantize_flat_jnp(qflat, sc, bits=bits,
                                           out_dtype=out_dtype)[:n]
            leaves.append(flat.reshape(shape))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(scatter,
                   donate_argnums=tuple(range(1, 1 + nq + nfw)))


class H2DUploader:
    """Chunked host->device upload with an optional staging copy.

    ``upload_flat`` returns a list of device chunks covering the host
    array.  With ``stage=True`` each chunk is copied into a reusable
    staging buffer before ``device_put`` so the caller may mutate the
    source immediately (the delayed-param-update overlap mutates the
    16-bit payload while the previous upload may still be in flight —
    the staging copy is the pinned-buffer double-buffering the reference
    gets from its CUDA pinned pool).  Staging buffers are recycled only
    after the transfer they feed is committed.
    """

    def __init__(self, chunk_bytes=DEFAULT_CHUNK_BYTES):
        self.chunk_bytes = chunk_bytes
        self._staging = []        # reusable host buffers
        # fresh: chunk pairs from upload_flat awaiting their settle_on
        # (their arrays are donated into the consuming scatter, so they
        # MUST re-key to its output).  settled: pairs keyed to a settle
        # target; once THAT is deleted downstream they are parked until
        # release_parked() — a later settle_on must NOT re-key them (it
        # would hide their deletion and defeat the recycling barrier).
        # Every pair carries the DISPATCH EPOCH of its upload_flat call:
        # release_parked(epoch) recycles only pairs dispatched at or
        # before the caller's proven barrier, so an upload dispatched
        # AFTER the barrier value was computed (prefetch racing the
        # throttle read) can never have its staging buffer reused while
        # its h2d DMA may still be reading it.
        self._fresh = []          # (device_array, staging_buf, epoch)
        self._settled = []        # (settle_target, staging_buf, epoch)
        self._epoch = 0           # bumped once per upload_flat call

    def _get_staging(self, nbytes):
        for i, buf in enumerate(self._staging):
            if buf.nbytes >= nbytes:
                return self._staging.pop(i)
        return np.empty(nbytes, np.uint8)

    @property
    def dispatch_epoch(self):
        """Epoch of the latest ``upload_flat`` dispatch.  Capture this
        BEFORE dispatching compute whose later value-read will serve as
        the completion barrier, and hand it to :meth:`release_parked` —
        uploads dispatched after the capture are excluded."""
        return self._epoch

    def _reclaim(self, block=False):
        def sweep(pairs):
            still = []
            for arr, buf, epoch in pairs:
                # is_deleted (e.g. donated downstream) does NOT mean the
                # h2d DMA finished reading the staging buffer — donation
                # marks deletion at dispatch.  Only an observed is_ready()
                # proves the transfer landed.  A deleted-but-never-
                # observed-ready pair stays PARKED (buffer referenced)
                # until release_parked() at a caller-proven barrier.
                deleted = arr.is_deleted()
                done = (not deleted) and arr.is_ready()
                if block and not done and not deleted:
                    arr.block_until_ready()
                    done = True
                if done:
                    if buf is not None:
                        self._staging.append(buf)
                else:
                    still.append((arr, buf, epoch))
            return still
        self._settled = sweep(self._settled)
        self._fresh = sweep(self._fresh)

    def upload_flat(self, host_flat, *, device=None, stage=False,
                    chunk_bytes=None):
        """host flat array -> list of device chunk arrays (async).
        ``chunk_bytes`` overrides the uploader default for payloads with
        alignment needs (the quantized layer wire keeps chunks on scale-
        block boundaries so each chunk dequantizes independently)."""
        host_flat = host_flat.reshape(-1)
        spans = _chunk_bounds(host_flat.shape[0], host_flat.dtype.itemsize,
                              chunk_bytes or self.chunk_bytes)
        self._reclaim()
        self._epoch += 1
        out = []
        for a, b in spans:
            src = host_flat[a:b]
            buf = None
            if stage:
                buf = self._get_staging(src.nbytes)
                view = buf[:src.nbytes].view(host_flat.dtype)
                np.copyto(view, src)
                src = view
            arr = (jax.device_put(src, device) if device is not None
                   else jax.device_put(src))
            out.append(arr)
            self._fresh.append((arr, buf, self._epoch))
        return out

    def settle_on(self, arr):
        """Re-key the FRESH (just-uploaded, donated-into-the-scatter)
        chunk pairs onto ``arr`` — a downstream array whose readiness
        implies their DMAs completed (the compute that overwrites a
        donated chunk cannot run before its h2d transfer lands).
        Already-settled pairs are NOT re-keyed: once their own target is
        deleted downstream they are parked, and re-keying them onto ever-
        newer targets would hide the deletion and defeat
        :meth:`release_parked` (the r5 6.7B probe leaked a staging buffer
        per layer fetch exactly this way)."""
        self._settled += [(arr, buf, epoch) for _, buf, epoch in self._fresh]
        self._fresh = []

    def release_parked(self, epoch=None):
        """Recycle parked pairs after the CALLER has executed a true
        completion barrier (a VALUE READ of a downstream result — on
        remote-attached runtimes ``is_ready``/``block_until_ready`` may
        never observe donated-then-deleted settle targets).

        ``epoch`` scopes the barrier's proof: only pairs whose upload was
        dispatched at or before that :attr:`dispatch_epoch` capture are
        eligible.  A pair dispatched AFTER the barrier value was computed
        (the next layer's prefetch races the throttle read) can be
        settled-and-deleted — its scatter was dispatched and donated its
        chunks — while its h2d DMA has not provably read the staging
        buffer yet; recycling it would hand a buffer still on the wire to
        the next upload.  ``epoch=None`` keeps the legacy behavior
        (recycle every deleted pair) for callers whose barrier, by
        construction, postdates every dispatch (e.g. final-step flush)."""
        def eligible(pair_epoch):
            return epoch is None or pair_epoch <= epoch
        for arr, buf, pair_epoch in self._settled:
            if eligible(pair_epoch) and arr.is_deleted() \
                    and buf is not None:
                self._staging.append(buf)
        self._settled = [(a, b, e) for a, b, e in self._settled
                         if not (eligible(e) and a.is_deleted())]

    def wait(self):
        self._reclaim(block=True)

    def close(self):
        """Engine shutdown: drop every staging buffer and tracked pair.
        `del engine` does not free buffers still referenced here:
        engines built one after another in a process leaked them until a
        later one died RESOURCE_EXHAUSTED."""
        self._fresh = []
        self._settled = []
        self._staging = []
