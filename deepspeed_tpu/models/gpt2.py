"""GPT-2 family — the flagship training model, TPU-first.

Role parity: the reference validates against Megatron GPT-2 checkouts
(``tests/model/Megatron_GPT2``, vendored mini-GPT2 in
``tests/unit/megatron_model.py``); the presets are GPT-2
125M → 1.3B.  This is a from-scratch JAX implementation designed for the
hardware, not a port:

- **scan over layers**: block params are stacked along a leading layer axis and
  the forward is one ``lax.scan`` — O(1) compile time in depth, and under
  ZeRO-3 the per-iteration all-gather of one layer's params IS the reference's
  prefetch/release coordinator (``partitioned_param_coordinator.py``): the
  block states it (``zero/partition.gather_layer``), XLA schedules it.
- **remat**: ``jax.checkpoint`` over the scanned block replaces the reference's
  activation-checkpointing subsystem for this model; the policy saves only
  block boundaries (+ optionally attention outputs).
- **tensor parallelism**: Megatron-style column/row sharding declared as
  ``partition_specs`` (qkv/fc column-split on 'tensor', proj row-split);
  first-class, where the reference delegates TP to an external mpu
  (SURVEY.md §1).
- **MXU-friendly**: all matmuls batched (B*T, D) × (D, ·) shapes, bf16 inputs,
  fp32 softmax/layernorm accumulations.
"""

import dataclasses
from functools import partial
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass
class GPT2Config:
    vocab_size: int = 50257
    max_seq: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    embd_pdrop: float = 0.1
    attn_pdrop: float = 0.1
    resid_pdrop: float = 0.1
    layer_norm_eps: float = 1e-5
    remat: bool = True
    # selective rematerialization (only meaningful with remat=True):
    #   None          — save block inputs only, recompute everything (max
    #                   memory savings, ~1/3 extra compute)
    #   "dots"        — jax dots_with_no_batch_dims_saveable: matmul outputs
    #                   are saved, only cheap elementwise work recomputes
    #   "names:a,b"   — save only the named tensors (checkpoint_name marks
    #                   "attn_out" and "mlp_fc" in the block).  "attn_out"
    #                   is what the attention hands its backward: on the
    #                   flash path the kernel's output and log-sum-exp
    #                   (2·D + 4·H bytes a token a layer in bf16), so the
    #                   backward runs no second forward kernel; on every
    #                   other path the attention's output (2·D bytes)
    remat_policy: Optional[str] = None
    # loss_chunk > 0: compute the tied-head logits + cross-entropy in
    # token chunks of ~this size under jax.checkpoint — the (B·T, V) fp32
    # logits (0.8 GB at 760M/micro4/T1024, plus its cotangent) never
    # materializes, for one extra head matmul in backward (~3% step FLOPs)
    loss_chunk: int = 0
    # unroll the layer loop instead of lax.scan: XLA then schedules each
    # layer's weights/residuals statically (no stacked dynamic-update-slice
    # traffic) at the cost of depth-linear compile time — the fast choice
    # for single-chip throughput runs; scan is the fast-compile choice
    unroll_layers: bool = False
    # attention implementation: "auto" picks pallas flash on TPU, jnp elsewhere
    attention_impl: str = "auto"
    # paged-attention implementation for the serving decode path
    # (decode_step_paged):
    #   "kernel"      — the in-place Pallas kernel
    #                   (ops/transformer/paged_attention.py): block
    #                   tables/lengths as scalar-prefetch operands, K/V
    #                   blocks DMA'd straight from the pool (int8 pools
    #                   dequantized in-kernel from the fp32 scales) —
    #                   zero gathered K/V materialization.  Runs
    #                   compiled on TPU (online softmax) and in
    #                   interpret mode elsewhere (exact mode: bit-exact
    #                   vs the gather oracle, tests/test_paged_attention.py).
    #   "gather"      — the legacy paged_kv.gather_kv materialized view
    #                   (kept as the kernel's test oracle; its gather
    #                   traffic is what analysis/roofline.py prices as
    #                   gather_materialization_bytes)
    #   "auto"        — "kernel"
    paged_attention_impl: str = "auto"
    # GPT-Neo compatibility knobs (HFGPTNEOLayerPolicy): no score scaling and
    # a local attention window on alternating (odd) layers
    scale_attn: bool = True
    local_attn_window: Optional[int] = None

    @property
    def head_dim(self):
        assert self.n_embd % self.n_head == 0
        return self.n_embd // self.n_head

    @property
    def kv_layers(self):
        """Layers that keep K/V for a token (what the serving layer and the
        capacity math multiply by): every layer."""
        return self.n_layer


# Named presets (125M → 1.3B)
PRESETS = {
    "gpt2-125m": dict(n_embd=768, n_layer=12, n_head=12),
    "gpt2-350m": dict(n_embd=1024, n_layer=24, n_head=16),
    "gpt2-760m": dict(n_embd=1536, n_layer=24, n_head=16),
    "gpt2-1.3b": dict(n_embd=2048, n_layer=24, n_head=32),
    "gpt2-tiny": dict(n_embd=128, n_layer=4, n_head=4, vocab_size=1024, max_seq=256),
}


def _layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def _dropout(x, rate, rng, deterministic):
    if deterministic or rate == 0.0:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def _attention_jnp(q, k, v, causal_mask, attn_drop, rng, deterministic,
                   scale=None):
    """Reference jnp attention: fp32 softmax, bf16 matmuls (XLA fuses).
    Its output is the selective-remat save point ``attn_out``."""
    head_dim = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores * (1.0 / np.sqrt(head_dim) if scale is None else scale)
    scores = jnp.where(causal_mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = _dropout(probs, attn_drop, rng, deterministic).astype(q.dtype)
    return checkpoint_name(jnp.einsum("bhqk,bkhd->bqhd", probs, v),
                           "attn_out")


def layer_slice(blocks, i):
    """Static per-layer view of a stacked block pytree (the unrolled-loop
    idiom shared by every scanned model family)."""
    return jax.tree_util.tree_map(lambda a: a[i], blocks)


def resolve_remat_policy(spec):
    """``GPT2Config.remat_policy`` string → jax checkpoint policy (None =
    recompute everything; the memory/compute dial VERDICT r2 asked for on
    the largest on-chip models)."""
    if spec is None:
        return None
    if spec == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if spec.startswith("names:"):
        names = [n.strip() for n in spec[len("names:"):].split(",") if n.strip()]
        return jax.checkpoint_policies.save_only_these_names(*names)
    raise ValueError(f"unknown remat_policy {spec!r} "
                     "(None | 'dots' | 'names:<n1,n2,...>')")


def flash_or_jnp_attention(q, k, v, causal_mask, attn_pdrop, rng,
                           deterministic, impl, *, scale=None,
                           nonstandard=False):
    """Shared standard-causal attention dispatch: resolve 'auto', warn for
    unsupported flash combinations, run the Pallas kernel or the jnp oracle.
    Used by every rotary/dense decoder family so the selection logic cannot
    drift between models.  Either path marks the remat save point
    ``attn_out``: the kernel the two residuals its backward reads (a policy
    that lists the name then spares the backward a second forward kernel),
    the oracle its output."""
    wants_dropout = attn_pdrop > 0.0 and not deterministic
    if impl == "auto":
        from ..ops import flash_attention_available
        impl = ("flash" if flash_attention_available() and not wants_dropout
                and not nonstandard else "jnp")
    if impl == "flash":
        if nonstandard:
            from ..utils.logging import warning_once
            warning_once("attention_impl='flash' does not support "
                         "scale_attn=False / local_attn_window; using the "
                         "jnp path")
        else:
            if wants_dropout:
                from ..utils.logging import warning_once
                warning_once("attention_impl='flash' has no in-kernel "
                             "dropout; attn_pdrop is ignored on this path")
            from ..ops.transformer.flash_attention import flash_attention
            from ..parallel.mesh import BATCH_AXES, per_device
            # (B, T, H, hd): batch over the data axes, heads over tensor
            spec = P(BATCH_AXES, None, "tensor", None)
            return per_device(partial(flash_attention, causal=True,
                                      residual_name="attn_out"),
                              (spec, spec, spec), spec)(q, k, v)
    return _attention_jnp(q, k, v, causal_mask, attn_pdrop, rng,
                          deterministic, scale=scale)


def gpt2_block_forward(c, p, x, rng, deterministic, causal_mask, attend,
                       is_local=None):
    """One GPT-2 block (LN → attn → residual → LN → MLP → residual).

    SHARED by the scanned model (GPT2._block) and the pipelined layer
    (models/gpt2_pipe.GPT2Block) so the forward math cannot drift between
    the DP and PP paths.  ``attend(q, k, v, mask, rng, deterministic)``.
    """
    B, T, D = x.shape
    H, hd = c.n_head, c.head_dim
    r1, r2, r3 = jax.random.split(rng, 3)

    # named_scope: the flops profiler attributes compiled work to these
    # module scopes (reference per-module hooks, profiler.py:230)
    with jax.named_scope("attention"):
        h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"], c.layer_norm_eps)
        qkv = h @ p["qkv_w"].astype(h.dtype) + p["qkv_b"].astype(h.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, H, hd)
        k = k.reshape(B, T, H, hd)
        v = v.reshape(B, T, H, hd)
        mask = causal_mask
        if c.local_attn_window is not None and is_local is not None:
            # GPT-Neo: odd layers attend within a sliding window
            pos = jnp.arange(T)
            local = (pos[None, :] > pos[:, None] - c.local_attn_window)
            local_mask = causal_mask & local[None, None]
            mask = jnp.where(is_local, local_mask, causal_mask)
        # ``attend`` names its own selective-remat save point "attn_out"
        attn = attend(q, k, v, mask, r1, deterministic)
        attn = attn.reshape(B, T, D)
        attn = attn @ p["proj_w"].astype(h.dtype) + p["proj_b"].astype(h.dtype)
        x = x + _dropout(attn, c.resid_pdrop, r2, deterministic)

    with jax.named_scope("mlp"):
        h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], c.layer_norm_eps)
        h = h @ p["fc_w"].astype(h.dtype) + p["fc_b"].astype(h.dtype)
        # named for selective remat policies (remat_policy="names:mlp_fc"):
        # saving the 4E-wide fc output skips the biggest single recompute
        # matmul (16E^2 of the block's 48E^2 MACs) for 8KB/token/layer
        h = checkpoint_name(h, "mlp_fc")
        h = jax.nn.gelu(h, approximate=True)
        h = h @ p["fc_proj_w"].astype(h.dtype) + p["fc_proj_b"].astype(h.dtype)
        return x + _dropout(h, c.resid_pdrop, r3, deterministic)


def _chunked_head_nll(c, wte, x, labels):
    """Tied-head + cross-entropy over token chunks, each under
    ``jax.checkpoint``: per-chunk logits live only inside the chunk
    (fwd AND bwd) — the (B·T, V) fp32 array never exists.  A chunk is
    the same ``loss_chunk // B`` positions of EVERY row, so a
    batch-sharded stream gives each device its rows of each chunk (a
    chunk of whole rows would sit on one device and the others would
    repeat its matmul).  The position axis pads up to a chunk multiple
    with masked positions (a divisor search could degenerate to
    per-token chunks on prime counts).

    ``x``: post-final-LN hidden states (B, T, D)."""
    B, T, D = x.shape
    ct = min(max(int(c.loss_chunk) // B, 1), T)
    n = -(-T // ct)
    pad = ((0, 0), (0, n * ct - T))

    def chunks(a):
        """(B, T, ...) -> (n, B, ct, ...)"""
        a = jnp.pad(a, pad + ((0, 0),) * (a.ndim - 2))
        return jnp.swapaxes(a.reshape((B, n, ct) + a.shape[2:]), 0, 1)

    @jax.checkpoint
    def chunk_nll(xc, lc, vc):
        xc, lc, vc = xc.reshape(B * ct, D), lc.reshape(-1), vc.reshape(-1)
        logits = jnp.einsum("td,vd->tv", xc, wte.astype(xc.dtype),
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - lab) * vc)

    with jax.named_scope("lm_head"):
        total = jax.lax.map(lambda args: chunk_nll(*args),
                            (chunks(x), chunks(labels.astype(jnp.int32)),
                             chunks(jnp.ones((B, T), jnp.float32))))
        return jnp.sum(total) / (B * T)


class GPT2:
    """Decoder-only LM. Params are a dict pytree with scanned block stacks."""

    def __init__(self, config: Optional[GPT2Config] = None, preset: str = None,
                 dtype=jnp.bfloat16, **overrides):
        if config is None:
            base = dict(PRESETS[preset or "gpt2-125m"])
            base.update(overrides)
            config = GPT2Config(**base)
        self.config = config
        self.dtype = dtype

    # ------------------------------------------------------------------ init
    def init(self, rng):
        c = self.config
        D, L, V, T = c.n_embd, c.n_layer, c.vocab_size, c.max_seq
        k = jax.random.split(rng, 8)
        # GPT-2 init: normal(0.02); output projections scaled by 1/sqrt(2L)
        # (reference fused-layer flag adjust_init_range, transformer.py:39-137)
        std = 0.02
        proj_std = std / np.sqrt(2.0 * L)
        n = lambda key, shape, s=std: jax.random.normal(key, shape, jnp.float32) * s
        params = {
            "wte": n(k[0], (V, D)),
            "wpe": n(k[1], (T, D), 0.01),
            "blocks": {
                "ln1_scale": jnp.ones((L, D), jnp.float32),
                "ln1_bias": jnp.zeros((L, D), jnp.float32),
                "qkv_w": n(k[2], (L, D, 3 * D)),
                "qkv_b": jnp.zeros((L, 3 * D), jnp.float32),
                "proj_w": n(k[3], (L, D, D), proj_std),
                "proj_b": jnp.zeros((L, D), jnp.float32),
                "ln2_scale": jnp.ones((L, D), jnp.float32),
                "ln2_bias": jnp.zeros((L, D), jnp.float32),
                "fc_w": n(k[4], (L, D, 4 * D)),
                "fc_b": jnp.zeros((L, 4 * D), jnp.float32),
                "fc_proj_w": n(k[5], (L, 4 * D, D), proj_std),
                "fc_proj_b": jnp.zeros((L, D), jnp.float32),
            },
            "lnf_scale": jnp.ones((D,), jnp.float32),
            "lnf_bias": jnp.zeros((D,), jnp.float32),
        }
        return params

    def init_numpy(self, seed=0):
        """Host-RAM numpy twin of :meth:`init` (same structure, shapes and
        init distribution; different RNG stream).  Used by the streamed
        param-offload tier's ``fast_init``: at multi-billion params the
        jitted XLA-CPU init costs minutes and ~3x the tree in transient
        RAM, while numpy fills the buffers in place."""
        c = self.config
        D, L, V, T = c.n_embd, c.n_layer, c.vocab_size, c.max_seq
        rng = np.random.default_rng(seed)
        std = 0.02
        proj_std = std / np.sqrt(2.0 * L)
        n = lambda shape, s=std: rng.normal(0.0, s, shape).astype(np.float32)
        return {
            "wte": n((V, D)),
            "wpe": n((T, D), 0.01),
            "blocks": {
                "ln1_scale": np.ones((L, D), np.float32),
                "ln1_bias": np.zeros((L, D), np.float32),
                "qkv_w": n((L, D, 3 * D)),
                "qkv_b": np.zeros((L, 3 * D), np.float32),
                "proj_w": n((L, D, D), proj_std),
                "proj_b": np.zeros((L, D), np.float32),
                "ln2_scale": np.ones((L, D), np.float32),
                "ln2_bias": np.zeros((L, D), np.float32),
                "fc_w": n((L, D, 4 * D)),
                "fc_b": np.zeros((L, 4 * D), np.float32),
                "fc_proj_w": n((L, 4 * D, D), proj_std),
                "fc_proj_b": np.zeros((L, D), np.float32),
            },
            "lnf_scale": np.ones((D,), np.float32),
            "lnf_bias": np.zeros((D,), np.float32),
        }

    # ------------------------------------------------- tensor-parallel specs
    def partition_specs(self, params=None):
        """Megatron-style TP sharding (reference delegates this to mpu;
        here it is first-class).  Column-parallel: qkv, fc (shard output dim);
        row-parallel: proj, fc_proj (shard input dim); vocab-parallel wte."""
        return {
            "wte": P("tensor", None),
            "wpe": P(),
            "blocks": {
                "ln1_scale": P(), "ln1_bias": P(),
                "qkv_w": P(None, None, "tensor"),
                "qkv_b": P(None, "tensor"),
                "proj_w": P(None, "tensor", None),
                "proj_b": P(),
                "ln2_scale": P(), "ln2_bias": P(),
                "fc_w": P(None, None, "tensor"),
                "fc_b": P(None, "tensor"),
                "fc_proj_w": P(None, "tensor", None),
                "fc_proj_b": P(),
            },
            "lnf_scale": P(), "lnf_bias": P(),
        }

    # --------------------------------------------------------------- forward
    def _block(self, x, layer_params, rng, deterministic, causal_mask,
               is_local=None):
        # ZeRO-3's fetch, inside what jax.checkpoint wraps: the layer's
        # weights become whole here (forward and rematerialised backward,
        # never a residual of the scan), the stream stays batch-sharded
        from ..runtime.zero.partition import gather_layer, shard_stream
        layer_params = gather_layer(layer_params, self._layer_specs())
        return gpt2_block_forward(self.config, layer_params, shard_stream(x),
                                  rng, deterministic, causal_mask,
                                  self._attend, is_local=is_local)

    def _whole(self, params, *names):
        """The named unstacked leaves, whole over ``fsdp`` (the tied
        embedding where the lookup and the head use it)."""
        from ..runtime.zero.partition import gather_layer
        specs = self.partition_specs()
        whole = gather_layer({n: params[n] for n in names},
                             {n: specs[n] for n in names})
        return [whole[n] for n in names]

    def _layer_specs(self):
        """``partition_specs()`` of one layer: the stacked dim dropped."""
        return jax.tree_util.tree_map(
            lambda sp: P(*tuple(sp)[1:]), self.partition_specs()["blocks"],
            is_leaf=lambda sp: isinstance(sp, P))

    def _attend(self, q, k, v, causal_mask, rng, deterministic):
        c = self.config
        impl = c.attention_impl
        wants_dropout = c.attn_pdrop > 0.0 and not deterministic
        # flash path covers the standard scaled-causal case only
        nonstandard = not c.scale_attn or c.local_attn_window is not None
        if impl in ("ring", "ring_flash", "ulysses"):
            # sequence parallelism: attention over the mesh `seq` axis
            # (engine-level long context; NEW vs the reference vintage)
            if nonstandard or wants_dropout:
                from ..utils.logging import warning_once
                warning_once(f"attention_impl={impl!r} ignores attn dropout "
                             "and GPT-Neo attention knobs")
            from ..parallel import sequence_parallel as sp
            from ..parallel.mesh import batch_spec
            fn = {"ring": sp.ring_attention,
                  "ring_flash": sp.ring_flash_attention,
                  "ulysses": sp.ulysses_attention}[impl]
            return checkpoint_name(
                fn(q, k, v, causal=True, batch_spec=batch_spec()), "attn_out")
        return flash_or_jnp_attention(
            q, k, v, causal_mask, c.attn_pdrop, rng, deterministic, impl,
            scale=None if c.scale_attn else 1.0, nonstandard=nonstandard)

    def apply(self, params, tokens, rng=None, deterministic=True,
              return_hidden=False):
        """tokens: (B, T) int32 → logits (B, T, V) (or the final-LN hidden
        states (B, T, D) with ``return_hidden`` — the chunked-loss entry)."""
        c = self.config
        B, T = tokens.shape
        # out-of-range positions would silently clamp in the wpe gather
        assert T <= c.max_seq, f"sequence length {T} exceeds max_seq {c.max_seq}"
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        dtype = self.dtype

        from ..runtime.zero.partition import shard_stream
        wte, wpe = self._whole(params, "wte", "wpe")
        with jax.named_scope("embed"):
            pos = jnp.arange(T)
            x = wte.astype(dtype)[tokens] + wpe.astype(dtype)[pos]
            x = shard_stream(_dropout(x, c.embd_pdrop,
                                      jax.random.fold_in(rng, 17),
                                      deterministic))
        causal_mask = jnp.tril(jnp.ones((T, T), bool))[None, None, :, :]

        block = self._block
        if c.remat:
            block = jax.checkpoint(block, static_argnums=(3,),
                                   policy=resolve_remat_policy(c.remat_policy))

        # GPT-Neo layer pattern: odd layers are local-window
        local_flags = jnp.arange(c.n_layer) % 2 == 1

        def scan_body(carry, xs):
            h = carry
            layer_params, layer_rng, is_local = xs
            h = block(h, layer_params, layer_rng, deterministic, causal_mask,
                      is_local)
            return h, None

        layer_rngs = jax.random.split(jax.random.fold_in(rng, 31), c.n_layer)
        with jax.named_scope("blocks"):
            if c.unroll_layers:
                for i in range(c.n_layer):
                    lp = layer_slice(params["blocks"], i)
                    x = block(x, lp, layer_rngs[i], deterministic,
                              causal_mask, local_flags[i])
            else:
                x, _ = jax.lax.scan(scan_body, x,
                                    (params["blocks"], layer_rngs, local_flags))

        with jax.named_scope("lm_head"):
            x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"],
                            c.layer_norm_eps)
            if return_hidden:
                return x
            # tied output head: bf16 operands, fp32 accumulation — full MXU
            # rate (a pure-fp32 matmul here runs at half rate and is ~25% of
            # 125M FLOPs)
            logits = jnp.einsum("btd,vd->btv", x, wte.astype(x.dtype),
                                preferred_element_type=jnp.float32)
        return logits

    # ------------------------------------------------------- KV-cache decode
    def init_cache(self, batch_size: int, max_len: Optional[int] = None,
                   dtype=None):
        """Empty KV cache pytree: k/v stacked over layers
        (role parity: the reference inference kernels' ``layer_past`` KV
        layout, ``ops/transformer/inference/transformer_inference.py:345``)."""
        c = self.config
        max_len = max_len or c.max_seq
        # position/rotary tables only have max_seq rows; beyond that JAX
        # gather CLAMPS the index and decoding goes silently wrong
        assert max_len <= c.max_seq, (
            f"init_cache max_len={max_len} exceeds config.max_seq="
            f"{c.max_seq}; raise max_seq when building the model")
        dtype = dtype or self.dtype
        # SEQ-MAJOR stacked cache (L, S, B, H, hd): the per-token update
        # writes ONE contiguous (B, H, hd) block per layer, where
        # batch-major (L, B, S, ...) scatters B strided rows per write
        shape = (c.n_layer, max_len, batch_size, c.n_head, c.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                "index": jnp.zeros((), jnp.int32)}

    # decode-path matmuls route through q_matmul/q_gather: params may be
    # int8 payloads from init_inference(dtype=int8) — the Pallas kernel
    # streams int8 bytes from HBM (the whole point of int8 decode; the
    # reference's qkv_gemm_int8/mlp_gemm_int8,
    # ``csrc/transformer/inference/csrc/pt_binding.cpp:1148``).  Plain
    # arrays pass through unchanged, so the float path is untouched.
    supports_quantized_decode = True

    @staticmethod
    def _mm(h, w, b=None, transpose=False):
        from ..module_inject.module_quantize import q_matmul
        out = q_matmul(h, w, w_transposed=transpose)
        if b is not None:
            out = out + b.astype(out.dtype)
        return out

    def _qkv(self, p, h):
        c = self.config
        B, T, D = h.shape
        H, hd = c.n_head, c.head_dim
        qkv = self._mm(h, p["qkv_w"], p["qkv_b"])
        q, k, v = jnp.split(qkv, 3, axis=-1)
        return (q.reshape(B, T, H, hd), k.reshape(B, T, H, hd),
                v.reshape(B, T, H, hd))

    def _masked_attend(self, q, keys, vals, valid, seq_major=False):
        """The decode attention core shared by EVERY cache layout
        (contiguous batch-major, contiguous seq-major, paged): fp32
        scores, scale_attn, mask, softmax, AV.  ``valid`` must broadcast
        to (B, H, T, S); keeping this in one place is what stops the
        scoring semantics drifting between decode paths."""
        c = self.config
        B, T = q.shape[0], q.shape[1]
        k_eq = "kbhd" if seq_major else "bkhd"
        scores = jnp.einsum(f"bqhd,{k_eq}->bhqk", q, keys).astype(jnp.float32)
        if c.scale_attn:
            scores = scores / np.sqrt(c.head_dim)
        scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum(f"bhqk,{k_eq}->bqhd", probs, vals).reshape(
            B, T, q.shape[2] * q.shape[3])

    def _attend_cached(self, q, cache_k, cache_v, index, is_local=None,
                       seq_major=False):
        """Contiguous-cache attention (both layouts): builds the causal/
        local-window mask from the scalar write ``index`` and defers to
        :meth:`_masked_attend`.  ``seq_major``: cache is (S, B, H, hd)
        (stacked decode path) instead of (B, S, H, hd)."""
        c = self.config
        T = q.shape[1]
        S = cache_k.shape[0] if seq_major else cache_k.shape[1]
        q_pos = index + jnp.arange(T)[:, None]          # (T, 1)
        k_pos = jnp.arange(S)[None, :]                  # (1, S)
        valid = k_pos <= q_pos                          # causal within cache
        if c.local_attn_window is not None and is_local is not None:
            # GPT-Neo local layers: same sliding window as apply()
            local = valid & (k_pos > q_pos - c.local_attn_window)
            valid = jnp.where(is_local, local, valid)
        return self._masked_attend(q, cache_k, cache_v, valid[None, None],
                                   seq_major=seq_major)

    def _ffn(self, p, x):
        """The decode MLP half-block (LN2 → fc → gelu → fc_proj +
        residual), shared by every decode path — int8-aware via
        ``_mm``."""
        with jax.named_scope("mlp"):
            c = self.config
            h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], c.layer_norm_eps)
            h = self._mm(h, p["fc_w"], p["fc_b"])
            h = jax.nn.gelu(h, approximate=True)
            return x + self._mm(h, p["fc_proj_w"], p["fc_proj_b"])

    def _cached_attention(self, p, h, cache_k, cache_v, index, is_local=None):
        """Per-layer batch-major cache variant (GPT2MoE's decode).

        ``h``: normalized block input (B, T, D).  Returns
        (attn_out (B, T, D), new_cache_k, new_cache_v)."""
        q, k, v = self._qkv(p, h)
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, index, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, index, 0, 0))
        attn = self._attend_cached(q, cache_k, cache_v, index, is_local)
        attn = self._mm(attn, p["proj_w"], p["proj_b"])
        return attn, cache_k, cache_v

    def _block_with_cache_stacked(self, x, layer_params, ck_all, cv_all,
                                  layer, index, is_local=None):
        """One decode block updating the FULL stacked (L, S, B, H, hd)
        cache IN PLACE via dynamic_update_slice at (layer, index, 0, 0, 0).

        The decode scan threads the whole cache through every layer so
        XLA aliases one buffer end-to-end (donated at the jit boundary);
        gathering ``cache[i]`` copies and re-stacking them after the loop
        would be a full cache copy per decoded token (B-proportional copy
        traffic on top of the B-independent weight streaming)."""
        c = self.config
        p = layer_params
        with jax.named_scope("attention"):
            h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"],
                            c.layer_norm_eps)
            q, k, v = self._qkv(p, h)
            # seq-major (L, S, B, H, hd): one CONTIGUOUS (T, B, H, hd) write
            # per layer per token (see init_cache)
            ck_all = jax.lax.dynamic_update_slice(
                ck_all, k.swapaxes(0, 1)[None].astype(ck_all.dtype),
                (layer, index, 0, 0, 0))
            cv_all = jax.lax.dynamic_update_slice(
                cv_all, v.swapaxes(0, 1)[None].astype(cv_all.dtype),
                (layer, index, 0, 0, 0))
            attn = self._attend_cached(q, ck_all[layer], cv_all[layer],
                                       index, is_local, seq_major=True)
            attn = self._mm(attn, p["proj_w"], p["proj_b"])
        return self._ffn(p, x + attn), ck_all, cv_all

    def apply_with_cache(self, params, tokens, cache):
        """Forward ``tokens: (B, T)`` starting at ``cache['index']``.

        Returns ``(logits (B, T, V), new_cache)``.  Used for both prefill
        (T = prompt length) and single-token decode (T = 1); dropout is
        always off (inference).
        """
        c = self.config
        B, T = tokens.shape
        dtype = self.dtype
        index = cache["index"]

        pos = index + jnp.arange(T)
        from ..module_inject.module_quantize import q_gather
        with jax.named_scope("embed"):
            x = q_gather(params["wte"], tokens, dtype) + \
                q_gather(params["wpe"], pos, dtype)

        local_flags = jnp.arange(c.n_layer) % 2 == 1

        # ONE lax.scan over the stacked layer weights: the whole layer
        # stack is a single executable a step (an XLA while loop), not
        # 4·L separately dispatched small matmuls with scheduling gaps
        # between them.  The seq-major stacked cache rides the carry
        # (donated at the jit boundary → in-place); weights are scan xs,
        # so each iteration dynamic-slices ONE layer's stack — including
        # int8 {"q","scale"} payloads, whose per-layer slices stream int8
        # through q_matmul inside the same launch
        # (ops/transformer/int8_matmul.py).
        def fused_body(carry, xs):
            h, ck, cv, layer = carry
            lp, is_local = xs
            h, ck, cv = self._block_with_cache_stacked(
                h, lp, ck, cv, layer, index, is_local)
            return (h, ck, cv, layer + 1), None

        (x, new_k, new_v, _), _ = jax.lax.scan(
            fused_body,
            (x, cache["k"], cache["v"], jnp.zeros((), jnp.int32)),
            (params["blocks"], local_flags))

        # bf16 operands + fp32 accumulation: a pure-fp32 head matmul runs
        # at a fraction of MXU rate and is the only B-proportional flop
        # term in decode — it was the b=8 throughput ceiling.  Tied head:
        # wte used transposed (and possibly int8 — the vocab matmul is
        # ~31% of 125M weight bytes, the single biggest decode stream).
        from ..module_inject.module_quantize import q_matmul
        with jax.named_scope("lm_head"):
            x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"],
                            c.layer_norm_eps)
            logits = q_matmul(x, params["wte"], w_transposed=True,
                              out_dtype=jnp.float32)
        new_cache = {"k": new_k, "v": new_v, "index": index + T}
        return logits, new_cache

    # ---------------------------------------------------- paged-KV decode
    # the serving layer's decode path (inference/serving.py): per-slot
    # block lists into a shared pool instead of one contiguous cache
    supports_paged_decode = True

    def paged_attention_impl(self) -> str:
        """Resolve ``config.paged_attention_impl`` ("auto" → "kernel").

        The live impl decides the decode step's HBM traffic, so the
        serving layer reports it into every ``exe_cost`` gauge and
        ``analysis/roofline.py`` prices ``gather_materialization_bytes``
        only for the gather fallback (0 for the kernel)."""
        impl = self.config.paged_attention_impl
        if impl == "auto":
            impl = "kernel"
        assert impl in ("kernel", "gather"), (
            f"paged_attention_impl must be auto|kernel|gather, got "
            f"{impl!r}")
        return impl

    def init_serving_state(self, batch_slots, num_blocks, block_size,
                           kv_bits=16, quant_block=64, dtype=None):
        """The pytree the serving engine donates through its steps.  A
        model owns its serving state; this family's is its K/V blocks and
        nothing else (``batch_slots`` sizes nothing here)."""
        from ..inference import paged_kv as pk
        c = self.config
        return pk.init_pool(c.n_layer, num_blocks, block_size, c.n_head,
                            c.head_dim, dtype or self.dtype, kv_bits=kv_bits,
                            quant_block=quant_block)

    def prefill_paged(self, params, toks, pool, blocks, slot, t_real):
        """One prompt, padded to its bucket, into the pool: the contiguous
        cached forward on ONE sequence, its K/V scattered into ``blocks``.
        ``toks``: (1, min(bucket, max_seq)) — a bucket rounded past
        ``max_seq`` (max_seq not a block multiple) would trip
        ``init_cache``'s position-table guard, so the extracted K/V rows
        zero-pad up to the bucket for the block scatter (pad rows sit
        beyond the slot's length: masked, then overwritten by decode
        writes).  ``slot`` names the stream's batch slot for families whose
        state lives per slot; unused here.  Returns ``(logits (1, V) at
        token t_real - 1, pool)``."""
        from ..inference import paged_kv as pk
        fwd_len = toks.shape[1]
        bucket = blocks.shape[0] * pool["k"].shape[2]
        cache = self.init_cache(1, fwd_len)
        logits, cache = self.apply_with_cache(params, toks, cache)
        # seq-major (L, S, B, H, hd): (L, T, H, hd) at B=1
        k, v = cache["k"][:, :, 0], cache["v"][:, :, 0]
        if fwd_len < bucket:
            pad = ((0, 0), (0, bucket - fwd_len), (0, 0), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        with jax.named_scope("kv.seat"):
            pool = pk.write_prefill(pool, blocks, k, v)
        return logits[0, t_real - 1][None], pool

    def _attend_paged(self, q, keys, vals, lengths):
        """Per-slot masked attention of a W-token query window over
        gathered pool blocks — builds the paged mask and defers to the
        shared :meth:`_masked_attend` core.  ``q``: (B, W, H, hd);
        ``keys``/``vals``: (B, S, H, hd) gathered block content
        (S = nb_max·block_size); ``lengths``: (B,) int32 position of the
        FIRST window token (its K/V already written), so
        ``k_pos <= lengths + w`` is the causal mask for window row w and
        everything past it — pad tail, scratch blocks, stale block
        content, later window tokens — masks out."""
        W = q.shape[1]
        valid = (jnp.arange(keys.shape[1])[None, None, :]
                 <= lengths[:, None, None]
                 + jnp.arange(W, dtype=lengths.dtype)[None, :, None])
        return self._masked_attend(q, keys, vals, valid[:, None])

    def decode_step_paged(self, params, toks, pool, block_tables, lengths):
        """One decode window for B slots over a paged/block KV pool.

        ``toks``: (B,) int32 current input token per slot — or (B, W)
        for a multi-token window (window token i sits at position
        ``lengths + i`` with in-window causal masking; the serving loop
        passes (B,), and chunked prefill, ROADMAP S3, is the window's
        next user);
        ``lengths``: (B,) int32 tokens already cached per slot (== the
        first window token's position); ``block_tables``: (B, nb_max)
        int32 pool block ids (unused entries point at the reserved
        scratch block 0).  Returns ``(logits, new_pool)`` with logits
        (B, V) fp32 for 1-D ``toks`` and (B, W, V) for a window.

        Same fused shape as :meth:`apply_with_cache`: one ``lax.scan``
        over the stacked layer weights, the pool carried in place, int8
        weight payloads sliced per layer inside the scan.  The
        attention core is the in-place Pallas kernel by default
        (``paged_attention_impl``): K/V blocks are read straight from
        the pool — zero gathered copies — with ``gather_kv`` kept one
        flag away as the fallback and test oracle.  Inactive slots
        decode garbage into scratch block 0 — the scheduler discards
        their outputs (fixed shapes keep ONE executable per
        (batch_slots, nb_max) config; see inference/serving.py).
        """
        from ..inference import paged_kv as pk
        from ..module_inject.module_quantize import q_gather, q_matmul
        from ..ops.transformer.paged_attention import paged_attention
        c = self.config
        assert c.local_attn_window is None, \
            "paged decode supports standard causal attention only"
        squeeze = toks.ndim == 1
        if squeeze:
            toks = toks[:, None]
        W = toks.shape[1]
        impl = self.paged_attention_impl()
        pos = jnp.minimum(
            lengths[:, None] + jnp.arange(W, dtype=lengths.dtype)[None, :],
            c.max_seq - 1)
        with jax.named_scope("embed"):
            x = q_gather(params["wte"], toks, self.dtype) + \
                q_gather(params["wpe"], pos, self.dtype)    # (B, W, D)

        def body(carry, lp):
            h, pool, layer = carry
            with jax.named_scope("attention"):
                hn = _layer_norm(h, lp["ln1_scale"], lp["ln1_bias"],
                                 c.layer_norm_eps)
                q, k, v = self._qkv(lp, hn)             # (B, W, H, hd)
                with jax.named_scope("kv.seat"):
                    pool = pk.write_tokens(pool, layer, block_tables,
                                           lengths, k, v)
                if impl == "kernel":
                    attn = paged_attention(q, pool, block_tables, lengths,
                                           layer, scale_attn=c.scale_attn)
                else:
                    keys, vals = pk.gather_kv(pool, layer, block_tables,
                                              self.dtype, c.n_head)
                    attn = self._attend_paged(q, keys, vals, lengths)
                attn = self._mm(attn, lp["proj_w"], lp["proj_b"])
            return (self._ffn(lp, h + attn), pool, layer + 1), None

        (x, pool, _), _ = jax.lax.scan(
            body, (x, pool, jnp.zeros((), jnp.int32)), params["blocks"])
        with jax.named_scope("lm_head"):
            x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"],
                            c.layer_norm_eps)
            if squeeze:
                x = x[:, 0]
            logits = q_matmul(x, params["wte"], w_transposed=True,
                              out_dtype=jnp.float32)
        return logits, pool

    # ------------------------------------------------------------------ loss
    def loss(self, params, batch, rng):
        """Next-token LM loss.  ``batch``: (B, T+1) int tokens, or a dict with
        'input_ids' (and optional 'labels'), or a (tokens,) tuple."""
        tokens, labels = self._split_batch(batch)
        if self.config.loss_chunk > 0:
            return self._chunked_loss(params, tokens, labels, rng)
        logits = self.apply(params, tokens, rng=rng, deterministic=False)
        # lse − label_logit instead of materializing the full (B,T,V) fp32
        # log-softmax: the logits array is ~1.6GB at 125M/seq512/mb16, and
        # skipping the logp write/read saves real HBM bandwidth
        lse = jax.nn.logsumexp(logits, axis=-1)
        label_logit = jnp.take_along_axis(
            logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.mean(lse - label_logit)

    def _chunked_loss(self, params, tokens, labels, rng):
        """Tied-head + cross-entropy over token chunks (see
        :func:`_chunked_head_nll`)."""
        x = self.apply(params, tokens, rng=rng, deterministic=False,
                       return_hidden=True)
        (wte,) = self._whole(params, "wte")
        return _chunked_head_nll(self.config, wte, x, labels)

    # ------------------------------------------------- param-offload streaming
    def stream_fns(self):
        """Decomposed forward for the ZeRO-3 parameter-offload runner
        (``runtime/zero/param_stream.py``): params live on the HOST and
        layer blocks stream through the device one at a time, so the
        forward must be callable in per-layer pieces.  RNG derivation
        matches :meth:`apply` exactly (embed dropout ``fold_in(rng, 17)``,
        layer rngs ``split(fold_in(rng, 31), L)``) so a streamed run
        loss-matches the monolithic one bit-for-bit.

        Parity: reference ``zero/stage3.py:656 _configure_offloading`` +
        ``partitioned_param_coordinator`` fetch/release per submodule.
        """
        c = self.config
        dtype = self.dtype

        def embed(nonblock, tokens, rng, deterministic):
            T = tokens.shape[1]
            pos = jnp.arange(T)
            x = (nonblock["wte"].astype(dtype)[tokens]
                 + nonblock["wpe"].astype(dtype)[pos])
            return _dropout(x, c.embd_pdrop, jax.random.fold_in(rng, 17),
                            deterministic)

        def layer_rngs(rng):
            return jax.random.split(jax.random.fold_in(rng, 31), c.n_layer)

        def block(layer_p, x, rng, is_local, deterministic):
            T = x.shape[1]
            causal_mask = jnp.tril(jnp.ones((T, T), bool))[None, None, :, :]
            return gpt2_block_forward(c, layer_p, x, rng, deterministic,
                                      causal_mask, self._attend,
                                      is_local=is_local)

        def head_loss(nonblock, x, labels):
            x = _layer_norm(x, nonblock["lnf_scale"], nonblock["lnf_bias"],
                            c.layer_norm_eps)
            if c.loss_chunk > 0:
                return _chunked_head_nll(c, nonblock["wte"], x, labels)
            logits = jnp.einsum("btd,vd->btv", x,
                                nonblock["wte"].astype(x.dtype),
                                preferred_element_type=jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            label_logit = jnp.take_along_axis(
                logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
            return jnp.mean(lse - label_logit)

        return {
            "stacked_key": "blocks",
            "n_layer": c.n_layer,
            "local_flags": np.arange(c.n_layer) % 2 == 1,
            "embed": embed,
            "layer_rngs": layer_rngs,
            "block": block,
            "head_loss": head_loss,
            "split_batch": self._split_batch,
        }

    @staticmethod
    def _split_batch(batch):
        if isinstance(batch, dict):
            tokens = batch["input_ids"]
            labels = batch.get("labels")
            if labels is None:
                tokens, labels = tokens[:, :-1], tokens[:, 1:]
            return tokens, labels
        if isinstance(batch, (tuple, list)):
            batch = batch[0]
        return batch[:, :-1], batch[:, 1:]

    # ----------------------------------------------------------- flop counts
    def num_params(self):
        """Exact parameter count (matmuls + biases + LayerNorms + embeddings)."""
        c = self.config
        per_layer = (12 * c.n_embd ** 2       # qkv, proj, fc, fc_proj weights
                     + 13 * c.n_embd)         # their biases + 2×LN scale/bias
        return (c.vocab_size * c.n_embd + c.max_seq * c.n_embd +
                c.n_layer * per_layer + 2 * c.n_embd)

    def flops_per_token(self):
        """Training FLOPs/token ≈ 6N + attention-score terms (MFU accounting).

        6N covers fwd(2N)+bwd(4N) of every matmul touching the params;
        12·L·D·T adds the QKᵀ/AV score matmuls (fwd 4·L·D·T, ×3 with bwd).
        """
        c = self.config
        return 6 * self.num_params() + 12 * c.n_layer * c.n_embd * c.max_seq
