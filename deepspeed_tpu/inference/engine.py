"""Inference engine: jitted forward + KV-cache generation with TP sharding.

Parity: reference ``deepspeed/inference/engine.py:23`` (``InferenceEngine``):
TP group construction (:148), injection policy (:230), MP-sharded checkpoint
loading (:286), dtype conversion (:340), CUDA-graph capture (:360) and
``forward`` (:389).

TPU re-design:

- CUDA-graph capture/replay disappears: XLA compiles the whole decode step
  (SURVEY.md §7 "What we explicitly will NOT rebuild").
- Tensor parallelism = the model's ``partition_specs`` bound over the
  ``tensor`` mesh axis; per-layer TP allreduces are inserted by the SPMD
  partitioner instead of ``LinearAllreduce`` modules.
- The KV cache is a device-resident pytree (reference: workspace +
  ``layer_past`` tensors inside the CUDA kernels); decode runs as one jitted
  step per token with donated cache.
- Kernel injection (``replace_with_kernel_inject``) = converting HF torch
  weights into this framework's model family (``module_inject``) — the
  "kernels" are the jitted/pallas paths those models already use.
"""

import os
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..monitor import spans as monspans
from ..parallel import mesh as M
from ..utils.logging import logger, log_dist


class InferenceEngine:
    @monspans.in_setup_span("setup.engine_init", engine="InferenceEngine")
    def __init__(self, model=None, mp_size: int = 1, dtype=None,
                 checkpoint: Optional[str] = None, params: Any = None,
                 replace_with_kernel_inject: bool = False,
                 injection_dict=None, replace_method: str = "auto",
                 triangular_masking: bool = True, return_tuple: bool = True,
                 mesh=None, moe: bool = False, moe_experts: int = 1,
                 quantization_setting=None, enable_cuda_graph: bool = False,
                 mpu=None, ep_size: int = 1, config=None, max_seq=None,
                 rng_seed: int = 0, compile_cache=None):
        # HF torch module → convert through the injection layer
        if _is_torch_module(model):
            from ..module_inject.replace_module import replace_transformer_layer
            model, params = replace_transformer_layer(
                None, model, policy=injection_dict, dtype=dtype)
        self.module = model
        assert hasattr(model, "apply"), \
            "InferenceEngine needs a model with .apply (or an HF module to inject)"

        if mesh is None:
            # serving is one-chip replicas: the default engine takes the
            # first ``mp_size`` devices, never every chip of the host
            mesh = M.make_mesh({"data": 1, "tensor": mp_size},
                               devices=jax.devices()[:mp_size])
        self.mesh = mesh
        self.mp_world_size = M.mesh_axis_size(mesh, "tensor")
        dtype = _normalize_dtype(dtype)
        self.dtype = dtype
        if dtype is not None and hasattr(model, "dtype"):
            model.dtype = {np.float32: jnp.float32}.get(dtype, dtype)

        # ---- parameters ---------------------------------------------------
        if params is None:
            if checkpoint is not None:
                params = self._load_checkpoint(checkpoint)
            else:
                assert hasattr(model, "init"), "need params=, checkpoint=, or model.init"
                params = model.init(jax.random.PRNGKey(rng_seed))
        # ---- int8 weight quantization (reference: quantization_setting +
        # int8 inference gemms; here dequant fuses into the jitted matmuls) --
        from ..module_inject.module_quantize import _is_quantized_leaf
        # params may arrive pre-quantized (QuantizedModel + int8 tree)
        self.quantized = any(
            _is_quantized_leaf(x) for x in jax.tree_util.tree_leaves(
                params, is_leaf=_is_quantized_leaf)
            if isinstance(x, dict))
        # dtype=int8 means "quantize", not "cast": a float->int8 astype would
        # truncate weights (mostly in [-1, 1]) to 0/±1 and destroy the model
        # before quantize_param_tree ever saw it.
        if (self.dtype is not None and self.dtype != jnp.int8
                and not self.quantized):
            params = jax.tree_util.tree_map(
                lambda p: p.astype(self.dtype) if hasattr(p, "astype") else p, params)
        wants_q = (quantization_setting is not None or dtype == jnp.int8) \
            and not self.quantized
        act_dtype = jnp.bfloat16 if dtype in (None, jnp.int8) else dtype
        if wants_q:
            from ..module_inject.module_quantize import (quantize_param_tree,
                                                         QuantizedModel)
            if isinstance(quantization_setting, (tuple, list)):
                # reference API shape: (mlp_extra_grouping, quantize_groups)
                _mlp_extra, groups = quantization_setting
            elif isinstance(quantization_setting, int):
                groups = quantization_setting
            elif quantization_setting is None:
                groups = 1
            else:
                raise ValueError("quantization_setting must be int, "
                                 "(mlp_extra_grouping, groups), or None; got "
                                 f"{quantization_setting!r}")
            params, _ = quantize_param_tree(params, bits=8, groups=max(1, groups))
            self.quantized = True
            self._quant_groups = max(1, groups)
        if self.quantized:
            from ..module_inject.module_quantize import QuantizedModel
            if not isinstance(model, QuantizedModel):
                # activations run in act_dtype; params keep int8 storage
                if hasattr(model, "dtype"):
                    model.dtype = act_dtype
                self.module = model = QuantizedModel(model, act_dtype)
            self.dtype = None      # params already hold their storage dtypes

        tp_specs = None
        tp_fn = getattr(model, "partition_specs", None)
        if not self.quantized:
            if callable(tp_fn):
                tp_specs = tp_fn(params)
        else:
            # int8 TP: an int8 payload has the SAME shape as the float
            # weight, so the model's Megatron specs slice "q" directly; the
            # per-tensor scale replicates.  groups>1 scales span flattened
            # group boundaries that axis-slicing would split — those trees
            # (including externally pre-quantized ones, detected from the
            # scale shapes) replicate instead.
            groups = getattr(self, "_quant_groups", None)
            if groups is None:
                groups = max((np.size(x["scale"])
                              for x in jax.tree_util.tree_leaves(
                                  params, is_leaf=_is_quantized_leaf)
                              if _is_quantized_leaf(x)), default=1)
            base = None
            if callable(tp_fn) and groups == 1:
                try:
                    base = tp_fn()
                except TypeError:
                    # model's partition_specs needs the (float) param tree,
                    # which no longer exists — replicate
                    base = None
            if base is not None:
                tp_specs = _quantized_tp_specs(base, params)
            elif self.mp_world_size > 1:
                logger.warning(
                    "InferenceEngine: int8-quantized params replicate across "
                    f"the tensor axis (mp_size={self.mp_world_size}); "
                    "sharded int8 needs quantize_groups=1 and a "
                    "params-independent partition_specs()")
        if tp_specs is not None:
            sh = jax.tree_util.tree_map(
                lambda sp: NamedSharding(self.mesh, sp), tp_specs,
                is_leaf=lambda v: isinstance(v, P))
            params = jax.device_put(params, sh)
        else:
            params = jax.device_put(params, NamedSharding(self.mesh, P()))
        self.params = params

        # ---- persistent compiled-step cache (AOT warm-start) --------------
        # prefill + per-(steps, sampling) decode loops are this engine's
        # compile cost; a serving restart warm-starts them from disk.
        # ``compile_cache`` accepts a CompileCache, a directory path, or
        # None (then env DSTPU_COMPILE_CACHE decides).
        from ..runtime import compile_cache as ccache
        if isinstance(compile_cache, str):
            compile_cache = ccache.from_dir(compile_cache)
        elif compile_cache is None:
            compile_cache = ccache.from_dir()
        self.compile_cache = compile_cache
        self._cc_key_slice = {
            "engine": "InferenceEngine",
            "dtype": str(self.dtype),
            "quantized": self.quantized,
            "tp": self.mp_world_size,
            "mesh": dict(self.mesh.shape),
        }

        self._jit_forward = None
        self._jit_prefill = None
        # (steps, do_sample, top_k) → CachedStep, LRU-ordered.  Each loop
        # routes through the persistent compile cache, so an evicted
        # config RE-ENTERS via AOT warm start (deserialize, no XLA
        # compile) instead of paying a fresh compile — the dict only
        # bounds LIVE executables' device programs, not compile work.
        from collections import OrderedDict
        self._decode_loops = OrderedDict()
        self._decode_loops_cap = 8
        log_dist(f"InferenceEngine ready: tp={self.mp_world_size} "
                 f"mesh={dict(self.mesh.shape)}", ranks=[0])

    def _wrap_step(self, name, fn, donate_argnums=()):
        from ..runtime import compile_cache as ccache
        return ccache.wrap_step(f"InferenceEngine.{name}", fn,
                                cache=self.compile_cache,
                                key_extra=self._cc_key_slice,
                                donate_argnums=donate_argnums)

    def compile_report(self):
        """Compile-cache status/hit-miss stats (docs/compile-cache.md)."""
        from ..runtime import compile_cache as ccache
        return ccache.report(self.compile_cache)

    def _check_open(self):
        """A closed engine's params are gone; using it would surface as a
        bare ``NoneType`` TypeError deep inside a jitted call.  The
        serving layer's drain/close path tears engines down while callers
        may still hold handles — fail with the actual contract instead."""
        if self.params is None:
            raise RuntimeError(
                "InferenceEngine is closed (close() released its params "
                "and executables); build a new engine — a ServingEngine "
                "tears down an engine it BUILT, never one passed in via "
                "engine= (docs/serving.md)")

    # ---------------------------------------------------------------- forward
    def forward(self, tokens, **kwargs):
        """Full-context forward → logits (parity: reference ``forward`` :389)."""
        self._check_open()
        if self._jit_forward is None:
            def fwd(params, toks):
                return self.module.apply(params, toks)
            self._jit_forward = self._wrap_step("forward", fwd)
        tokens = jnp.asarray(tokens)
        with jax.set_mesh(self.mesh):
            return self._jit_forward(self.params, tokens)

    __call__ = forward

    # --------------------------------------------------------------- generate
    def generate(self, tokens, max_new_tokens: int = 32, temperature: float = 1.0,
                 do_sample: bool = False, top_k: Optional[int] = None,
                 rng=None, max_len: Optional[int] = None):
        """Autoregressive generation with a device-resident KV cache.

        ``tokens``: (B, T) int32 prompt.  Greedy when ``do_sample=False``.
        Requires the model to implement ``init_cache``/``apply_with_cache``
        (the GPT-2 family does).

        The whole decode runs as ONE jitted ``lax.scan`` over the new-token
        count — one dispatch per generate() call, not one per token (a
        Python token loop pays a host→device round-trip per step; on
        remote-attached runtimes that dominated at ~275 ms/token).
        """
        assert hasattr(self.module, "apply_with_cache"), \
            f"{type(self.module).__name__} does not support cached decoding"
        self._check_open()
        tokens = jnp.asarray(tokens, jnp.int32)
        B, T = tokens.shape
        total = T + max_new_tokens
        max_len = max_len or total
        assert max_len >= total, "max_len must cover prompt + new tokens"
        rng = rng if rng is not None else jax.random.PRNGKey(0)

        # int8 weight handling, two tiers (shared helper — serving.py
        # routes through the same function, so the paths cannot drift):
        #  - models whose decode path consumes quantized leaves directly
        #    (supports_quantized_decode) get the params UNTOUCHED —
        #    weights stream int8 from HBM through the decode matmuls,
        #    halving decode's binding byte term;
        #  - otherwise dequantize ONCE per jitted call, outside the token
        #    scan (re-materializing per token measured 1.6x slower than
        #    bf16; hoisted it matches bf16 speed but still streams
        #    full-width)
        from ..module_inject.module_quantize import resolve_decode_params
        inner, deq = resolve_decode_params(self.module)

        if self._jit_prefill is None:
            def prefill(params, toks, cache):
                logits, cache = inner.apply_with_cache(deq(params), toks,
                                                       cache)
                return logits[:, -1], cache
            self._jit_prefill = self._wrap_step("prefill", prefill)

        # temperature is a RUNTIME operand (no recompile per value); the
        # compile key is only what changes the program structure
        key = (max_new_tokens, bool(do_sample), top_k)
        loop = self._decode_loops.get(key)
        if loop is not None:
            self._decode_loops.move_to_end(key)    # LRU touch
        else:
            def decode_loop(params, last_logits, cache, r, temp):
                params = deq(params)      # once, OUTSIDE the token scan
                first = _select_token(last_logits, temp, do_sample,
                                      top_k, jax.random.fold_in(r, 0))

                def body(carry, i):
                    tok, cache = carry
                    logits, cache = inner.apply_with_cache(
                        params, tok[:, None], cache)
                    nxt = _select_token(logits[:, -1], temp, do_sample,
                                        top_k, jax.random.fold_in(r, i))
                    return (nxt, cache), tok

                if max_new_tokens == 1:
                    return first[:, None]
                (last, _), prev = jax.lax.scan(
                    body, (first, cache), jnp.arange(1, max_new_tokens))
                # prev stacks the carry INPUT each step: first..t_{n-2}
                return jnp.concatenate([prev.T, last[:, None]], axis=1)

            # donate the cache: XLA reuses its HBM for the scan's carried
            # cache (without it, input + updated cache coexist — double the
            # KV memory).  The 1-token path never touches the cache, where
            # donation would only warn.
            loop = self._wrap_step(
                f"decode[{max_new_tokens},{do_sample},{top_k}]", decode_loop,
                donate_argnums=(2,) if max_new_tokens > 1 else ())
            # bound LIVE executables, least-recently-USED out (the old
            # dict popped in FIFO insertion order, so a hot config could
            # be evicted while a cold one idled); clear() frees the
            # evicted device programs, and the next use of that config
            # deserializes from the compile cache (AOT warm start)
            while len(self._decode_loops) >= self._decode_loops_cap:
                _, old = self._decode_loops.popitem(last=False)
                old.clear()
            self._decode_loops[key] = loop

        with jax.set_mesh(self.mesh):
            cache = self.module.init_cache(B, max_len)
            last_logits, cache = self._jit_prefill(self.params, tokens, cache)
            new_toks = loop(self.params, last_logits, cache, rng,
                            jnp.float32(temperature))
        return jnp.concatenate([tokens, new_toks], axis=1)

    # ------------------------------------------------------------ checkpoints
    def _load_checkpoint(self, load_dir, tag=None):
        """Load params saved by ``DeepSpeedEngine.save_checkpoint`` (resharding
        is a device_put; parity: reference ``_load_checkpoint`` :286 +
        ``SDLoaderFactory`` MP resharding)."""
        import os
        from ..checkpoint.serialization import load_tree
        if os.path.isdir(load_dir):
            latest = os.path.join(load_dir, "latest")
            if tag is None and os.path.isfile(latest):
                with open(latest) as f:
                    tag = f.read().strip()
            path = os.path.join(load_dir, tag) if tag else load_dir
            path = os.path.join(path, "model_states.msgpack")
        else:
            path = load_dir
        tree, _ = load_tree(path, with_meta=True)
        return tree["params"]

    def profile_model_time(self, tokens=None, trace_dir=None):
        """Capture a ``jax.profiler`` device trace of one forward pass and
        return the xplane artifact path (None when the profiler is
        unavailable).  This used to be a warning telling the user to do
        it themselves; the monitor layer (``monitor/trace.py``,
        docs/monitoring.md) now owns the capture — training gets the
        same thing config-driven via ``monitor.trace_steps``."""
        from ..monitor import core as moncore
        from ..monitor import trace as mtrace
        if tokens is None:
            tokens = np.zeros((1, 8), np.int32)
        trace_dir = trace_dir or os.path.join(moncore.resolve_run_dir(),
                                              "traces")
        # the value read synchronizes: the trace window must not close
        # before the device executed the forward
        path = mtrace.capture(
            trace_dir, lambda: np.asarray(self.forward(tokens)[:1, :1]))
        if path is not None:
            log_dist(f"profile_model_time: trace captured at {path}",
                     ranks=[0])
        return path

    def close(self):
        """Release live compiled executables and the param tree.
        ``del engine`` alone does not free device programs (the bench-
        ladder lesson, ``DeepSpeedEngine.close``); call between engine
        lifetimes sharing one process.  Idempotent."""
        for wrapper in ([self._jit_forward, self._jit_prefill]
                        + list(self._decode_loops.values())):
            if wrapper is not None and hasattr(wrapper, "clear"):
                wrapper.clear()
        self._jit_forward = None
        self._jit_prefill = None
        self._decode_loops.clear()
        self.params = None


def _quantized_tp_specs(base_specs, qparams):
    """Map float-weight partition specs onto a quantized tree: a quantized
    leaf ``{"q", "scale"}`` gets ``{"q": spec, "scale": P()}`` (int8 payload
    shape == float weight shape; per-tensor scale replicates)."""
    from ..module_inject.module_quantize import _is_quantized_leaf
    is_p = lambda x: isinstance(x, P)
    spec_leaves = jax.tree_util.tree_leaves(base_specs, is_leaf=is_p)
    flat, treedef = jax.tree_util.tree_flatten(
        qparams, is_leaf=_is_quantized_leaf)
    assert len(spec_leaves) == len(flat), \
        (f"partition_specs has {len(spec_leaves)} leaves but params have "
         f"{len(flat)} — spec tree must mirror the param tree")
    out = []
    for sp, leaf in zip(spec_leaves, flat):
        if _is_quantized_leaf(leaf):
            out.append({"q": sp, "scale": P()})
        else:
            out.append(sp)
    return jax.tree_util.tree_unflatten(treedef, out)


def _normalize_dtype(dtype):
    """Map torch/numpy dtype spellings onto jnp dtypes — reference users call
    ``init_inference(dtype=torch.int8)`` (``deepspeed/inference/engine.py:23``)."""
    if dtype is None:
        return None
    try:
        import torch
        torch_map = {torch.float32: jnp.float32, torch.float16: jnp.float16,
                     torch.bfloat16: jnp.bfloat16, torch.int8: jnp.int8}
        if isinstance(dtype, torch.dtype):
            return torch_map[dtype]
    except ImportError:
        pass
    if dtype is np.float32:
        return jnp.float32
    return dtype


def _is_torch_module(model):
    try:
        import torch
        return isinstance(model, torch.nn.Module)
    except Exception:
        return False


def _select_token(logits, temperature, do_sample, top_k, rng):
    """logits: (B, V) fp32 → (B,) int32."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)
