"""GPT-2 with Mixture-of-Experts FFN layers.

Role parity: the reference's MoE usage pattern (``deepspeed/moe/layer.py``
applied inside Megatron GPT, e.g. a "GPT-MoE 350M×16e"
config): every other transformer block replaces its dense FFN with a
top-k-gated expert layer; the gate's aux loss is added to the LM loss with
a configurable coefficient.

Unlike the dense GPT-2's scanned blocks, MoE blocks alternate two block
types, so the layer loop is a Python loop over per-layer param subtrees
(L is small for the MoE configs; compile time stays manageable) — expert
dispatch inside sharded over the mesh ``expert`` axis via all_to_all
(``moe/sharded_moe.py``).
"""

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax.ad_checkpoint import checkpoint_name

from .gpt2 import GPT2, GPT2Config, PRESETS as GPT2_PRESETS, _layer_norm, \
    _dropout, _attention_jnp


@dataclasses.dataclass
class GPT2MoEConfig(GPT2Config):
    num_experts: int = 8
    moe_every: int = 2          # an MoE FFN every k-th layer (reference style)
    top_k: int = 1
    capacity_factor: float = 1.25
    eval_capacity_factor: Optional[float] = None   # None → capacity_factor
    min_capacity: int = 4
    aux_loss_coef: float = 0.01
    use_residual: bool = False  # PR-MoE (pyramid-residual)
    noisy_gate_policy: Optional[str] = None
    dispatch_impl: str = "scatter"   # "scatter" (O(S·M)) | "einsum" (GShard)


MOE_PRESETS = {
    "gpt2-moe-350m-16e": dict(n_embd=1024, n_layer=24, n_head=16,
                              num_experts=16),
    "gpt2-moe-tiny": dict(n_embd=128, n_layer=4, n_head=4, vocab_size=1024,
                          max_seq=256, num_experts=4),
}


class _ExpertFFN:
    """One expert: the GPT-2 FFN (fc → gelu → proj), layer protocol."""

    def __init__(self, d, hidden, proj_std):
        self.d, self.hidden, self.proj_std = d, hidden, proj_std

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        n = lambda k, s, std: jax.random.normal(k, s, jnp.float32) * std
        return {"fc_w": n(k1, (self.d, self.hidden), 0.02),
                "fc_b": jnp.zeros((self.hidden,), jnp.float32),
                "proj_w": n(k2, (self.hidden, self.d), self.proj_std),
                "proj_b": jnp.zeros((self.d,), jnp.float32)}

    def apply(self, params, x, rng=None):
        h = x @ params["fc_w"].astype(x.dtype) + params["fc_b"].astype(x.dtype)
        h = checkpoint_name(h, "mlp_fc")   # selective-remat save point
        h = jax.nn.gelu(h, approximate=True)
        return h @ params["proj_w"].astype(x.dtype) + params["proj_b"].astype(x.dtype)


class GPT2MoE:
    """Decoder LM with alternating dense/MoE FFN blocks."""

    def __init__(self, config: Optional[GPT2MoEConfig] = None,
                 preset: str = None, dtype=jnp.bfloat16, **overrides):
        if config is None:
            base = dict(MOE_PRESETS[preset or "gpt2-moe-tiny"])
            base.update(overrides)
            config = GPT2MoEConfig(**base)
        if config.loss_chunk:
            raise NotImplementedError(
                "loss_chunk is a GPT2 (dense) option; the MoE loss does not "
                "chunk its head yet — unset it rather than silently "
                "ignoring the memory tuning")
        self.config = config
        self.dtype = dtype
        c = config
        proj_std = 0.02 / np.sqrt(2.0 * c.n_layer)
        from ..moe.layer import MoE
        self._expert = _ExpertFFN(c.n_embd, 4 * c.n_embd, proj_std)
        self._moe = MoE(hidden_size=c.n_embd, expert=self._expert,
                        num_experts=c.num_experts, k=c.top_k,
                        capacity_factor=c.capacity_factor,
                        eval_capacity_factor=(c.eval_capacity_factor
                                              if c.eval_capacity_factor
                                              is not None
                                              else c.capacity_factor),
                        min_capacity=c.min_capacity,
                        use_residual=c.use_residual,
                        noisy_gate_policy=c.noisy_gate_policy,
                        dispatch_impl=c.dispatch_impl)

    def is_moe_layer(self, i):
        # last layer of every `moe_every` window hosts the experts
        return (i + 1) % self.config.moe_every == 0

    # attention dispatch (flash/jnp by config) shared with the dense model
    _attend = GPT2._attend

    # ------------------------------------------------------------------ init
    def init(self, rng):
        c = self.config
        D, V, T = c.n_embd, c.vocab_size, c.max_seq
        k = jax.random.split(rng, 4 + c.n_layer)
        std, proj_std = 0.02, 0.02 / np.sqrt(2.0 * c.n_layer)
        n = lambda key, shape, s=std: jax.random.normal(key, shape, jnp.float32) * s
        layers = []
        for i in range(c.n_layer):
            ki = jax.random.split(k[4 + i], 6)
            layer = {
                "ln1_scale": jnp.ones((D,), jnp.float32),
                "ln1_bias": jnp.zeros((D,), jnp.float32),
                "qkv_w": n(ki[0], (D, 3 * D)),
                "qkv_b": jnp.zeros((3 * D,), jnp.float32),
                "proj_w": n(ki[1], (D, D), proj_std),
                "proj_b": jnp.zeros((D,), jnp.float32),
                "ln2_scale": jnp.ones((D,), jnp.float32),
                "ln2_bias": jnp.zeros((D,), jnp.float32),
            }
            if self.is_moe_layer(i):
                layer["moe"] = self._moe.init(ki[2])
            else:
                layer["ffn"] = self._expert.init(ki[3])
            layers.append(layer)
        return {
            "wte": n(k[0], (V, D)),
            "wpe": n(k[1], (T, D), 0.01),
            "layers": layers,
            "lnf_scale": jnp.ones((D,), jnp.float32),
            "lnf_bias": jnp.zeros((D,), jnp.float32),
        }

    # ------------------------------------------------- tensor-parallel specs
    def partition_specs(self, params):
        specs = {"wte": P("tensor", None), "wpe": P(),
                 "lnf_scale": P(), "lnf_bias": P(), "layers": []}
        for i, layer in enumerate(params["layers"]):
            s = {"ln1_scale": P(), "ln1_bias": P(),
                 "qkv_w": P(None, "tensor"), "qkv_b": P("tensor"),
                 "proj_w": P("tensor", None), "proj_b": P(),
                 "ln2_scale": P(), "ln2_bias": P()}
            if "moe" in layer:
                s["moe"] = self._moe.partition_specs(layer["moe"])
            else:
                s["ffn"] = {"fc_w": P(None, "tensor"), "fc_b": P("tensor"),
                            "proj_w": P("tensor", None), "proj_b": P()}
            specs["layers"].append(s)
        return specs

    # --------------------------------------------------------------- forward
    def _apply_with_aux(self, params, tokens, rng, deterministic):
        c = self.config
        B, T = tokens.shape
        assert T <= c.max_seq
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        dtype = self.dtype

        pos = jnp.arange(T)
        x = params["wte"].astype(dtype)[tokens] + params["wpe"].astype(dtype)[pos]
        x = _dropout(x, c.embd_pdrop, jax.random.fold_in(rng, 17), deterministic)
        causal = jnp.tril(jnp.ones((T, T), bool))[None, None, :, :]
        D, H, hd = c.n_embd, c.n_head, c.head_dim

        def block(p, x, r, is_moe):
            r1, r2, r3, r4 = jax.random.split(r, 4)
            h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"], c.layer_norm_eps)
            qkv = h @ p["qkv_w"].astype(h.dtype) + p["qkv_b"].astype(h.dtype)
            q, k_, v = jnp.split(qkv, 3, axis=-1)
            f = lambda t: t.reshape(B, T, H, hd)
            attn = self._attend(f(q), f(k_), f(v), causal, r1, deterministic)
            attn = attn.reshape(B, T, D)     # _attend named it "attn_out"
            attn = attn @ p["proj_w"].astype(h.dtype) + p["proj_b"].astype(h.dtype)
            x = x + _dropout(attn, c.resid_pdrop, r2, deterministic)

            h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], c.layer_norm_eps)
            if is_moe:
                out, l_aux, _, ovf = self._moe.apply(p["moe"], h, rng=r4,
                                                     train=not deterministic,
                                                     return_overflow=True)
            else:
                out = self._expert.apply(p["ffn"], h)
                l_aux = jnp.float32(0.0)
                ovf = jnp.int32(0)
            return (x + _dropout(out, c.resid_pdrop, r3, deterministic),
                    l_aux, ovf)

        if c.remat:
            from .gpt2 import resolve_remat_policy
            block = jax.checkpoint(block, static_argnums=(3,),
                                   policy=resolve_remat_policy(c.remat_policy))

        aux_total = jnp.float32(0.0)
        ovf_total = jnp.int32(0)
        for i, p in enumerate(params["layers"]):
            r = jax.random.fold_in(rng, 100 + i)
            x, l_aux, ovf = block(p, x, r, "moe" in p)
            aux_total = aux_total + l_aux
            ovf_total = ovf_total + ovf

        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"],
                        c.layer_norm_eps)
        logits = jnp.einsum("btd,vd->btv", x.astype(jnp.float32),
                            params["wte"].astype(jnp.float32))
        return logits, aux_total, ovf_total

    def apply(self, params, tokens, rng=None, deterministic=True):
        logits, _, _ = self._apply_with_aux(params, tokens, rng, deterministic)
        return logits

    def apply_with_metrics(self, params, tokens, rng=None, deterministic=True):
        """(logits, {"moe_aux_loss", "moe_tokens_dropped"}) — the per-step
        routing health signals (dropped = capacity-thinned token count summed
        over MoE layers; nonzero under ``drop_tokens=False`` means the
        ``nodrop_capacity`` bound was exceeded by routing skew)."""
        logits, aux, ovf = self._apply_with_aux(params, tokens, rng,
                                                deterministic)
        return logits, {"moe_aux_loss": aux, "moe_tokens_dropped": ovf}

    # ------------------------------------------------------- KV-cache decode
    # (role parity: reference ``ops/transformer/inference/moe_inference.py``
    # DeepSpeedMoEInference — expert layers served through the same gate +
    # dispatch path at decode time, dense layers as usual)
    def init_cache(self, batch_size: int, max_len: Optional[int] = None,
                   dtype=None):
        c = self.config
        max_len = max_len or c.max_seq
        # position/rotary tables only have max_seq rows; beyond that JAX
        # gather CLAMPS the index and decoding goes silently wrong
        assert max_len <= c.max_seq, (
            f"init_cache max_len={max_len} exceeds config.max_seq="
            f"{c.max_seq}; raise max_seq when building the model")
        dtype = dtype or self.dtype
        shape = (c.n_layer, batch_size, max_len, c.n_head, c.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                "index": jnp.zeros((), jnp.int32)}

    # cached-attention core shared with the dense model (scale_attn /
    # local-window semantics live in ONE place) — including the helpers
    # _cached_attention delegates to
    _mm = staticmethod(GPT2._mm)
    # NOT quantized-decode-capable: the expert FFN decode path multiplies
    # expert weights directly (no q_matmul routing yet), so int8 MoE
    # decode takes the hoisted-dequant route in the inference engine
    supports_quantized_decode = False
    # NOT paged-decode-capable either: GPT2.decode_step_paged scans the
    # DENSE block stack; the alternating MoE blocks need their own paged
    # step before ServingEngine can host this family (serving.py asserts
    # on this flag instead of mis-running the dense math)
    supports_paged_decode = False
    _qkv = GPT2._qkv
    _masked_attend = GPT2._masked_attend
    _attend_cached = GPT2._attend_cached
    _cached_attention = GPT2._cached_attention

    def apply_with_cache(self, params, tokens, cache):
        c = self.config
        index = cache["index"]
        dtype = self.dtype

        pos = index + jnp.arange(tokens.shape[1])
        x = params["wte"].astype(dtype)[tokens] + params["wpe"].astype(dtype)[pos]
        new_k, new_v = [], []
        for i, p in enumerate(params["layers"]):
            h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"], c.layer_norm_eps)
            attn, ck, cv = self._cached_attention(
                p, h, cache["k"][i], cache["v"][i], index)
            new_k.append(ck)
            new_v.append(cv)
            x = x + attn

            h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], c.layer_norm_eps)
            if "moe" in p:
                # fixed key: eval-mode gating is deterministic (RTS thinning
                # only randomizes during training in spirit; any key works)
                out, _, _ = self._moe.apply(p["moe"], h,
                                            rng=jax.random.PRNGKey(0),
                                            train=False)
            else:
                out = self._expert.apply(p["ffn"], h)
            x = x + out

        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"],
                        c.layer_norm_eps)
        logits = jnp.einsum("btd,vd->btv", x.astype(jnp.float32),
                            params["wte"].astype(jnp.float32))
        return logits, {"k": jnp.stack(new_k), "v": jnp.stack(new_v),
                        "index": index + tokens.shape[1]}

    # ------------------------------------------------------------------ loss
    def loss_with_metrics(self, params, batch, rng):
        """(total_loss, {"moe_aux_loss", "moe_tokens_dropped"}).

        The engine detects this method and carries the aux dict into its
        per-step ``metrics`` (reference: the engine surfaces MoE state —
        expert grads, gate timing — ``runtime/engine.py:1639``; a user
        training MoE through DeepSpeedEngine sees aux loss and token
        overflow without bypassing the engine)."""
        from .gpt2 import GPT2
        tokens, labels = GPT2._split_batch(batch)
        logits, aux, ovf = self._apply_with_aux(params, tokens, rng,
                                                deterministic=False)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        total = -jnp.mean(ll) + self.config.aux_loss_coef * aux
        return total, {"moe_aux_loss": aux,
                       "moe_tokens_dropped": ovf.astype(jnp.float32)}

    def loss(self, params, batch, rng):
        return self.loss_with_metrics(params, batch, rng)[0]

    def num_params(self):
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return sum(int(np.prod(l.shape or (1,)))
                   for l in jax.tree_util.tree_leaves(shapes))
