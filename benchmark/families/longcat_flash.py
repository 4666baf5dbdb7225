"""The LongCat-Flash family (``"model_type": "longcat_flash"``:
LongCat-Flash-Chat, -Thinking): everything the harness asks of an
architecture, in one file found by the configuration's ``model_type``.  Its
plain reference is the file of the same name,
``benchmark/reference/longcat_flash.py``.

A configuration file keeps the published key names (HF ``config.json``:
``num_layers``, ``ffn_hidden_size``, ``expert_ffn_hidden_size``, ``moe_topk``,
``zero_expert_num`` ...).  Where it states ONE CHIP'S SHARE of a deployment,
three keys count what is held here and are listed in its ``reduced``:
``n_routed_experts`` (the REAL experts held; ``experts_held`` gives the first
id beside the count), ``vocab_size`` (the rows held; ``vocab_held``) and
``num_layers``; ``published`` gives the model's own values beside them.  The
program's ``LongcatFlashConfig`` takes the real experts' full count as
``n_routed_experts`` (the router is that plus ``zero_expert_num`` wide) and
the share as ``experts_held`` / ``vocab_held``: ``build`` hands them over.

A LAYER here is the published double block: two latent-attention sub-layers,
two dense FFNs, one expert layer.  A token keeps a latent row a SUB-layer,
``2 x num_layers`` in all.

``costs`` prices this family's latent paged-attention calls and its decode
step.  Nothing here imports JAX at module level.
"""

from benchmark import costs as _costs, program_spans

# published keys the program's LongcatFlashConfig takes under the same name
_MODEL_KEYS = (
    "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size", "num_layers",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora",
    "zero_expert_num", "zero_expert_type", "moe_topk",
    "routed_scaling_factor", "rms_norm_eps", "rope_theta",
    "max_position_embeddings", "attention_method", "attention_bias")
# keys that state what models/longcat_flash.py computes and has no switch
# for: a file that states anything else is refused, not run differently
_FIXED = {"hidden_act": "silu", "tie_word_embeddings": False,
          "norm_topk_prob": False}


def published(cfg, key):
    """``key`` as the model's own ``config.json`` has it: the file's
    ``published`` value where the file's own counts the chip's share."""
    return cfg.get("published", {}).get(key, cfg[key])


def model_overrides(cfg):
    for key, want in _FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key} = {cfg[key]!r}: models/longcat_flash.py "
                             f"computes {want!r} and has no switch")
    out = {key: cfg[key] for key in _MODEL_KEYS}
    out["n_routed_experts"] = published(cfg, "n_routed_experts")
    out["vocab_size"] = published(cfg, "vocab_size")
    # not a published key: the width the selection bias is drawn at (the
    # file's ``assumed.e_score_correction_bias``)
    out["router_bias_std"] = cfg.get("router_bias_std", 0.0)
    for key, counted in (("experts_held", "n_routed_experts"),
                         ("vocab_held", "vocab_size")):
        # absent: the whole of what the model has, which the file must count
        held = cfg.get(key, [0, out[counted]])
        if held[1] != cfg[counted]:
            raise ValueError(f"{key} = {cfg.get(key)!r} holds another count "
                             f"than the file's {counted} = {cfg[counted]}")
        if key in cfg:
            out[key] = tuple(held)
    return out


def build(cfg, dtype, max_positions=None, **extra):
    """The model through the normal path: ``models.build`` with the
    published keys as overrides (no preset is added to the program for a
    benchmark configuration)."""
    from deepspeed_tpu.models import build as build_preset
    overrides = model_overrides(cfg)
    if max_positions is not None:
        overrides["max_position_embeddings"] = max_positions
    return build_preset("longcat-flash-tiny", dtype=dtype,
                        **{**overrides, **extra})


# ------------------------------------------------------------------ the sizes
def latent_row_values(cfg):
    """Values a cached row has as the pool STORES it: ``kv_lora_rank +
    qk_rope_head_dim`` in whole 128-lane tiles (512 + 64 -> 640)."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128


def sub_layers(cfg):
    """Attention sub-layers: the latent rows a token keeps."""
    return 2 * cfg["num_layers"]


def dims(cfg):
    """The sizes the readers and the traffic generator use, under names no
    family owns.  ``n_layer`` counts what keeps a cache row, the SUB-layers;
    a token caches ONE row for all the query heads in each: ``n_kv_head`` 1,
    ``head_dim`` and ``kv_width`` the row as the pool stores it.
    ``vocab_size`` is the rows held: the traffic draws its ids from them."""
    row = latent_row_values(cfg)
    return {"n_layer": sub_layers(cfg),
            "n_head": cfg["num_attention_heads"], "n_kv_head": 1,
            "head_dim": row, "d_model": cfg["hidden_size"], "kv_width": row,
            "vocab_size": cfg["vocab_size"],
            "max_positions": cfg["max_position_embeddings"]}


def mla_matrix_params(cfg):
    """One sub-layer's five attention matrices: q_a, q_b, kv_a, kv_b, o."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    n, r, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
               cfg["v_head_dim"])
    C, Rq = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    return (D * Rq + Rq * H * (n + r) + D * (C + r) + C * H * (n + v)
            + H * v * D)


def dense_ffn_params(cfg):
    """One dense SwiGLU's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def expert_params(cfg):
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def router_width(cfg):
    """The router's outputs: the PUBLISHED real experts and the identity
    ones."""
    return published(cfg, "n_routed_experts") + cfg["zero_expert_num"]


def parameters(cfg):
    """Every parameter of what ``cfg`` counts (``n_routed_experts`` experts
    a layer, ``vocab_size`` rows).  A sub-layer: the attention matrices and
    their two inner norms (90,572,800 at the published widths), a dense
    SwiGLU (226,492,416), two norms (12,288).  A layer: two of those, the
    router at its PUBLISHED width with its selection bias (4,718,592 + 768)
    and the held experts (37,748,736 each).  The embedding, the untied head
    and the final norm.  5,172,749,312 for the cell's file."""
    D = cfg["hidden_size"]
    sub = (mla_matrix_params(cfg) + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
           + dense_ffn_params(cfg) + 2 * D)
    layer = (2 * sub + (D + 1) * router_width(cfg)
             + cfg["n_routed_experts"] * expert_params(cfg))
    return cfg["num_layers"] * layer + 2 * cfg["vocab_size"] * D + D


def matmul_params_per_token(cfg):
    """Parameters a token really multiplies HERE: every sub-layer's
    attention matrices and dense SwiGLU, a layer's router, the real experts
    it reaches among those held (its ``moe_topk`` picks fall here in the
    share ``held / router width``: 12 x 16 / 768 = 0.25 on average; an
    identity expert multiplies nothing) and the head's slice."""
    D = cfg["hidden_size"]
    reached = cfg["moe_topk"] * cfg["n_routed_experts"] / router_width(cfg)
    layer = (2 * (mla_matrix_params(cfg) + dense_ffn_params(cfg))
             + D * router_width(cfg) + reached * expert_params(cfg))
    return cfg["num_layers"] * layer + cfg["vocab_size"] * D


# ------------------------------------------------- what a traced step needs
def live_tokens_in_capture(view):
    f = view["facts"]
    t0, t1 = view["trace_span"]
    return sum(n for t, n in f["live_tokens"] if t0 <= t < t1)


def need_mla_paged_attention(view):
    """``(flops, bytes)`` of the latent kernel's calls in the capture.  A
    live token and a SUB-layer: every one of the H query heads meets the row
    once as key (``kv_lora_rank + qk_rope_head_dim`` wide) and once as value
    (``kv_lora_rank`` wide), 2 FLOPs each: 64 x (576 + 512) x 2 = 139,264;
    and the row is read ONCE, at the bytes the pool stores (640 x 2 = 1,280:
    the 64 padding values are read too): 109 FLOPs a stored byte, under the
    v5e's ridge of 240, so the bytes bound it."""
    cfg, f = view["config"], view["facts"]
    live = live_tokens_in_capture(view) * sub_layers(cfg)
    C, r = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return (live * cfg["num_attention_heads"] * (C + r + C) * 2,
            live * latent_row_values(cfg) * f["kv_bytes_per_element"])


def dense_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of weights EVERY decode step reads: all but the routed experts'
    matrices and the embedding (a gather of as many rows as slots)."""
    routed = cfg["num_layers"] * cfg["n_routed_experts"] * expert_params(cfg)
    embedding = cfg["vocab_size"] * cfg["hidden_size"]
    return bytes_per_param * (parameters(cfg) - routed - embedding)


def experts_touched_in_capture(view):
    """``(touched, steps with the attribute)``: the ``experts_touched`` of
    the program's ``serving.step`` rows that began inside the capture,
    summed; ``(None, 0)`` where the program records no such attribute."""
    t0, t1 = view["trace_span"]
    rows = program_spans.rows_from(view, t0)
    got = [r.attrs["experts_touched"] for r in rows or ()
           if r.name == "serving.step" and t0 <= r.t_start < t1
           and r.attrs and "experts_touched" in r.attrs]
    return (sum(got), len(got)) if got else (None, 0)


def need_decode_step(view, module_match):
    """``(flops, bytes)`` the decode steps in the capture need: the dense
    parts' weights once a step (both attentions, both dense FFNs, the
    router, the head's slice), the held experts that GOT a token (an expert
    without one need not be read: the ``experts_touched`` the program's step
    rows carry; every held expert of every layer where the view carries no
    such attribute), and the live latent rows of all the sub-layers.  An
    identity expert reads nothing.  FLOPs: 160 rows a step are nothing
    beside the bytes and are left out."""
    cfg = view["config"]
    steps = _costs.traced_steps(view, module_match)
    touched, rows = experts_touched_in_capture(view)
    per_step = (touched / rows if rows else
                cfg["num_layers"] * cfg["n_routed_experts"])
    _, latent = need_mla_paged_attention(view)
    return 0.0, steps * (dense_weight_bytes(cfg)
                         + per_step * 2 * expert_params(cfg)) + latent


costs = {"longcat_mla_paged_attention": need_mla_paged_attention,
         "longcat_decode_step": need_decode_step}
