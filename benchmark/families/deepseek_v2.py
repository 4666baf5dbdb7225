"""The DeepSeek-V2 family (``"model_type": "deepseek_v2"``: DeepSeek-V2,
DeepSeek-Coder-V2): everything the harness asks of an architecture, in one
file found by the configuration's ``model_type``.  Its plain reference is the
file of the same name, ``benchmark/reference/deepseek_v2.py``.

A configuration file keeps the published key names (HF ``config.json``).
Where it states ONE CHIP'S SHARE of a deployment, three keys count what is
held here and are listed in its ``reduced``: ``n_routed_experts`` (the
experts held; ``experts_held`` gives the first id beside the count),
``vocab_size`` (the rows held; ``vocab_held``) and ``num_hidden_layers``;
``published`` gives the model's own values beside them.  The program's
``DeepseekV2Config`` takes the router's full width as ``n_routed_experts``
and the share as ``experts_held`` / ``vocab_held``: ``build`` hands them over.

``costs`` prices this family's latent paged-attention calls and its decode
step.  Nothing here imports JAX at module level (the harness loads a family
before ``run.py`` has refused a machine without a TPU).
"""

from benchmark import costs as _costs, program_spans

# published keys the program's DeepseekV2Config takes under the same name
_MODEL_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "n_shared_experts", "num_experts_per_tok",
    "first_k_dense_replace", "moe_layer_freq", "n_group", "topk_group",
    "topk_method", "scoring_func", "norm_topk_prob", "routed_scaling_factor",
    "rms_norm_eps", "rope_theta", "rope_scaling", "max_position_embeddings")
# published keys that state what models/deepseek_v2.py computes and has no
# switch for: a file that states anything else is refused, not run differently
_FIXED = {"hidden_act": "silu", "tie_word_embeddings": False,
          "attention_bias": False}


def published(cfg, key):
    """``key`` as the model's own ``config.json`` has it: the file's
    ``published`` value where the file's own counts the chip's share."""
    return cfg.get("published", {}).get(key, cfg[key])


def model_overrides(cfg):
    for key, want in _FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key} = {cfg[key]!r}: models/deepseek_v2.py "
                             f"computes {want!r} and has no switch")
    out = {key: cfg[key] for key in _MODEL_KEYS}
    out["n_routed_experts"] = published(cfg, "n_routed_experts")
    out["vocab_size"] = published(cfg, "vocab_size")
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
    return build_preset("deepseek-v2-tiny", dtype=dtype,
                        **{**overrides, **extra})


# ------------------------------------------------------------------ the sizes
def latent_row_values(cfg):
    """Values a cached row has as the pool STORES it: ``kv_lora_rank +
    qk_rope_head_dim`` in whole 128-lane tiles (512 + 64 -> 640)."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128


def dims(cfg):
    """The sizes the readers and the traffic generator use, under names no
    family owns.  A token caches ONE row for all the query heads:
    ``n_kv_head`` 1, ``head_dim`` and ``kv_width`` the row as the pool
    stores it.  ``vocab_size`` is the rows held: the traffic draws its ids
    from them."""
    row = latent_row_values(cfg)
    return {"n_layer": cfg["num_hidden_layers"],
            "n_head": cfg["num_attention_heads"], "n_kv_head": 1,
            "head_dim": row, "d_model": cfg["hidden_size"], "kv_width": row,
            "vocab_size": cfg["vocab_size"],
            "max_positions": cfg["max_position_embeddings"]}


def mla_matrix_params(cfg):
    """One layer's five attention matrices: q_a, q_b, kv_a, kv_b, o."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    n, r, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
               cfg["v_head_dim"])
    C, Rq = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    return (D * Rq + Rq * H * (n + r) + D * (C + r) + C * H * (n + v)
            + H * v * D)


def expert_params(cfg):
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_counts(cfg):
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def parameters(cfg):
    """Every parameter of what ``cfg`` counts (``n_routed_experts`` experts
    a layer, ``vocab_size`` rows): the attention matrices and their two
    inner norms, two norms a layer, the dense layers' SwiGLU, an expert
    layer's router (at its PUBLISHED width), routed and shared experts, the
    embedding and the untied head, the final norm.  4,483,671,040 for the
    cell's file, 235,741,434,880 for the published keys."""
    D = cfg["hidden_size"]
    dense, moe = layer_counts(cfg)
    mla = mla_matrix_params(cfg) + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    expert_layer = (D * published(cfg, "n_routed_experts")
                    + (cfg["n_routed_experts"] + cfg["n_shared_experts"])
                    * expert_params(cfg))
    return (cfg["num_hidden_layers"] * (mla + 2 * D)
            + dense * 3 * D * cfg["intermediate_size"] + moe * expert_layer
            + 2 * cfg["vocab_size"] * D + D)


def matmul_params_per_token(cfg):
    """Parameters a token really multiplies HERE: every layer's attention
    matrices, the dense layers' SwiGLU, an expert layer's router and shared
    experts, the routed experts it reaches among those held (its
    ``num_experts_per_tok`` picks fall here in the share ``held / all``: 6 x
    20 / 160 = 0.75 on average) and the head's slice.  The embedding is a
    gather; the norms do no matmul work."""
    D = cfg["hidden_size"]
    dense, moe = layer_counts(cfg)
    reached = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
               / published(cfg, "n_routed_experts"))
    expert_layer = (D * published(cfg, "n_routed_experts")
                    + (reached + cfg["n_shared_experts"])
                    * expert_params(cfg))
    return (cfg["num_hidden_layers"] * mla_matrix_params(cfg)
            + dense * 3 * D * cfg["intermediate_size"] + moe * expert_layer
            + cfg["vocab_size"] * D)


# ------------------------------------------------- what a traced step needs
def live_tokens_in_capture(view):
    f = view["facts"]
    t0, t1 = view["trace_span"]
    return sum(n for t, n in f["live_tokens"] if t0 <= t < t1)


def need_mla_paged_attention(view):
    """``(flops, bytes)`` of the latent kernel's calls in the capture.  A
    live token and a layer: every one of the H query heads meets the row
    once as key (``kv_lora_rank + qk_rope_head_dim`` wide) and once as value
    (``kv_lora_rank`` wide), 2 FLOPs each: 128 x (576 + 512) x 2 = 278,528;
    and the row is read ONCE, at the bytes the pool stores (640 x 2 =
    1,280: the 64 padding values are read too)."""
    cfg, f = view["config"], view["facts"]
    live = live_tokens_in_capture(view) * cfg["num_hidden_layers"]
    C, r = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return (live * cfg["num_attention_heads"] * (C + r + C) * 2,
            live * latent_row_values(cfg) * f["kv_bytes_per_element"])


def dense_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of weights EVERY decode step reads: all but the routed
    experts' matrices and the embedding (a gather of 128 rows)."""
    routed = layer_counts(cfg)[1] * cfg["n_routed_experts"] * expert_params(cfg)
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
    parts' weights once a step, the routed experts that GOT a token (an
    expert without one need not be read: the ``experts_touched`` the
    program's step rows carry, scaled to the steps the trace holds; every
    held expert of every expert layer where the view carries no such
    attribute), and the live latent rows.  FLOPs: 128 rows a step are
    nothing beside the bytes and are left out."""
    cfg = view["config"]
    steps = _costs.traced_steps(view, module_match)
    touched, rows = experts_touched_in_capture(view)
    per_step = (touched / rows if rows else
                layer_counts(cfg)[1] * cfg["n_routed_experts"])
    _, latent = need_mla_paged_attention(view)
    return 0.0, steps * (dense_weight_bytes(cfg)
                         + per_step * 2 * expert_params(cfg)) + latent


costs = {"dsv2_mla_paged_attention": need_mla_paged_attention,
         "dsv2_decode_step": need_decode_step}
