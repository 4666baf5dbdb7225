"""The AFMoE family (``"model_type": "afmoe"``: Arcee Trinity Large, Mini and
Nano): everything the harness asks of an architecture, in one file found by
the configuration's ``model_type``.  Its plain reference is the file of the
same name, ``benchmark/reference/afmoe.py``.

A configuration file keeps the published key names (HF ``config.json``).
Where it states ONE CHIP'S SHARE of a deployment, the keys that count what is
held here are listed in its ``reduced``: ``num_experts`` (the experts held;
``experts_held`` gives the first id beside the count), ``vocab_size`` (the
rows held; ``vocab_held``), ``num_hidden_layers`` and ``num_dense_layers``
(``layers_held`` names the published layers kept, each with the type the
published ``layer_types`` gives it); ``published`` gives the model's own
values beside them.  The program's ``AfmoeConfig`` takes the router's full
width as ``num_experts``, the share as ``experts_held`` / ``vocab_held`` and
the held layers' types as ``layer_types``: ``build`` hands them over.

``costs`` prices the family's two paged-attention kernels (a window layer's
walk over its ring, a global layer's over its table), its prompt attention
and its decode step.  Nothing here imports JAX at module level (the harness
loads a family before ``run.py`` has refused a machine without a TPU).
"""

from benchmark import costs as _costs, program_spans

SLIDING, FULL = "sliding_attention", "full_attention"

# published keys the program's AfmoeConfig takes under the same name
_MODEL_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_dense_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_experts_per_tok",
    "num_shared_experts", "score_func", "route_norm", "route_scale",
    "n_group", "topk_group", "sliding_window", "global_attn_every_n_layers",
    "hidden_act", "rms_norm_eps", "rope_theta", "rope_scaling",
    "max_position_embeddings", "mup_enabled")
# published keys that state what models/afmoe.py computes and has no switch
# for: a file that states anything else is refused, not run differently
_FIXED = {"tie_word_embeddings": False, "num_expert_groups": 1,
          "num_limited_groups": 1}


def published(cfg, key):
    """``key`` as the model's own ``config.json`` has it: the file's
    ``published`` value where the file's own counts the chip's share."""
    return cfg.get("published", {}).get(key, cfg[key])


def layers_held(cfg):
    """The published layer ids kept here, in order (absent: all)."""
    held = cfg.get("layers_held")
    return sorted(int(l) for l in held) if held is not None \
        else list(range(cfg["num_hidden_layers"]))


def layer_types(cfg):
    """The attention type of each layer held."""
    return [cfg["layer_types"][l] for l in layers_held(cfg)]


def model_overrides(cfg):
    for key, want in _FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key} = {cfg[key]!r}: models/afmoe.py "
                             f"computes {want!r} and has no switch")
    held = layers_held(cfg)
    dense = sum(l < published(cfg, "num_dense_layers") for l in held)
    if (len(held), dense) != (cfg["num_hidden_layers"],
                              cfg["num_dense_layers"]):
        raise ValueError(
            f"layers_held = {held!r} is {len(held)} layers, {dense} of them "
            f"dense; the file counts {cfg['num_hidden_layers']} and "
            f"{cfg['num_dense_layers']}")
    out = {key: cfg[key] for key in _MODEL_KEYS}
    out["layer_types"] = tuple(layer_types(cfg))
    out["num_experts"] = published(cfg, "num_experts")
    out["vocab_size"] = published(cfg, "vocab_size")
    for key, counted in (("experts_held", "num_experts"),
                         ("vocab_held", "vocab_size")):
        # absent: the whole of what the model has, which the file must count
        share = cfg.get(key, [0, out[counted]])
        if share[1] != cfg[counted]:
            raise ValueError(f"{key} = {cfg.get(key)!r} holds another count "
                             f"than the file's {counted} = {cfg[counted]}")
        if key in cfg:
            out[key] = tuple(share)
    return out


def build(cfg, dtype, max_positions=None, **extra):
    """The model through the normal path: ``models.build`` with the
    published keys as overrides (no preset is added to the program for a
    benchmark configuration)."""
    from deepspeed_tpu.models import build as build_preset
    overrides = model_overrides(cfg)
    if max_positions is not None:
        overrides["max_position_embeddings"] = max_positions
    return build_preset("afmoe-tiny", dtype=dtype, **{**overrides, **extra})


# ------------------------------------------------------------------ the sizes
def dims(cfg):
    """The sizes the readers and the traffic generator use, under names no
    family owns.  ``kv_width``: the elements of K, and of V, a token keeps in
    ONE layer.  ``vocab_size`` is the rows held: the traffic draws its ids
    from them."""
    return {"n_layer": cfg["num_hidden_layers"],
            "n_head": cfg["num_attention_heads"],
            "n_kv_head": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "d_model": cfg["hidden_size"],
            "kv_width": cfg["num_key_value_heads"] * cfg["head_dim"],
            "vocab_size": cfg["vocab_size"],
            "max_positions": cfg["max_position_embeddings"]}


def attention_matrix_params(cfg):
    """One layer's five attention matrices: q, gate, o (D x H hd each) and
    k, v (D x Hkv hd each)."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    return (3 * D * cfg["num_attention_heads"] * hd
            + 2 * D * cfg["num_key_value_heads"] * hd)


def expert_params(cfg):
    """One expert's three matrices (routed and shared alike)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_counts(cfg):
    dense = min(cfg["num_dense_layers"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def kind_counts(cfg):
    """``(window layers, global layers)`` among those held."""
    types = layer_types(cfg)
    return types.count(SLIDING), types.count(FULL)


def parameters(cfg):
    """Every parameter of what ``cfg`` counts (``num_experts`` experts a
    layer, ``vocab_size`` rows): a layer's attention matrices, its q and k
    norms (``head_dim`` each) and its four norms; the dense layers' SwiGLU;
    an expert layer's router (at its PUBLISHED width) and ``expert_bias``,
    routed and shared experts; the embedding and the untied head; the final
    norm.  4,321,903,872 for the cell's file."""
    D = cfg["hidden_size"]
    dense, moe = layer_counts(cfg)
    attn = attention_matrix_params(cfg) + 2 * cfg["head_dim"]
    E = published(cfg, "num_experts")
    expert_layer = (D * E + E + (cfg["num_experts"]
                                 + cfg["num_shared_experts"])
                    * expert_params(cfg))
    return (cfg["num_hidden_layers"] * (attn + 4 * D)
            + dense * 3 * D * cfg["intermediate_size"] + moe * expert_layer
            + 2 * cfg["vocab_size"] * D + D)


def matmul_params_per_token(cfg):
    """Parameters a token really multiplies HERE: every layer's attention
    matrices, the dense layers' SwiGLU, an expert layer's router and shared
    expert, the routed experts it reaches among those held (its
    ``num_experts_per_tok`` picks fall here in the share ``held / all``: 4 x
    32 / 256 = 0.5 on average) and the head's slice.  The embedding is a
    gather; the norms do no matmul work."""
    D = cfg["hidden_size"]
    dense, moe = layer_counts(cfg)
    E = published(cfg, "num_experts")
    reached = cfg["num_experts_per_tok"] * cfg["num_experts"] / E
    expert_layer = D * E + (reached + cfg["num_shared_experts"]) \
        * expert_params(cfg)
    return (cfg["num_hidden_layers"] * attention_matrix_params(cfg)
            + dense * 3 * D * cfg["intermediate_size"] + moe * expert_layer
            + cfg["vocab_size"] * D)


# ------------------------------------------------- what a traced step needs
def step_rows_in_capture(view):
    """The program's ``serving.step`` rows that began inside the capture and
    carry attributes; ``[]`` where the program records none."""
    t0, t1 = view["trace_span"]
    rows = program_spans.rows_from(view, t0)
    return [r for r in rows or () if r.name == "serving.step"
            and t0 <= r.t_start < t1 and r.attrs]


def attr_in_capture(view, name):
    """``(sum, rows)`` of one attribute over those rows; ``(None, 0)`` where
    none carries it."""
    got = [r.attrs[name] for r in step_rows_in_capture(view)
           if name in r.attrs]
    return (sum(got), len(got)) if got else (None, 0)


def live_tokens_in_capture(view):
    f = view["facts"]
    t0, t1 = view["trace_span"]
    return sum(n for t, n in f["live_tokens"] if t0 <= t < t1)


def _paged(view, live, layers):
    f = view["facts"]
    return (_costs.paged_attention_flops(live, layers,
                                         f["n_head"] * f["head_dim"]),
            _costs.paged_attention_bytes(live, layers, f["kv_width"],
                                         f["kv_bytes_per_element"]))


def need_global_paged_attention(view):
    """``(flops, bytes)`` of the global layers' decode kernel in the capture:
    K and V of every live token, in each global layer held."""
    return _paged(view, live_tokens_in_capture(view),
                  kind_counts(view["config"])[1])


def need_window_paged_attention(view):
    """``(flops, bytes)`` of the window layers' decode kernel in the capture:
    K and V of a stream's last ``sliding_window`` tokens and no more, in each
    window layer held: the ``window_kv_tokens`` of the program's
    ``serving.step`` rows (each stream's length capped at the window).  A
    program that records no such attribute gives (0, 0)."""
    live, _ = attr_in_capture(view, "window_kv_tokens")
    return _paged(view, live or 0, kind_counts(view["config"])[0])


def need_prefill_attention(view, kinds="both"):
    """``(flops, bytes)`` of the prompts' attention in the capture
    (``kinds``: ``"global"`` for the global layers' alone), from the
    ``prompt_len`` of the program's ``serving.prefill`` rows that began
    inside it: a query at position t meets ``min(t + 1, window)`` keys in a
    window layer and ``t + 1`` in a global one, 2 matmuls of 2 FLOPs x
    (query heads x head size) a key.  Bytes: q, k, v and the output once,
    far under the FLOPs, left out."""
    cfg = view["config"]
    t0, t1 = view["trace_span"]
    rows = program_spans.rows_from(view, t0)
    lens = [r.attrs["prompt_len"] for r in rows or ()
            if r.name == "serving.prefill" and t0 <= r.t_start < t1
            and r.attrs and "prompt_len" in r.attrs]
    W = cfg["sliding_window"]
    n_win, n_glob = kind_counts(cfg)
    if kinds == "global":
        n_win = 0
    pairs = 0
    for T in lens:
        full = T * (T + 1) // 2
        band = full if T <= W else W * (W + 1) // 2 + (T - W) * W
        pairs += n_win * band + n_glob * full
    return pairs * 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"], 0.0


def dense_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of weights EVERY decode step reads: all but the routed
    experts' matrices and the embedding (a gather of a row a slot)."""
    routed = layer_counts(cfg)[1] * cfg["num_experts"] * expert_params(cfg)
    embedding = cfg["vocab_size"] * cfg["hidden_size"]
    return bytes_per_param * (parameters(cfg) - routed - embedding)


def need_decode_step(view, module_match):
    """``(flops, bytes)`` the decode steps in the capture need: the dense
    parts' weights once a step; the routed experts that GOT a token (the
    ``experts_touched`` the program's step rows carry, scaled to the steps
    the trace holds; every held expert of every expert layer where the view
    carries no such attribute); the global layers' live K/V and the window
    layers' capped at the window.  FLOPs: 96 rows a step are nothing beside
    the bytes and are left out."""
    cfg = view["config"]
    steps = _costs.traced_steps(view, module_match)
    touched, rows = attr_in_capture(view, "experts_touched")
    per_step = (touched / rows if rows else
                layer_counts(cfg)[1] * cfg["num_experts"])
    kv = need_global_paged_attention(view)[1] \
        + need_window_paged_attention(view)[1]
    return 0.0, steps * (dense_weight_bytes(cfg)
                         + per_step * 2 * expert_params(cfg)) + kv


costs = {"trinity_window_paged_attention": need_window_paged_attention,
         "trinity_global_paged_attention": need_global_paged_attention,
         "trinity_prefill_attention": need_prefill_attention,
         "trinity_decode_step": need_decode_step}
