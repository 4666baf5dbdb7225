"""The Nemotron-H family (``"model_type": "nemotron_h"``: NVIDIA Nemotron-3
Nano): everything the harness asks of an architecture, in one file found by
the configuration's ``model_type``.  Its plain reference is the file of the
same name, ``benchmark/reference/nemotron_h.py``.

A configuration file keeps the published key names (HF ``config.json``), and so
does the program's ``NemotronHConfig``: ``build`` hands them over as they are.
Where the file states ONE CHIP'S SHARE of a deployment, the keys that count
what is held here are listed in its ``reduced``: ``n_routed_experts`` (the
experts held; ``experts_held`` gives the first id beside the count),
``vocab_size`` (the rows held; ``vocab_held``), ``num_hidden_layers`` and
``hybrid_override_pattern`` (``layers_held`` names the published layers kept,
each with the kind the published pattern gives it); ``published`` gives the
model's own values beside them.  The program takes the router's full width as
``n_routed_experts`` and the share as ``experts_held`` / ``vocab_held``.

``costs`` prices the family's kernels (the one-token state update, the chunked
scan of a prompt, the attention layers' paged kernel) and its decode step.
Nothing here imports JAX at module level (the harness loads a family before
``run.py`` has refused a machine without a TPU).
"""

from benchmark import costs as _costs, program_spans

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"

# published keys the program's NemotronHConfig takes under the same name
_MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "hybrid_override_pattern",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
    "conv_kernel", "chunk_size", "expand", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "n_shared_experts",
    "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob",
    "routed_scaling_factor", "mlp_hidden_act", "mamba_hidden_act",
    "layer_norm_epsilon", "time_step_min", "time_step_max",
    "time_step_floor", "max_position_embeddings")
# published keys that state what models/nemotron_h.py computes and has no
# switch for: a file that states anything else is refused, not run differently
_FIXED = {"attention_bias": False, "mamba_proj_bias": False,
          "mlp_bias": False, "use_bias": False, "use_conv_bias": True,
          "tie_word_embeddings": False, "sliding_window": None}


def published(cfg, key):
    """``key`` as the model's own ``config.json`` has it: the file's
    ``published`` value where the file's own counts the chip's share."""
    return cfg.get("published", {}).get(key, cfg[key])


def kinds(cfg):
    """The kind of each layer held, in order."""
    return tuple(cfg["hybrid_override_pattern"])


def count(cfg, kind):
    return kinds(cfg).count(kind)


def model_overrides(cfg):
    for key, want in _FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key} = {cfg[key]!r}: models/nemotron_h.py "
                             f"computes {want!r} and has no switch")
    if cfg.get("norm_eps", cfg["layer_norm_epsilon"]) != \
            cfg["layer_norm_epsilon"]:
        raise ValueError("norm_eps and layer_norm_epsilon differ: "
                         "models/nemotron_h.py norms with one epsilon")
    held = cfg.get("layers_held")
    if held is not None:
        whole = published(cfg, "hybrid_override_pattern")
        ids = sorted(int(l) for l in held)
        if "".join(whole[l] for l in ids) != cfg["hybrid_override_pattern"] \
                or any(held[str(l)] != whole[l] for l in ids):
            raise ValueError(
                f"layers_held = {held!r} does not pick "
                f"{cfg['hybrid_override_pattern']!r} out of the published "
                f"pattern {whole!r}")
    if len(kinds(cfg)) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"hybrid_override_pattern has {len(kinds(cfg))} layers; the file "
            f"counts {cfg['num_hidden_layers']}")
    out = {key: cfg[key] for key in _MODEL_KEYS}
    out["n_routed_experts"] = published(cfg, "n_routed_experts")
    out["vocab_size"] = published(cfg, "vocab_size")
    for key, counted in (("experts_held", "n_routed_experts"),
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
    return build_preset("nemotron-h-tiny", dtype=dtype,
                        **{**overrides, **extra})


# ------------------------------------------------------------------ the sizes
def dims(cfg):
    """The sizes the readers and the traffic generator use, under names no
    family owns.  ``n_layer`` is every layer held; ``kv_width`` the elements
    of K, and of V, a token keeps in ONE attention layer.  ``vocab_size`` is
    the rows held: the traffic draws its ids from them."""
    return {"n_layer": cfg["num_hidden_layers"],
            "n_head": cfg["num_attention_heads"],
            "n_kv_head": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "d_model": cfg["hidden_size"],
            "kv_width": cfg["num_key_value_heads"] * cfg["head_dim"],
            "vocab_size": cfg["vocab_size"],
            "max_positions": cfg["max_position_embeddings"]}


def d_inner(cfg):
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def conv_dim(cfg):
    return d_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def mamba_matrix_params(cfg):
    """``in_proj`` (to z, xBC and dt) and ``out_proj``."""
    D, Di = cfg["hidden_size"], d_inner(cfg)
    return D * (Di + conv_dim(cfg) + cfg["mamba_num_heads"]) + Di * D


def mamba_mixer_params(cfg):
    """One Mamba-2 layer with its layer norm: the two matrices, the
    convolution and its bias, ``dt_bias``, ``A_log`` and ``D`` a head, the
    gated norm's weight.  38,744,896 at the published widths."""
    Dc = conv_dim(cfg)
    return (mamba_matrix_params(cfg) + cfg["conv_kernel"] * Dc + Dc
            + 3 * cfg["mamba_num_heads"] + d_inner(cfg) + cfg["hidden_size"])


def attention_matrix_params(cfg):
    return 2 * cfg["hidden_size"] * cfg["head_dim"] * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def expert_params(cfg):
    """One routed expert's two matrices."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg):
    return 2 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]


def expert_layer_params(cfg, experts):
    """An expert layer holding ``experts`` routed experts: them, the shared
    expert, the router at its PUBLISHED width with its correction bias, the
    layer norm."""
    E = published(cfg, "n_routed_experts")
    return (experts * expert_params(cfg) + shared_expert_params(cfg)
            + cfg["hidden_size"] * E + E + cfg["hidden_size"])


def parameters(cfg, uncut=False):
    """Every parameter of what ``cfg`` counts (its layers, ``n_routed_experts``
    experts a layer, ``vocab_size`` rows); ``uncut``: of the published model
    (its pattern, every expert, the whole vocabulary).  2,871,333,696 for the
    cell's file and 31,577,940,288 uncut."""
    D = cfg["hidden_size"]
    get = (lambda k: published(cfg, k)) if uncut else cfg.__getitem__
    pattern = get("hybrid_override_pattern")
    n = pattern.count
    return (n(MAMBA) * mamba_mixer_params(cfg)
            + n(ATTENTION) * (attention_matrix_params(cfg) + D)
            + n(EXPERTS) * expert_layer_params(cfg, get("n_routed_experts"))
            + 2 * get("vocab_size") * D + D)


def matmul_params_per_token(cfg):
    """Parameters a token really multiplies HERE: a Mamba layer's two
    matrices, an attention layer's four, an expert layer's router and shared
    expert and the routed experts it reaches among those held (its
    ``num_experts_per_tok`` picks fall here in the share ``held / all``: 6 x
    32 / 128 = 1.5 on average), and the head's slice.  The embedding is a
    gather; the convolution, the norms and the recurrence do no matmul work
    counted here."""
    D = cfg["hidden_size"]
    E = published(cfg, "n_routed_experts")
    reached = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / E
    expert_layer = D * E + shared_expert_params(cfg) \
        + reached * expert_params(cfg)
    return (count(cfg, MAMBA) * mamba_matrix_params(cfg)
            + count(cfg, ATTENTION) * attention_matrix_params(cfg)
            + count(cfg, EXPERTS) * expert_layer + cfg["vocab_size"] * D)


def state_bytes_per_layer(cfg):
    """One stream's recurrent state in one Mamba layer: float32."""
    return 4 * d_inner(cfg) * cfg["ssm_state_size"]


# ------------------------------------------------- what a traced step needs
def rows_in_capture(view, name):
    """The program's ``name`` rows that began inside the capture and carry
    attributes; ``[]`` where the program records none."""
    t0, t1 = view["trace_span"]
    if t0 is None:
        return []
    rows = program_spans.rows_from(view, t0)
    return [r for r in rows or () if r.name == name
            and t0 <= r.t_start < t1 and r.attrs]


def attr_in_capture(view, span, name):
    """``(sum, rows)`` of one attribute over those rows; ``(None, 0)`` where
    none carries it."""
    got = [r.attrs[name] for r in rows_in_capture(view, span)
           if name in r.attrs]
    return (sum(got), len(got)) if got else (None, 0)


def need_state_update(view):
    """``(flops, bytes)`` of the one-token state update in the capture, from
    the configuration: the slots seated in the program's ``serving.step``
    rows (``seated_slots``, the one thing taken from the program) x the Mamba
    layers held x the state of a layer a stream, read and written
    (:func:`state_bytes_per_layer`: float32, whatever the program keeps).  A
    dead slot's rows need not move; a program that records no such attribute
    gives (0, 0).  FLOPs: five a state element on the VPU, nothing beside the
    bytes."""
    cfg = view["config"]
    seated, _ = attr_in_capture(view, "serving.step", "seated_slots")
    return 0.0, float((seated or 0) * count(cfg, MAMBA)
                      * 2 * state_bytes_per_layer(cfg))


def ssd_need(cfg, tokens, calls):
    """``(flops, bytes)`` the chunked scan of ``tokens`` prompt tokens over
    ``calls`` prompts needs in EVERY Mamba layer held, whatever implements it.
    FLOPs a token a layer, chunks of ``L = chunk_size``: inside a chunk the
    causal half of ``C B^T`` (a group: 2 N) and of its product with ``X`` (a
    head: 2 P), (L + 1) / 2 visible tokens each; between chunks ``C S^T`` and
    ``X^T B`` (a head: 2 P N each).  Bytes a token a layer: ``x`` in and ``y``
    out at 2 bytes a channel, ``B`` and ``C`` at 2 bytes, ``dt`` at 4; a call
    a layer: the float32 state out."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, L = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    flops = (G * 2 * N + H * 2 * P) * (L + 1) / 2 + 2 * H * 2 * P * N
    nbytes = 2 * 2 * H * P + 2 * 2 * G * N + 4 * H
    layers = count(cfg, MAMBA)
    return (layers * tokens * flops,
            layers * (tokens * nbytes + calls * state_bytes_per_layer(cfg)))


def need_ssd_prefill(view):
    """:func:`ssd_need` of the prompts whose ``serving.prefill`` row began
    inside the capture (``ssd_tokens``: the true prompt length)."""
    tokens, calls = attr_in_capture(view, "serving.prefill", "ssd_tokens")
    return ssd_need(view["config"], tokens or 0, calls)


def live_tokens_in_capture(view):
    f = view["facts"]
    t0, t1 = view["trace_span"]
    return sum(n for t, n in f["live_tokens"] if t0 <= t < t1)


def need_paged_attention(view):
    """``costs.need_paged_attention`` over the ATTENTION layers alone."""
    f = view["facts"]
    live, layers = live_tokens_in_capture(view), count(view["config"],
                                                       ATTENTION)
    return (_costs.paged_attention_flops(live, layers,
                                         f["n_head"] * f["head_dim"]),
            _costs.paged_attention_bytes(live, layers, f["kv_width"],
                                         f["kv_bytes_per_element"]))


def dense_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of weights EVERY decode step reads: all but the routed experts'
    matrices and the embedding (a gather of a row a slot)."""
    routed = count(cfg, EXPERTS) * cfg["n_routed_experts"] * expert_params(cfg)
    embedding = cfg["vocab_size"] * cfg["hidden_size"]
    return bytes_per_param * (parameters(cfg) - routed - embedding)


def need_decode_step(view, module_match):
    """``(flops, bytes)`` the decode steps in the capture need: the dense
    parts' weights once a step; the routed experts that GOT a token (the
    ``experts_touched`` the program's step rows carry, scaled to the steps the
    trace holds; every held expert of every expert layer where the view
    carries no such attribute); the attention layers' live K/V; AND the
    recurrent state of the seated streams, read and written
    (:func:`need_state_update`).  FLOPs: 256 rows a step are nothing beside
    the bytes and are left out."""
    cfg = view["config"]
    steps = _costs.traced_steps(view, module_match)
    touched, rows = attr_in_capture(view, "serving.step", "experts_touched")
    per_step = (touched / rows if rows else
                count(cfg, EXPERTS) * cfg["n_routed_experts"])
    return 0.0, (steps * (dense_weight_bytes(cfg)
                          + per_step * 2 * expert_params(cfg))
                 + need_paged_attention(view)[1] + need_state_update(view)[1])


costs = {"nemotron_state_update": need_state_update,
         "nemotron_ssd_prefill": need_ssd_prefill,
         "nemotron_paged_attention": need_paged_attention,
         "nemotron_decode_step": need_decode_step}
