"""The Jamba family (``"model_type": "jamba"``: AI21 Jamba / Jamba2 with
``num_experts`` 1): everything the harness asks of an architecture, in one
file found by the configuration's ``model_type``.  Its plain reference is
the file of the same name, ``benchmark/reference/jamba.py``.

A configuration file keeps the published key names (HF ``config.json``),
and so does the program's ``JambaConfig``: ``build`` hands them over as they
are.  ``dims`` gives the family-neutral names the runners, the readers and
the traffic generator use; ``costs`` prices this family's kernels for
``kernel_roofline`` (``benchmark/costs.py``'s own ``need_paged_attention``
multiplies by ``dims["n_layer"]``, every layer; here 2 of 28 are attention).

Nothing here imports JAX at module level (the harness loads a family before
``run.py`` has refused a machine without a TPU).
"""

from benchmark import costs as _costs

# published keys the program's JambaConfig takes under the same name
_MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "num_attention_heads", "num_key_value_heads",
               "intermediate_size", "attn_layer_period", "attn_layer_offset",
               "mamba_d_state", "mamba_d_conv", "mamba_expand",
               "mamba_dt_rank", "rms_norm_eps", "max_position_embeddings")
# published keys that state what models/jamba.py computes and has no switch
# for: a file that states anything else is refused, not run differently
_FIXED = {"hidden_act": "silu", "mamba_conv_bias": True,
          "mamba_proj_bias": False, "num_experts": 1,
          "tie_word_embeddings": True, "sliding_window": None}


def model_overrides(cfg):
    for key, want in _FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key} = {cfg[key]!r}: models/jamba.py computes "
                             f"{want!r} and has no switch")
    return {key: cfg[key] for key in _MODEL_KEYS}


def build(cfg, dtype, max_positions=None, **extra):
    """The model through the normal path: ``models.build`` with the
    published keys as overrides (no preset is added to the program for a
    benchmark configuration)."""
    from deepspeed_tpu.models import build as build_preset
    overrides = model_overrides(cfg)
    if max_positions is not None:
        overrides["max_position_embeddings"] = max_positions
    return build_preset("jamba-tiny", dtype=dtype, **{**overrides, **extra})


def attention_layers(cfg):
    return [l for l in range(cfg["num_hidden_layers"])
            if l % cfg["attn_layer_period"] == cfg["attn_layer_offset"]]


def dims(cfg):
    """The sizes the readers and the traffic generator use, under names no
    family owns.  ``n_layer`` is every layer; ``kv_width`` the elements of K,
    and of V, one token keeps in ONE attention layer.  What only this family
    has (how many layers are attention, the state's sizes) its own ``costs``
    read from the configuration (``view["config"]``)."""
    head_dim = cfg["hidden_size"] // cfg["num_attention_heads"]
    return {"n_layer": cfg["num_hidden_layers"],
            "n_head": cfg["num_attention_heads"],
            "n_kv_head": cfg["num_key_value_heads"], "head_dim": head_dim,
            "d_model": cfg["hidden_size"],
            "kv_width": cfg["num_key_value_heads"] * head_dim,
            "vocab_size": cfg["vocab_size"],
            "max_positions": cfg["max_position_embeddings"]}


def mamba_mixer_params(cfg):
    """Parameters of one Mamba mixer, norms and biases included."""
    D = cfg["hidden_size"]
    Di = cfg["mamba_expand"] * D
    N, K, R = (cfg["mamba_d_state"], cfg["mamba_d_conv"],
               cfg["mamba_dt_rank"])
    return (D * 2 * Di + Di * K + Di + Di * (R + 2 * N) + R * Di + Di
            + Di * N + Di + Di * D + R + 2 * N)


def attention_mixer_params(cfg):
    D = cfg["hidden_size"]
    hd = D // cfg["num_attention_heads"]
    return 2 * D * cfg["num_attention_heads"] * hd \
        + 2 * D * cfg["num_key_value_heads"] * hd


def parameters(cfg):
    """Every parameter: mixers, SwiGLU MLPs, two RMSNorm vectors a layer,
    the tied embedding, the final norm."""
    D = cfg["hidden_size"]
    n_attn = len(attention_layers(cfg))
    n_mamba = cfg["num_hidden_layers"] - n_attn
    mlp = 3 * D * cfg["intermediate_size"]
    return (n_mamba * (mamba_mixer_params(cfg) + mlp + 2 * D)
            + n_attn * (attention_mixer_params(cfg) + mlp + 2 * D)
            + cfg["vocab_size"] * D + D)


def matmul_params_per_token(cfg):
    """Parameters that sit in a matmul for every token: the mixers' and the
    MLPs' matrices and the tied head; the convolution, the norms, ``A``,
    ``D`` and the biases do no matmul work."""
    D = cfg["hidden_size"]
    Di = cfg["mamba_expand"] * D
    N, R = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    n_attn = len(attention_layers(cfg))
    n_mamba = cfg["num_hidden_layers"] - n_attn
    mamba = D * 2 * Di + Di * (R + 2 * N) + R * Di + Di * D
    return (n_mamba * mamba + n_attn * attention_mixer_params(cfg)
            + cfg["num_hidden_layers"] * 3 * D * cfg["intermediate_size"]
            + cfg["vocab_size"] * D)


# ------------------------------------------------- what a traced kernel needs
def selective_scan_need(tokens, n_layer, d_inner, d_state, calls):
    """``(flops, bytes)`` the prefill recurrence needs for ``tokens``
    tokens in each of ``n_layer`` Mamba layers, over ``calls`` kernel calls
    a layer.  Bytes: ``x``, ``delta``, ``z`` in and ``y`` out, 2 bytes an
    element a token; ``B`` and ``C`` (float32) a token; ``A``, ``D`` and the
    float32 state out once a call.  FLOPs: 9 a state element a token (the
    exponent's product, the exponential, the decay, ``delta x B`` and its
    add, ``S C`` and its add, counted as the kernel's algebra needs them)."""
    flops = 9 * tokens * n_layer * d_inner * d_state
    per_token = 4 * d_inner * 2 + 2 * d_state * 4
    per_call = (2 * d_inner * d_state + d_inner) * 4
    return flops, n_layer * (tokens * per_token + calls * per_call)


def prefills_in_capture(view):
    """``(tokens, prefills)`` of the ``serving.prefill`` spans the program's
    recorder holds that began inside the profiler's capture: the tokens the
    recurrence walked (``scan_tokens``, the true prompt length)."""
    from benchmark import program_spans
    t0, t1 = view["trace_span"]
    if t0 is None:
        return 0, 0
    rows = program_spans.rows_from(view, t0) or ()
    seen = [r.attrs["scan_tokens"] for r in rows
            if r.name == "serving.prefill" and t0 <= r.t_start < t1
            and r.attrs and "scan_tokens" in r.attrs]
    return sum(seen), len(seen)


def need_selective_scan(view):
    cfg = view["config"]
    tokens, calls = prefills_in_capture(view)
    return selective_scan_need(
        tokens, cfg["num_hidden_layers"] - len(attention_layers(cfg)),
        cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"], calls)


def need_paged_attention(view):
    """``costs.need_paged_attention`` over the ATTENTION layers alone."""
    f = view["facts"]
    n_attn = len(attention_layers(view["config"]))
    t0, t1 = view["trace_span"]
    live = sum(n for t, n in f["live_tokens"] if t0 <= t < t1)
    return (_costs.paged_attention_flops(live, n_attn,
                                         f["n_head"] * f["head_dim"]),
            _costs.paged_attention_bytes(live, n_attn, f["kv_width"],
                                         f["kv_bytes_per_element"]))


costs = {"jamba_paged_attention": need_paged_attention,
         "jamba_selective_scan": need_selective_scan}
