"""The Ouro family (``"model_type": "ouro"``: ByteDance Ouro 1.4B / 2.6B
LoopLM): everything the harness asks of an architecture, in one file found
by the configuration's ``model_type``.  Its plain reference is the file of
the same name, ``benchmark/reference/ouro.py``.

A configuration file keeps the published key names (HF ``config.json``),
and so does the program's ``OuroConfig``: ``build`` hands them over as they
are.  ``dims`` gives the family-neutral names the runners, the readers and
the traffic generator use; ``costs`` prices this family's decode step and
its paged-attention calls: ``benchmark/costs.py``'s own
``need_paged_attention`` multiplies by ``dims["n_layer"]``, the 48 layers
that have weights; a token keeps K/V in ``total_ut_steps`` times as many.

Nothing here imports JAX at module level (the harness loads a family before
``run.py`` has refused a machine without a TPU).
"""

from benchmark import costs as _costs

# published keys the program's OuroConfig takes under the same name
_MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "num_attention_heads", "num_key_value_heads", "head_dim",
               "intermediate_size", "rms_norm_eps", "rope_theta",
               "rope_scaling", "max_position_embeddings", "total_ut_steps",
               "early_exit_threshold")
# published keys that state what models/ouro.py computes and has no switch
# for: a file that states anything else is refused, not run differently
_FIXED = {"hidden_act": "silu", "tie_word_embeddings": False,
          "sliding_window": None, "use_sliding_window": False}


def model_overrides(cfg):
    for key, want in _FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key} = {cfg[key]!r}: models/ouro.py computes "
                             f"{want!r} and has no switch")
    kinds = set(cfg.get("layer_types", ())) - {"full_attention"}
    if kinds:
        raise ValueError(f"layer_types holds {sorted(kinds)}: models/ouro.py "
                         "runs full attention in every layer")
    return {key: cfg[key] for key in _MODEL_KEYS}


def build(cfg, dtype, max_positions=None, **extra):
    """The model through the normal path: ``models.build`` with the
    published keys as overrides (no preset is added to the program for a
    benchmark configuration)."""
    from deepspeed_tpu.models import build as build_preset
    overrides = model_overrides(cfg)
    if max_positions is not None:
        overrides["max_position_embeddings"] = max_positions
    return build_preset("ouro-tiny", dtype=dtype, **{**overrides, **extra})


def kv_layers(cfg):
    """Layer-applications that keep K/V for a token: every loop of every
    layer its own."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def dims(cfg):
    """The sizes the readers and the traffic generator use, under names no
    family owns.  ``n_layer`` is the layers that have weights; ``kv_width``
    the elements of K, and of V, one token keeps in ONE layer-application.
    What only this family has (the loops) its own ``costs`` read from the
    configuration (``view["config"]``)."""
    return {"n_layer": cfg["num_hidden_layers"],
            "n_head": cfg["num_attention_heads"],
            "n_kv_head": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "d_model": cfg["hidden_size"],
            "kv_width": cfg["num_key_value_heads"] * cfg["head_dim"],
            "vocab_size": cfg["vocab_size"],
            "max_positions": cfg["max_position_embeddings"]}


def layer_matrix_params(cfg):
    """One layer's seven matrices: q, k, v, o and the SwiGLU's three."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    attn = 2 * D * cfg["num_attention_heads"] * hd \
        + 2 * D * cfg["num_key_value_heads"] * hd
    return attn + 3 * D * cfg["intermediate_size"]


def parameters(cfg):
    """Every parameter: the layers' matrices and their four RMSNorm vectors
    each, the embedding and the untied head, the final norm, the exit gate
    (a vector and a bias)."""
    D = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * (layer_matrix_params(cfg) + 4 * D)
            + 2 * cfg["vocab_size"] * D + D + D + 1)


def matmul_params_per_token(cfg):
    """Parameters that sit in a matmul for every token: the layers'
    matrices ONCE A LOOP, and the head.  The embedding is a gather, the
    norms and the gate do no matmul work."""
    return (kv_layers(cfg) * layer_matrix_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])


# ------------------------------------------------- what a traced step needs
def decode_step_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of weights one decode step has to read: every layer's matrices
    and norm vectors once a LOOP (nothing of a layer stays on the chip until
    the next loop comes round to it), and the head once."""
    layer = layer_matrix_params(cfg) + 4 * cfg["hidden_size"]
    return bytes_per_param * (kv_layers(cfg) * layer
                              + cfg["vocab_size"] * cfg["hidden_size"])


def live_tokens_in_capture(view):
    f = view["facts"]
    t0, t1 = view["trace_span"]
    return sum(n for t, n in f["live_tokens"] if t0 <= t < t1)


def need_paged_attention(view):
    """``costs.need_paged_attention`` over the ``total_ut_steps x
    num_hidden_layers`` layer-applications a token keeps K/V in."""
    f = view["facts"]
    live, n = live_tokens_in_capture(view), kv_layers(view["config"])
    return (_costs.paged_attention_flops(live, n,
                                         f["n_head"] * f["head_dim"]),
            _costs.paged_attention_bytes(live, n, f["kv_width"],
                                         f["kv_bytes_per_element"]))


def need_decode_step(view, module_match):
    """``(flops, bytes)`` the decode steps in the capture need: the weights
    streamed once a step (``decode_step_weight_bytes``) and the K and V of
    every live token in every layer-application.  FLOPs: the matmuls of 16
    rows a step are nothing beside the bytes and are left out."""
    f, cfg = view["facts"], view["config"]
    steps = _costs.traced_steps(view, module_match)
    kv = _costs.paged_attention_bytes(
        live_tokens_in_capture(view), kv_layers(cfg), f["kv_width"],
        f["kv_bytes_per_element"])
    return 0.0, steps * decode_step_weight_bytes(cfg) + kv


costs = {"ouro_paged_attention": need_paged_attention,
         "ouro_decode_step": need_decode_step}
