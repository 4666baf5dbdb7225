"""The EvaByte family (``"model_type": "evabyte"``: EvaByte/EvaByte 6.5B, a
byte-level decoder with EVA attention): everything the harness asks of an
architecture, in one file found by the configuration's ``model_type``.  Its
plain reference is the file of the same name, ``benchmark/reference/
evabyte.py``.

A configuration file keeps the published key names (HF ``config.json``), and
so does the program's ``EvaByteConfig``: ``build`` hands them over as they
are.  ``dims`` gives the family-neutral names the runners, the readers and
the traffic generator use; ``costs`` prices this family's decode step and its
paged-attention calls BY THE ROWS THE TABLES HOLD (``kv_tokens`` of the
program's ``serving.step`` rows: summary rows and the current windows' rows),
not by the streams' lengths, which the cache no longer holds.

Nothing here imports JAX at module level (the harness loads a family before
``run.py`` has refused a machine without a TPU).
"""

from benchmark import costs as _costs, program_spans

# published keys the program's EvaByteConfig takes under the same name
_MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "num_attention_heads", "num_key_value_heads",
               "intermediate_size", "rms_norm_eps", "rope_theta",
               "rope_scaling", "max_position_embeddings", "chunk_size",
               "window_size", "num_pred_heads", "norm_add_unit_offset")
# published keys that state what models/evabyte.py computes and has no switch
# for: a file that states anything else is refused, not run differently
_FIXED = {"hidden_act": "silu", "tie_word_embeddings": False,
          "attention_bias": False, "attention_class": "eva",
          "fp32_logits": True, "fp32_skip_add": True, "mixedp_attn": True,
          "fp32_ln": False, "num_chunks": None}


def model_overrides(cfg):
    for key, want in _FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key} = {cfg[key]!r}: models/evabyte.py "
                             f"computes {want!r} and has no switch")
    if cfg.get("max_seq_length", cfg["max_position_embeddings"]) \
            != cfg["max_position_embeddings"]:
        raise ValueError("max_seq_length and max_position_embeddings differ: "
                         "models/evabyte.py serves one limit")
    return {key: cfg[key] for key in _MODEL_KEYS}


def build(cfg, dtype, max_positions=None, **extra):
    """The model through the normal path: ``models.build`` with the
    published keys as overrides (no preset is added to the program for a
    benchmark configuration)."""
    from deepspeed_tpu.models import build as build_preset
    overrides = model_overrides(cfg)
    if max_positions is not None:
        overrides["max_position_embeddings"] = max_positions
    return build_preset("evabyte-tiny", dtype=dtype, **{**overrides, **extra})


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def dims(cfg):
    """The sizes the readers and the traffic generator use, under names no
    family owns.  ``kv_width`` is the elements of K, and of V, one ROW of the
    cache keeps in one layer: an exact row and a summary row alike.
    ``vocab_size`` is the ids a prompt is drawn from: the 256 bytes and the
    64 special ids."""
    return {"n_layer": cfg["num_hidden_layers"],
            "n_head": cfg["num_attention_heads"],
            "n_kv_head": cfg["num_key_value_heads"],
            "head_dim": head_dim(cfg), "d_model": cfg["hidden_size"],
            "kv_width": cfg["num_key_value_heads"] * head_dim(cfg),
            "vocab_size": cfg["vocab_size"],
            "max_positions": cfg["max_position_embeddings"]}


def layer_matrix_params(cfg):
    """One layer's seven matrices: q, k, v, o and the SwiGLU's three."""
    D = cfg["hidden_size"]
    return 4 * D * D + 3 * D * cfg["intermediate_size"]


def layer_params(cfg):
    """A layer's matrices, its two norm vectors, and ``adaptive_phi`` and
    ``adaptive_mu_k``, a vector a head each."""
    return (layer_matrix_params(cfg) + 2 * cfg["hidden_size"]
            + 2 * cfg["num_attention_heads"] * head_dim(cfg))


def parameters(cfg):
    """Every parameter: the layers, the embedding, the ``num_pred_heads``
    untied heads, the final norm."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + (1 + cfg["num_pred_heads"]) * V * D + D)


def matmul_params_per_token(cfg):
    """Parameters that sit in a matmul for every SERVED token: the layers'
    matrices and head 0 (the step samples the next byte from it and computes
    no other head; the embedding is a gather, the norms, ``phi`` and ``mu``
    do no matmul work)."""
    return (cfg["num_hidden_layers"] * layer_matrix_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])


# ------------------------------------------------- what a traced step needs
def row_bytes(cfg, bytes_per_element=2):
    """One cache row (an exact row or a summary row) in one layer: K and V."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * bytes_per_element


def decode_step_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of weights one decode step has to read: every layer whole, the
    final norm and head 0."""
    D = cfg["hidden_size"]
    return bytes_per_param * (cfg["num_hidden_layers"] * layer_params(cfg)
                              + D + cfg["vocab_size"] * D)


def rows_in_capture(view, name):
    """The program's ``name`` rows that began inside the capture and carry
    attributes; ``[]`` where the program records none."""
    t0, t1 = view["trace_span"]
    if t0 is None:
        return []
    rows = program_spans.rows_from(view, t0)
    return [r for r in rows or () if r.name == name
            and t0 <= r.t_start < t1 and r.attrs]


def table_rows_in_capture(view):
    """The rows the seated streams' tables held, summed over the capture's
    decode steps (``kv_tokens`` of the program's ``serving.step`` rows: a
    folded cache reports its rows there, summaries and window rows, where a
    growing cache reports its tokens); 0 where the program records none."""
    return sum(r.attrs["kv_tokens"]
               for r in rows_in_capture(view, "serving.step")
               if "kv_tokens" in r.attrs)


def need_paged_attention(view):
    """``(flops, bytes)`` of the capture's decode attention, whatever
    implements it: every row a table holds (summaries + window rows) is read
    once a layer, ``row_bytes`` each (16,384 B at the published widths), and
    meets one query of every head."""
    cfg, f = view["config"], view["facts"]
    rows = table_rows_in_capture(view)
    return (_costs.paged_attention_flops(
                rows, cfg["num_hidden_layers"], f["n_head"] * f["head_dim"]),
            float(rows * cfg["num_hidden_layers"]
                  * row_bytes(cfg, f["kv_bytes_per_element"])))


def need_decode_step(view, module_match):
    """``(flops, bytes)`` the decode steps in the capture need: the weights
    streamed once a step and every row the tables hold once a layer.  FLOPs:
    the matmuls of 64 rows a step are nothing beside the bytes."""
    cfg = view["config"]
    steps = _costs.traced_steps(view, module_match)
    return 0.0, (steps * decode_step_weight_bytes(cfg)
                 + need_paged_attention(view)[1])


costs = {"evabyte_paged_attention": need_paged_attention,
         "evabyte_decode_bytes": need_decode_step}
