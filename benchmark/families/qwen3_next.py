"""The Qwen3-Next family (``"model_type": "qwen3_next"``:
Qwen3-Next-80B-A3B): everything the harness asks of an architecture, in one
file found by the configuration's ``model_type``.  Its plain reference is the
file of the same name, ``benchmark/reference/qwen3_next.py``.

A configuration file keeps the published key names (HF ``config.json``), and so
does the program's ``Qwen3NextConfig``: ``build`` hands them over as they are.
Where the file states ONE CHIP'S SHARE of a deployment, the keys that count
what is held here are listed in its ``reduced``: ``num_experts`` (the experts
held; ``experts_held`` gives the first id beside the count), ``vocab_size``
(the rows held; ``vocab_held``) and ``num_hidden_layers`` (whole periods of
``full_attention_interval`` from layer 0 on); ``published`` gives the model's
own values beside them.  The program takes the router's full width as
``num_experts`` and the share as ``experts_held`` / ``vocab_held``.

``costs`` prices the family's kernels (the one-token delta-rule update, the
attention layers' paged kernel and flash forward at head size 256) and its
decode step.  Nothing here imports JAX at module level (the harness loads a
family before ``run.py`` has refused a machine without a TPU).
"""

from benchmark import costs as _costs
from benchmark.families.nemotron_h import (attr_in_capture,
                                           live_tokens_in_capture,
                                           rows_in_capture)

# published keys the program's Qwen3NextConfig takes under the same name
_MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "full_attention_interval",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
    "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "intermediate_size", "norm_topk_prob",
    "decoder_sparse_step", "hidden_act", "rms_norm_eps",
    "max_position_embeddings")
# published keys that state what models/qwen3_next.py computes and has no
# switch for: a file that states anything else is refused, not run differently
_FIXED = {"tie_word_embeddings": False, "use_sliding_window": False,
          "rope_scaling": None, "attention_bias": False,
          "mlp_only_layers": []}


def published(cfg, key):
    """``key`` as the model's own ``config.json`` has it: the file's
    ``published`` value where the file's own counts the chip's share."""
    return cfg.get("published", {}).get(key, cfg[key])


def layer_counts(cfg, uncut=False):
    """``(DeltaNet layers, attention layers)`` held (``uncut``: published)."""
    L = published(cfg, "num_hidden_layers") if uncut \
        else cfg["num_hidden_layers"]
    attention = L // cfg["full_attention_interval"]
    return L - attention, attention


def model_overrides(cfg):
    for key, want in _FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key} = {cfg[key]!r}: models/qwen3_next.py "
                             f"computes {want!r} and has no switch")
    if cfg["num_hidden_layers"] % cfg["full_attention_interval"]:
        raise ValueError(
            f"num_hidden_layers = {cfg['num_hidden_layers']}: whole periods "
            f"of full_attention_interval = {cfg['full_attention_interval']} "
            "keep the published ratio of the two mixers")
    out = {key: cfg[key] for key in _MODEL_KEYS}
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
    # the chunk of the chunked rule is no key of the model's: the program's
    # own default, not the tiny preset's
    from deepspeed_tpu.models.qwen3_next import Qwen3NextConfig
    overrides["chunk_size"] = Qwen3NextConfig.chunk_size
    return build_preset("qwen3-next-tiny", dtype=dtype,
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


def key_dim(cfg):
    return cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]


def value_dim(cfg):
    return cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]


def delta_matrix_params(cfg):
    """``in_proj_qkvz``, ``in_proj_ba`` and ``out_proj``."""
    D = cfg["hidden_size"]
    return D * (2 * key_dim(cfg) + 2 * value_dim(cfg)
                + 2 * cfg["linear_num_value_heads"]) + value_dim(cfg) * D


def delta_mixer_params(cfg):
    """One Gated DeltaNet mixer: the three matrices, the convolution (no
    bias), ``dt_bias`` and ``A_log`` a value head, the gated norm's weight.
    33,718,464 at the published widths."""
    return (delta_matrix_params(cfg)
            + cfg["linear_conv_kernel_dim"] * (2 * key_dim(cfg)
                                               + value_dim(cfg))
            + 2 * cfg["linear_num_value_heads"] + cfg["linear_value_head_dim"])


def attention_matrix_params(cfg):
    """``q_proj`` (with the gate: twice the heads), ``k_proj``, ``v_proj``,
    ``o_proj``."""
    return cfg["hidden_size"] * cfg["head_dim"] * (
        3 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])


def attention_mixer_params(cfg):
    """27,263,488 at the published widths (the q and k norms a head)."""
    return attention_matrix_params(cfg) + 2 * cfg["head_dim"]


def expert_params(cfg):
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"]


def layer_rest_params(cfg, experts):
    """What every layer has beside its mixer, holding ``experts`` routed
    experts: them, the router at its PUBLISHED width, the shared expert and
    its gate, the two layer norms."""
    D = cfg["hidden_size"]
    return (experts * expert_params(cfg) + D * published(cfg, "num_experts")
            + shared_expert_params(cfg) + D + 2 * D)


def parameters(cfg, uncut=False):
    """Every parameter of what ``cfg`` counts (its layers, ``num_experts``
    experts a layer, ``vocab_size`` rows); ``uncut``: of the published model.
    2,929,374,400 for the cell's file."""
    D = cfg["hidden_size"]
    get = (lambda k: published(cfg, k)) if uncut else cfg.__getitem__
    n_delta, n_attn = layer_counts(cfg, uncut)
    return (n_delta * delta_mixer_params(cfg)
            + n_attn * attention_mixer_params(cfg)
            + (n_delta + n_attn) * layer_rest_params(cfg, get("num_experts"))
            + 2 * get("vocab_size") * D + D)


def matmul_params_per_token(cfg):
    """Parameters a token really multiplies HERE: a DeltaNet mixer's three
    matrices, an attention mixer's four, every layer's router, shared expert
    with its gate and the routed experts it reaches among those held (its
    ``num_experts_per_tok`` picks fall here in the share ``held / all``: 10 x
    64 / 512 = 1.25 on average), and the head's slice.  The embedding is a
    gather; the convolution, the norms and the delta rule do no matmul work
    counted here."""
    D = cfg["hidden_size"]
    E = published(cfg, "num_experts")
    reached = cfg["num_experts_per_tok"] * cfg["num_experts"] / E
    n_delta, n_attn = layer_counts(cfg)
    layer = D * E + shared_expert_params(cfg) + D + reached * expert_params(cfg)
    return (n_delta * delta_matrix_params(cfg)
            + n_attn * attention_matrix_params(cfg)
            + (n_delta + n_attn) * layer + cfg["vocab_size"] * D)


def state_bytes_per_layer(cfg):
    """One stream's delta-rule state in one DeltaNet layer: float32."""
    return 4 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]


# ------------------------------------------------- what a traced step needs
def need_state_update(view):
    """``(flops, bytes)`` of the one-token delta-rule update in the capture,
    from the configuration: the slots seated in the program's
    ``serving.step`` rows (``seated_slots``, the one thing taken from the
    program) x the DeltaNet layers held x the state of a layer a stream, read
    and written (:func:`state_bytes_per_layer`: float32, whatever the program
    keeps).  A dead slot's rows need not move; a program that records no such
    attribute gives (0, 0).  FLOPs: seven a state element on the VPU, nothing
    beside the bytes."""
    cfg = view["config"]
    seated, _ = attr_in_capture(view, "serving.step", "seated_slots")
    return 0.0, float((seated or 0) * layer_counts(cfg)[0]
                      * 2 * state_bytes_per_layer(cfg))


def need_paged_attention(view):
    """``costs.need_paged_attention`` over the ATTENTION layers alone."""
    f = view["facts"]
    live, layers = live_tokens_in_capture(view), layer_counts(
        view["config"])[1]
    return (_costs.paged_attention_flops(live, layers,
                                         f["n_head"] * f["head_dim"]),
            _costs.paged_attention_bytes(live, layers, f["kv_width"],
                                         f["kv_bytes_per_element"]))


def need_prefill_attention(view):
    """``(flops, bytes)`` of the prompts' attention in the capture, from the
    ``prompt_len`` of the program's ``serving.prefill`` rows that began inside
    it: a query at position t meets ``t + 1`` keys in each attention layer, 2
    matmuls of 2 FLOPs x (query heads x head size) a key.  Bytes: q, k, v and
    the output once, far under the FLOPs, left out."""
    cfg = view["config"]
    lens = [r.attrs["prompt_len"]
            for r in rows_in_capture(view, "serving.prefill")
            if "prompt_len" in r.attrs]
    pairs = layer_counts(cfg)[1] * sum(T * (T + 1) // 2 for T in lens)
    return pairs * 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"], 0.0


def dense_weight_bytes(cfg, bytes_per_param=2):
    """Bytes of weights EVERY decode step reads: all but the routed experts'
    matrices and the embedding (a gather of a row a slot)."""
    routed = cfg["num_hidden_layers"] * cfg["num_experts"] * expert_params(cfg)
    embedding = cfg["vocab_size"] * cfg["hidden_size"]
    return bytes_per_param * (parameters(cfg) - routed - embedding)


def need_decode_step(view, module_match):
    """``(flops, bytes)`` the decode steps in the capture need: the dense
    parts' weights once a step; the routed experts that GOT a token (the
    ``experts_touched`` the program's step rows carry, scaled to the steps the
    trace holds; every held expert of every layer where the view carries no
    such attribute); the attention layers' live K/V; AND the delta-rule state
    of the seated streams, read and written (:func:`need_state_update`).
    FLOPs: 128 rows a step are nothing beside the bytes and are left out."""
    cfg = view["config"]
    steps = _costs.traced_steps(view, module_match)
    touched, rows = attr_in_capture(view, "serving.step", "experts_touched")
    per_step = (touched / rows if rows else
                cfg["num_hidden_layers"] * cfg["num_experts"])
    return 0.0, (steps * (dense_weight_bytes(cfg)
                          + per_step * 2 * expert_params(cfg))
                 + need_paged_attention(view)[1] + need_state_update(view)[1])


costs = {"qwen3next_state_update": need_state_update,
         "qwen3next_paged_attention": need_paged_attention,
         "qwen3next_prefill_attention": need_prefill_attention,
         "qwen3next_decode_step": need_decode_step}
