"""The ``phi4flash`` family (``"model_type": "phi4flash"``:
Phi-4-mini-flash-reasoning, the SambaY decoder-hybrid-decoder): everything the
harness asks of an architecture, in one file found by the configuration's
``model_type``.  Its plain reference is the file of the same name,
``benchmark/reference/phi4flash.py``.

A configuration file keeps the published key names (HF ``config.json``) and
so does the program's ``Phi4FlashConfig``.  The published file gives no Mamba
size: they stand under ``assumed`` as ``{"value": ..., "from": ...}`` and
``with_assumed`` puts them beside the published keys.

``costs`` prices the family's three named calls (the paged kernel over the one
shared cache and over the rings, the prefill's recurrence; a prefill holds no
attention kernel: the full layer's one query row and the window layers' band
run in ``jax.numpy``) and its decode step, counting the operations and bytes of
the MATHEMATICS (``benchmark/reference/phi4flash.py``'s docstring), whatever
implements it: the zeros a padded query carries are not work.  Nothing here
imports JAX at module level (the harness loads a family before ``run.py`` has
refused a machine without a TPU).
"""

from benchmark import costs as _costs, program_spans

# published keys the program's Phi4FlashConfig takes under the same name
_MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "num_attention_heads", "num_key_value_heads",
               "intermediate_size", "sliding_window", "mb_per_layer",
               "layer_norm_eps", "max_position_embeddings", "hidden_act",
               "tie_word_embeddings", "mlp_bias", "lm_head_bias",
               "mamba_d_state", "mamba_d_conv", "mamba_expand",
               "mamba_dt_rank")
# published keys that state what models/phi4flash.py computes and has no
# switch for: a file that states anything else is refused, not run differently
_FIXED = {"embd_pdrop": 0, "resid_pdrop": 0}


def with_assumed(cfg):
    """``cfg`` with the sizes its ``assumed`` block states (``{"value": ...}``
    entries) beside the published keys."""
    return {**{k: v["value"] for k, v in cfg.get("assumed", {}).items()
               if isinstance(v, dict) and "value" in v}, **cfg}


def model_overrides(cfg):
    cfg = with_assumed(cfg)
    for key, want in _FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key} = {cfg[key]!r}: models/phi4flash.py "
                             f"computes {want!r} and has no switch")
    return {key: cfg[key] for key in _MODEL_KEYS}


def build(cfg, dtype, max_positions=None, **extra):
    """The model through the normal path: ``models.build`` with the
    published keys as overrides (no preset is added to the program for a
    benchmark configuration)."""
    from deepspeed_tpu.models import build as build_preset
    overrides = model_overrides(cfg)
    if max_positions is not None:
        overrides["max_position_embeddings"] = max_positions
    return build_preset("phi4flash-tiny", dtype=dtype,
                        **{**overrides, **extra})


# ------------------------------------------------------------------ the sizes
def dims(cfg):
    """The sizes the readers and the traffic generator use, under names no
    family owns.  ``kv_width``: the elements of K, and of V, a token keeps in
    ONE layer with a cache (all the K/V heads: both halves of every pair)."""
    head_dim = cfg["hidden_size"] // cfg["num_attention_heads"]
    return {"n_layer": cfg["num_hidden_layers"],
            "n_head": cfg["num_attention_heads"],
            "n_kv_head": cfg["num_key_value_heads"], "head_dim": head_dim,
            "d_model": cfg["hidden_size"],
            "kv_width": cfg["num_key_value_heads"] * head_dim,
            "vocab_size": cfg["vocab_size"],
            "max_positions": cfg["max_position_embeddings"]}


def layer_counts(cfg):
    """``{kind: layers}``: Mamba, window attention, the full layer, Gated
    Memory Units, cross attention."""
    L = cfg["num_hidden_layers"]
    return {"mamba": L // 4 + 1, "window": L // 4, "full": 1,
            "gmu": L // 4 - 1, "cross": L // 4 - 1}


def mixer_params(cfg):
    """``{kind: (all parameters, those in a matmul)}`` of one mixer."""
    cfg = with_assumed(cfg)
    D = cfg["hidden_size"]
    Di = cfg["mamba_expand"] * D
    N, K, R = cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    hd = D // cfg["num_attention_heads"]
    Q, KV = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    mamba_mm = D * 2 * Di + Di * (R + 2 * N) + R * Di + Di * D
    mamba = mamba_mm + Di * K + Di + Di + Di * N + Di
    # the four lambda vectors, the pair norm's weight, W_o and its bias
    diff, diff_mm = 4 * hd + 2 * hd + Q * D + D, Q * D
    attn = D * (Q + 2 * KV) + Q + 2 * KV
    cross = D * Q + Q
    return {"mamba": (mamba, mamba_mm),
            "window": (attn + diff, attn - Q - 2 * KV + diff_mm),
            "full": (attn + diff, attn - Q - 2 * KV + diff_mm),
            "gmu": (2 * D * Di, 2 * D * Di),
            "cross": (cross + diff, D * Q + diff_mm)}


def parameters(cfg):
    """Every parameter: the mixers, every layer's fused SwiGLU (3 D F) and
    its two LayerNorms (weight and bias), the tied embedding, the final
    LayerNorm.  3,852,562,944 for the cell's file."""
    D = cfg["hidden_size"]
    per = mixer_params(cfg)
    return (sum(n * per[kind][0] for kind, n in layer_counts(cfg).items())
            + cfg["num_hidden_layers"] * (3 * D * cfg["intermediate_size"]
                                          + 4 * D)
            + cfg["vocab_size"] * D + 2 * D)


def matmul_params_per_token(cfg):
    """Parameters that sit in a matmul for a token that runs EVERY layer (a
    decode step's token; a prompt's last; a prompt's other tokens stop after
    layer ``L/2 + 1``): the mixers' and the MLPs' matrices and the tied
    head."""
    D = cfg["hidden_size"]
    per = mixer_params(cfg)
    return (sum(n * per[kind][1] for kind, n in layer_counts(cfg).items())
            + cfg["num_hidden_layers"] * 3 * D * cfg["intermediate_size"]
            + cfg["vocab_size"] * D)


# ------------------------------------------------- what a traced step needs
def _rows(view, name):
    """The program's ``name`` rows that began inside the capture and carry
    attributes; ``[]`` where the program records none."""
    t0, t1 = view["trace_span"]
    if t0 is None:
        return []
    rows = program_spans.rows_from(view, t0)
    return [r for r in rows or () if r.name == name
            and t0 <= r.t_start < t1 and r.attrs]


def _attr_sum(view, span, name):
    return sum(r.attrs[name] for r in _rows(view, span) if name in r.attrs)


def live_tokens_in_capture(view):
    f = view["facts"]
    t0, t1 = view["trace_span"]
    return sum(n for t, n in f["live_tokens"] if t0 <= t < t1)


def attention_need(cfg, keys, layers):
    """``(flops, bytes)`` of differential attention's one-query calls over
    ``keys`` visible keys in each of ``layers`` calls: a key's K and V (all
    ``num_key_value_heads`` heads of each, 2 bytes an element) read once a
    call; a key meets every pair's two score maps (2 FLOPs x ``hd`` each) and
    two value maps over ``2 hd`` values (2 FLOPs x ``2 hd`` each): 6 FLOPs a
    query-head element, where plain attention has 4."""
    d = dims(cfg)
    q_width = d["n_head"] * d["head_dim"]
    return (keys * layers * 6 * q_width,
            keys * layers * 2 * d["kv_width"] * 2)


def need_shared_kv_attention(view):
    """The calls over the ONE growing cache in the capture: K and V of every
    live token, once for the full layer and once for every cross layer."""
    cfg = view["config"]
    n = layer_counts(cfg)
    return attention_need(cfg, live_tokens_in_capture(view),
                          n["full"] + n["cross"])


def need_window_paged_attention(view):
    """The window layers' calls in the capture: K and V of a stream's last
    ``sliding_window`` tokens and no more, a window layer: the
    ``window_kv_tokens`` of the program's ``serving.step`` rows."""
    cfg = view["config"]
    return attention_need(cfg, _attr_sum(view, "serving.step",
                                         "window_kv_tokens"),
                          layer_counts(cfg)["window"])


def need_selective_scan(view):
    """The prefill recurrence in the capture, as ``families/jamba.py``
    counts it (9 FLOPs a state element a token; ``x``, ``delta``, ``z`` in and
    ``y`` out at 2 bytes, ``B`` and ``C`` float32, ``A``, ``D`` and the state
    out once a call), over the ``scan_tokens`` of the ``serving.prefill``
    rows; the layer whose output the GMUs read takes no ``z``."""
    cfg = with_assumed(view["config"])
    rows = _rows(view, "serving.prefill")
    tokens = sum(r.attrs.get("scan_tokens", 0) for r in rows)
    Di = cfg["mamba_expand"] * cfg["hidden_size"]
    N, Lm = cfg["mamba_d_state"], layer_counts(cfg)["mamba"]
    flops = 9 * tokens * Lm * Di * N
    per_token = (4 * Lm - 1) * Di * 2 + Lm * 2 * N * 4
    per_call = Lm * (2 * Di * N + Di) * 4
    return flops, tokens * per_token + len(rows) * per_call


def state_bytes_per_stream(cfg):
    """A stream's recurrent rows: every Mamba layer's float32 state and its
    convolution's carry (2 bytes an element)."""
    cfg = with_assumed(cfg)
    Di = cfg["mamba_expand"] * cfg["hidden_size"]
    return layer_counts(cfg)["mamba"] * (
        4 * cfg["mamba_d_state"] * Di + 2 * (cfg["mamba_d_conv"] - 1) * Di)


def need_decode_step(view, module_match):
    """``(flops, bytes)`` the decode steps in the capture need: every weight
    but the embedding once a step (the head is the embedding read as a
    matrix: counted), the shared cache's live K/V once a READER, the rings'
    capped at the window once a window layer, and each seated stream's
    recurrent rows read and written.  FLOPs: 96 rows a step are nothing
    beside the bytes and are left out."""
    cfg = view["config"]
    steps = _costs.traced_steps(view, module_match)
    weights = 2 * parameters(cfg)
    state = 2 * state_bytes_per_stream(cfg) * _attr_sum(
        view, "serving.step", "seated_slots")
    return 0.0, (steps * weights + need_shared_kv_attention(view)[1]
                 + need_window_paged_attention(view)[1] + state)


costs = {"phi4flash_shared_kv_attention": need_shared_kv_attention,
         "phi4flash_window_paged_attention": need_window_paged_attention,
         "phi4flash_selective_scan": need_selective_scan,
         "phi4flash_decode_step": need_decode_step}
