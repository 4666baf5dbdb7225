"""The GPT-2 family (``"model_type": "gpt2"``: GPT-2 Large, Cerebras-GPT):
everything the harness asks of an architecture, in one file found by the
configuration's ``model_type``.  Its plain reference is the file of the
same name, ``benchmark/reference/gpt2.py``.

A configuration file keeps the published key names (HF ``config.json``);
this module is the only place they are translated, to the program's
``GPT2Config`` fields for ``build`` and to the family-neutral names of
``dims`` for the runners, the readers and the traffic generator.

Nothing here imports JAX at module level (the harness loads a family before
``run.py`` has refused a machine without a TPU).
"""

from benchmark import costs

_HF_TO_GPT2 = {"n_embd": "n_embd", "n_layer": "n_layer", "n_head": "n_head",
               "n_positions": "max_seq", "vocab_size": "vocab_size",
               "layer_norm_epsilon": "layer_norm_eps"}


def model_overrides(cfg):
    """``GPT2`` keyword overrides from a configuration file's published
    keys.  The repo's block is fixed at a 4x MLP and the tanh GELU: a file
    that states anything else is refused, not silently run differently."""
    inner = cfg.get("n_inner")
    if inner not in (None, 4 * cfg["n_embd"]):
        raise ValueError(f"n_inner {inner} is not 4 x n_embd: models/gpt2.py "
                         "cannot run it")
    if cfg.get("activation_function", "gelu_new") != "gelu_new":
        raise ValueError("models/gpt2.py computes gelu_new (tanh); the file "
                         f"states {cfg['activation_function']!r}")
    return {ours: cfg[theirs] for theirs, ours in _HF_TO_GPT2.items()}


def build(cfg, dtype, max_positions=None, **extra):
    """The model through the normal path: ``models.build`` with overrides
    (no preset is added to the program for a benchmark configuration).
    ``max_positions`` is the longest sequence the run will use, where that
    is shorter than the file's (a training cell's ``seq``); ``extra`` are
    the program's own model options from the traffic file."""
    from deepspeed_tpu.models import build as build_preset
    overrides = model_overrides(cfg)
    if max_positions is not None:
        overrides["max_seq"] = max_positions
    return build_preset("gpt2-125m", dtype=dtype, **{**overrides, **extra})


def dims(cfg):
    """The sizes the readers and the traffic generator use, under names no
    family owns.  ``kv_width`` is the elements of K, and of V, that one token
    keeps in one layer: all heads here, since GPT-2 is multi-head."""
    head_dim = cfg["n_embd"] // cfg["n_head"]
    return {"n_layer": cfg["n_layer"], "n_head": cfg["n_head"],
            "n_kv_head": cfg["n_head"], "head_dim": head_dim,
            "d_model": cfg["n_embd"], "kv_width": cfg["n_head"] * head_dim,
            "vocab_size": cfg["vocab_size"],
            "max_positions": cfg["n_positions"]}


def matmul_params_per_token(cfg):
    """Parameters that do matmul work for every token (``train.mfu``
    multiplies them): 12 d^2 a layer and the tied head."""
    return costs.matmul_params(cfg["n_embd"], cfg["n_layer"],
                               cfg["vocab_size"])
