"""Model FLOP/s utilisation of a training cell, in percent: the FLOPs the
forward and backward passes need per token (``costs.train_flops_per_token``,
no recomputation counted) times tokens per second over the window, over
chips times the chip's published bf16 peak."""

from benchmark import costs


def read(view):
    f, peaks = view["facts"], view["peaks"]
    if peaks is None or "tokens_per_s" not in f:
        return None
    need = costs.train_flops_per_token(f["n_embd"], f["n_layer"],
                                       f["vocab_size"], f["seq"])
    return 100.0 * need * f["tokens_per_s"] / (
        f["chips"] * peaks["bf16_flops_per_s"])
