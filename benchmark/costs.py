"""Operations and bytes that the algorithm NEEDS, from shapes.  Kept with the
benchmark so that no PR that claims a gain can change what a kernel's time is
divided into.  Recomputation (rematerialised layers, a flash backward that
forms the scores twice) is never counted: it is work the program chose.
"""


def matmul_params(n_embd, n_layer, vocab_size):
    """Parameters that sit in a matmul for every token: the blocks' four
    weight matrices (12 d^2 a layer) and the tied head (V d).  The position
    table, the biases and the LayerNorm vectors do no matmul work."""
    return n_layer * 12 * n_embd * n_embd + vocab_size * n_embd


def attention_flops_per_token(q_width, n_layer, seq):
    """Forward FLOPs per token of causal attention's two matmuls (scores and
    weighted values): a query at position t meets t + 1 keys, (seq + 1) / 2
    on average, 2 FLOPs x ``q_width`` (query heads x head size; ``n_embd``
    for GPT-2) per key for each matmul."""
    return n_layer * 2 * 2 * q_width * (seq + 1) / 2


def train_flops_per_token_of(matmul_params_per_token, q_width, n_layer, seq):
    """Forward + backward FLOPs per trained token of any family: the
    backward pass costs twice the forward, so 3 x (2 x the parameters that
    do matmul work for a token + causal attention)."""
    return 3 * (2 * matmul_params_per_token
                + attention_flops_per_token(q_width, n_layer, seq))


def train_flops_per_token(n_embd, n_layer, vocab_size, seq):
    """GPT-2's closed form of the above: 6N + 6 L T d, half the attention
    term of the usual 6N + 12 L T d, which counts the masked half of the
    scores too."""
    return train_flops_per_token_of(
        matmul_params(n_embd, n_layer, vocab_size), n_embd, n_layer, seq)


def flash_attention_flops(batch, n_head, head_dim, seq, n_layer):
    """FLOPs one training step's causal flash attention needs on one device,
    forward and backward, all layers.  One "unit" is a (T x hd) by (hd x T)
    matmul under the causal mask: B H T^2 hd FLOPs.  Forward: scores and
    weighted values, 2 units.  Backward: scores again (the algorithm keeps
    no T x T matrix), dP, dV, dQ, dK, 5 units."""
    unit = batch * n_head * seq * seq * head_dim
    return n_layer * 7 * unit


def paged_attention_bytes(live_tokens, n_layer, kv_width,
                          kv_bytes_per_element):
    """HBM bytes one decode step's paged attention needs: the keys and the
    values (``kv_width`` elements each: KV heads x head size) of every live
    token, in every layer.  The queries, the outputs and the block tables
    are thousands of times smaller and are left out."""
    return live_tokens * n_layer * 2 * kv_width * kv_bytes_per_element


def paged_attention_flops(live_tokens, n_layer, q_width):
    """FLOPs one decode step's attention needs: each live token's key and
    value meet one query of every query head, 2 FLOPs x ``q_width`` (query
    heads x head size) each."""
    return live_tokens * n_layer * 2 * 2 * q_width


# ------------------------------------------------- what a traced kernel needs
# ``kernel_roofline`` looks a metric file's ``cost`` up here first and in the
# family's ``costs`` second.  Each takes the reader's view and the metric
# file's remaining parameters and returns ``(flops, bytes)`` for the kernel's
# calls inside the traced window.
def traced_steps(view, module_match):
    """How many executions of the step module the trace holds, counting a
    cut one by the part that is there: module time over the median call."""
    calls = [(n, s) for n, s in view["trace"]["module_calls"].items()
             if module_match in n]
    return sum(s / med for n, (s, med) in calls if med > 0) if calls else 0.0


def need_paged_attention(view):
    f = view["facts"]
    t0, t1 = view["trace_span"]
    live = sum(n for t, n in f["live_tokens"] if t0 <= t < t1)
    return (paged_attention_flops(live, f["n_layer"],
                                  f["n_head"] * f["head_dim"]),
            paged_attention_bytes(live, f["n_layer"], f["kv_width"],
                                  f["kv_bytes_per_element"]))


def need_flash_attention(view, module_match):
    f = view["facts"]
    steps = traced_steps(view, module_match)
    per_device_batch = f["global_batch"] // f["chips"]
    flops = steps * flash_attention_flops(
        per_device_batch, f["n_head"], f["head_dim"], f["seq"], f["n_layer"])
    # bytes: q, k, v, o and their gradients once each, far under the FLOPs
    return flops, 0.0


KERNEL_NEEDS = {"paged_attention": need_paged_attention,
                "flash_attention": need_flash_attention}
