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


def attention_flops_per_token(n_embd, n_layer, seq):
    """Forward FLOPs per token of causal attention's two matmuls (scores and
    weighted values): a query at position t meets t + 1 keys, (seq + 1) / 2
    on average, 2 FLOPs x d per key for each matmul."""
    return n_layer * 2 * 2 * n_embd * (seq + 1) / 2


def train_flops_per_token(n_embd, n_layer, vocab_size, seq):
    """Forward + backward FLOPs per trained token: the backward pass costs
    twice the forward, so 3 x (2 x matmul parameters + causal attention).
    This is 6N + 6 L T d: half the attention term of the usual
    6N + 12 L T d, which counts the masked half of the scores too."""
    fwd = (2 * matmul_params(n_embd, n_layer, vocab_size)
           + attention_flops_per_token(n_embd, n_layer, seq))
    return 3 * fwd


def flash_attention_flops(batch, n_head, head_dim, seq, n_layer):
    """FLOPs one training step's causal flash attention needs on one device,
    forward and backward, all layers.  One "unit" is a (T x hd) by (hd x T)
    matmul under the causal mask: B H T^2 hd FLOPs.  Forward: scores and
    weighted values, 2 units.  Backward: scores again (the algorithm keeps
    no T x T matrix), dP, dV, dQ, dK, 5 units."""
    unit = batch * n_head * seq * seq * head_dim
    return n_layer * 7 * unit


def paged_attention_bytes(live_tokens, n_layer, n_embd, kv_bytes_per_element):
    """HBM bytes one decode step's paged attention needs: the keys and the
    values (n_embd elements each) of every live token, in every layer.  The
    queries, the outputs and the block tables are thousands of times
    smaller and are left out."""
    return live_tokens * n_layer * 2 * n_embd * kv_bytes_per_element


def paged_attention_flops(live_tokens, n_layer, n_embd):
    """FLOPs one decode step's attention needs: each live token's key and
    value meet one query, 2 FLOPs x d each."""
    return live_tokens * n_layer * 2 * 2 * n_embd
