"""gpt2-tiny in the published key names: the size the CPU tests run at."""

TINY = {"model_type": "gpt2", "n_embd": 128, "n_layer": 4, "n_head": 4, "n_positions": 256,
        "vocab_size": 1024, "layer_norm_epsilon": 1e-5,
        "activation_function": "gelu_new"}
