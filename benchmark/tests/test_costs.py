"""The FLOP and byte functions against numbers worked by hand for both
configurations."""

from benchmark import costs

LARGE = dict(n_embd=1280, n_layer=36, vocab_size=50257)
CEREBRAS = dict(n_embd=2048, n_layer=24, vocab_size=50257)


def test_matmul_params():
    # 36 x 12 x 1280^2 + 50257 x 1280
    assert costs.matmul_params(**LARGE) == 707_788_800 + 64_328_960
    # 24 x 12 x 2048^2 + 50257 x 2048
    assert costs.matmul_params(**CEREBRAS) == 1_207_959_552 + 102_926_336


def test_attention_flops_per_token():
    # 36 layers x 2 matmuls x 2 FLOPs x 1280 x 512.5 keys on average
    assert costs.attention_flops_per_token(1280, 36, 1024) == 94_464_000
    assert costs.attention_flops_per_token(2048, 24, 2048) == 201_424_896


def test_train_flops_per_token():
    # 3 x (2 x 772,117,760 + 94,464,000)
    assert costs.train_flops_per_token(seq=1024, **LARGE) == 4_916_098_560
    # 3 x (2 x 1,310,885,888 + 201,424,896)
    assert costs.train_flops_per_token(seq=2048, **CEREBRAS) == 8_469_590_016


def test_flash_attention_flops():
    # unit = 4 x 20 x 1024^2 x 64 = 5,368,709,120; x 7 units x 36 layers
    assert costs.flash_attention_flops(4, 20, 64, 1024, 36) \
        == 1_352_914_698_240
    # unit = 1 x 16 x 2048^2 x 128 = 8,589,934,592; x 7 x 24
    assert costs.flash_attention_flops(1, 16, 128, 2048, 24) \
        == 1_443_109_011_456


def test_paged_attention_needs():
    # one live token: K and V, 2048 wide, 2 bytes, 24 layers = 196,608 B
    assert costs.paged_attention_bytes(1, 24, 2048, 2) == 196_608
    assert costs.paged_attention_bytes(8000, 24, 2048, 2) == 1_572_864_000
    assert costs.paged_attention_bytes(8000, 24, 2048, 1) == 786_432_000
    # 2 matmuls x 2 FLOPs x 2048 x 24 layers a token
    assert costs.paged_attention_flops(8000, 24, 2048) == 1_572_864_000
