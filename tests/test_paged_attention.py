"""In-place paged-attention kernel vs the gather oracle
(ops/transformer/paged_attention.py; docs/serving.md#paged-attention-kernel).

The oracle is the legacy materialized path — ``paged_kv.gather_kv`` +
``GPT2._attend_paged`` (the shared ``_masked_attend`` core) — kept
exported exactly so the kernel has something to be tested against:

- **exact mode** (the interpret/CPU fallback) must be BIT-exact on
  bf16/fp16 pools — that is what keeps CPU tier-1 exact when the
  serving decode routes through the kernel — and is held to the same
  bit-exactness on int8 pools (same dequant formula, same op order).
  On fp32 pools the contract is 4 ulp of the output scale: the
  interpreter's (H, W, S) softmax and the oracle's (B, H, W, S) one
  vectorize their fp32 row sums differently once W > 1;
- **online mode** (the compiled-TPU online-softmax/DMA-ring variant,
  run here through the interpreter) is tolerance-bounded: it skips the
  oracle's probs→compute-dtype rounding, so agreement is to compute-
  dtype rounding error, not bitwise.

Edge coverage per the serving layer's invariants: partial last blocks,
SCRATCH-slot inactivity (all-zero tables), per-slot length edges (block
boundary, single token), multi-token windows (no caller in the product
since PR 45, kept honest here for chunked prefill: ROADMAP D17), and the write_tokens overflow-to-scratch guard.

The online kernel's WALK (PR 35: dead rows do nothing and come last, one
DMA ring over the whole call, the last chunk fetches its live blocks only;
PR 49: a chunk sized by the pool's bytes, its last products over the live
tail tiles, grouped-query rows packed) has its own cases below, over a table
of 320 positions (one chunk with tail tiles of 128 at the file's own
constants, two or three chunks where a test shrinks ``_CHUNK_BYTES``) and a
pool whose unreferenced blocks hold NaN: the interpreter fills VMEM scratch
with NaN too, so a block that was not fetched and still reached the MXU
fails them.
"""

import importlib
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
from deepspeed_tpu.inference import paged_kv as pk
from deepspeed_tpu.ops.transformer.paged_attention import paged_attention

# the package re-exports the function under the module's name
pa_module = importlib.import_module(
    "deepspeed_tpu.ops.transformer.paged_attention")

BS, NB_MAX, NB, L, H, HD = 8, 4, 16, 2, 4, 16


def _model(dtype=jnp.bfloat16):
    cfg = GPT2Config(vocab_size=64, max_seq=BS * NB_MAX, n_embd=H * HD,
                     n_layer=L, n_head=H, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    return GPT2(cfg, dtype=dtype)


def _filled_pool(rng, dtype, kv_bits=16):
    pool = pk.init_pool(L, NB, BS, H, HD,
                        dtype if kv_bits == 16 else jnp.bfloat16,
                        kv_bits=kv_bits, quant_block=8)
    k = jnp.asarray(rng.standard_normal((L, NB * BS, H, HD)), dtype)
    v = jnp.asarray(rng.standard_normal((L, NB * BS, H, HD)), dtype)
    return pk.write_prefill(pool, jnp.arange(NB, dtype=jnp.int32), k, v)


# per-slot edges in one batch: full blocks, partial last block, block
# boundary, single token, inactive (all-scratch table)
TABLES = np.asarray([[1, 2, 3, 4],      # len 31: partial last block
                     [5, 6, 7, 0],      # len 23: exactly 3 blocks
                     [8, 9, 0, 0],      # len 8: first row of block 2
                     [10, 0, 0, 0],     # len 0: single token
                     [0, 0, 0, 0]],     # inactive slot (scratch)
                    np.int32)
LENGTHS = np.asarray([31, 23, 8, 0, 0], np.int32)
LIVE = TABLES[:, 0] != pk.SCRATCH_BLOCK


def _oracle(model, q, pool, tables, lengths, layer):
    keys, vals = pk.gather_kv(pool, layer, jnp.asarray(tables),
                              q.dtype, H)
    return model._attend_paged(q, keys, vals, jnp.asarray(lengths))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16, jnp.float32])
@pytest.mark.parametrize("n_window", [1, 3])
def test_exact_mode_bit_exact_16bit(dtype, n_window, devices):
    """Exact mode == gather oracle on full-width pools — bit for bit at
    bf16/fp16, within 4 ulp of the output scale at fp32 — on every
    length edge, partial last block, and the scratch slot."""
    model = _model(dtype)
    rng = np.random.default_rng(0)
    pool = _filled_pool(rng, dtype)
    B = TABLES.shape[0]
    q = jnp.asarray(rng.standard_normal((B, n_window, H, HD)), dtype)
    ref = np.asarray(_oracle(model, q, pool, TABLES, LENGTHS, 1))
    out = np.asarray(jax.jit(
        lambda q, p: paged_attention(q, p, TABLES, LENGTHS, 1,
                                     mode="exact"))(q, pool))
    assert out.dtype == ref.dtype
    if dtype == jnp.float32:
        np.testing.assert_allclose(
            out, ref, rtol=0,
            atol=4 * np.finfo(np.float32).eps * np.abs(ref).max())
    else:
        np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("mode", ["exact", "online"])
def test_int8_pool_within_tolerance(mode, devices):
    """int8 pools dequantize IN-KERNEL from the fp32 block scales with
    the oracle's exact formula: exact mode lands bit-equal, online mode
    within compute-dtype rounding of the dequantized values."""
    model = _model(jnp.bfloat16)
    rng = np.random.default_rng(1)
    pool = _filled_pool(rng, jnp.bfloat16, kv_bits=8)
    B = TABLES.shape[0]
    q = jnp.asarray(rng.standard_normal((B, 1, H, HD)), jnp.bfloat16)
    ref = np.asarray(_oracle(model, q, pool, TABLES, LENGTHS, 0),
                     np.float32)
    out = np.asarray(jax.jit(
        lambda q, p: paged_attention(q, p, TABLES, LENGTHS, 0,
                                     mode=mode))(q, pool), np.float32)
    if mode == "exact":
        np.testing.assert_array_equal(out, ref)
    else:
        scale = np.abs(ref).max()
        assert np.abs(out - ref)[LIVE].max() < 0.02 * scale
        assert not out[~LIVE].any()       # a dead row: zeros, not scratch


@pytest.mark.parametrize("n_window", [1, 4])
def test_online_mode_within_compute_dtype_rounding(n_window, devices):
    """Online softmax (the compiled-TPU variant, interpreted here) must
    track the oracle within bf16 rounding — it keeps probabilities in
    fp32 through the accumulation where the oracle rounds them to the
    compute dtype, so bitwise equality is not expected and ~1e-2
    disagreement would be a real bug."""
    model = _model(jnp.bfloat16)
    rng = np.random.default_rng(2)
    pool = _filled_pool(rng, jnp.bfloat16)
    B = TABLES.shape[0]
    q = jnp.asarray(rng.standard_normal((B, n_window, H, HD)), jnp.bfloat16)
    ref = np.asarray(_oracle(model, q, pool, TABLES, LENGTHS, 1),
                     np.float32)
    out = np.asarray(jax.jit(
        lambda q, p: paged_attention(q, p, TABLES, LENGTHS, 1,
                                     mode="online"))(q, pool), np.float32)
    scale = np.abs(ref).max()
    assert np.abs(out - ref)[LIVE].max() < 1e-2 * scale
    assert not out[~LIVE].any()


# ------------------------------------------------- the online kernel's walk
# blocks of 16 and a table of 20: 320 positions.  These pools are 16 to 64
# bytes a token wide, so the file's own ``_CHUNK_BYTES`` makes the table ONE
# chunk whose products run over tail tiles of 128, 256 or all 320 positions;
# ``_chunks_of`` shrinks it to chunks of 128 (three, the third half past the
# table's end: the walk before PR 49) or 256 (two, a tail tile inside each)
WALK_BS, WALK_NB_MAX = 16, 20
#          name: (query heads, K/V heads, window, kv_bits[, head size])
WALK_POOLS = {"bf16": (2, 2, 1, 16), "int8": (2, 2, 1, 8),
              "mqa20": (20, 1, 1, 16), "window3": (2, 2, 3, 16),
              # Nemotron-3's grouping: 16 query heads a K/V head, 32 packed
              # score rows (128 of which 32 were real before PR 49)
              "gqa32": (32, 2, 1, 16),
              # Qwen3-Next's attention layers (PR 54): heads of 256, 8 query
              # heads a K/V head, a pool 512 lanes wide
              "gqa8_hd256": (16, 2, 1, 16, 256)}


def _walk_pool(kind):
    """``(query heads, K/V heads, window, kv_bits, head size)`` of a kind."""
    return (WALK_POOLS[kind] + (HD,))[:5]
# a row's length: the position of its first window token; None: a dead row
# (its whole table names the scratch block, its length 0)
WALK_ROWS = {
    # dead rows first, in the middle and last
    "dead_rows": [None, 40, None, None, 200, 5, None],
    # one token before, on and after a block edge and a chunk edge, the
    # second chunk's, and the table's last position
    "edges": [14, 15, 16, 126, 127, 128, 254, 255, 256, 316],
    "all_dead": [None, None, None],
    # every chunk whole and every ring hand-over: one-chunk rows in a run
    # (the fetch two positions ahead belongs to the row after next)
    "handover": [127, 0, 3, 255, 127, None, 319 - 2, 1],
    # every row seated and every chunk whole: the walk before PR 35
    "full_rows": [127, 255, 127, 255],
    # one before, on and after a tail tile's edge (128) and a 256-token
    # chunk's, and the table's end, live and dead rows mixed
    "chunk_edges": [127, None, 128, 129, 255, None, 256, 257, None, 317],
}


def _chunks_of(monkeypatch, tokens, kv_heads, kv_bits, hd=HD):
    """Make the online walk's chunk ``tokens`` positions of this pool."""
    monkeypatch.setattr(pa_module, "_CHUNK_BYTES",
                        tokens * kv_heads * hd * kv_bits // 8)


def _walk_case(rows, heads, kv_heads, window, kv_bits, HD=HD):
    """Pool, tables, lengths and queries of one case: every live row owns
    its table's worth of blocks with finite K/V; every block no table
    names holds NaN (an int8 pool: NaN scales)."""
    rng = np.random.default_rng(5)
    B = len(rows)
    n_blocks = 1 + B * WALK_NB_MAX + 3
    pool = pk.init_pool(1, n_blocks, WALK_BS, heads, HD, jnp.bfloat16,
                        kv_bits=kv_bits, quant_block=8, n_kv_head=kv_heads)
    tables = np.zeros((B, WALK_NB_MAX), np.int32)
    lengths = np.zeros((B,), np.int32)
    named = {pk.SCRATCH_BLOCK}
    for b, length in enumerate(rows):
        if length is None:
            continue
        n = -(-(length + window) // WALK_BS)           # the blocks it holds
        tables[b, :n] = 1 + b * WALK_NB_MAX + np.arange(n)
        lengths[b] = length
        named.update(tables[b, :n].tolist())
        kv = [jnp.asarray(rng.standard_normal(
            (1, n * WALK_BS, kv_heads, HD)), jnp.bfloat16) for _ in "kv"]
        pool = pk.write_prefill(pool, jnp.asarray(tables[b, :n]), *kv)
    poison = np.asarray([i not in named for i in range(n_blocks)])
    for name in (("k_scale", "v_scale") if kv_bits == 8 else ("k", "v")):
        pool[name] = jnp.where(poison[None, :, None, None], jnp.nan,
                               pool[name])
    q = jnp.asarray(rng.standard_normal((B, window, heads, HD)),
                    jnp.bfloat16)
    return pool, tables, lengths, q


def _dense_oracle(q, pool, tables, lengths, kv_heads):
    """Plain float64 softmax attention over the ``gather_kv`` view."""
    keys, vals = pk.gather_kv(pool, 0, jnp.asarray(tables), jnp.float32,
                              kv_heads)
    keys, vals = np.asarray(keys, np.float64), np.asarray(vals, np.float64)
    q = np.asarray(q, np.float64)
    B, W, n_head, hd = q.shape
    out = np.zeros((B, W, n_head * hd))
    for b, w, h in np.ndindex(B, W, n_head):
        n = int(lengths[b]) + w + 1
        kv = h // (n_head // kv_heads)
        s = keys[b, :n, kv] @ q[b, w, h] / np.sqrt(hd)
        p = np.exp(s - s.max())
        out[b, w, h * hd:(h + 1) * hd] = (p / p.sum()) @ vals[b, :n, kv]
    return out


def _check_walk(rows, pool_kind):
    """Live rows equal the float64 oracle within the online mode's
    tolerance, dead rows are exactly zero, nothing is NaN."""
    heads, kv_heads, window, kv_bits, hd = _walk_pool(pool_kind)
    pool, tables, lengths, q = _walk_case(rows, heads, kv_heads, window,
                                          kv_bits, hd)
    out = np.asarray(jax.jit(
        lambda q, p: paged_attention(q, p, tables, lengths, 0,
                                     mode="online"))(q, pool), np.float32)
    live = tables[:, 0] != pk.SCRATCH_BLOCK
    assert np.isfinite(out).all()
    assert not out[~live].any()
    if live.any():
        ref = _dense_oracle(q, pool, tables, lengths, kv_heads)
        tol = (2e-2 if kv_bits == 8 else 1e-2) * np.abs(ref[live]).max()
        assert np.abs(out - ref)[live].max() < tol


@pytest.mark.parametrize("pool_kind", list(WALK_POOLS))
@pytest.mark.parametrize("rows", list(WALK_ROWS))
def test_online_walk_live_rows_match_dead_rows_are_zero(rows, pool_kind,
                                                        devices):
    """Live rows equal the gather oracle within the online mode's
    tolerance wherever they stand in the batch and wherever their length
    ends; dead rows are exactly zero; nothing is NaN though every block
    the walk must not fetch is."""
    _check_walk(WALK_ROWS[rows], pool_kind)


@pytest.mark.parametrize("pool_kind", list(WALK_POOLS))
@pytest.mark.parametrize("chunk", [128, 256])
def test_online_walk_in_several_chunks(chunk, pool_kind, devices,
                                       monkeypatch):
    """The same walk with the table cut into three chunks of 128 positions
    (one tail width: the walk before PR 49) and into two of 256 (a tail tile
    of 128 inside each): whole chunks land under one wait and run unmasked,
    the last chunk's products cover its live tail tiles only, a row hands the
    ring over wherever it ends."""
    _, kv_heads, _, kv_bits, hd = _walk_pool(pool_kind)
    _chunks_of(monkeypatch, chunk, kv_heads, kv_bits, hd)
    assert pa_module.score_tile(kv_heads * hd * kv_bits // 8, WALK_BS,
                                WALK_NB_MAX, 8)[0] == chunk
    _check_walk(WALK_ROWS["chunk_edges"] + WALK_ROWS["handover"], pool_kind)


# what `_online_call` reads of its input -> the (Tc, R) score tile.
# id: (K/V heads x head dim, bytes an element, block, nb_max, query heads,
#      Tc, R); "as before PR 49" where the tile is the one every pool had
SCORE_TILES = {
    # 2,048 lanes, multi-head: 128 tokens are 512 KB of K; as before PR 49
    "cerebras-gpt-1.3b": (16 * 128, 2, 16, 128, 16, 128, 16),
    "ouro-2.6b": (16 * 128, 2, 64, 320, 16, 128, 16),
    # 1,024 and 1,280 lanes: 256 tokens (128 before PR 49); Trinity's 6
    # query heads a K/V head are 48 rows as before, Phi-4's 4 over 10 K/V
    # heads 40 packed rows where 4 window rows padded to 16 made 64
    "trinity-global": (8 * 128, 2, 64, 272, 48, 256, 48),
    "trinity-window": (8 * 128, 2, 64, 65, 48, 256, 48),
    "phi4-shared": (10 * 128, 2, 64, 128, 40, 256, 40),
    "phi4-window": (10 * 128, 2, 64, 9, 40, 256, 40),
    "gpt2-large": (20 * 64, 2, 16, 64, 20, 256, 24),
    # 256 lanes: 1,024 tokens are 512 KB of K; 32 rows, all real (128 of
    # which 32 were)
    "nemotron-3-nano": (2 * 128, 2, 64, 64, 32, 1024, 32),
    # 512 lanes (2 K/V heads of 256, PR 54): 512 tokens are 512 KB of K; 8
    # query heads a K/V head are 16 rows, all real
    "qwen3-next": (2 * 256, 2, 64, 104, 16, 512, 16),
    # 128 lanes: the whole table of 2,048; 20 rows in 24 (160 of which 20
    # were)
    "jamba2-3b": (1 * 128, 2, 16, 128, 20, 2048, 24),
    # never more than the table
    "short-table": (1 * 128, 2, 16, 20, 20, 320, 24),
    # an int8 pool: half the bytes a lane, so twice the tokens
    "int8-2048": (16 * 128, 1, 16, 128, 32, 256, 32),
    "int8-256": (2 * 128, 1, 64, 64, 8, 2048, 8),
    # never so wide that (R, Tc) float32 scores pass 512 KB
    "many-rows": (1 * 128, 2, 16, 512, 256, 512, 256),
}


@pytest.mark.parametrize("case", list(SCORE_TILES))
def test_score_tile_follows_the_pool(case):
    """``score_tile`` is the one place the walk's tile is chosen: a chunk by
    its bytes (``_CHUNK_BYTES`` of K rows, in whole blocks and whole
    128-lane score columns), the rows packed and rounded up once."""
    lanes, itemsize, block, nb_max, rows, chunk, padded = SCORE_TILES[case]
    assert pa_module.score_tile(lanes * itemsize, block, nb_max,
                                rows) == (chunk, padded)
    assert chunk % block == 0 and (chunk % 128 == 0
                                   or chunk == nb_max * block)


def test_decode_step_kernel_vs_gather_impl(devices):
    """The whole fused decode step — embeddings, QKV, pool writes,
    attention, FFN, head — must be bit-identical between
    ``paged_attention_impl="kernel"`` (exact interpret mode) and
    ``"gather"`` on a 16-bit pool: the kernel is a traffic change, not
    a math change."""
    rng = np.random.default_rng(3)
    logits = {}
    pools = {}
    for impl in ("kernel", "gather"):
        cfg = GPT2Config(vocab_size=64, max_seq=BS * NB_MAX, n_embd=H * HD,
                         n_layer=L, n_head=H, embd_pdrop=0.0,
                         attn_pdrop=0.0, resid_pdrop=0.0,
                         attention_impl="jnp", paged_attention_impl=impl)
        model = GPT2(cfg, dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0))
        pool = _filled_pool(np.random.default_rng(7), jnp.float32)
        toks = jnp.asarray(rng.integers(0, 64, (TABLES.shape[0],)),
                           jnp.int32)
        lg, pl_out = jax.jit(model.decode_step_paged)(
            params, toks, pool, jnp.asarray(TABLES), jnp.asarray(LENGTHS))
        logits[impl] = np.asarray(lg)
        pools[impl] = jax.tree_util.tree_map(np.asarray, pl_out)
        rng = np.random.default_rng(3)        # same tokens for both
    np.testing.assert_array_equal(logits["kernel"], logits["gather"])
    for leaf_k, leaf_g in zip(
            jax.tree_util.tree_leaves(pools["kernel"]),
            jax.tree_util.tree_leaves(pools["gather"])):
        np.testing.assert_array_equal(leaf_k, leaf_g)


def test_multi_token_window_matches_sequential_steps(devices):
    """A (B, W) window through decode_step_paged must produce, at each
    window position, the same logits as W sequential single-token steps
    committing the same tokens (window position i == what plain decode
    would see): what a chunk of a prompt taken as a window relies on.

    Mathematically identical, not bitwise: the window matmuls carry
    (B, W, D) operands where sequential carries (B, 1, D), so XLA's
    reduction order differs in the last ulps — hence a tight tolerance
    plus argmax identity (what the accept rule actually consumes)."""
    cfg = GPT2Config(vocab_size=64, max_seq=BS * NB_MAX, n_embd=H * HD,
                     n_layer=L, n_head=H, embd_pdrop=0.0, attn_pdrop=0.0,
                     resid_pdrop=0.0, attention_impl="jnp")
    model = GPT2(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(4)
    tables = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    lengths = np.asarray([9, 3], np.int32)
    W = 3
    toks = rng.integers(0, 64, (2, W)).astype(np.int32)

    pool = _filled_pool(np.random.default_rng(8), jnp.float32)
    win_logits, _ = jax.jit(model.decode_step_paged)(
        params, jnp.asarray(toks), pool, jnp.asarray(tables),
        jnp.asarray(lengths))

    pool = _filled_pool(np.random.default_rng(8), jnp.float32)
    step = jax.jit(model.decode_step_paged)
    seq_logits = []
    lens = jnp.asarray(lengths)
    for i in range(W):
        lg, pool = step(params, jnp.asarray(toks[:, i]), pool,
                        jnp.asarray(tables), lens)
        seq_logits.append(np.asarray(lg))
        lens = lens + 1
    for i in range(W):
        win = np.asarray(win_logits[:, i])
        np.testing.assert_allclose(win, seq_logits[i],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(win.argmax(-1),
                                      seq_logits[i].argmax(-1))


def test_write_tokens_overflow_lands_in_scratch(devices):
    """A window position past the slot's table (a window
    running beyond the allocation) must be REDIRECTED to the scratch
    block — the take-along-axis clamp would otherwise silently
    overwrite the table's LAST REAL block."""
    pool = pk.init_pool(1, 4, 4, 1, 8, jnp.float32)
    tables = jnp.asarray([[1, 2, 0, 0]], jnp.int32)   # 2 real blocks
    k = jnp.ones((1, 3, 1, 8), jnp.float32)           # 3-token window
    # first window token at position 6: positions 6, 7 fill block 2;
    # position 8 is PAST the 2-block allocation (idx 2 -> table 0)
    out = pk.write_tokens(pool, 0, tables, jnp.asarray([6], jnp.int32),
                          k, 2 * k)
    k_np = np.asarray(out["k"])
    assert k_np[0, 2, 2:].any() and k_np[0, 2].sum() == 2 * 8  # rows 2,3
    assert k_np[0, 1].sum() == 0          # block 1 (real) untouched
    assert k_np[0, pk.SCRATCH_BLOCK, 0].sum() == 8   # overflow -> scratch


# ------------------------------------------ the kernel the TPU compiles
@pytest.fixture(scope="module")
def v5e():
    """A described v5e:2x2 (no chip needed); built here and never at
    import, so every xdist worker collects the same tests."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize(
    "slots,heads,kv_heads,head_dim,positions,kv_bits,block", [
        (16, 16, 16, 128, 2048, 16, 16),  # cerebras-gpt-1.3b, ouro-2.6b
        (16, 20, 20, 64, 1024, 16, 16),   # gpt2-large
        (64, 20, 1, 128, 2048, 16, 16),   # jamba2-3b: 20 query heads, one
        #                                   K/V: chunks of 1,024 tokens
        (16, 16, 16, 128, 2048, 8, 16),   # an int8 pool and its scale rows
        (256, 32, 2, 128, 4096, 16, 64),  # nemotron-3-nano: chunks of 512
        (96, 48, 8, 128, 17408, 16, 64),  # trinity's global layers
        (64, 16, 2, 256, 6656, 16, 64),   # qwen3-next: heads of 256
    ], ids=["cerebras-gpt-1.3b", "gpt2-large", "jamba2-3b", "int8-pool",
            "nemotron-3-nano", "trinity-global", "qwen3-next"])
def test_online_kernel_compiles_for_a_v5e(v5e, slots, heads, kv_heads,
                                          head_dim, positions, kv_bits,
                                          block):
    """The online kernel at the served shapes, through Mosaic and XLA:TPU
    for a described v5e: one custom call, and no copy of the pool (the
    kernel reads blocks where they lie)."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(v5e.devices[0])
    layers = 2
    nb_max = positions // block
    width = kv_heads * head_dim
    rows = (layers, min(slots * nb_max, 8192) + 1, block)
    pool = {n: (rows + (width,), jnp.bfloat16 if kv_bits == 16 else jnp.int8)
            for n in ("k", "v")}
    if kv_bits == 8:
        pool.update({n + "_scale": (rows + (width // 64,), jnp.float32)
                     for n in ("k", "v")})
    names = sorted(pool)

    def fn(q, tables, lengths, *leaves):
        return paged_attention(q, dict(zip(names, leaves)), tables, lengths,
                               1, mode="online", interpret=False)
    shapes = [((slots, 1, heads, head_dim), jnp.bfloat16),
              ((slots, nb_max), jnp.int32), ((slots,), jnp.int32),
              *[pool[n] for n in names]]
    text = jax.jit(fn).trace(*[
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
    ]).lower(lowering_platforms=("tpu",)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    payload = "{}[{}]".format("bf16" if kv_bits == 16 else "s8",
                              ",".join(map(str, pool["k"][0])))
    assert payload in text                     # the pool is an operand
    assert not re.search(re.escape(" = " + payload) + r"\S* copy\(", text)


# ---- the other kernel of a decode step that writes in place: Mamba-2's update
def test_state_update_kernel_compiles_for_a_v5e(v5e):
    """``ops/mamba2.py``'s one-token update at the served shape (7 Mamba
    layers x 256 slots x 2 MB, Nemotron-3-Nano), through Mosaic and XLA:TPU
    for a described v5e: one custom call, the 3.67 GB leaf aliased to its
    output (a copy of it is a quarter of the chip), and the kernel's three
    buffers of 8 slots inside its VMEM limit."""
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.ops import mamba2
    one_chip = SingleDeviceSharding(v5e.devices[0])
    L, slots, H, P, N, G = 7, 256, 64, 64, 128, 8
    leaf = (L, slots) + mamba2.step_layout(H, P, N, G)
    assert leaf == (7, 256, 8, 128, 512)
    assert mamba2._step_slots(slots, 4 * H * P * N) == 8
    f32 = jnp.float32
    shapes = [(leaf, f32), ((), jnp.int32), ((slots, H), f32),
              ((slots, H, P), f32), ((slots, G, N), f32), ((slots, G, N), f32)]
    exe = jax.jit(lambda *a: mamba2._step_call(*a, interpret=False),
                  donate_argnums=(0,)).trace(*[
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
    ]).lower(lowering_platforms=("tpu",)).compile()
    text = exe.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    call = next(line for line in text.splitlines()
                if "tpu_custom_call" in line)
    assert mamba2.STEP_KERNEL in call
    assert "output_to_operand_aliasing={{1}: (4, {})}" in call
    state = 4 * int(np.prod(leaf))
    m = exe.memory_analysis()
    assert m.alias_size_in_bytes == state
    assert m.temp_size_in_bytes < 64 * 2 ** 20
    assert not re.search(r"f32\[7,(256,8|2048),128,512\]\S* copy\(", text)


def test_delta_state_update_kernel_compiles_for_a_v5e(v5e):
    """``ops/gated_delta.py``'s one-token update at the served shape (9
    DeltaNet layers x 64 slots x 2 MB, Qwen3-Next, PR 54), through Mosaic and
    XLA:TPU for a described v5e: one custom call, the 1.2 GB leaf aliased to
    its output, the kernel's three buffers of 8 slots inside its VMEM limit,
    and no copy of the leaf."""
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.ops import gated_delta as gd
    one_chip = SingleDeviceSharding(v5e.devices[0])
    L, slots, H, dk, dv = 9, 64, 32, 128, 128
    assert gd._step_slots(slots, 4 * H * dk * dv) == 8
    assert 3 * 8 * 4 * H * dk * dv <= gd._STEP_BUF < gd._STEP_VMEM
    f32, bf16 = jnp.float32, jnp.bfloat16
    leaf = (L, slots, H, dk, dv)
    shapes = [(leaf, f32), ((), jnp.int32), ((slots, H, dk), bf16),
              ((slots, H, dk), bf16), ((slots, H, dv), bf16),
              ((slots, H), f32), ((slots, H), f32), ((slots,), jnp.bool_)]
    exe = jax.jit(lambda st, l, q, k, v, g, b, a: gd.delta_step(
        st, l, q, k, v, g, b, active=a, impl="kernel", interpret=False),
        donate_argnums=(0,)).trace(*[
            jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
        ]).lower(lowering_platforms=("tpu",)).compile()
    text = exe.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    call = next(line for line in text.splitlines()
                if "tpu_custom_call" in line)
    assert gd.STEP_KERNEL in call
    assert "output_to_operand_aliasing={{1}: (4, {})}" in call
    m = exe.memory_analysis()
    assert m.alias_size_in_bytes == 4 * int(np.prod(leaf))
    assert m.temp_size_in_bytes < 64 * 2 ** 20
    assert not re.search(r"f32\[9,(64,32|2048),128,128\]\S* copy\(", text)


@pytest.mark.parametrize("T", [2048, 3072, 3414, 3584, 4096])
def test_delta_chunk_scan_kernel_compiles_for_a_v5e(v5e, T):
    """``ops/gated_delta.py``'s chunked rule at the published widths (32
    value heads of 128 x 128, chunks of 64, bfloat16 operands, a float32
    state handed in) at the five segment lengths ``serve_longctx_qwen3next``'s
    prompts are cut into, through Mosaic and XLA:TPU for a described v5e: ONE
    custom call of the kernel's name (the number of chunks is a grid extent:
    3,414 is padded to 54 of them), inside the VMEM it asks for (Mosaic
    refuses a kernel over its ``vmem_limit_bytes``), and no transpose of an
    operand round it: q, k, v and o cross the call as the projection leaves
    them."""
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.ops import gated_delta as gd
    one_chip = SingleDeviceSharding(v5e.devices[0])
    H, dk, dv = 32, 128, 128
    f32, bf16 = jnp.float32, jnp.bfloat16
    assert gd._chunk_kernel_lowers(H, 64, dk, dv)
    assert gd._CHUNK_VMEM <= 100 << 20          # a v5e has 128 MiB
    shapes = [((1, T, H, dk), bf16), ((1, T, H, dk), bf16),
              ((1, T, H, dv), bf16), ((1, T, H), f32), ((1, T, H), f32),
              ((1, H, dk, dv), f32), ((), jnp.int32)]
    exe = jax.jit(lambda q, k, v, g, b, S, n: gd.delta_chunk(
        q, k, v, g, b, S, chunk=64, t_real=n, impl="kernel",
        interpret=False)).trace(*[
            jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
        ]).lower(lowering_platforms=("tpu",)).compile()
    text = exe.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    call = next(line for line in text.splitlines()
                if "tpu_custom_call" in line)
    assert gd.CHUNK_KERNEL in call
    assert not re.search(r"bf16\[[0-9,]*\]\S* transpose\(", text)
    # beside the call XLA keeps G and beta (two small fusions) and, HERE,
    # where q, k, v arrive as parameters in the default 4-D tiling, one
    # re-tiling copy each and the output's: nothing the size of the parent's
    # U, T, W and score matrices (0.4 GB a layer at 4,096 tokens)
    tokens = T + -T % 64
    assert exe.memory_analysis().temp_size_in_bytes < 5 * 2 * tokens * H * dv


def test_a_decode_step_updates_the_recurrent_state_where_it_lies(v5e,
                                                                 monkeypatch):
    """``NemotronH.decode_step_paged`` at the published widths, 256 slots,
    cut to two Mamba-2 layers round an attention layer: both updates are the
    named kernel over the leaf as it lies (aliased through the step, never
    copied, never transposed), ``dt x`` and ``y`` cross the call as ``(slots,
    H, P)`` in ``x``'s own order, and what XLA re-lays round a call is the
    three ``(256, 64, 64)``-sized operands at most (``dt x``, the decay rows
    and ``y``: the step's activations lie with the SLOTS minor, XLA's choice
    for a one-token stream, and the kernel takes rows)."""
    import importlib
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.models import nemotron_h
    from deepspeed_tpu.ops import mamba2
    one_chip = SingleDeviceSharding(v5e.devices[0])
    monkeypatch.setattr(importlib.import_module(
        "deepspeed_tpu.ops.transformer.paged_attention"), "_interpret",
        lambda: False)
    monkeypatch.setattr(mamba2, "_interpret", lambda: False)
    slots, blocks = 256, 1024
    model = nemotron_h.NemotronH(nemotron_h.NemotronHConfig(
        num_hidden_layers=3, hybrid_override_pattern="M*M",
        vocab_held=(0, 2048)), dtype=jnp.bfloat16)
    on = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = jax.tree_util.tree_map(on, jax.eval_shape(
        lambda: model.init_serving_state(slots, blocks, 64)))
    assert pool["ssm"].shape == (2, 256, 8, 128, 512)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                               sharding=one_chip)
    exe = jax.jit(model.decode_step_paged, donate_argnums=(2,)).trace(
        params, ints(slots), pool, ints(slots, 4096 // 64), ints(slots)
    ).lower(lowering_platforms=("tpu",)).compile()
    text = exe.as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and mamba2.STEP_KERNEL in line]
    assert len(calls) == 2
    for call in calls:
        assert "output_to_operand_aliasing={{1}: (4, {})}" in call
    state = 4 * int(np.prod(pool["ssm"].shape))
    assert exe.memory_analysis().alias_size_in_bytes >= state
    assert not re.search(
        r"f32\[2,(256,8|2048),128,512\]\S* (copy|transpose)\(", text)
    relaid = re.findall(
        r" = f32\[256,(?:64,64|8,512|1,4096)\]\S* (?:copy|transpose)\(", text)
    assert len(relaid) <= 3 * len(calls)


# ------------------------------------------------ a window layer's ring walk
# name: (block, window, rows' lengths; None: a dead row)
RING_CASES = {
    # a window that is no multiple of the block: a ring of 8 blocks of 16,
    # ONE chunk of 128 positions; rows before, on and after the window's
    # edge, a block's edge, the chunk's edge, and long after the ring wrapped
    "ring8": (16, 100, [5, None, 14, 99, 100, 101, 127, 128, 250, 1000, None,
                        317, 0]),
    # a ring of 14 blocks: two chunks, the second part past the ring
    "ring14": (16, 200, [3, 199, 200, 201, 255, 256, 257, 4095, None]),
    # the served shape: blocks of 64, window 4,096, a ring of 65
    "ring65": (64, 4096, [10, 4095, 4096, 4100, 9000, None, 16383]),
}


def _ring_case(rows, heads, kv_heads, bs, window):
    """A ring pool as a stream of ``length + 1`` tokens leaves it (logical
    block ``j`` in entry ``j % ring``; blocks the window has slid past are
    overwritten), every block no ring names NaN, and the float64 oracle over
    the last ``window`` positions."""
    rng = np.random.default_rng(5)
    ring = pk.ring_blocks(window, bs)
    B = len(rows)
    k = np.full((1, 1 + B * ring + 3, bs, kv_heads * HD), np.nan, np.float32)
    k[:, 0] = 0
    v = k.copy()
    tables = np.zeros((B, ring), np.int32)
    lengths = np.zeros((B,), np.int32)
    ref = np.zeros((B, 1, heads * HD))
    q = rng.standard_normal((B, 1, heads, HD)).astype(np.float32)
    for b, length in enumerate(rows):
        if length is None:
            continue
        last = length // bs
        n = min(last + 1, ring)
        tables[b, :n] = 1 + b * ring + np.arange(n)
        lengths[b] = length
        K, V = (np.asarray(jnp.asarray(rng.standard_normal(
            ((last + 1) * bs, kv_heads, HD)), jnp.bfloat16), np.float32)
            for _ in "kv")
        for j in range(max(0, last - ring + 1), last + 1):
            at = tables[b, j % ring]
            k[0, at] = K[j * bs:(j + 1) * bs].reshape(bs, -1)
            v[0, at] = V[j * bs:(j + 1) * bs].reshape(bs, -1)
        lo = max(0, length - window + 1)
        for h in range(heads):
            g = h // (heads // kv_heads)
            s = K[lo:length + 1, g].astype(np.float64) @ q[b, 0, h] \
                / np.sqrt(HD)
            p = np.exp(s - s.max())
            ref[b, 0, h * HD:(h + 1) * HD] = (p / p.sum()) @ V[lo:length + 1,
                                                               g]
    pool = {"k": jnp.asarray(k, jnp.bfloat16), "v": jnp.asarray(v,
                                                                jnp.bfloat16)}
    return pool, tables, lengths, jnp.asarray(q, jnp.bfloat16), ref


def _check_ring(case, heads, kv_heads, mode):
    """Live rows equal the oracle over the last ``window`` positions, dead
    rows are zero (online), nothing is NaN."""
    bs, window, rows = RING_CASES[case]
    pool, tables, lengths, q, ref = _ring_case(rows, heads, kv_heads, bs,
                                               window)
    out = np.asarray(jax.jit(lambda q, p: paged_attention(
        q, p, tables, lengths, 0, mode=mode, window=window))(q, pool),
        np.float32)
    live = tables[:, 0] != pk.SCRATCH_BLOCK
    assert np.isfinite(out).all()
    if mode == "online":
        assert not out[~live].any()
    assert np.abs(out - ref)[live].max() < 1e-2 * np.abs(ref[live]).max()


@pytest.mark.parametrize("mode", ["online", "exact"])
@pytest.mark.parametrize("heads, kv_heads", [(2, 2), (6, 1)])
@pytest.mark.parametrize("case", list(RING_CASES))
def test_window_walk_over_a_ring(case, heads, kv_heads, mode, devices):
    """A window layer's decode attention over a ring table: the walk starts
    at the chunk of the first live position, looks blocks up at their ring
    entries, masks the first block's dead positions; live rows equal the
    oracle over the last ``window`` positions, dead rows are zero, nothing
    is NaN though every block no ring names is."""
    _check_ring(case, heads, kv_heads, mode)


@pytest.mark.parametrize("heads, kv_heads", [(2, 2), (6, 1)])
@pytest.mark.parametrize("case", list(RING_CASES))
def test_window_walk_in_chunks_of_128(case, heads, kv_heads, devices,
                                      monkeypatch):
    """The ring walk with the chunk a wide pool takes (128 positions: ring14
    is two chunks, the second part past the ring, ring65 thirty-three, the
    walk starting at the chunk of the first live position); at the file's
    own constants these narrow rings are one chunk (two for ring65)."""
    _chunks_of(monkeypatch, 128, kv_heads, 16)
    _check_ring(case, heads, kv_heads, "online")


def test_a_ring_too_short_for_its_window_is_refused(devices):
    pool, tables, lengths, q, _ = _ring_case([40], 2, 2, 16, 100)
    with pytest.raises(AssertionError, match="cannot hold a window"):
        paged_attention(q, pool, tables[:, :6], lengths, 0, window=100)


def test_window_kernel_compiles_for_a_v5e(v5e):
    """The window walk at the served shape (96 slots, 48 query heads over 8
    K/V heads of 128, a ring of 65 blocks of 64) through Mosaic and XLA:TPU
    for a described v5e: one custom call under its own name, no copy of the
    pool."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(v5e.devices[0])
    slots, heads, kv_heads, hd, block, layers = 96, 48, 8, 128, 64, 4
    ring = pk.ring_blocks(4096, block)
    rows = (layers, 2688, block, kv_heads * hd)

    def fn(q, tables, lengths, k, v):
        return paged_attention(q, {"k": k, "v": v}, tables, lengths, 1,
                               mode="online", interpret=False, window=4096,
                               name="paged_attention_window")
    shapes = [((slots, 1, heads, hd), jnp.bfloat16),
              ((slots, ring), jnp.int32), ((slots,), jnp.int32),
              (rows, jnp.bfloat16), (rows, jnp.bfloat16)]
    text = jax.jit(fn).trace(*[
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
    ]).lower(lowering_platforms=("tpu",)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "paged_attention_window" in text
    payload = "bf16[{}]".format(",".join(map(str, rows)))
    assert payload in text
    assert not re.search(re.escape(" = " + payload) + r"\S* copy\(", text)



def test_latent_kernel_compiles_for_a_v5e(v5e):
    """The latent kernel's walk at ``serve_batch_deepseek_v2``'s shape (128
    slots, tables of 64 blocks of 64 rows of 640 bfloat16, 128 heads, a
    value 512 wide) through Mosaic and XLA:TPU for a described v5e: the plan
    in SMEM scratch and the semaphores carried across the grid pass, one
    custom call under the name the benchmark reads, no copy of the pool."""
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.ops.transformer.paged_latent_attention import (
        paged_latent_attention)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    slots, heads, row, block, nb_max = 128, 128, 640, 64, 64
    rows = (7, 4096, block, row)

    def fn(q, tables, lengths, latent):
        return paged_latent_attention(q, {pk.LATENT: latent}, tables,
                                      lengths, 1, value_width=512,
                                      sm_scale=0.1, interpret=False)
    shapes = [((slots, heads, row), jnp.bfloat16),
              ((slots, nb_max), jnp.int32), ((slots,), jnp.int32),
              (rows, jnp.bfloat16)]
    exe = jax.jit(fn).trace(*[
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
    ]).lower(lowering_platforms=("tpu",)).compile()
    text = exe.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "mla_paged_attention" in text
    payload = "bf16[{}]".format(",".join(map(str, rows)))
    assert payload in text
    assert not re.search(re.escape(" = " + payload) + r"\S* copy\(", text)
    assert exe.memory_analysis().temp_size_in_bytes < 2 ** 20


# ------------------------------- the routed experts' grouped products, for
# the same described chip (kept in THIS file: one worker describes the
# topology once, and a third file doing so could land on another worker)
@pytest.mark.parametrize("layers, count, D, F, slots, k", [
    (6, 20, 5120, 1536, 128, 6),      # DeepSeek-V2: 120 groups, 768 rows
    (4, 32, 3072, 3072, 96, 4),       # Trinity: 128 groups, 384 rows
], ids=["deepseek-v2", "trinity"])
def test_a_stacked_expert_layer_compiles_to_three_gmm_calls(
        v5e, monkeypatch, layers, count, D, F, slots, k):
    """``held_experts`` alone over a stacked expert layer at the published
    widths, a decode step's rows, ``layer`` traced, compiled for a described
    v5e as the chip's backend would choose: three ``gmm`` Mosaic calls, no
    ``ragged-dot``, and no copy of a whole expert stack in front of a
    product (PR 42's 2.2 GB re-layout was found this way)."""
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.moe import dropless
    monkeypatch.setattr(dropless, "_on_tpu", lambda: True)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    stack = lambda *dims: ((layers, count) + dims, jnp.bfloat16)
    shapes = [((slots, D), jnp.bfloat16), ((slots, k), jnp.int32),
              ((slots, k), jnp.float32), stack(D, F), stack(D, F),
              stack(F, D), ((), jnp.int32)]

    def fn(x, experts, weights, gate_w, up_w, down_w, layer):
        return dropless.held_experts(x, experts, weights, gate_w, up_w,
                                     down_w, 2 * count, layer=layer)
    exe = jax.jit(fn).trace(*[
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
    ]).lower(lowering_platforms=("tpu",)).compile()
    text = exe.as_text()
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 3
    assert text.count("tpu_custom_call") == 3
    assert "ragged-dot" not in text
    assert not re.search(r"bf16\[(\d+,)+\d{4,},\d{4,}\]\S* copy\(", text)
    # the stacks are operands, and nothing of their size is a transient
    assert exe.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20
