"""EvaByte (``models/evabyte.py``): EVA attention, whose cache is a window of
exact K/V rows that is folded, a chunk to a summary row, at every window's
end.  At the tiny preset (4 tokens a chunk, 4 chunks a window) a stream of 64
tokens folds three times: the program against the plain reference
(``benchmark/reference/evabyte.py``) across window boundaries, in a prompt and
in decoding, through the model's own entry points and through the serving
engine; the fold's arithmetic (``paged_kv.WindowFold``); what is refused."""

import os
import sys
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepspeed_tpu.inference import Request, ServingEngine
from deepspeed_tpu.inference import paged_kv as pk
from deepspeed_tpu.models import build, evabyte
from benchmark.reference import evabyte as reference

TINY = {"model_type": "evabyte", "vocab_size": 320, "hidden_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 256,
        "rms_norm_eps": 1e-5, "rope_theta": 1e5, "chunk_size": 4,
        "window_size": 16, "num_pred_heads": 8,
        "max_position_embeddings": 128}
W, V = TINY["window_size"], TINY["vocab_size"]
BS = 2                  # 8 blocks a window, 2 a folded one


@pytest.fixture(scope="module")
def model():
    return build("evabyte-tiny", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model):
    return jax.jit(model.init)(jax.random.PRNGKey(7))


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, V, n).astype(np.int32)


# ------------------------------------------------------------ the arithmetic
@pytest.mark.parametrize("n, row, held, charge", [
    (1, 0, 1, 1), (2048, 2047, 32, 34), (2049, 128, 3, 3),
    (4096, 128 + 2047, 34, 36), (4097, 256, 5, 5), (2048 + 65, 128 + 64, 4, 4),
    (32768, 15 * 128 + 2047, 62, 64)])
def test_what_a_folded_stream_holds_at_a_length(n, row, held, charge):
    """The published fold over blocks of 64: token ``n`` is table row
    ``row``; writing it takes ``held`` blocks; the step that ends a window
    holds 2 more before the window's 32 come home."""
    fold = pk.WindowFold(2048, 16, 64)
    assert (fold.summary_blocks, fold.window_blocks) == (2, 32)
    assert int(fold.row(n - 1)) == row
    assert int(fold.held(n)) == held and int(fold.charge(n)) == charge
    assert int(fold.column(n - 1)) == held - 1


@pytest.mark.parametrize("total, peak", [
    (100, 2), (2047, 32), (2048, 34), (7000, 38), (26112, 56), (32768, 64)])
def test_the_most_blocks_a_folded_stream_ever_holds(total, peak):
    fold = pk.WindowFold(2048, 16, 64)
    assert fold.life_peak(total) == peak == max(
        int(fold.charge(n)) for n in range(1, total + 1))
    assert fold.table_blocks(32768) == 62


@pytest.mark.parametrize("block_size", [3, 8, 64])
def test_a_fold_that_is_no_whole_blocks_is_refused(model, block_size):
    with pytest.raises(ValueError, match="whole blocks"):
        model.cache_fold(block_size)


def test_the_closed_form_counts_the_parameters(model, params):
    shapes = jax.tree_util.tree_leaves(params)
    assert sum(x.size for x in shapes) == model.num_params()
    assert params["head"].shape == (8 * V, 128)
    assert params["blocks"]["phi"].shape == (2, 4, 32)


def test_random_weights_exercise_the_fold(model, params):
    """Within a chunk the largest pooling weight is far more than e times
    the smallest: a mean-pooled value does not pass for ``b^``."""
    h = model._embed(params, jnp.asarray(tokens(64))[None])
    p = jax.tree_util.tree_map(lambda x: x[0], params["blocks"])
    _, k, _ = model._qkv(p, h, jnp.arange(64))
    logit = jnp.einsum("bthd,hd->bth", k, p["phi"]).reshape(16, 4, 4)
    assert float((logit.max(1) - logit.min(1)).mean()) > 1.0


# ------------------------------------------------- the program, no cache
@pytest.mark.parametrize("T", [13, 16, 37, 64])
def test_apply_equals_the_reference_on_all_eight_heads(model, params, T):
    toks = jnp.asarray(np.stack([tokens(T, 1), tokens(T, 2)]))
    ours = model.apply(params, toks)
    assert ours.shape == (2, T, 8 * V)
    np.testing.assert_allclose(ours, reference.all_logits(TINY, params, toks),
                               rtol=2e-4, atol=2e-5)
    last = jnp.array([T - 1, T // 2], jnp.int32)
    np.testing.assert_allclose(
        reference.logits_at(TINY, params, toks, last),
        ours[jnp.arange(2), last, :V], rtol=2e-4, atol=2e-5)


def test_the_loss_is_head_zeros_next_byte_cross_entropy(model, params):
    batch = jnp.asarray(np.stack([tokens(41, 3), tokens(41, 4)]))
    np.testing.assert_allclose(model.loss(params, batch),
                               reference.loss(TINY, params, batch), rtol=1e-5)


def test_the_contiguous_cache_decodes_across_a_boundary(model, params):
    toks = jnp.asarray(tokens(36, 5))[None]
    full = model.apply(params, toks)[..., :V]
    cache = model.init_cache(1, 64, jnp.float32)
    got, cache = model.apply_with_cache(params, toks[:, :14], cache)
    np.testing.assert_allclose(got, full[:, :14], rtol=2e-4, atol=2e-5)
    for t in range(14, 36):
        got, cache = model.apply_with_cache(params, toks[:, t:t + 1], cache)
        np.testing.assert_allclose(got[:, 0], full[:, t], rtol=2e-4,
                                   atol=2e-5)


# ------------------------------------------------------ through the pool
class Stream:
    """One stream through the model's paged entry points, the blocks handed
    out as ``inference/serving.py`` hands them out."""

    def __init__(self, model, params, num_blocks=96):
        self.m, self.p = model, params
        self.fold = model.cache_fold(BS)
        self.pool = model.init_serving_state(2, num_blocks, BS,
                                             dtype=jnp.float32)
        self.free = list(range(1, num_blocks))
        self.blocks, self.length = [], 0
        self.prefill_fn = jax.jit(model.prefill_paged,
                                  static_argnames="fold")
        self.decode_fn = jax.jit(model.decode_step_paged)
        self.fold_fn = jax.jit(model.fold_paged)

    def take(self, n):
        out, self.free = self.free[:n], self.free[n:]
        return out

    def prefill(self, toks):
        """A window at a time, then the tail; returns the last logits."""
        full, tail = divmod(len(toks), W)
        table = np.zeros(self.m.summary_table_blocks(BS), np.int32)
        for j in range(full + bool(tail)):
            whole = j < full
            piece = toks[j * W:(j + 1) * W]
            width = W if whole else -(-tail // BS) * BS
            own = self.take(self.fold.summary_blocks if whole
                            else width // BS)
            padded = np.zeros((1, width), np.int32)
            padded[0, :len(piece)] = piece
            table[:len(self.blocks)] = self.blocks
            logits, self.pool = self.prefill_fn(
                self.p, jnp.asarray(padded), self.pool,
                jnp.asarray(np.concatenate([table, own]).astype(np.int32)),
                jnp.int32(j * W), jnp.int32(len(piece)), fold=whole)
            self.blocks += own
        self.length = len(toks)
        return np.asarray(logits[0])

    def decode(self, tok):
        """One step; a window that ends is folded after it."""
        if int(self.fold.column(self.length)) >= len(self.blocks):
            self.blocks += self.take(1)
        tables = np.zeros((2, self.fold.table_blocks(128)), np.int32)
        tables[0, :len(self.blocks)] = self.blocks
        logits, self.pool = self.decode_fn(
            self.p, jnp.asarray([tok, 0], jnp.int32), self.pool,
            jnp.asarray(tables), jnp.asarray([self.length, 0], jnp.int32))
        self.length += 1
        if self.length % W == 0:
            src, dst = self.blocks[-self.fold.window_blocks:], self.take(
                self.fold.summary_blocks)
            self.pool = self.fold_fn(self.p, self.pool, jnp.asarray(src),
                                     jnp.asarray(dst))
            self.blocks[-self.fold.window_blocks:] = dst
            self.free += src
        return np.asarray(logits[0])

    def summary_rows(self, window):
        """(k~, b^) of folded window ``window``, every layer."""
        sb = self.fold.summary_blocks
        blk = np.asarray(self.blocks[window * sb:(window + 1) * sb])
        return tuple(np.asarray(self.pool[n][:, blk]).reshape(
            (2, -1, 4 * 32)) for n in ("k", "v"))


@pytest.mark.parametrize("T", [16, 35, 48, 63])
def test_the_segmented_prefill_equals_the_one_piece_forward(model, params, T):
    """A prompt through the pool a window at a time gives the logits the
    one-piece forward gives, at the end of every segment."""
    toks = tokens(T, 11)
    full = np.asarray(model.apply(params, jnp.asarray(toks)[None]))[0, :, :V]
    s = Stream(model, params)
    np.testing.assert_allclose(s.prefill(toks), full[T - 1], rtol=2e-4,
                               atol=2e-5)
    assert len(s.blocks) == s.fold.summary_blocks * (T // W) \
        + -(-(T % W) // BS)


@pytest.mark.parametrize("prompt", [3, 14, 16, 30])
def test_prefill_then_decode_across_two_boundaries_equals_the_reference(
        model, params, prompt):
    """Every decode step's logits, from the prompt's end over two window
    ends (two folds made in decoding), against the reference's full forward;
    the table holds what the arithmetic says at every length."""
    toks = tokens(prompt + 35, 12)
    want = np.asarray(reference.all_logits(
        TINY, params, jnp.asarray(toks)[None]))[0, :, :V]
    s = Stream(model, params)
    np.testing.assert_allclose(s.prefill(toks[:prompt]), want[prompt - 1],
                               rtol=2e-4, atol=2e-5)
    folds = 0
    for t in range(prompt, prompt + 35):
        before = s.length // W
        np.testing.assert_allclose(s.decode(int(toks[t])), want[t],
                                   rtol=2e-4, atol=2e-5)
        folds += s.length // W - before
        held = s.fold.summary_blocks * (s.length // W) \
            + -(-(s.length % W) // BS)
        assert len(s.blocks) == held
    assert folds >= 2


@pytest.mark.parametrize("decoded_from", [1, 9, 15])
def test_a_fold_made_in_decoding_equals_the_prompts_fold(model, params,
                                                         decoded_from):
    """The same 32 tokens, the second window's rows written by decode steps
    from ``decoded_from`` on and folded by ``fold_paged``, against both
    windows folded inside the prompt's prefill: the same summary rows."""
    toks = tokens(2 * W, 13)
    a, b = Stream(model, params), Stream(model, params)
    a.prefill(toks)
    b.prefill(toks[:W + decoded_from])
    for t in range(W + decoded_from, 2 * W):
        b.decode(int(toks[t]))
    assert len(a.blocks) == len(b.blocks) == 2 * a.fold.summary_blocks
    for window in (0, 1):
        for x, y in zip(a.summary_rows(window), b.summary_rows(window)):
            np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)
    # and they are the reference's k~ and b^ of that window's exact rows
    k_sum, _ = a.summary_rows(1)
    assert np.abs(k_sum).max() > 0


# ------------------------------------------------------ through the engine
def serve(model, params, requests, **config):
    srv = ServingEngine(model=model, params=params, dtype=jnp.float32,
                        config={"batch_slots": 4, "block_size": BS,
                                "sanitize": True, **config})
    began = time.monotonic()         # the span recorder is the process's
    uids = [srv.submit(r) for r in requests]
    while srv.step():
        pass
    rows = [r.attrs for r in srv._spans.rows()
            if r.name == "serving.step" and r.attrs and r.t_start >= began]
    out = [srv.results[u] for u in uids]
    stats, free = srv.stats(), srv.allocator.free_blocks
    srv.close()
    return out, rows, stats, free


def reference_greedy(params, prompt, n):
    """``n`` greedy tokens of head 0 by the reference's full forward."""
    fn = jax.jit(lambda t, pos: reference.logits_at(TINY, params, t, pos))
    seq = list(prompt)
    for _ in range(n):
        padded = np.zeros((1, 128), np.int32)
        padded[0, :len(seq)] = seq
        seq.append(int(np.asarray(fn(jnp.asarray(padded), jnp.asarray(
            [len(seq) - 1]))).argmax()))
    return seq[len(prompt):]


@pytest.mark.parametrize("prompt, new", [(5, 30), (15, 36), (16, 20),
                                         (33, 40), (47, 12)])
def test_served_streams_fold_and_match_the_reference(model, params, prompt,
                                                     new):
    """Through ``ServingEngine``'s normal entry points, beside other
    streams: the tokens are the reference's, windows were folded in
    decoding and their blocks given back, every block comes home."""
    mine = Request(tokens=tokens(prompt, 20 + prompt), max_new_tokens=new)
    others = [Request(tokens=tokens(n, n), max_new_tokens=9)
              for n in (7, 18, 40)]
    out, rows, stats, free = serve(model, params, [mine] + others,
                                   num_blocks=64)
    assert out[0]["outcome"] == "ok"
    assert out[0]["tokens"] == reference_greedy(params, mine.tokens, new)
    folds = (prompt + new - 1) // W - prompt // W
    assert stats["windows_folded_total"] >= folds >= 1
    assert stats["blocks_released_by_fold_total"] \
        == stats["windows_folded_total"] * (W // BS)
    assert sum(r["windows_folded"] for r in rows) \
        == stats["windows_folded_total"]
    assert sum(r["blocks_released_by_fold"] for r in rows) \
        == stats["blocks_released_by_fold_total"] > 0
    assert all(r["summary_blocks"] + r["window_blocks"] == r["blocks_in_use"]
               for r in rows)
    assert free == 63


def test_a_pool_sized_for_folded_holdings_seats_what_the_arithmetic_says(
        model, params):
    """Six streams of 20 + 30 tokens over blocks of 4: a stream's life peaks
    at 7 blocks (the step that ends its third window), where a growing table
    would end at 13.  21 allocatable blocks seat 3 such streams in step with
    each other (and no fourth: 28), where whole lives would seat 1; once
    they are out of step their window ends no longer coincide and more sit
    side by side; nothing waits once seated, every block comes home."""
    fold = model.cache_fold(4)
    assert fold.life_peak(50) == 7 and pk.blocks_needed(50, 4) == 13
    reqs = [Request(tokens=tokens(20, i), max_new_tokens=30)
            for i in range(6)]
    out, rows, stats, free = serve(model, params, reqs, block_size=4,
                                   num_blocks=22, batch_slots=6)
    assert all(r["outcome"] == "ok" and len(r["tokens"]) == 30 for r in out)
    assert rows[0]["n_active"] == 3 <= max(r["n_active"] for r in rows)
    assert max(r["blocks_in_use"] for r in rows) <= 21
    assert any(r["waits_for_blocks"] for r in rows)
    assert stats["windows_folded_total"] == 6 * 2 and free == 21


@pytest.mark.parametrize("config, said", [
    ({"kv_bits": 8}, "a summary row is kept at 16 bits"),
    ({"block_size": 8}, "whole blocks")])
def test_what_the_fold_cannot_serve_is_refused(model, params, config, said):
    with pytest.raises(ValueError, match=said):
        ServingEngine(model=model, params=params, dtype=jnp.float32,
                      config={"batch_slots": 2, "block_size": BS, **config})


def test_models_build_knows_the_family():
    m = build("evabyte-tiny", window_size=32, chunk_size=8)
    assert isinstance(m, evabyte.EvaByte) and m.has_folded_cache
    assert m.config.head_dim == 32 and m.summary_table_blocks(4) == 3
