"""The traffic generator: the same seed gives the same schedule, every seed
gets the same sizes and gaps in another order, and the stated statistics
hold."""

import numpy as np
import pytest

from benchmark import harness, traffic_gen

CHAT = harness.load_traffic("serve_chat")
DOCS = harness.load_traffic("serve_docs_offline")
VOCAB = 50257
BIG_SEED = 2 ** 31 + 12345


def schedule(seed, seconds=40):
    return traffic_gen.open_loop_schedule(CHAT, seconds, seed, VOCAB)


def test_same_seed_same_schedule():
    a, b = schedule(BIG_SEED), schedule(BIG_SEED)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.due == y.due and x.new_tokens == y.new_tokens
        assert x.seed == y.seed and np.array_equal(x.prompt, y.prompt)


FREE = {**CHAT, "arrivals": {"process": "poisson", "rate": 5.0}}


def test_free_seeds_permute_one_multiset():
    """Without ``order_seed`` every seed gets the same lengths and gaps in
    another order, and so the same prefill shapes to warm."""
    a = traffic_gen.open_loop_schedule(FREE, 20, 1, VOCAB)
    b = traffic_gen.open_loop_schedule(FREE, 20, BIG_SEED, VOCAB)
    assert len(a) == 100 and a[0].due == 0.0
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt)
                                                      for x in b)
    assert sorted(x.new_tokens for x in a) == sorted(x.new_tokens for x in b)
    # the same gaps too, but for the one each seed puts first and drops
    ga, gb = ({round(g, 9) for g in np.diff([x.due for x in s])}
              for s in (a, b))
    assert len(ga ^ gb) <= 2
    block = CHAT["serving"]["block_size"]
    assert traffic_gen.prefill_buckets(a, block) \
        == traffic_gen.prefill_buckets(b, block)


def test_order_seed_fixes_the_queue():
    """serve_chat's file fixes who arrives when; the seed draws the tokens
    and the sampling seeds only."""
    assert "order_seed" in CHAT["arrivals"]
    a, b = schedule(1), schedule(BIG_SEED)
    assert [(x.due, len(x.prompt), x.new_tokens, x.do_sample) for x in a] \
        == [(x.due, len(x.prompt), x.new_tokens, x.do_sample) for x in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    assert [x.seed for x in a] != [x.seed for x in b]


def test_stated_length_statistics():
    cls = CHAT["classes"][0]
    items = schedule(7)
    prompts = np.array([len(x.prompt) for x in items])
    outs = np.array([x.new_tokens for x in items])
    # prompts are rounded up to the next multiple of ``round_to``
    assert 0 <= np.median(prompts) - cls["prompt_tokens"]["median"] \
        <= cls["prompt_tokens"]["round_to"]
    assert abs(np.median(outs) - cls["output_tokens"]["median"]) <= 2
    assert prompts.min() >= cls["prompt_tokens"]["min"]
    assert prompts.max() <= cls["prompt_tokens"]["max"]
    assert outs.min() >= cls["output_tokens"]["min"]
    assert outs.max() <= cls["output_tokens"]["max"]
    # fits the context of the configuration the cell runs
    assert prompts.max() + outs.max() <= 2048
    assert sum(x.do_sample for x in items) == len(items) // 2
    assert all(x.prompt.dtype == np.int32 and x.prompt.max() < VOCAB
               and 0 <= x.seed < 2 ** 31 for x in items)


def test_stated_rate():
    rate = CHAT["arrivals"]["rate"]
    items = schedule(3, seconds=40)
    dues = np.array([x.due for x in items])
    assert len(items) == int(rate * 40)
    assert dues[0] == 0.0 and np.all(np.diff(dues) > 0) and dues[-1] < 40
    gaps = np.diff(dues)
    assert abs(gaps.mean() * rate - 1) < 0.02
    # exponential gaps: the standard deviation is about the mean
    assert 0.8 < gaps.std() / gaps.mean() < 1.1


def test_gamma_arrivals_are_burstier():
    gaps = traffic_gen.stratified_gaps(
        {"process": "gamma", "rate": 5.0, "cv": 2.5}, 400)
    assert abs(gaps.mean() - 0.2) < 1e-9
    assert gaps.std() / gaps.mean() > 1.8


def test_backlog_is_uniform_and_greedy():
    pool = traffic_gen.backlog(DOCS, 5, VOCAB)
    cls = DOCS["classes"][0]
    prompts = np.array([len(x.prompt) for x in pool])
    assert len(pool) == DOCS["pool_requests"]
    assert prompts.min() >= cls["prompt_tokens"]["min"]
    assert prompts.max() <= cls["prompt_tokens"]["max"]
    mid = (cls["prompt_tokens"]["min"] + cls["prompt_tokens"]["max"]) / 2
    assert 0 <= prompts.mean() - mid <= cls["prompt_tokens"]["round_to"]
    assert not any(x.do_sample for x in pool)
    assert all(x.due == 0.0 for x in pool)
    other = traffic_gen.backlog(DOCS, 6, VOCAB)
    assert sorted(len(x.prompt) for x in other) == sorted(prompts)
    assert [len(x.prompt) for x in other] != list(prompts)


def test_classes_and_shared_prefixes():
    mix = {"classes": [
        {"share": 0.75, "prompt_tokens": {"kind": "fixed", "value": 64},
         "output_tokens": {"kind": "fixed", "value": 8},
         "shared_prefix": {"tokens": 32, "groups": 2}},
        {"share": 0.25, "prompt_tokens": {"kind": "uniform", "min": 200,
                                          "max": 300},
         "output_tokens": {"kind": "fixed", "value": 4},
         "sampling": "sampled", "temperature": 0.7}]}
    items = traffic_gen.make_items(mix, 40, 9, 1000)
    short = [x for x in items if len(x.prompt) == 64]
    assert len(short) == 30 and len(items) == 40
    heads = {tuple(x.prompt[:32]) for x in short}
    assert len(heads) == 2                       # two groups, shared heads
    assert len({tuple(x.prompt) for x in short}) == 30   # own tails
    assert all(x.do_sample and x.temperature == 0.7
               for x in items if len(x.prompt) >= 200)


def test_token_batches():
    tr = harness.load_traffic("train_z1")
    a = traffic_gen.token_batches(tr, BIG_SEED, VOCAB, 4)
    b = traffic_gen.token_batches(tr, BIG_SEED, VOCAB, 4)
    assert len(a) == tr["pool_batches"]
    assert a[0].shape == (4, tr["seq"] + 1) and a[0].dtype == np.int32
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], a[1])


def test_unknown_kinds_are_refused():
    with pytest.raises(ValueError):
        traffic_gen.stratified_lengths({"kind": "zipf"}, 4)
    with pytest.raises(ValueError):
        traffic_gen.stratified_gaps({"process": "replay", "rate": 1}, 4)
