"""The one general traffic generator.  A traffic mix is a data file of
parameters (``benchmark/traffic/<name>.json``); this module turns it and a
seed into a schedule.  A later PR adds a mix by adding a file.

Every seed gets the SAME multiset of lengths and of gaps between arrivals,
in another order: sizes are the stratified quantiles of the distribution the
file states (so the median, the tails and the total work do not move with the
seed, and neither does the set of prefill shapes to warm up), and the seed
only permutes them and draws the token ids.

A distribution is ``{"kind": "lognormal", "median", "sigma", "min", "max"}``,
``{"kind": "uniform", "min", "max"}`` or ``{"kind": "fixed", "value"}``.
``"round_to": r`` rounds each length up to a multiple of ``r`` and
``"short_by": j`` then takes ``i mod j`` tokens off the i-th: the lengths fall
into few prefill buckets (set-up warms one executable a bucket) without all
sitting exactly on a bucket's edge.
Arrivals are ``{"process": "poisson", "rate"}`` (exponential gaps) or
``{"process": "gamma", "rate", "cv"}`` (bursty: gamma gaps with that
coefficient of variation).  With ``"order_seed"`` the FILE fixes which
request follows which gap, and ``--seed`` draws only the token ids and each
request's sampling seed: every seed then offers the same queue.  A tail under
queueing is set by the two or three densest stretches of a window, and
letting the seed reorder even whole eighths of the window made every seed a
different queue (serve_chat at 7.2 req/s: p95 wait 127 to 568 ms over six
seeds, spread 0.90 of the median; my chip runs, PR 24).  Without it the seed
shuffles lengths and gaps freely, which suits a rate or a mean.
"""

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Item:
    due: float              # seconds from the start of the window
    prompt: np.ndarray      # int32 token ids
    new_tokens: int
    do_sample: bool
    temperature: float
    seed: int


def stratified_lengths(dist, n):
    """``n`` integer lengths: the (i + 1/2)/n quantiles of ``dist``, clipped
    to its bounds, ascending."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["kind"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    if kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    x = np.rint(x)
    if "round_to" in dist:
        step = int(dist["round_to"])
        x = np.ceil(x / step) * step - np.arange(n) % int(
            dist.get("short_by", 1))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def stratified_gaps(arrivals, n):
    """``n`` gaps between arrivals whose mean is exactly 1/rate, ascending."""
    u = (np.arange(n) + 0.5) / n
    process = arrivals["process"]
    if process == "poisson":
        g = -np.log1p(-u)
    elif process == "gamma":
        from scipy.stats import gamma
        shape = 1.0 / float(arrivals["cv"]) ** 2
        g = gamma.ppf(u, shape)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    return g / g.mean() / float(arrivals["rate"])


def class_counts(classes, n):
    """How many of ``n`` requests each class gets (largest remainders)."""
    shares = np.array([c.get("share", 1.0) for c in classes], np.float64)
    exact = shares / shares.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(exact - counts)[::-1][:n - counts.sum()]:
        counts[i] += 1
    return counts


def request_shapes(traffic, n, rng):
    """(prompt_len, new_tokens, do_sample, temperature, prefix_group) for
    ``n`` requests over the file's classes, shuffled by ``rng``."""
    rows = []
    for cls, k in zip(traffic["classes"], class_counts(traffic["classes"], n)):
        prompts = rng.permutation(stratified_lengths(cls["prompt_tokens"], k))
        outs = rng.permutation(stratified_lengths(cls["output_tokens"], k))
        sampling = cls.get("sampling", "greedy")
        groups = cls.get("shared_prefix", {}).get("groups", 0)
        for i in range(k):
            sample = {"greedy": False, "sampled": True,
                      "alternate": bool(i % 2)}[sampling]
            rows.append((int(prompts[i]), int(outs[i]), sample,
                         float(cls.get("temperature", 1.0)),
                         (i % groups) if groups else -1, cls))
    return [rows[i] for i in rng.permutation(len(rows))]


def make_items(traffic, n, seed, vocab_size, order_seed=None):
    """``n`` requests, all due at 0.  Their shapes and order come from
    ``order_seed`` when the file fixes one, else from ``seed``; the token
    ids and the sampling seeds always come from ``seed``."""
    shapes = request_shapes(traffic, n, np.random.default_rng(
        [int(seed if order_seed is None else order_seed), 0]))
    rng = np.random.default_rng([int(seed), 3])
    prefixes = {}
    items = []
    for p_len, new, sample, temp, group, cls in shapes:
        prompt = rng.integers(0, vocab_size, size=(p_len,)).astype(np.int32)
        if group >= 0:
            # requests of one group share their first tokens (a system
            # prompt); the rest of each prompt is its own
            shared = cls["shared_prefix"]["tokens"]
            key = (id(cls), group)
            if key not in prefixes:
                prefixes[key] = rng.integers(
                    0, vocab_size, size=(shared,)).astype(np.int32)
            k = min(shared, p_len)
            prompt[:k] = prefixes[key][:k]
        items.append(Item(due=0.0, prompt=prompt, new_tokens=new,
                          do_sample=sample, temperature=temp,
                          seed=int(rng.integers(0, 2 ** 31 - 1))))
    return items


def open_loop_schedule(traffic, seconds, seed, vocab_size):
    """Requests due inside ``[0, seconds)``: ``floor(rate x seconds)`` of
    them, the first at 0, independent of the server."""
    arrivals = traffic["arrivals"]
    n = max(1, math.floor(float(arrivals["rate"]) * seconds))
    order_seed = arrivals.get("order_seed")
    rng = np.random.default_rng(
        [int(seed if order_seed is None else order_seed), 1])
    # gaps[i] is the wait before request i; the first request is due at 0,
    # so whichever gap comes first is the one gap that is not used
    gaps = rng.permutation(stratified_gaps(arrivals, n))
    items = make_items(traffic, n, seed, vocab_size, order_seed=order_seed)
    for it, due in zip(items, np.cumsum(gaps) - gaps[0]):
        it.due = float(due)
    return items


def backlog(traffic, seed, vocab_size):
    """The ``pool_requests`` requests of a closed backlog, all due at 0; the
    runner submits them round and round so the queue never empties."""
    return make_items(traffic, int(traffic["pool_requests"]), seed,
                      vocab_size)


def token_batches(traffic, seed, vocab_size, global_batch):
    """A host pool of ``pool_batches`` seeded token batches of shape
    ``(global_batch, seq + 1)`` for a training cell."""
    rng = np.random.default_rng(int(seed))
    return [rng.integers(0, vocab_size, size=(global_batch,
                                              traffic["seq"] + 1)
                         ).astype(np.int32)
            for _ in range(int(traffic["pool_batches"]))]


def prefill_buckets(items, block_size):
    """The distinct prefill shapes (prompt lengths rounded up to a block)
    the schedule will use: what set-up has to warm, and nothing else."""
    return sorted({-(-len(it.prompt) // block_size) * block_size
                   for it in items})
