"""Paged/block KV cache: a shared device pool + per-sequence block lists.

Role parity: the reference's inference workspace — one pre-allocated
``layer_past`` arena sized for the max batch×seq
(``csrc/transformer/inference/csrc/pt_binding.cpp`` workspace alloc) —
generalized to the continuous-batching serving layer the reference never
shipped: sequences of different lengths share one fixed pool of
``block_size``-token blocks (the vLLM PagedAttention layout), so a slot
holds exactly the blocks its sequence needs and frees them on
completion instead of reserving max_seq tokens per slot.

Device layout (pure pytree — jit-carry/donation friendly):

- ``pool["k"]/["v"]``: (L, num_blocks, block_size, H·hd) in the cache
  dtype, or int8 when the pool is quantized.  Heads are MERGED into
  the minor dim: a TPU tile is 128 lanes wide, so a (…, H, hd=64)
  array can neither be DMA'd a block at a time by the paged-attention
  kernel nor stored without lane padding;
- ``pool["k_scale"]/["v_scale"]`` (int8 pools only): fp32 per-block
  quantization scales, (L, num_blocks, block_size, H·hd//qb) — the
  ``runtime/comm/quantized.py`` block quantizer over the merged dim
  (``qb`` divides ``hd``, so a block never spans two heads).

Block 0 is a reserved SCRATCH block: inactive batch slots carry
all-zero block tables, so their (masked, discarded) decode writes land
in scratch instead of corrupting a live sequence's block.  The
host-side :class:`BlockAllocator` therefore hands out ids from
``[1, num_blocks)``.

XLA cost note (honest roofline accounting, docs/serving.md): the
per-layer ``gather_kv`` materializes each slot's gathered block view —
a dense (B, nb_max·block_size, H, hd) copy per layer per token.  The
in-place Pallas kernel (``ops/transformer/paged_attention.py``, the
default paged-attention impl) deletes that copy by DMA-ing blocks
straight from this pool; ``gather_kv`` stays as the fallback path
(``paged_attention_impl="gather"``) and as the oracle the kernel is
tested bit-exact against (``analysis/roofline.py`` prices whichever
impl is live).
"""

import hashlib
import json
import os
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from .. import fault
from ..runtime.comm.quantized import (quantize_blockwise,
                                      dequantize_blockwise, pick_block)

SCRATCH_BLOCK = 0     # reserved; never allocated (see module docstring)


def blocks_needed(total_tokens: int, block_size: int) -> int:
    """Blocks a sequence of ``total_tokens`` (prompt + max new) occupies."""
    return max(1, -(-int(total_tokens) // int(block_size)))


class WindowFold:
    """The holding of a cache that FOLDS itself (docs/serving.md#folded-cache):
    a stream keeps the exact K/V rows of its current ``window`` tokens, and at
    each window's end those rows are folded ``chunk`` to 1 into summary rows
    of the same shape and given back.  A slot's table is its summary blocks
    (``summary_blocks`` a folded window) followed by its current window's
    blocks (at most ``window_blocks``); stream position ``p`` is table row
    :meth:`row`.  Plain integer arithmetic: every method takes Python ints,
    numpy arrays and traced ``jax.numpy`` arrays alike, so the host's
    admission sum and the compiled step read one rule."""

    def __init__(self, window: int, chunk: int, block_size: int):
        window, chunk, bs = int(window), int(chunk), int(block_size)
        assert window % chunk == 0, (window, chunk)
        self.window, self.chunk, self.block_size = window, chunk, bs
        self.summaries = window // chunk        # rows a folded window keeps
        if self.summaries % bs or window % bs:
            raise ValueError(
                f"block_size {bs}: a window of {window} tokens and its "
                f"{self.summaries} summary rows must both be whole blocks")
        self.summary_blocks = self.summaries // bs
        self.window_blocks = window // bs

    def row(self, p):
        """Table row of stream position ``p``: behind the summaries of the
        windows before its own."""
        return self.summaries * (p // self.window) + p % self.window

    def column(self, p):
        """Table column (block) that position ``p`` is written into."""
        return self.row(p) // self.block_size

    def held(self, n):
        """Blocks a stream of ``n >= 1`` tokens holds when its newest token
        is written, before the fold that token may bring."""
        return self.column(n - 1) + 1

    def charge(self, n):
        """The most blocks the stream holds in the step that brings it to
        ``n`` tokens: a window's end needs the fold's new summary blocks
        BEFORE the window's blocks come home."""
        return self.held(n) + self.summary_blocks * (n % self.window == 0)

    def life_peak(self, total: int) -> int:
        """The most blocks a stream of ``total`` tokens ever holds: the
        fold of its last whole window, or its end if it never folds."""
        ends = int(total) // self.window
        return int(self.charge(ends * self.window) if ends
                   else self.held(total))

    def table_blocks(self, max_seq: int) -> int:
        """Columns a table needs for any stream of up to ``max_seq``
        tokens: the last window full behind every earlier one's summaries."""
        if max_seq <= self.window:
            return blocks_needed(max_seq, self.block_size)
        return int(self.held((int(max_seq) // self.window) * self.window))


def _folded_peak(L, E, fold: WindowFold) -> int:
    """:func:`timeline_peak` for holdings that FALL: a stream holds
    ``fold.charge(n)`` blocks in the step that brings it to ``n`` tokens,
    which drops after a window's end and at its finish alone, so the sum is
    read at every stream's window ends and last step."""
    live = E > L
    L, E = L[live], E[live]
    if not L.size:
        return 0
    W = fold.window
    first = L // W + 1                      # window ends k W in (L, E]
    ks = first[:, None] + np.arange(int((E // W - first).max()) + 1)[None, :]
    ends = ks[ks <= (E // W)[:, None]] * W - np.repeat(
        L, np.maximum(E // W - first + 1, 0)) - 1
    t = np.unique(np.concatenate([E - L - 1, ends]))[:, None]
    blocks = fold.charge(np.minimum(L + t + 1, E))
    return int((blocks * (E - L > t)).sum(axis=1).max())


def timeline_peak(written, ends, held, block_size: int, fold=None) -> int:
    """The most blocks a set of streams will ever hold at one decode step,
    if each runs to its end: the admission rule's one sum
    (docs/serving.md#capacity-math--admission-control).  ``fold``: the
    streams' cache folds itself (:class:`WindowFold`): a stream then holds
    exactly what its length says (``held`` is not read) and the sum is
    :func:`_folded_peak`'s.

    Stream ``i`` has written ``written[i]`` tokens, writes one a step, and
    leaves after ``ends[i]`` at the latest (prompt + ``max_new_tokens``),
    when all its blocks come home; it never holds fewer than the ``held[i]``
    it holds now.  At step ``t`` from now it holds::

        max(held[i], blocks_needed(min(written[i] + t + 1, ends[i])))

    while ``t < ends[i] - written[i]``, and nothing after.  The sum only
    rises between finishes, so its peak stands at the last step of some
    stream: one row of sums a stream, no walk over the steps."""
    L = np.asarray(written, np.int64)
    E = np.asarray(ends, np.int64)
    if fold is not None:
        return _folded_peak(L, E, fold)
    left = E - L                           # <= 0: no stream (an empty slot)
    t = left[left > 0, None] - 1           # the last step of each stream
    if not t.size:
        return 0
    blocks = np.maximum(-(-np.minimum(L + t + 1, E) // int(block_size)),
                        np.asarray(held, np.int64))
    return int((blocks * (left > t)).sum(axis=1).max())


class BlockAllocator:
    """Host-side free-list over pool block ids ``[1, num_blocks)``.

    Allocation is all-or-nothing (a request either gets every block its
    admission math asked for, or is left queued); ``free`` returns
    blocks for reuse in LIFO order so hot blocks stay hot.

    Every in-use block carries a **refcount** (PR 19, prefix sharing):
    ``alloc`` hands a block out at refcount 1, each additional holder —
    a co-tenant reading a shared prefix, or the :class:`PrefixIndex`'s
    own cache reference — goes through :meth:`incref`, and ``free``
    *decrements*: a block returns to the free list only when its last
    holder lets go.  ``free`` therefore returns the list of block ids
    it actually released, so callers (and the shadow sanitizer's
    ``on_free``) see physical releases, never logical decrefs.
    """

    def __init__(self, num_blocks: int):
        assert num_blocks >= 2, \
            "need >= 2 blocks (block 0 is the reserved scratch block)"
        self.num_blocks = int(num_blocks)
        self._free = list(range(self.num_blocks - 1, SCRATCH_BLOCK, -1))
        self._in_use = set()
        self._refs = {}     # block id -> holder count (in-use blocks only)

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """UNIQUE blocks checked out (physical residency)."""
        return len(self._in_use)

    @property
    def shared_blocks(self) -> int:
        """Blocks with two or more holders (kv-block FSM ``shared``)."""
        return sum(1 for c in self._refs.values() if c >= 2)

    @property
    def logical_blocks(self) -> int:
        """Sum of refcounts — what residency WOULD cost without
        sharing; ``logical - used`` is the pool's sharing dividend."""
        return sum(self._refs.values())

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def is_allocated(self, b: int) -> bool:
        """True while ``b`` is checked out (kv-block FSM: allocated or
        quarantined) — the exception-path cleanup probe, so recovery
        code never guesses at the free list's contents."""
        return b in self._in_use

    def refcount(self, b: int) -> int:
        """Holder count of ``b`` (0 when free)."""
        return self._refs.get(b, 0)

    def alloc(self, n: int):
        """``n`` block ids, or None when the pool cannot serve them."""
        if n < 1 or n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        self._in_use.update(out)
        for b in out:
            self._refs[b] = 1
        return out

    def incref(self, blocks):
        """Add one holder to each of ``blocks`` (kv-block FSM allocated
        -> shared).  Only checked-out blocks can gain holders — an
        incref of a free block would resurrect reclaimed storage."""
        blocks = list(blocks)
        for b in blocks:
            if b not in self._in_use:
                raise ValueError(
                    f"incref of block {b} which is not in use — only "
                    "allocated blocks can be shared")
        for b in blocks:
            self._refs[b] += 1

    def free(self, blocks):
        """Drop one holder from each block; return the ids actually
        RELEASED to the free list (refcount hit zero).  Rejections are
        real exceptions, not asserts: a double free or a free of the
        reserved scratch block is silent pool corruption (two tenants
        writing one block) and must fail under ``python -O`` too — the
        DSTPU3xx lifecycle audit's kv-block FSM says only 'allocated'
        blocks may return to 'free'."""
        blocks = list(blocks)
        seen = set()
        for b in blocks:
            if b == SCRATCH_BLOCK:
                raise ValueError(
                    f"free of reserved scratch block {SCRATCH_BLOCK} — "
                    "it is never allocated and never freed")
            if b not in self._in_use or b in seen:
                raise ValueError(
                    f"double free of block {b} (not in use; kv-block "
                    "FSM allows free only from 'allocated')")
            seen.add(b)
        released = []
        for b in blocks:
            self._refs[b] -= 1
            if self._refs[b] > 0:
                continue
            del self._refs[b]
            self._in_use.discard(b)
            self._free.append(b)
            released.append(b)
        return released


# ------------------------------------------------- prefix cache (radix)
def block_key(parent_key, tokens) -> str:
    """Chained content hash of one FULL token block: SHA-256 over the
    parent block's key bytes + this block's int32 token bytes.  The
    chaining makes the key position-dependent — two identical token
    blocks under different prefixes hash apart — so one flat dict IS a
    radix tree: looking up block i's key implies every ancestor block
    matched.  Keys are adapter-neutral by construction: only token ids
    enter the hash, so any state that changes the K/V for the same
    tokens (a LoRA adapter, a different model) must key a separate
    PrefixIndex."""
    h = hashlib.sha256()
    if parent_key is not None:
        h.update(parent_key.encode("ascii"))
    h.update(np.ascontiguousarray(np.asarray(tokens, np.int32)).tobytes())
    return h.hexdigest()


def prefix_block_keys(tokens, block_size: int) -> list:
    """Chained :func:`block_key` sequence over every FULL block of a
    token prefix — the content identity a transfer seat record carries
    so the decode side can VERIFY a local radix match against the
    prefill side's view before re-sharing (two engines hashing the same
    tokens produce the same chain by construction)."""
    toks = np.asarray(tokens, np.int32).reshape(-1)
    bs = int(block_size)
    keys, parent = [], None
    for i in range(toks.size // bs):
        parent = block_key(parent, toks[i * bs:(i + 1) * bs])
        keys.append(parent)
    return keys


class PrefixIndex:
    """Block-granular radix cache over a :class:`BlockAllocator`.

    Maps chained content keys (:func:`block_key`) of FULL prompt blocks
    to pool block ids holding their K/V.  The index owns ONE refcount
    on every block it lists (taken via ``allocator.incref`` at insert,
    dropped via ``allocator.free`` at evict), so a cached block
    survives its inserting sequence and is reclaimed only when both
    the cache and every live reader have let go.

    Collision discipline: the full token content of each block rides in
    the entry and every lookup compares it — a SHA-256 collision (or a
    test forcing one) degrades to a cache MISS, never to serving
    another prefix's K/V.

    Eviction is LRU over **leaf** entries (no cached children) whose
    block has no live reader (refcount exactly 1 — the cache's own);
    peeling leaves repeatedly reclaims whole cold chains while a hot
    chain's interior blocks stay pinned by their children.
    """

    def __init__(self, allocator: "BlockAllocator", *, max_blocks: int = 0):
        self.allocator = allocator
        self.max_blocks = int(max_blocks)   # 0 = pool-pressure-only
        self._entries = {}   # key -> {block, tokens, parent, children}
        self._by_block = {}  # block id -> key
        self._lru = {}       # key -> None; dict order = LRU (oldest first)
        self.hits = 0            # full-block lookup hits
        self.lookups = 0         # full-block lookup attempts
        self.collisions = 0      # hash matched, token content did not
        self.inserted = 0
        self.evicted = 0

    def __len__(self):
        return len(self._entries)

    @property
    def cached_blocks(self) -> int:
        return len(self._entries)

    def holds(self, block: int) -> bool:
        """True while the cache holds its reference on ``block``."""
        return int(block) in self._by_block

    def _touch(self, key):
        self._lru.pop(key, None)
        self._lru[key] = None

    # ------------------------------------------------------------ match
    def match(self, tokens, block_size: int, limit_blocks=None):
        """Longest cached prefix of ``tokens`` at block granularity.

        Walks full ``block_size``-token chunks down the radix chain,
        content-verifying every hit.  Returns a dict:

        - ``blocks``: pool block ids of the matched prefix, in order
          (NOT incref'd — the caller decides to take the share);
        - ``keys``: their chain keys (parents for a later insert);
        - ``donor``: ``(block_id, shared_tokens)`` for copy-on-write
          when the first unmatched chunk shares ``shared_tokens >= 1``
          leading tokens with a cached sibling, else None.

        ``limit_blocks`` caps the match (the caller's write-safety
        clamp: positions the sequence will still WRITE must land in
        private blocks)."""
        tokens = np.asarray(tokens, np.int64).tolist()
        bs = int(block_size)
        nb_full = len(tokens) // bs
        if limit_blocks is not None:
            nb_full = min(nb_full, max(0, int(limit_blocks)))
        blocks, keys = [], []
        parent = None
        stopped_i = 0
        for i in range(nb_full):
            chunk = tuple(tokens[i * bs:(i + 1) * bs])
            key = block_key(parent, chunk)
            self.lookups += 1
            ent = self._entries.get(key)
            if ent is None:
                stopped_i = i
                break
            if ent["tokens"] != chunk:
                # hash collision: full-content check demotes to a miss
                self.collisions += 1
                stopped_i = i
                break
            self.hits += 1
            self._touch(key)
            blocks.append(ent["block"])
            keys.append(key)
            parent = key
            stopped_i = i + 1
        donor = None
        # COW donor: a cached child of the last matched parent whose
        # content shares >= 1 leading token with our divergent chunk
        chunk = tuple(tokens[stopped_i * bs:(stopped_i + 1) * bs])
        if chunk:
            best = 0
            for ck in self._children(parent):
                ent = self._entries.get(ck)
                if ent is None:
                    continue
                j = 0
                for a, b in zip(ent["tokens"], chunk):
                    if a != b:
                        break
                    j += 1
                # j may equal len(chunk): a clamped or tail chunk whose
                # cached sibling matches it fully still COWs (the
                # caller re-ingests only the write-clamped positions)
                if 0 < j and j > best:
                    best, donor = j, (ent["block"], j)
        return {"blocks": blocks, "keys": keys, "donor": donor}

    def _children(self, parent_key):
        if parent_key is None:
            return [k for k, e in self._entries.items()
                    if e["parent"] is None]
        ent = self._entries.get(parent_key)
        return sorted(ent["children"]) if ent else []

    # ----------------------------------------------------------- insert
    def insert(self, parent_key, tokens, block: int):
        """Index ``block`` (holding the K/V of full block ``tokens``
        chained under ``parent_key``) and take the cache's refcount on
        it.  Returns the chain key, or None when the entry was not
        inserted (true hash collision — first writer wins, content
        check keeps lookups safe — or an uncachable block).

        A key that already exists with the SAME content dedupes: the
        existing entry (and its block) stays authoritative, the
        caller's physical block keeps only its own holders."""
        block = int(block)
        if block == SCRATCH_BLOCK:
            return None
        tokens = tuple(np.asarray(tokens, np.int64).tolist())
        key = block_key(parent_key, tokens)
        ent = self._entries.get(key)
        if ent is not None:
            if ent["tokens"] != tokens:
                self.collisions += 1
                return None
            self._touch(key)
            return key
        if parent_key is not None and parent_key not in self._entries:
            return None     # parent evicted mid-walk: chain is broken
        if self.max_blocks > 0 and len(self._entries) >= self.max_blocks:
            if not self.evict(1 + len(self._entries) - self.max_blocks):
                return None     # everything referenced: nothing to evict
        self.allocator.incref([block])
        self._entries[key] = {"block": block, "tokens": tokens,
                              "parent": parent_key, "children": set()}
        self._by_block[block] = key
        if parent_key is not None:
            self._entries[parent_key]["children"].add(key)
        self._touch(key)
        self.inserted += 1
        return key

    # ---------------------------------------------------------- evict
    def _drop_entry(self, key):
        ent = self._entries.pop(key)
        self._lru.pop(key, None)
        self._by_block.pop(ent["block"], None)
        if ent["parent"] is not None:
            par = self._entries.get(ent["parent"])
            if par is not None:
                par["children"].discard(key)
        return ent

    def evict(self, want: int = 1):
        """Reclaim up to ``want`` cached blocks, LRU-first, restricted
        to LEAF entries with no live reader (refcount exactly 1 — the
        cache's own reference).  A referenced block is NEVER reclaimed.
        Returns the pool block ids actually released."""
        released = []
        progress = True
        while len(released) < int(want) and progress:
            progress = False
            for key in list(self._lru):
                ent = self._entries.get(key)
                if ent is None or ent["children"]:
                    continue
                if self.allocator.refcount(ent["block"]) != 1:
                    continue    # a live sequence still reads it
                self._drop_entry(key)
                released.extend(self.allocator.free([ent["block"]]))
                self.evicted += 1
                progress = True
                break
        return released

    def clear(self):
        """Drop every cache reference (engine close / pool teardown).
        Returns ``(dropped, released)``: all block ids the cache held,
        and the subset physically released (no surviving holder)."""
        dropped = list(self._by_block)
        released = []
        for key in list(self._entries):
            ent = self._entries.pop(key)
            self._lru.pop(key, None)
            self._by_block.pop(ent["block"], None)
            released.extend(self.allocator.free([ent["block"]]))
        return dropped, released

    # ----------------------------------------------------------- stats
    def stats(self) -> dict:
        return {"entries": len(self._entries),
                "hits": self.hits, "lookups": self.lookups,
                "hit_rate": (self.hits / self.lookups
                             if self.lookups else 0.0),
                "collisions": self.collisions,
                "inserted": self.inserted, "evicted": self.evicted}


# ------------------------------------------------------------- device pool
def init_pool(n_layer: int, num_blocks: int, block_size: int, n_head: int,
              head_dim: int, dtype=jnp.bfloat16, kv_bits: int = 16,
              quant_block: int = 64, n_kv_head: Optional[int] = None):
    """Zeroed pool pytree (see module docstring for the layout).

    ``kv_bits=8`` stores int8 payloads + fp32 block scales over the head
    dim (``quant_block`` clipped to a divisor of ``head_dim``).

    ``n_kv_head`` (default ``n_head``: multi-head) is the number of K/V
    heads a token keeps; the pool's minor dim is ``n_kv_head * head_dim``.
    Grouped and multi-query models keep fewer K/V heads than they have
    query heads, and ``n_layer`` counts their ATTENTION layers only."""
    assert kv_bits in (8, 16), f"kv_bits must be 8 or 16, got {kv_bits}"
    if n_kv_head is None:
        n_kv_head = n_head
    assert n_head % n_kv_head == 0, (n_head, n_kv_head)
    shape = (n_layer, num_blocks, block_size, n_kv_head * head_dim)
    if kv_bits == 16:
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    qb = pick_block(head_dim, quant_block)
    sshape = shape[:-1] + (shape[-1] // qb,)
    return {"k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            # scale 1 ≡ the quantizer's all-zero-block convention
            "k_scale": jnp.ones(sshape, jnp.float32),
            "v_scale": jnp.ones(sshape, jnp.float32)}


def is_quantized_pool(pool) -> bool:
    return "k_scale" in pool


def pool_quant_block(pool) -> Optional[int]:
    """The int8 pool's quantization block over the merged head dim (None
    for a full-width pool)."""
    if not is_quantized_pool(pool):
        return None
    return pool["k"].shape[-1] // pool["k_scale"].shape[-1]


def pool_bytes(pool) -> int:
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(pool))


def capacity_tokens(pool) -> int:
    """Token capacity of the allocatable pool (scratch block excluded)."""
    _, num_blocks, block_size, _ = pool[payload_names(pool)[0]].shape
    return (num_blocks - 1) * block_size


def _merge_heads(x):
    """(…, H, hd) → (…, H·hd): the pool's minor dim."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def _window_slots(block_tables, lengths, W, bs, ring=False):
    """``(block ids, offsets)``, each (B, W): where window token i of slot b
    (position ``lengths[b] + i``) lands.  A position past the table goes to
    the scratch block (:func:`write_tokens` says why); in a ``ring`` table
    (:func:`ring_blocks`) no position is past it."""
    nb_max = block_tables.shape[1]
    pos = lengths[:, None] + jnp.arange(W, dtype=lengths.dtype)[None, :]
    idx = pos // bs                                        # (B, W)
    if ring:
        return jnp.take_along_axis(block_tables, idx % nb_max, axis=1), \
            pos % bs
    blk = jnp.take_along_axis(block_tables,
                              jnp.minimum(idx, nb_max - 1), axis=1)
    return jnp.where(idx < nb_max, blk, SCRATCH_BLOCK), pos % bs


def write_tokens(pool, layer, block_tables, lengths, k, v, ring=False):
    """Scatter a W-token decode window's K/V per slot into the pool.

    ``layer``: scalar (traced inside the layer scan); ``block_tables``:
    (B, nb_max) int32; ``lengths``: (B,) int32 — the FIRST window
    token's position (window token i lands at ``lengths + i``);
    ``k``/``v``: (B, W, H, hd) in compute dtype, ``H`` the pool's K/V
    heads (W=1 is the serving decode step; no caller in the product passes
    more, ROADMAP D17).  Slots whose tables are
    all-scratch write into block 0 (discarded), and a window position
    that overflows the table (a window running past the slot's
    allocation) is REDIRECTED to the scratch block instead of
    letting the gather clamp silently overwrite the table's last real
    block — any token whose logits depend on such a position is beyond
    ``max_new`` and truncated by the scheduler anyway.  ``ring``: the
    tables are rings (:func:`ring_blocks`), position ``p`` in entry
    ``(p // block_size) % nb_max``."""
    blk, off = _window_slots(block_tables, lengths, k.shape[1],
                             pool["k"].shape[2], ring=ring)
    k, v = _merge_heads(k), _merge_heads(v)
    if not is_quantized_pool(pool):
        dt = pool["k"].dtype
        return dict(pool,
                    k=pool["k"].at[layer, blk, off].set(k.astype(dt)),
                    v=pool["v"].at[layer, blk, off].set(v.astype(dt)))
    qb = pool_quant_block(pool)
    qk, sk = quantize_blockwise(k, block_size=qb, bits=8)
    qv, sv = quantize_blockwise(v, block_size=qb, bits=8)
    return dict(pool,
                k=pool["k"].at[layer, blk, off].set(qk),
                v=pool["v"].at[layer, blk, off].set(qv),
                k_scale=pool["k_scale"].at[layer, blk, off].set(sk),
                v_scale=pool["v_scale"].at[layer, blk, off].set(sv))


def write_token(pool, layer, block_tables, lengths, k, v):
    """Single-token :func:`write_tokens` (``k``/``v``: (B, H, hd))."""
    return write_tokens(pool, layer, block_tables, lengths,
                        k[:, None], v[:, None])


def gather_kv(pool, layer, block_tables, dtype, n_head):
    """Per-slot gathered cache views for one layer — the legacy/fallback
    paged-attention path AND the oracle the in-place Pallas kernel
    (``ops/transformer/paged_attention.py``) is tested against.

    ``dtype`` is the attention compute dtype and is REQUIRED: both this
    path and the kernel resolve it in one place
    (``GPT2.decode_step_paged`` passes the model compute dtype), so
    int8 pools dequantize identically on either route — a defaulted
    dtype here let a caller's fp16 model silently read bf16 views.

    Returns ``(keys, vals)`` of shape (B, nb_max·block_size, H, hd) in
    ``dtype`` (``n_head`` splits the pool's merged minor dim: the pool's
    K/V heads, which a grouped or multi-query caller repeats to its query
    heads) — position
    p of slot b is row p of its view, so the caller's causal mask over
    ``lengths`` is layout-independent."""
    def view(name):
        x = pool[name][layer][block_tables]     # (B, nb, bs, H·hd)
        B, nb, bs, HD = x.shape
        x = x.reshape(B, nb * bs, HD)
        if is_quantized_pool(pool):
            s = pool[name + "_scale"][layer][block_tables]
            x = dequantize_blockwise(x, s.reshape(B, nb * bs, -1), bits=8,
                                     out_dtype=dtype)
        return x.astype(dtype).reshape(B, nb * bs, n_head, HD // n_head)
    return view("k"), view("v")


def write_prefill(pool, blocks, k, v, layer=None):
    """Scatter a prefilled sequence's K/V into its assigned blocks.

    ``blocks``: (nb,) int32 block ids; ``k``/``v``: (L, T, H, hd), ``L``
    and ``H`` the pool's layers and K/V heads, with
    ``T == nb · block_size`` (the prompt padded up to a block multiple —
    pad rows are masked by the slot's length at attention time).

    With ``layer`` (a scalar, traced inside a layer scan) ``k``/``v`` are
    ONE layer's, (T, H, hd): a model whose pool has more layers than it can
    hold a prompt's K/V for at once writes each as it is computed."""
    T = k.shape[-3]
    bs = pool["k"].shape[2]
    nb = T // bs
    assert nb * bs == T, f"prefill length {T} is not a multiple of {bs}"
    assert blocks.shape == (nb,), (
        f"write_prefill needs exactly T//block_size={nb} block ids, got "
        f"{blocks.shape} (pass the sequence's FIRST nb blocks; later "
        "blocks fill during decode)")
    assert k.ndim == (4 if layer is None else 3), (k.shape, layer)

    def put(name, x):
        x = x.reshape(x.shape[:-2] + (nb, bs, x.shape[-1]))
        at = pool[name].at
        return (at[:, blocks] if layer is None else at[layer, blocks]).set(x)

    k, v = _merge_heads(k), _merge_heads(v)
    if not is_quantized_pool(pool):
        dt = pool["k"].dtype
        return dict(pool, k=put("k", k.astype(dt)), v=put("v", v.astype(dt)))
    qb = pool_quant_block(pool)
    qk, sk = quantize_blockwise(k, block_size=qb, bits=8)
    qv, sv = quantize_blockwise(v, block_size=qb, bits=8)
    return dict(pool, k=put("k", qk), v=put("v", qv),
                k_scale=put("k_scale", sk), v_scale=put("v_scale", sv))


# -------------------------------------------------- block images (migration)
# A *block image* is one sequence's block list serialized in the PR-8
# wire format — int8 payloads + fp32 block scales over the head dim —
# so an in-flight decode's KV state can move between workers
# (docs/serving.md#kv-migration).  int8 pools export by PASS-THROUGH
# (bit-exact, so a restored stream re-decodes token-identically);
# full-width pools quantize on export and dequantize on import (wire
# precision, the same trade the comms compressor makes).  Per-block
# SHA-256 digests ride in the image so corruption is pinned to a block,
# and the on-disk form commits through the ``checkpoint/atomic.py``
# stage/manifest/rename protocol: a torn write is detectable, never
# restorable.

IMAGE_FILE = "image.npz"
IMAGE_HEAD_FILE = "image.json"


class BlockImageError(RuntimeError):
    """A block image failed validation (torn, corrupt, or wrong
    geometry) — the caller must fall back to recompute, never restore."""


def _block_digests(k, v, k_scale, v_scale):
    """Per-block SHA-256 over the payload AND scale bytes of each block
    (axis 1 of every image array)."""
    out = []
    for i in range(k.shape[1]):
        h = hashlib.sha256()
        for arr in (k, v, k_scale, v_scale):
            h.update(np.ascontiguousarray(arr[:, i]).tobytes())
        out.append(h.hexdigest())
    return out


def export_block_image(pool, blocks, quant_block: int = 64) -> dict:
    """Serialize ``blocks`` (one sequence's block list) as an in-memory
    int8+scales image — host numpy arrays of shape (L, nb, bs, H·hd)
    plus (L, nb, bs, H·hd//qb) scales (the pool's own layout),
    per-block digests, and the geometry needed to validate an import.
    ``quant_block`` must divide the head dim for a full-width pool."""
    idx = jnp.asarray(np.asarray(blocks, np.int32))
    if is_quantized_pool(pool):
        qb = pool_quant_block(pool)
        qk, sk = pool["k"][:, idx], pool["k_scale"][:, idx]
        qv, sv = pool["v"][:, idx], pool["v_scale"][:, idx]
    else:
        qb = pick_block(pool["k"].shape[-1], quant_block)
        qk, sk = quantize_blockwise(pool["k"][:, idx], block_size=qb, bits=8)
        qv, sv = quantize_blockwise(pool["v"][:, idx], block_size=qb, bits=8)
    qk, sk, qv, sv = (np.asarray(jax.device_get(x))
                      for x in (qk, sk, qv, sv))
    return {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv,
            "quant_block": int(qb),
            "source_bits": 8 if is_quantized_pool(pool) else 16,
            "block_sha256": _block_digests(qk, qv, sk, sv)}


def verify_block_image(image) -> list:
    """Indices (into the image's block axis) whose bytes no longer match
    their recorded digest — empty for a healthy image."""
    fresh = _block_digests(image["k"], image["v"],
                           image["k_scale"], image["v_scale"])
    return [i for i, (a, b) in enumerate(zip(fresh, image["block_sha256"]))
            if a != b]


def import_block_image(pool, blocks, image, pad_to=None):
    """Scatter a verified image into ``blocks`` of ``pool`` (the
    :func:`write_prefill` idiom), returning the new pool.

    int8 pools take the payloads and scales verbatim (requires the same
    ``quant_block``); full-width pools dequantize to the pool dtype.
    Geometry or digest mismatches raise :class:`BlockImageError` — a
    bad image must degrade to recompute, never scatter garbage.

    ``pad_to`` pads the scatter to a fixed block count (extra lanes
    write zeros into :data:`SCRATCH_BLOCK`, garbage by design), so one
    XLA compile serves every restore regardless of stream depth — the
    specialization on ``len(blocks)`` otherwise puts a fresh trace
    (~100-650 ms) inside each first-of-its-size restore window."""
    k = image["k"]
    L, nb = k.shape[:2]
    pshape = pool["k"].shape
    if k.ndim != 4 or (L,) + k.shape[2:] != (pshape[0],) + pshape[2:]:
        raise BlockImageError(
            f"image geometry {k.shape} does not match pool {pshape} "
            "(layers, block size and merged head dim must agree)")
    if len(blocks) != nb:
        raise BlockImageError(
            f"image holds {nb} blocks, import got {len(blocks)} ids")
    bad = verify_block_image(image)
    if bad:
        raise BlockImageError(f"block digest mismatch at image block(s) "
                              f"{bad} — refusing to restore")
    pad = max(0, int(pad_to or 0) - nb)
    idx = jnp.asarray(np.concatenate(
        [np.asarray(blocks, np.int32),
         np.full((pad,), SCRATCH_BLOCK, np.int32)]))

    def _pad(x):
        # host-side, BEFORE any device op: padding on device would
        # re-specialize the very compiles pad_to exists to pin
        x = np.asarray(x)
        if pad:
            x = np.concatenate(
                [x, np.zeros((L, pad) + x.shape[2:], x.dtype)], axis=1)
        return x

    def put(name, x):
        return pool[name].at[:, idx].set(jnp.asarray(x))

    if is_quantized_pool(pool):
        if pool_quant_block(pool) != int(image["quant_block"]):
            raise BlockImageError(
                f"image quant_block {image['quant_block']} != pool "
                f"{pool_quant_block(pool)}")
        return dict(pool, k=put("k", _pad(image["k"])),
                    v=put("v", _pad(image["v"])),
                    k_scale=put("k_scale", _pad(image["k_scale"])),
                    v_scale=put("v_scale", _pad(image["v_scale"])))
    dt = pool["k"].dtype
    dk = dequantize_blockwise(jnp.asarray(_pad(image["k"])),
                              jnp.asarray(_pad(image["k_scale"])),
                              bits=8, out_dtype=dt)
    dv = dequantize_blockwise(jnp.asarray(_pad(image["v"])),
                              jnp.asarray(_pad(image["v_scale"])),
                              bits=8, out_dtype=dt)
    return dict(pool, k=put("k", dk), v=put("v", dv))


def save_block_image(save_dir: str, tag: str, image: dict,
                     meta: Optional[dict] = None) -> str:
    """Commit ``image`` as ``<save_dir>/<tag>/`` via the atomic
    checkpoint protocol: stage ``image.npz`` + ``image.json``, manifest
    (per-file sha256), one publish rename.  Returns the committed dir.

    Fault sites: ``serving.kv_snapshot_torn`` fires between staging and
    commit (a kill there leaves an invisible ``.tmp``);
    ``serving.kv_image_corrupt`` (a ``corrupt_at=`` VALUE fault) flips a
    committed payload byte — bit rot the restore digests must catch."""
    from ..checkpoint import atomic
    import shutil
    os.makedirs(save_dir, exist_ok=True)
    stage = atomic.stage_path(save_dir, tag)
    if os.path.isdir(stage):
        shutil.rmtree(stage)
    os.makedirs(stage)
    np.savez(os.path.join(stage, IMAGE_FILE),
             k=image["k"], v=image["v"],
             k_scale=image["k_scale"], v_scale=image["v_scale"])
    head = {"quant_block": int(image["quant_block"]),
            "source_bits": int(image["source_bits"]),
            "shape": list(image["k"].shape),
            "block_sha256": list(image["block_sha256"])}
    with open(os.path.join(stage, IMAGE_HEAD_FILE), "w") as f:
        json.dump(head, f)  # dstpu: disable=DSTPU104 (wire format, not metrics)
    fault.site("serving.kv_snapshot_torn", path=stage)
    atomic.write_manifest(stage, meta or {})
    atomic.commit_staged(save_dir, tag)
    final = os.path.join(save_dir, str(tag))
    if fault.corrupt_at("serving.kv_image_corrupt"):
        payload = os.path.join(final, IMAGE_FILE)
        with open(payload, "r+b") as f:
            f.seek(os.path.getsize(payload) // 2)
            b = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([b[0] ^ 0xFF]))
    return final


def load_block_image(ckpt_dir: str, verify: str = "full"):
    """Load a committed image dir back into the in-memory form, raising
    :class:`BlockImageError` unless the manifest verifies at ``verify``
    level AND every per-block digest matches.  Returns
    ``(image, manifest_meta)``."""
    from ..checkpoint import atomic
    ok, problems = atomic.verify_checkpoint(ckpt_dir, level=verify)
    if not ok:
        raise BlockImageError(
            f"image manifest failed verification: {problems}")
    manifest = atomic.read_manifest(ckpt_dir) or {}
    try:
        with open(os.path.join(ckpt_dir, IMAGE_HEAD_FILE)) as f:
            head = json.load(f)
        with np.load(os.path.join(ckpt_dir, IMAGE_FILE)) as z:
            image = {name: z[name] for name in
                     ("k", "v", "k_scale", "v_scale")}
    except Exception as e:  # torn zip / missing file / bad json
        raise BlockImageError(f"unreadable image in {ckpt_dir}: {e}") from e
    image.update(quant_block=head["quant_block"],
                 source_bits=head["source_bits"],
                 block_sha256=head["block_sha256"])
    bad = verify_block_image(image)
    if bad:
        raise BlockImageError(f"block digest mismatch at image block(s) "
                              f"{bad} in {ckpt_dir}")
    return image, manifest.get("meta", {})


# ------------------------------------------------------------- latent pool
# A model with latent attention (MLA: ``models/deepseek_v2.py``) caches, a
# token and a layer, ONE row shared by every query head: the compressed
# ``c_kv`` (``kv_lora_rank`` values, after its norm) and the rotated
# ``k_pe`` (``rope_dim`` values).  The row is K whole and V in its first
# ``kv_lora_rank`` columns: the same bytes, read once.
#
# THE LAYOUT, stated here once: ONE leaf ``pool["latent"]`` of shape
# ``(L, num_blocks, block_size, row)``, ``row`` the row's values rounded up
# to whole 128-lane tiles (512 + 64 -> 640: columns 0..511 ``c_kv``, 512..575
# ``k_pe``, 576..639 zero), because Mosaic DMAs and slices whole tiles
# (ROADMAP D11).  The zero columns meet zero columns of the query, so the
# scores are exact; they cost 64 of 640 values of every read.  16-bit only.
LATENT = "latent"


def payload_names(pool) -> tuple:
    """The pool's K/V payload leaves: ``("k", "v")``, or the one latent
    leaf."""
    return (LATENT,) if LATENT in pool else ("k", "v")


def is_latent_pool(pool) -> bool:
    return LATENT in pool


def latent_row_width(kv_lora_rank: int, rope_dim: int) -> int:
    """Values a stored latent row has: whole 128-lane tiles."""
    return -(-(kv_lora_rank + rope_dim) // 128) * 128


def init_latent_pool(n_layer: int, num_blocks: int, block_size: int,
                     kv_lora_rank: int, rope_dim: int, dtype=jnp.bfloat16):
    """Zeroed latent pool (layout above)."""
    return {LATENT: jnp.zeros(
        (n_layer, num_blocks, block_size,
         latent_row_width(kv_lora_rank, rope_dim)), dtype)}


def latent_row_bytes(pool) -> int:
    """Bytes one token keeps in ONE layer, as stored."""
    x = pool[LATENT]
    return int(x.shape[-1]) * x.dtype.itemsize


def latent_rows(c_kv, k_pe, width):
    """``[c_kv | k_pe | 0]``: (..., width) rows as the pool stores them (and
    an absorbed query, ``[q_lat | q_pe | 0]``, that meets them)."""
    pad = width - c_kv.shape[-1] - k_pe.shape[-1]
    return jnp.concatenate(
        [c_kv, k_pe, jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype)], -1)


def write_latent_tokens(pool, layer, block_tables, lengths, rows):
    """:func:`write_tokens` for a latent pool: ``rows`` (B, W, row), window
    token i of slot b at position ``lengths[b] + i``; an empty slot's row
    lands in the scratch block, as does a position past the table."""
    x = pool[LATENT]
    blk, off = _window_slots(block_tables, lengths, rows.shape[1],
                             x.shape[2])
    return dict(pool, **{LATENT: x.at[layer, blk, off].set(
        rows.astype(x.dtype))})


def write_latent_prefill(pool, blocks, rows, layer):
    """:func:`write_prefill` for a latent pool, one layer at a time:
    ``rows`` (T, row) with ``T == len(blocks) * block_size``."""
    x = pool[LATENT]
    bs = x.shape[2]
    nb = rows.shape[0] // bs
    assert nb * bs == rows.shape[0] and blocks.shape == (nb,), (
        rows.shape, blocks.shape, bs)
    return dict(pool, **{LATENT: x.at[layer, blocks].set(
        rows.reshape(nb, bs, rows.shape[-1]).astype(x.dtype))})


def gather_latent(pool, layer, block_tables, dtype):
    """Per-slot gathered rows of one layer, (B, nb_max * block_size, row):
    the fallback path and the oracle of the latent kernel
    (``ops/transformer/paged_latent_attention.py``)."""
    x = pool[LATENT][layer][block_tables]          # (B, nb, bs, row)
    B, nb, bs, row = x.shape
    return x.reshape(B, nb * bs, row).astype(dtype)


# ------------------------------------------------------------- window pool
# A model with SLIDING-WINDOW layers beside global ones (``models/afmoe.py``)
# keeps two kinds of block in one serving state: the ``k`` / ``v`` leaves over
# its global layers, whose tables grow with the stream, and the ``wk`` / ``wv``
# leaves over its window layers, whose tables are RINGS: a window layer reads
# the last ``window`` positions and no more, so a stream holds at most
# :func:`ring_blocks` of them and position ``p`` lives in entry ``(p //
# block_size) % ring`` of its table, a block reused when the window has slid
# past what it held.  A stream shorter than the ring holds only the blocks
# its own tokens fill.  Each kind has its own :class:`BlockAllocator`, its
# own scratch block 0, and its own count of blocks.
WINDOW = ("wk", "wv")


def ring_blocks(window: int, block_size: int) -> int:
    """Entries of a window layer's ring: the blocks that ``window``
    consecutive positions can touch (the window behind the token being
    written, and that token's own block)."""
    return -(-(int(window) - 1) // int(block_size)) + 1


def init_window_pool(n_layer, num_blocks, block_size, n_kv_head, head_dim,
                     dtype=jnp.bfloat16):
    """Zeroed ``wk`` / ``wv`` leaves (16-bit; the layout of ``k`` / ``v``)."""
    shape = (n_layer, num_blocks, block_size, n_kv_head * head_dim)
    return {name: jnp.zeros(shape, dtype) for name in WINDOW}


def window_view(pool):
    """The window leaves under the names every ``k`` / ``v`` function and
    the paged kernel take; :func:`with_window` puts a written view back."""
    return {"k": pool[WINDOW[0]], "v": pool[WINDOW[1]]}


def with_window(pool, view):
    return dict(pool, **{WINDOW[0]: view["k"], WINDOW[1]: view["v"]})


def write_prefill_ring(view, ring_table, k, v, layer, t_real):
    """Seat a prompt's LAST blocks in a window layer's ring: ``k`` / ``v``
    (T, H, hd) with ``T`` a block multiple, ``ring_table`` (ring,) the
    stream's ring (scratch where it holds no block), ``t_real`` the prompt's
    true length.  The ``min(T // block_size, ring)`` blocks that end at the
    one holding token ``t_real - 1`` are written, each at its ring entry:
    everything position ``t_real`` and later can still see."""
    bs = view["k"].shape[2]
    ring = ring_table.shape[0]
    nb = k.shape[0] // bs
    n = min(nb, ring)
    first = jnp.clip((t_real - 1) // bs - (n - 1), 0, nb - n)   # first block
    entries = ring_table[(first + jnp.arange(n)) % ring]

    def put(x, rows):
        rows = jax.lax.dynamic_slice_in_dim(_merge_heads(rows), first * bs,
                                            n * bs, axis=0)
        return x.at[layer, entries].set(
            rows.reshape(n, bs, rows.shape[-1]).astype(x.dtype))
    return {"k": put(view["k"], k), "v": put(view["v"], v)}


def gather_ring(view, layer, ring_tables, lengths, dtype, n_head):
    """:func:`gather_kv` over ring tables, and where each gathered row
    stands: ``(keys, vals (B, ring * block_size, H, hd), positions (B, ring *
    block_size))``, the position negative where the ring holds nothing yet.
    ``lengths``: the position of the token just written."""
    from ..ops.transformer.paged_attention import ring_positions
    keys, vals = gather_kv(view, layer, ring_tables, dtype, n_head)
    ring, bs = ring_tables.shape[1], view["k"].shape[2]
    cell = jnp.arange(ring * bs)
    pos = ring_positions((cell // bs)[None, :], (cell % bs)[None, :],
                         lengths[:, None], ring, bs)
    return keys, vals, pos
