"""Paged KV block pool: one preallocated HBM buffer for every session
(one a cache group, where a model's layers keep different rows of a
token or keep them for different lengths: ``serve/kernels.py``
``cache_groups``; each group has a buffer and a :class:`BlockPool` of
its own).

The single-session decode paths (inference/session.py, models.gpt
generate) each allocate private ``(B, H, S_max, D)`` caches sized for
their own worst case — at serving concurrency that is the classic
fragmentation failure: a thousand mostly-short sessions reserve a
thousand full-context caches.  vLLM's paged-attention observation is
that KV state is append-only and block-granular, so sessions can share
ONE fixed pool of ``block_size``-position blocks and hold only an
integer block table (logical block i -> physical block id).  HBM for
the serving tier becomes a single static allocation; admission control
is an integer free-list; and — the property the whole serve engine is
built around — the decode program's operand shapes depend only on the
POOL geometry and the bucket dims, never on which sessions are resident,
so session churn cannot force a recompile.

Layout: ``(layers, streams, num_blocks, block_size, heads*head_dim)`` —
the geometry is the group's layers' (``block.cache_rows``): a GPT block
keeps two streams, k and v on axis 1, each a row of all heads (a
grouped-query block: of its stored heads); a latent (MLA) block keeps
one stream of one "head", the token's latent row.
Block id on axis 2 so a session's table indexes one axis, and one
token's row contiguous and minor-most (a whole number of lane rows, so
the device keeps the array row-major and the serve programs read and
write it where it lies).  **Physical block 0 is the null
block**: it is never allocated, stays all-zeros, and pads every block
table out to its bucket width — gathers through it read zeros that the
position-validity mask already excludes, so padding is free instead of
a branch.  ``dtype="int8"`` builds the quantized pool as a
:class:`~apex_tpu.inference.quant.QuantKV` (int8 payload + one fp32
scale per cached position — the same per-position absmax convention as
the contiguous int8 cache, via :func:`~apex_tpu.inference.quant.
absmax_int8`).

**Reference counting + content addressing** (the prefix cache): the
pool is no longer a plain free-list.  Every held block carries a
refcount — the cross-request prefix cache (RadixAttention / vLLM's
automatic prefix caching lineage) lets N sessions whose token chains
share a committed prefix hold the SAME physical blocks.  Full blocks
are *committed* under a rolling content hash of their token chain
(:func:`chain_key` — keyed by the parent block's hash, the block's
tokens, and a tag carrying cache dtype / block size / the cache group's
window / model weight epoch, so an int8 pool never matches an fp32 chain and
a ``publish_weights`` hot-swap never serves stale KV).  The
``hash → physical block`` index (:meth:`BlockPool.acquire_prefix`)
turns admission into a chain walk: matched blocks are adopted by
refcount, and only the cold suffix is granted from the free list.

Shared blocks are IMMUTABLE — a session that must write into one forks
it copy-on-write (scheduler policy + the paged block-copy program in
serve/kernels.py; the pool only does the id bookkeeping).  A freed
block whose hash entry is still live retires into an LRU **cached
tier** instead of the free list: refcount zero, bytes intact, re-usable
by the next matching chain, evicted (hash entry dropped, id returned
to the free list) only under allocation pressure.  Cached blocks are
headroom, not leaks: ``check_no_leaks`` and ``free_count`` both count
them as reclaimable.

The host side (:class:`BlockPool`) remains deliberately dumb: integer
bookkeeping with leak accounting.  Policy (who gets blocks, who is
preempted, when to fork) lives in the scheduler; device-side index
arithmetic lives in serve/kernels.py.
"""
from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..inference.quant import QuantKV
from ..observe import registry as _obs

#: physical id of the all-zeros block every table pads with
NULL_BLOCK = 0


def blocks_for(n_positions: int, block_size: int) -> int:
    """Blocks needed to hold ``n_positions`` KV rows."""
    return -(-max(int(n_positions), 0) // block_size)


class BlockTable:
    """One session's block table of one cache group: logical block ``i``
    -> physical id, in logical order, held as an int32 row (its capacity
    doubles as it grows) and a length.  It reads as a list of Python
    ints — ``len``, iteration, indexing and slices (a slice is a list)
    — so the pools, the hash index and the handoff manifests see plain
    ids; ``append`` / ``extend`` grow it and ``table[i] = NULL_BLOCK``
    retires an entry.  The scheduler packs a tick's tables by copying
    :attr:`ids` (and :attr:`ring`) into a fresh array, so no Python int
    is made on the way to the device.

    ``ring``: the width of a window group's ring (``Scheduler.ring``),
    None for a group that keeps every key.  The table then keeps the
    ring as the programs read it beside its row, logical block ``i`` at
    entry ``i mod ring``, written where an entry is; retiring an entry
    clears its ring slot only while the slot still holds that block, so
    the ring does not depend on whether a tick grows the table before or
    after it retires the band's tail.

    Every entry written in place counts into the counter
    ``serve.tables.entries_patched`` (docs/observability.md)."""

    __slots__ = ("_ids", "_n", "ring")

    def __init__(self, ids: Sequence[int] = (), ring: Optional[int] = None):
        self._ids = np.zeros(max(len(ids), 4), np.int32)
        self._n = 0
        self.ring = None if ring is None else np.zeros(ring, np.int32)
        self.extend(ids)

    @property
    def ids(self) -> np.ndarray:
        """The entries as an int32 view of the table's own row: copy it
        before a program may read it (the row changes in place)."""
        return self._ids[:self._n]

    def __len__(self) -> int:
        return self._n

    def __iter__(self):
        return iter(self.ids.tolist())

    def __getitem__(self, i):
        if isinstance(i, slice):
            return self.ids[i].tolist()
        return int(self.ids[i])

    def __setitem__(self, i: int, block: int) -> None:
        old = self[i]
        i %= self._n
        self._ids[i] = block
        if self.ring is not None:
            slot = i % len(self.ring)
            if block != NULL_BLOCK:
                self.ring[slot] = block
            elif self.ring[slot] == old:
                self.ring[slot] = NULL_BLOCK
        _obs.counter("serve.tables.entries_patched").inc()

    def __repr__(self) -> str:
        return f"BlockTable({self.ids.tolist()})"

    def append(self, block: int) -> None:
        self.extend((block,))

    def extend(self, ids: Sequence[int]) -> None:
        k = len(ids)
        if not k:
            return
        n = self._n
        if n + k > len(self._ids):
            grown = np.zeros(max(2 * len(self._ids), n + k), np.int32)
            grown[:n] = self._ids[:n]
            self._ids = grown
        self._ids[n:n + k] = ids
        self._n = n + k
        if self.ring is not None:
            w = len(self.ring)
            for i in range(n, n + k):
                self.ring[i % w] = self._ids[i]
        _obs.counter("serve.tables.entries_patched").inc(k)

    def clear(self) -> None:
        """Every entry dropped (the blocks went back to their pool)."""
        if self._n:
            _obs.counter("serve.tables.entries_patched").inc(self._n)
        self._n = 0
        if self.ring is not None:
            self.ring[:] = NULL_BLOCK


def init_pool_buffer(layers, heads, head_dim, num_blocks, block_size,
                     dtype=jnp.float32, streams=2):
    """The device-side pool array
    ``(layers, streams, num_blocks, block_size, heads*head_dim)`` —
    zeros, so the null block is born valid.  ``streams, heads, head_dim``
    are a layer's ``cache_rows``: what it keeps of one token is
    ``streams`` contiguous rows of ``heads*head_dim`` elements (head
    ``h`` at ``[h*head_dim, (h+1)*head_dim)``), and one block is one
    contiguous piece: with a minor dimension that is a whole number of
    lane rows the device stores the array row-major, which is the layout
    both the row writes and the block-table reads of serve/kernels.py
    want, so no program relayouts it.

    **A latent row is stored 640 wide, not 576.**  A latent layer keeps
    ``c_kv`` (512) and the rotated ``k_rope`` (64) of a token: 576
    elements, 4.5 lane rows.  Stored as ``(1, 1, 640)`` — one stream,
    one row read by every head alike, zeros in the last 64 — a row is
    five whole lane rows, so the device keeps the pool row-major as it
    does a GPT pool (a minor dimension of 576 it would pad to 640 in its
    tiles anyway, and a block would no longer be one contiguous piece
    for the table reader's DMA), and the absorbed query ``[q_lat |
    q_rope | 0]`` meets a whole row in ONE product, with no split of the
    row at a half lane row.  512 + 128 as two streams costs the same
    bytes and two DMAs a block.  The price is 64 / 576 = 11% more bytes
    read than the algorithm needs; rooflines count 576.

    ``dtype="int8"``/``jnp.int8`` builds the :class:`QuantKV` pair: int8
    payload in the same shape, scales fp32 ``(layers, streams,
    num_blocks, block_size, heads)`` — one per position and head."""
    shape = (layers, streams, num_blocks, block_size, heads * head_dim)
    if jnp.dtype(dtype) == jnp.dtype("int8"):
        return QuantKV(jnp.zeros(shape, jnp.int8),
                       jnp.zeros(shape[:-1] + (heads,), jnp.float32))
    return jnp.zeros(shape, dtype)


def init_state_buffers(state, layers: int, slots: int):
    """The device-side buffers of one *state group* (``serve/kernels.py``
    ``state_groups``: layers that keep the same state of a session and
    nothing of a token): one ``(layers, slots + 1, *shape)`` array of
    zeros for each ``(shape, dtype)`` of ``state``, a row a slot.  A
    session holds one slot from admission to ``finish`` and every layer
    of the group reads, updates and writes back its row of that slot
    each step; the last row is the *null slot*, which the padding rows
    of a batch bucket read and write and no session holds.  The dtype is
    the layer's own (a state-space layer's state is float32 whatever the
    cache's: the recurrence sums thousands of steps), and nothing here
    depends on a session's depth, so the group is sized by ``max_batch``
    alone."""
    return tuple(jnp.zeros((layers, slots + 1) + tuple(shape), dtype)
                 for shape, dtype in state)


class SlotPool:
    """Host-side free list of the state slots ``0 .. slots - 1`` (the
    null slot, ``slots``, is never handed out).  There are as many slots
    as batch rows, so a session that is admitted always finds one."""

    def __init__(self, slots: int):
        self.slots = int(slots)
        self._free = list(range(self.slots - 1, -1, -1))
        self.started = 0

    @property
    def null(self) -> int:
        return self.slots

    @property
    def in_use(self) -> int:
        return self.slots - len(self._free)

    def take(self) -> int:
        self.started += 1
        _obs.counter("serve.state.slots_started").inc()
        slot = self._free.pop()
        _obs.gauge("serve.state.slots_in_use").set(self.in_use)
        return slot

    def give(self, slot: int) -> None:
        if slot in self._free or not 0 <= slot < self.slots:
            raise ValueError(f"state slot {slot} is not held")
        self._free.append(slot)
        _obs.gauge("serve.state.slots_in_use").set(self.in_use)

    def check_no_leaks(self) -> None:
        if self.in_use:
            raise AssertionError(
                f"state slot leak: {self.in_use} of {self.slots} still held")


# ---------------------------------------------------------------------------
# Content hashing: the rolling token-chain key
# ---------------------------------------------------------------------------


def chain_key(parent: str, tokens: Sequence[int], tag: str) -> str:
    """The content hash of ONE full block: rolling over ``parent`` (the
    previous block's key, ``""`` for the chain head), the block's token
    ids, and ``tag`` — the engine's cache-compatibility stamp (dtype,
    block size, the cache group's window, weight epoch).  Two blocks share a key iff they
    hold the KV of the same token prefix computed under the same cache
    geometry and weights — which is exactly when their bytes are
    interchangeable."""
    h = hashlib.blake2b(digest_size=16)
    h.update(parent.encode("ascii"))
    h.update(b"\x00")
    h.update(tag.encode("utf-8"))
    h.update(b"\x00")
    h.update(",".join(str(int(t)) for t in tokens).encode("ascii"))
    return h.hexdigest()


def chain_keys(tokens: Sequence[int], block_size: int,
               tag: str) -> List[str]:
    """The hash chain over every FULL block of ``tokens`` (partial tail
    blocks are never content-addressed — their rows are still being
    written)."""
    keys: List[str] = []
    prev = ""
    for i in range(len(tokens) // block_size):
        prev = chain_key(prev, tokens[i * block_size:(i + 1) * block_size],
                         tag)
        keys.append(prev)
    return keys


class BlockPool:
    """Host-side refcounted allocator over physical block ids
    ``1 .. num_blocks-1`` (id 0 is :data:`NULL_BLOCK`, never handed
    out).

    ``alloc(n)`` returns ``n`` exclusive ids (refcount 1) or None
    (all-or-nothing — a partial grant would deadlock two half-admitted
    sessions against each other), evicting LRU cached-tier blocks under
    pressure; ``free(ids)`` drops one reference per id — a block
    reaching refcount zero retires to the cached tier when its hash
    entry is live, else returns to the free list.  Freeing more times
    than references held raises (the shared-block double-free).
    ``acquire_prefix(keys)`` walks a request's hash chain and adopts
    the longest matched prefix by refcount; ``commit(id, key)``
    registers a full block under its chain hash.  Every transition
    keeps the ``pool.free`` / ``pool.cached`` / ``pool.active`` gauges
    current, and the churn tests pin ``in_use == 0`` +
    ``free + cached == capacity`` after drain.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 metrics_prefix: str = "serve."):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is the reserved null "
                f"block), got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._prefix = metrics_prefix
        self._lock = threading.Lock()
        # LIFO: recently freed blocks are re-issued first (their pool
        # rows are hottest in cache on CPU runs; on TPU it is a wash)
        self._free = list(range(num_blocks - 1, 0, -1))
        self._refs: Dict[int, int] = {}          # id -> refcount (held)
        # refcount-zero blocks with live hash entries, LRU order
        # (oldest retired first); values are their chain keys
        self._cached: "OrderedDict[int, str]" = OrderedDict()
        self._hash_index: Dict[str, int] = {}    # chain key -> id
        self._block_hash: Dict[int, str] = {}    # id -> chain key
        self.cache_evictions = 0
        self._gauge()

    # -- accounting --------------------------------------------------------

    @property
    def capacity(self) -> int:
        """Allocatable blocks (the null block is not one)."""
        return self.num_blocks - 1

    @property
    def free_count(self) -> int:
        """Allocatable headroom NOW: free-list blocks plus cached-tier
        blocks (evictable on demand) — what admission and the elastic
        fleet's backpressure should budget against."""
        with self._lock:
            return len(self._free) + len(self._cached)

    @property
    def free_exact(self) -> int:
        """Free-list blocks only (no cached-tier eviction needed)."""
        with self._lock:
            return len(self._free)

    @property
    def cached_count(self) -> int:
        """Cached-tier blocks: refcount zero, hash entry live."""
        with self._lock:
            return len(self._cached)

    @property
    def in_use(self) -> int:
        """Blocks held by at least one live table (refcount >= 1)."""
        with self._lock:
            return len(self._refs)

    @property
    def occupancy(self) -> float:
        """Fraction of allocatable blocks currently held (cached-tier
        blocks are reclaimable headroom, not occupancy)."""
        with self._lock:
            return len(self._refs) / (self.num_blocks - 1)

    def refcount(self, block_id: int) -> int:
        """Live references to ``block_id`` (0 = free or cached)."""
        with self._lock:
            return self._refs.get(block_id, 0)

    def _gauge(self):
        cap = self.num_blocks - 1
        _obs.gauge(self._prefix + "pool_occupancy").set(
            len(self._refs) / cap)
        _obs.gauge(self._prefix + "pool_free_blocks").set(
            len(self._free) + len(self._cached))
        # the split gauges: free conflated with soon-to-be-cached was
        # hiding true headroom from the elastic fleet's shed decisions
        _obs.gauge(self._prefix + "pool.free").set(len(self._free))
        _obs.gauge(self._prefix + "pool.cached").set(len(self._cached))
        _obs.gauge(self._prefix + "pool.active").set(len(self._refs))

    # -- alloc / free ------------------------------------------------------

    def _evict_locked(self) -> None:
        """Drop the LRU cached-tier block's hash entry and return its
        id to the free list (caller holds the lock)."""
        bid, key = self._cached.popitem(last=False)
        del self._hash_index[key]
        del self._block_hash[bid]
        self._free.append(bid)
        self.cache_evictions += 1
        _obs.counter(self._prefix + "cache.evictions").inc()

    def alloc(self, n: int):
        """``n`` exclusive physical block ids (refcount 1), or None if
        the pool cannot cover the whole request (nothing is taken on
        refusal).  Cached-tier blocks are evicted LRU-first when the
        free list alone cannot cover ``n`` — allocation pressure is the
        cached tier's only eviction trigger."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        with self._lock:
            if n > len(self._free) + len(self._cached):
                return None
            while len(self._free) < n:
                self._evict_locked()
            ids = [self._free.pop() for _ in range(n)]
            for b in ids:
                self._refs[b] = 1
            self._gauge()
        return ids

    def free(self, ids) -> None:
        """Drop ONE reference per id.  A block reaching refcount zero
        retires to the cached tier when its hash entry is live (bytes
        stay adoptable), else returns to the free list.  Freeing an id
        with no live reference raises — that is a double free (of an
        exclusive OR a shared block: sharing never grants extra
        frees)."""
        with self._lock:
            for b in ids:
                r = self._refs.get(b)
                if r is None:
                    raise ValueError(
                        f"free of block {b} not held by this pool "
                        f"(double free, foreign id, or more frees than "
                        f"references) — block tables and the refcounts "
                        f"have diverged")
                if r > 1:
                    self._refs[b] = r - 1
                    continue
                del self._refs[b]
                key = self._block_hash.get(b)
                if key is not None:
                    self._cached[b] = key        # MRU end of the LRU
                else:
                    self._free.append(b)
            self._gauge()

    # -- content addressing ------------------------------------------------

    def acquire_prefix(self, keys: Sequence[str]) -> List[int]:
        """Walk a request's hash chain and adopt the longest matched
        prefix: each matched block gains a reference (cached-tier
        blocks are resurrected to refcount 1; held blocks just
        increment).  Returns the matched physical ids, chain order —
        the caller budgets only the cold suffix.  Adopted blocks are
        shared and immutable; release them with :meth:`free`."""
        out: List[int] = []
        with self._lock:
            for key in keys:
                bid = self._hash_index.get(key)
                if bid is None:
                    break
                if bid in self._refs:
                    self._refs[bid] += 1
                else:
                    del self._cached[bid]
                    self._refs[bid] = 1
                out.append(bid)
            if out:
                self._gauge()
        return out

    def commit(self, block_id: int, key: str) -> bool:
        """Register a held, FULL block under its chain hash — from now
        on :meth:`acquire_prefix` can adopt it and :meth:`free` retires
        it to the cached tier instead of the free list.  First writer
        wins: a key already mapped (another session committed the same
        chain first) or a block already hashed is left untouched
        (returns False)."""
        with self._lock:
            if block_id not in self._refs:
                return False                 # freed/evicted underneath
            if key in self._hash_index or block_id in self._block_hash:
                return False
            self._hash_index[key] = block_id
            self._block_hash[block_id] = key
            return True

    def flush_cache(self) -> int:
        """Drop EVERY hash entry and return all cached-tier blocks to
        the free list — the ``publish_weights`` invalidation path: a
        weight hot-swap changes the chain tag, so no stale entry can
        ever match again; flushing reclaims the memory immediately.
        Held blocks stay held (their sessions continue under mixed
        weights, documented in docs/rollout.md) but lose their hash
        entries, so they free to the free list later.  Returns the
        number of cached blocks reclaimed."""
        with self._lock:
            n = len(self._cached)
            for bid in self._cached:
                self._free.append(bid)
            self._cached.clear()
            self._hash_index.clear()
            self._block_hash.clear()
            self._gauge()
            return n

    def check_no_leaks(self) -> None:
        """Raise unless every allocatable block is reclaimable — on the
        free list or in the cached tier (refcount zero, adoptable).
        Cached blocks are NOT leaks: they are the prefix cache
        surviving session churn, evictable on demand.  The post-drain
        invariant of the churn tests."""
        with self._lock:
            if self._refs or \
                    len(self._free) + len(self._cached) \
                    != self.num_blocks - 1:
                raise AssertionError(
                    f"block pool leak: {len(self._refs)} blocks still "
                    f"held (refcounts {dict(self._refs)}), free list "
                    f"{len(self._free)} + cached {len(self._cached)} != "
                    f"{self.num_blocks - 1}")
