"""Continuous-batching scheduler: requests -> per-tick packed batches.

Static-batch serving admits N requests, runs them in lockstep, and
returns when the LAST one finishes — short requests pay the longest
request's latency and the batch slots they vacate idle.  Continuous
batching (Orca's iteration-level scheduling, vLLM's default) re-packs
the live set every tick: a session that finishes frees its batch slot
and its KV blocks *this* tick, and a queued request can take them the
next.  This module is the host-side half of that loop — host integer
bookkeeping (a session's block tables are int32 rows, packed by array
copies), deterministic for a given request/arrival stream (the
packing-determinism test replays a seeded Poisson trace twice and
diffs the decisions).

Three policies live here, and only here (the device programs in
serve/kernels.py are policy-free):

* **admission** — FIFO, gated on three budgets: batch slots
  (``max_batch``), KV blocks (the prompt plus one decode block of
  headroom must fit the pool *whole* — half-admitted sessions would
  deadlock; where the model's layers form several cache groups, every
  group's pool has to take the session, or none does), and prefill
  backlog (``max_prefill_backlog`` tokens not
  yet ingested across admitted sessions — the queue-depth/token-budget
  backpressure that keeps time-to-first-token bounded under load:
  admitting a 30th long prompt helps nobody's SLO; a prompt longer than
  the whole budget is admitted when no other prompt is waiting to be
  ingested).
* **packing** — every decode tick takes ALL decoding sessions (in
  admission order), padded to the next batch bucket; block tables pad
  to the next block bucket.  Buckets are powers of two, so the set of
  decode program shapes is ``O(log(max_batch) · log(max_blocks))`` —
  the recompile-free-after-warmup property the step cache pins.
* **preemption** — when a decode tick needs a block and the pool is
  dry, the LAST-admitted session is evicted (LIFO victim: it has the
  least sunk prefill work and FIFO fairness protects the oldest),
  its blocks freed, and it re-queues at the queue's FRONT in recompute
  mode: on re-admission it re-prefills prompt + tokens generated so
  far, then continues decoding — greedy decode makes the recomputed
  continuation identical to the one the eviction interrupted.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..inference.rolling import window_retired_blocks
from .pool import (BlockPool, BlockTable, NULL_BLOCK, SlotPool, blocks_for,
                   chain_key, chain_keys)

QUEUED, PREFILL, DECODE, DONE = "queued", "prefill", "decode", "done"


def bucket(n: int, cap: Optional[int] = None) -> int:
    """Next power of two >= n (>= 1); ``cap`` bounds it (a request that
    legitimately needs more than cap is the caller's validation bug)."""
    b = 1
    while b < n:
        b *= 2
    return b if cap is None else min(b, cap)


#: admission classes the elastic fleet routes/sheds by — "latency"
#: sessions migrate on capacity loss, "batch" sessions are re-queued
SLO_CLASSES = ("latency", "batch")


@dataclass
class Request:
    """One serving request: ``prompt`` token ids, up to
    ``max_new_tokens`` generated (greedy), optional ``eos`` stop id
    (emitted, then the session finishes).  ``slo`` is the request's
    service class (:data:`SLO_CLASSES`) — a single engine ignores it;
    the elastic fleet (serve/elastic.py) migrates latency-tier sessions
    on a shrink and sheds batch-tier ones first (re-queued, not
    dropped)."""
    rid: str
    prompt: Tuple[int, ...]
    max_new_tokens: int
    eos: Optional[int] = None
    slo: str = "latency"

    def __post_init__(self):
        self.prompt = tuple(int(t) for t in self.prompt)
        if not self.prompt:
            raise ValueError(f"request {self.rid}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"request {self.rid}: max_new_tokens must be >= 1, got "
                f"{self.max_new_tokens}")
        if self.slo not in SLO_CLASSES:
            raise ValueError(
                f"request {self.rid}: slo must be one of {SLO_CLASSES}, "
                f"got {self.slo!r}")


@dataclass
class Session:
    """Scheduler-side state of one admitted request.  The KV state a
    session owns is exactly ``tables`` (physical block ids, one
    :class:`BlockTable` a cache group; ``table`` is the first group's,
    the only one of most models) plus ``position`` (KV rows written) —
    no private cache buffer; the pools hold the bytes.  A table is in
    logical order, entry ``i`` the block of positions ``[i*bs,
    (i+1)*bs)``; under a window the entries before the band are NULL."""
    request: Request
    seq: int                               # admission order (preemption)
    tables: List[BlockTable] = field(
        default_factory=lambda: [BlockTable()])
    position: int = 0                      # KV rows written so far
    state: str = PREFILL
    prefill_src: Tuple[int, ...] = ()      # tokens still to ingest
    emit_on_prefill: bool = True           # fresh: 1st token from logits
    pending_tok: Optional[int] = None      # next token to ingest
    out: List[int] = field(default_factory=list)
    # speculative mode only: the draft model's own block table over the
    # SAME BlockPool free-list, and how many draft KV rows are written
    # (lags `position` when a handed-off session's draft cache is still
    # catching up on the prompt; equal once spec ticks may include it)
    draft_table: BlockTable = field(default_factory=BlockTable)
    draft_position: int = 0
    # target weight epoch the session was admitted under (engine-stamped
    # at admission; epochs only grow, so this is the OLDEST weights any
    # of its tokens saw — the conservative age a staleness bound wants).
    # -1 until admission on an engine that publishes weights.
    weight_epoch: int = -1
    # lifecycle timestamps (engine-stamped, telemetry only — no
    # scheduling decision reads them, so packing stays deterministic)
    t_queued: float = 0.0
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    # -- prefix cache state (tentpole: content-addressed block reuse) --
    # rolling chain keys of the session's committed full blocks, one
    # per table entry < committed_blocks (adopted keys included)
    hash_chain: List[str] = field(default_factory=list)
    committed_blocks: int = 0
    # False when the session's KV provenance is mixed (e.g. adopted
    # under a different weight epoch) — its blocks must never enter
    # the hash index
    cacheable: bool = True
    # copy-on-write forks decided at admission: (table index, shared
    # source id, exclusive destination id).  The engine dispatches the
    # paged block-copy for each, then complete_cow() releases the
    # source reference — the source stays referenced until the copy is
    # in the dispatch stream, so eviction cannot recycle it first.
    cow_pending: List[Tuple[int, int, int]] = field(default_factory=list)
    # tokens of this request's prompt that admission found cached (the
    # rows prefill will NOT recompute) — telemetry for hit-rate
    prefix_hit_tokens: int = 0
    # its row of the state groups' buffers, from admission to finish or
    # preemption (None: the model keeps no state of a session)
    slot: Optional[int] = None

    @property
    def rid(self) -> str:
        return self.request.rid

    @property
    def table(self) -> BlockTable:
        return self.tables[0]

    @property
    def prefill_remaining(self) -> int:
        return len(self.prefill_src) - self.position

    @property
    def fed_tokens(self) -> Tuple[int, ...]:
        """Every token whose target KV row is committed: the prompt
        plus all output except the last (still pending ingest) —
        exactly the recompute-mode prefill source, and the draft
        catch-up source for handed-off speculative sessions."""
        if self.out:
            return self.request.prompt + tuple(self.out[:-1])
        return self.request.prompt

    def finished(self) -> bool:
        r = self.request
        return len(self.out) >= r.max_new_tokens or \
            (r.eos is not None and self.out and self.out[-1] == r.eos)


class Scheduler:
    """The per-tick policy engine.  Owns the request queue and the live
    session set; the serve engine calls, in tick order: ``admit()``,
    ``next_prefill()``, ``decode_sessions()`` (+ ``grow()`` /
    ``preempt_for()`` when blocks run out), and ``finish()``."""

    def __init__(self, pool, *, max_batch: int,
                 prefill_chunk: int, max_prefill_backlog: int,
                 max_positions: int, spec_tables: bool = False,
                 pos_slack: int = 0, prefix_cache: bool = True,
                 cache_tag: str = "kv",
                 windows: Optional[Sequence[Optional[int]]] = None,
                 state_slots: bool = False):
        """``pool``: one :class:`BlockPool`, or one a cache group with
        ``windows`` beside them (each group's window, None where its
        layers read every key).  ``state_slots``: the model keeps a state
        of a session (``serve/kernels.py`` ``state_groups``), so every
        admitted session holds one of ``max_batch`` slots."""
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.pools: List[BlockPool] = list(pool) \
            if isinstance(pool, (list, tuple)) else [pool]
        self.pool = self.pools[0]
        self.windows = list(windows) if windows is not None \
            else [None] * len(self.pools)
        if len(self.windows) != len(self.pools):
            raise ValueError("one window (or None) a pool")
        if len(self.pools) > 1 and (spec_tables or prefix_cache):
            raise ValueError(
                "several cache groups: the prefix cache and draft tables "
                "address one group's blocks; serve without them")
        bs = self.pool.block_size
        #: a window group's tables as packed: a ring this wide, enough
        #: for the band, a prefill chunk after it and their two ends
        self.ring = [None if w is None else bucket(
            blocks_for(w + prefill_chunk, bs) + 2)
            for w in self.windows]
        self.slots = SlotPool(max_batch) if state_slots else None
        if state_slots and (spec_tables or prefix_cache):
            raise ValueError(
                "a state a session: a cached prefix has no snapshot of it "
                "and a rejected draft no way back; serve without them")
        self.max_batch = max_batch
        self.prefill_chunk = prefill_chunk
        self.max_prefill_backlog = max_prefill_backlog
        self.max_positions = max_positions
        # prefix cache: admission walks each request's token chain
        # through the pool's hash index and prefills only the cold
        # suffix.  cache_tag stamps the chain keys with everything KV
        # bytes depend on besides tokens (dtype/block size/the cache
        # group's window/weight epoch) — the engine owns it and re-tags
        # on publish.  One group: a prefix is one table's blocks.
        self.prefix_cache = bool(prefix_cache)
        self.cache_tag = cache_tag
        # speculative mode: every session also owns a draft block table
        # (admission doubles its block ask, finish/preempt free both),
        # and each tick may write up to `pos_slack` rows PAST the last
        # committed position (the verify chunk's rejected tail), so
        # admission budgets that headroom out of max_positions up front
        self.spec_tables = spec_tables
        self.pos_slack = int(pos_slack)
        self.queue: deque = deque()
        self.sessions: List[Session] = []      # admission order
        self._seq = 0
        self.rejected: List[str] = []

    # -- intake ------------------------------------------------------------

    def _reject_never_fit(self, request: Request) -> None:
        need = len(request.prompt) + request.max_new_tokens \
            + self.pos_slack
        bs = self.pool.block_size
        for pool, window in zip(self.pools, self.windows):
            # a window group holds the band and the chunk being written
            held = need if window is None \
                else min(need, window + self.prefill_chunk + bs)
            blocks_need = blocks_for(held, bs)
            if self.spec_tables:
                blocks_need *= 2           # target + draft tables
            if need > self.max_positions or blocks_need > pool.capacity:
                self.rejected.append(request.rid)
                raise ValueError(
                    f"request {request.rid}: {need} positions exceed "
                    f"max_positions {self.max_positions} / pool capacity "
                    f"{pool.capacity * bs}")

    def submit(self, request: Request) -> None:
        """Queue a request (FIFO).  Requests that can NEVER fit — more
        positions than the model or the whole pool can hold — are
        rejected now, loudly, instead of deadlocking the queue head."""
        self._reject_never_fit(request)
        self.queue.append(Session(request, -1))

    def submit_recompute(self, request: Request, out) -> None:
        """Queue a request whose first ``len(out)`` tokens were already
        generated on ANOTHER engine (a session shed or lost during a
        fleet shrink, re-homed here).  Admission treats it exactly like
        a locally preempted session: re-prefill ``prompt + out[:-1]``
        with ``out[-1]`` pending — greedy decode makes the continuation
        bitwise the one the shrink interrupted (the preemption pin)."""
        self._reject_never_fit(request)
        s = Session(request, -1)
        s.state = QUEUED
        out = [int(t) for t in out]
        if out:
            s.out = out
            s.prefill_src = request.prompt + tuple(out[:-1])
            s.emit_on_prefill = False
            s.pending_tok = out[-1]
        else:
            s.prefill_src = request.prompt
        self.queue.append(s)

    def _backlog_tokens(self) -> int:
        return sum(s.prefill_remaining for s in self.sessions
                   if s.state == PREFILL)

    def admit(self) -> List[Session]:
        """Move queue-head sessions into the live set while every budget
        (batch slots, cold-suffix blocks + headroom, prefill backlog)
        holds.  All-or-nothing per session; FIFO order preserved.

        Prefix cache: each request's token chain is walked through the
        pool's hash index first (:meth:`BlockPool.acquire_prefix`) —
        matched full blocks are adopted shared (refcounted, immutable)
        and the session's ``position`` starts past them, so the engine
        prefills only the uncached suffix and the backlog budget counts
        only suffix tokens.  A FULL-chain hit still re-ingests the last
        prompt token (first-token logits must come from somewhere), and
        that write lands inside the last shared block — so admission
        forks it copy-on-write: a fresh block joins the table, the
        shared original stays referenced in ``cow_pending`` until the
        engine dispatches the paged block-copy.  Recompute re-admission
        (preempted or shed sessions) takes the same path and typically
        re-acquires its own just-retired blocks from the cached tier —
        preemption recovery without re-prefill.  Under a window a hit
        keeps only the adopted blocks the band still reaches and, like
        any prompt there, is granted its cold blocks a chunk at a time
        (:meth:`_first_grant`), so its table never outgrows the ring
        :meth:`pack_tables` packs it into."""
        admitted = []
        while self.queue:
            s = self.queue[0]
            if len(self.sessions) >= self.max_batch:
                break
            # fresh sessions ingest the prompt; preempted ones carry
            # their recompute source from preempt_for
            src = s.prefill_src if s.pending_tok is not None \
                else s.request.prompt
            bs = self.pool.block_size
            shared: List[int] = []
            keys: List[str] = []
            if self.prefix_cache:
                keys = chain_keys(src, bs, self.cache_tag)
                shared = self.pool.acquire_prefix(keys)
            hit = len(shared) * bs
            fork = False
            if hit >= len(src):
                # full-chain hit (len(src) is block-aligned and every
                # block matched)
                if s.pending_tok is not None:
                    pos0 = len(src)      # recompute source fully cached
                else:
                    pos0 = len(src) - 1  # re-ingest one token -> logits
                    fork = True
            else:
                pos0 = hit
            # under a window the blocks of a cached prefix that lie
            # wholly before the band are not kept: their entries are
            # NULL from the start, as retirement would leave them
            lo = window_retired_blocks(pos0, self.windows[0], bs)
            self.pool.free(shared[:lo])
            held = shared[lo:]
            # the budget is on tokens not yet ingested: a request
            # longer than all of it enters when nothing else is waiting
            # to be ingested (sessions that only decode are no backlog)
            backlog = self._backlog_tokens()
            if backlog and backlog + (len(src) - pos0) \
                    > self.max_prefill_backlog:
                self.pool.free(held)
                break
            need_total = self._first_grant(0, pos0, len(src) + 1)
            cold = need_total - len(shared) + (1 if fork else 0)
            ids = self.pool.alloc(cold)
            if ids is None:
                self.pool.free(held)
                break
            # every further cache group takes the session too, or none
            # does (no prefix is shared there: several groups serve
            # without the cache)
            more = self._alloc_more(pos0, len(src) + 1)
            if more is None:
                self.pool.free(ids)
                self.pool.free(held)
                break
            draft_ids: List[int] = []
            if self.spec_tables:
                # all-or-nothing across BOTH tables: a session holding
                # a target table but no draft table would deadlock the
                # spec tick exactly like a half-admitted prompt.  The
                # draft cache is never content-addressed (draft-model
                # KV lives under different weights) — always cold.
                draft_ids = self.pool.alloc(need_total)
                if draft_ids is None:
                    self.pool.free(ids)
                    self.pool.free(held)
                    break
            self.queue.popleft()
            s.seq = self._seq
            self._seq += 1
            table = [NULL_BLOCK] * lo + held
            if fork:
                s.cow_pending = [(len(table) - 1, table[-1], ids[0])]
                table = table[:-1]
            else:
                s.cow_pending = []
            s.tables = [self.new_table(g, t)
                        for g, t in enumerate([table + ids] + more)]
            s.draft_table = BlockTable(draft_ids)
            if self.slots is not None:
                s.slot = self.slots.take()
            s.position = pos0
            s.draft_position = 0
            s.prefill_src = src
            s.hash_chain = keys[:len(shared)]
            s.committed_blocks = len(shared)
            s.prefix_hit_tokens = pos0
            s.cacheable = True
            # a fully cached recompute source needs no prefill at all —
            # the pending token ingests through the next decode tick
            s.state = DECODE if pos0 >= len(src) else PREFILL
            self.sessions.append(s)
            admitted.append(s)
        return admitted

    def new_table(self, group: int, ids: Sequence[int] = ()) -> BlockTable:
        """A session's table of cache group ``group`` holding ``ids``
        (a ring beside it where the group keeps a window)."""
        return BlockTable(ids, self.ring[group])

    def import_session(self, request: Request, ids: Sequence[int],
                       draft_ids: Sequence[int], position: int) -> Session:
        """A session whose KV rows ``0 .. position - 1`` arrived in the
        blocks ``ids`` from another engine (a KV handoff; one cache
        group), joined to the live set in DECODE; ``draft_ids`` is its
        draft table, empty of rows.  The caller retires a window's
        blocks before the band, as after any advance."""
        s = Session(request, self._seq)
        self._seq += 1
        s.tables = [self.new_table(0, ids)]
        s.draft_table = BlockTable(draft_ids)
        s.position = int(position)
        s.state = DECODE
        s.prefill_src = ()
        s.emit_on_prefill = False
        self.sessions.append(s)
        return s

    def _first_grant(self, group: int, pos0: int, n_positions: int) -> int:
        """The length admission gives the table of ``group`` for a
        session that starts at position ``pos0`` (0, or past a cached
        prefix): all of ``n_positions`` where the group keeps every key,
        through the first chunk where it keeps a window (the rest is
        granted chunk by chunk, :meth:`grow`, as the blocks before the
        band are retired: a table never spans more than the ring)."""
        if self.windows[group] is not None:
            n_positions = min(n_positions, pos0 + self.prefill_chunk)
        return blocks_for(n_positions, self.pool.block_size)

    def _alloc_more(self, pos0: int,
                    n_positions: int) -> Optional[List[List[int]]]:
        """The tables of the cache groups after the first.  None, and
        nothing taken, unless every group can give its part."""
        out: List[List[int]] = []
        for g in range(1, len(self.pools)):
            ids = self.pools[g].alloc(
                self._first_grant(g, pos0, n_positions))
            if ids is None:
                for p, t in zip(self.pools[1:], out):
                    p.free(t)
                return None
            out.append(ids)
        return out

    def complete_cow(self, s: Session) -> int:
        """Release the shared source of every pending copy-on-write
        fork — the engine calls this AFTER dispatching the block-copy
        program(s), so the source's bytes cannot be recycled before the
        copy is in the dispatch stream.  Host-only harnesses (the churn
        sim) call it right after admit.  Returns the fork count."""
        n = len(s.cow_pending)
        for _idx, fsrc, _fdst in s.cow_pending:
            self.pool.free([fsrc])
        s.cow_pending = []
        return n

    def note_commit(self, s: Session) -> int:
        """Commit every newly FULL block of ``s`` into the pool's hash
        index: extend the session's rolling chain over its fed tokens
        and register each block (first writer wins — a chain another
        session committed already just leaves ours unhashed).  Called
        by the engine after every position advance; returns the number
        of blocks newly chained."""
        if not self.prefix_cache or not s.cacheable:
            return 0
        bs = self.pool.block_size
        toks = s.fed_tokens
        full = min(s.position // bs, len(s.table), len(toks) // bs)
        n = 0
        while s.committed_blocks < full:
            i = s.committed_blocks
            prev = s.hash_chain[i - 1] if i else ""
            key = chain_key(prev, toks[i * bs:(i + 1) * bs],
                            self.cache_tag)
            s.hash_chain.append(key)
            b = s.table[i]
            if b != NULL_BLOCK and not any(
                    idx == i for idx, _src, _dst in s.cow_pending):
                self.pool.commit(b, key)
            s.committed_blocks = i + 1
            n += 1
        return n

    # -- per-tick views ----------------------------------------------------

    def next_prefill(self) -> Optional[Session]:
        for s in self.sessions:
            if s.state == PREFILL:
                return s
        return None

    def decode_sessions(self) -> List[Session]:
        return [s for s in self.sessions if s.state == DECODE]

    def has_work(self) -> bool:
        return bool(self.queue) or bool(self.sessions)

    # -- block growth / preemption ----------------------------------------

    def grow(self, s: Session, n_positions: int,
             draft: bool = False) -> bool:
        """Extend every table of ``s`` (or its ``draft_table``) to cover
        ``n_positions`` KV rows; False, and nothing taken, if a pool is
        dry (caller preempts and retries)."""
        tables = [s.draft_table] if draft else s.tables
        bs = self.pool.block_size
        want = blocks_for(n_positions, bs)
        short = [g for g, table in enumerate(tables) if len(table) < want]
        for g in short:
            # the blocks from the band on have to fit the ring the
            # table is packed into (the most they are is now: the band
            # only moves on from here)
            window, width = self.windows[g], self.ring[g]
            if window is None:
                continue
            live = want - min(window_retired_blocks(s.position, window, bs),
                              want)
            if live > width:
                raise RuntimeError(
                    f"session {s.rid} would hold {live} blocks of a window "
                    f"group whose ring is {width} wide")
        got = []
        for g in short:
            ids = self.pools[g].alloc(want - len(tables[g]))
            if ids is None:
                for g2, ids2 in got:
                    self.pools[g2].free(ids2)
                return False
            got.append((g, ids))
        for g, ids in got:
            tables[g].extend(ids)
        return True

    def evict(self, victim: Session) -> Session:
        """Free a live session's blocks (BOTH tables) and detach it
        from the live set in recompute mode, WITHOUT re-queueing it
        locally — local preemption (:meth:`preempt_for`) re-queues at
        the queue front; the elastic fleet instead re-homes the evicted
        session to another engine (its shed path).  Either way the
        recompute re-prefill of ``prompt + out[:-1]`` continues
        bitwise.

        Shared blocks just lose this session's reference; committed
        ones retire to the cached tier, so the re-admission (here or on
        another engine with the same chain) usually re-adopts them —
        eviction stops costing the prefix its prefill."""
        self.complete_cow(victim)
        self._free_tables(victim)
        self.sessions.remove(victim)
        victim.position = 0
        victim.draft_position = 0
        victim.hash_chain = []
        victim.committed_blocks = 0
        victim.prefix_hit_tokens = 0
        victim.state = QUEUED
        if victim.out:
            # recompute mode: re-prefill prompt + generated-so-far
            # except the last token, which is still waiting to be
            # ingested — it becomes pending again after re-prefill
            victim.prefill_src = victim.request.prompt \
                + tuple(victim.out[:-1])
            victim.emit_on_prefill = False
            victim.pending_tok = victim.out[-1]
        else:
            victim.prefill_src = victim.request.prompt
            victim.emit_on_prefill = True
            victim.pending_tok = None
        return victim

    def preempt_for(self, needy: Session) -> Optional[Session]:
        """Evict the last-admitted live session other than ``needy``
        (or ``needy`` itself if it is alone — it re-queues with its
        progress and re-admits when blocks exist).  Freed state:
        ALL the victim's blocks; the victim re-enters the queue FRONT
        in recompute mode."""
        victims = [s for s in self.sessions if s is not needy]
        victim = max(victims, key=lambda s: s.seq) if victims else needy
        self.evict(victim)
        self.queue.appendleft(victim)
        return victim

    def _free_tables(self, s: Session) -> None:
        """Every group's blocks, and the draft table's, back to their
        pools, and the session's state slot with them."""
        held = list(zip(self.pools, s.tables)) + [(self.pool, s.draft_table)]
        for pool, table in held:
            pool.free(b for b in table if b != NULL_BLOCK)
            table.clear()
        if s.slot is not None:
            self.slots.give(s.slot)
            s.slot = None

    def finish(self, s: Session) -> None:
        self.complete_cow(s)
        self._free_tables(s)
        s.state = DONE
        self.sessions.remove(s)

    def retire_window_blocks(self, s: Session) -> int:
        """Free the leading blocks of a window group's table that no
        future query's band can reach (rolling.py's closed form,
        block-tabled), in every group that has a window.  Retired table
        entries become NULL — a session's table stays in logical order,
        so the prefix stays, pointing at the zero block the band mask
        already excludes (and :meth:`pack_tables` leaves out).  Returns
        the number of blocks returned to the pools."""
        total = 0
        for pool, window, table in zip(self.pools, self.windows, s.tables):
            if window is None:
                continue
            n = window_retired_blocks(s.position, window, pool.block_size)
            # entries before the last retirement's are NULL already
            i = min(n, len(table))
            freed = []
            while i > 0 and table[i - 1] != NULL_BLOCK:
                i -= 1
                freed.append(table[i])
                table[i] = NULL_BLOCK
            if freed:
                pool.free(freed)
                total += len(freed)
        return total

    # -- packing -----------------------------------------------------------
    # Every pack fills fresh int32 arrays from the sessions' BlockTable
    # rows, one slice copy a session: the program's operand is never a
    # view of a table that a later grow or retirement patches in place
    # (JAX may alias a host array it is handed until it has read it).

    @staticmethod
    def pack_rows(tables: Sequence[BlockTable], rows: int):
        """``(bucket_blocks, tables)`` of tables packed whole, in logical
        order: ``rows`` rows (the tables, then all-null padding) of the
        next block bucket of the longest."""
        nb = bucket(max(len(t) for t in tables))
        out = np.full((rows, nb), NULL_BLOCK, np.int32)
        for r, t in enumerate(tables):
            ids = t.ids
            out[r, :len(ids)] = ids
        return nb, out

    def pack_tables(self, sessions: List[Session], rows: int,
                    group: int = 0):
        """``(bucket_blocks, tables)`` of one cache group for a dispatch
        of ``rows`` batch rows (the sessions, then all-null padding).  A
        group that keeps every key packs each table whole, padded to the
        next block bucket; a window group packs the blocks from the band
        on as a ring of its fixed width, logical block ``i`` at entry
        ``i mod width`` — each table's own :attr:`BlockTable.ring`
        (:meth:`grow` keeps a table's blocks from the band on within it)."""
        nb = self.ring[group]
        if nb is None:
            return self.pack_rows([s.tables[group] for s in sessions], rows)
        out = np.full((rows, nb), NULL_BLOCK, np.int32)
        out[:len(sessions)] = np.stack([s.tables[group].ring
                                        for s in sessions])
        return nb, out

    def pack_decode(self, sessions: List[Session]):
        """Bucketed operands for one decode tick:
        ``(bucket_batch, bucket_blocks, tokens, positions, tables)`` —
        tokens and positions as host int lists, the tables as int32
        arrays; dead rows carry ``position = -1`` and all-null tables
        (the kernels' drop encoding).  With several cache groups
        ``bucket_blocks`` and ``tables`` are tuples, one of each a
        group."""
        b = bucket(len(sessions), self.max_batch)
        tokens = [s.pending_tok for s in sessions] + [0] * (b - len(sessions))
        positions = [s.position for s in sessions] \
            + [-1] * (b - len(sessions))
        nb, tables = self.pack_groups(sessions, b)
        return b, nb, tokens, positions, tables

    def pack_slots(self, sessions: List[Session], rows: int) -> List[int]:
        """Each session's state slot for a dispatch of ``rows`` batch
        rows; the padding rows take the null slot."""
        return [s.slot for s in sessions] \
            + [self.slots.null] * (rows - len(sessions))

    def pack_groups(self, sessions: List[Session], rows: int):
        """:meth:`pack_tables` of every cache group as the programs take
        them: ``(bucket_blocks, tables)`` of the one group, or a tuple of
        each where there are several."""
        packed = [self.pack_tables(sessions, rows, g)
                  for g in range(len(self.pools))]
        if len(packed) == 1:
            return packed[0]
        return tuple(nb for nb, _ in packed), tuple(t for _, t in packed)

    def pack_spec(self, sessions: List[Session]):
        """Bucketed operands for one speculative tick:
        ``(bucket_batch, bucket_t_blocks, bucket_d_blocks, tokens,
        positions, t_tables, d_tables)`` — the decode packing plus the
        draft pool's tables, bucketed independently (the draft cache
        may cover fewer rows than the target's after a handoff)."""
        b = bucket(len(sessions), self.max_batch)
        pad = b - len(sessions)
        tokens = [s.pending_tok for s in sessions] + [0] * pad
        positions = [s.position for s in sessions] + [-1] * pad
        nbt, t_tables = self.pack_rows([s.table for s in sessions], b)
        nbd, d_tables = self.pack_rows([s.draft_table for s in sessions], b)
        return b, nbt, nbd, tokens, positions, t_tables, d_tables
