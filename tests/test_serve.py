"""apex_tpu.serve: the paged KV pool's alloc/free/leak invariants under
random admit/finish/preempt churn, packing determinism on a seeded
Poisson trace, the recompile-free-decode property pinned through
``step_cache.stats()``, prefill-chunking's latency interleave, and
bitwise greedy parity against ``inference.DecodeSession``."""
import numpy as np
import pytest

import jax.numpy as jnp

from apex_tpu import nn
from apex_tpu.inference.rolling import window_retired_blocks
from apex_tpu.inference.session import DecodeSession, PagedSession
from apex_tpu.models.gpt import GptModel
from apex_tpu.observe import registry as obs
from apex_tpu.runtime import step_cache as sc
from apex_tpu.serve import (BlockPool, NULL_BLOCK, Request, Scheduler,
                            ServeEngine, blocks_for, bucket)
from apex_tpu.serve.scheduler import DECODE

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def model():
    nn.manual_seed(6)
    m = GptModel(vocab_size=73, hidden=32, layers=2, heads=4,
                 max_positions=96, dropout=0.0, attn_dropout=0.0)
    m.eval()
    return m


# ---------------------------------------------------------------------------
# host-side units: buckets, pool accounting
# ---------------------------------------------------------------------------


def test_bucket_and_blocks_for():
    assert [bucket(n) for n in (1, 2, 3, 4, 5, 9)] == [1, 2, 4, 4, 8, 16]
    assert bucket(9, cap=8) == 8
    assert blocks_for(0, 4) == 0
    assert blocks_for(1, 4) == 1
    assert blocks_for(4, 4) == 1
    assert blocks_for(5, 4) == 2


def test_pool_alloc_is_all_or_nothing():
    pool = BlockPool(num_blocks=8, block_size=4)
    ids = pool.alloc(7)
    assert ids is not None and len(ids) == 7
    assert NULL_BLOCK not in ids          # block 0 is never handed out
    assert pool.alloc(1) is None
    assert pool.in_use == 7 and pool.free_count == 0
    pool.free(ids[:3])
    # shortfall refuses whole: nothing taken, accounting unchanged
    assert pool.alloc(4) is None
    assert pool.free_count == 3
    got = pool.alloc(3)
    assert sorted(got) == sorted(ids[:3])
    pool.free(got)
    pool.free(ids[3:])
    pool.check_no_leaks()


def test_pool_double_free_and_foreign_free_raise():
    pool = BlockPool(num_blocks=8, block_size=4)
    ids = pool.alloc(2)
    pool.free(ids)
    with pytest.raises(ValueError):
        pool.free(ids)                    # double free
    with pytest.raises(ValueError):
        pool.free([NULL_BLOCK])           # the null block is not held


# ---------------------------------------------------------------------------
# churn: 500 requests of random admit/finish/preempt, zero leaked blocks
# ---------------------------------------------------------------------------


def _sim_tok(position):
    """Deterministic stand-in for a generated token (host-only sims
    never dispatch the model)."""
    return (position * 7 + 3) % 70 + 1


def _sim_prefill_tick(sched):
    """Advance the oldest prefilling session one chunk, mirroring
    ``ServeEngine._prefill_chunk`` at the scheduler level (including
    the chain commit after the position advance)."""
    s = sched.next_prefill()
    if s is None:
        return
    s.position += min(sched.prefill_chunk, s.prefill_remaining)
    sched.note_commit(s)
    if s.prefill_remaining > 0:
        return
    s.state = DECODE
    if s.emit_on_prefill:
        tok = _sim_tok(s.position)
        s.out.append(tok)
        s.pending_tok = tok
        if s.finished():
            sched.finish(s)


def _sim_decode_tick(sched):
    """One packed decode tick, mirroring ``_ensure_decode_blocks`` +
    ``_decode_tick``: grow-or-preempt, then advance every survivor."""
    preempted = []
    for s in list(sched.decode_sessions()):
        if s.state != DECODE:
            continue                      # preempted below us
        while not sched.grow(s, s.position + 1):
            victim = sched.preempt_for(s)
            preempted.append(victim.rid)
            if victim is s:
                break
    live = sched.decode_sessions()
    packed = sched.pack_decode(live) if live else None
    for s in list(live):
        s.position += 1
        tok = _sim_tok(s.position)
        s.out.append(tok)
        s.pending_tok = tok
        sched.note_commit(s)
        if s.finished():
            sched.finish(s)
    return preempted, packed


def _pool_books_balance(sched):
    """Refcount bookkeeping: every table occurrence of a block is one
    live reference (shared prefix blocks appear in SEVERAL tables), the
    held set matches the pool's, and held + free + cached covers the
    whole pool."""
    from collections import Counter
    occ = Counter(b for s in sched.sessions
                  for b in [*s.table, *s.draft_table] if b != NULL_BLOCK)
    for b, n in occ.items():
        assert sched.pool.refcount(b) == n, \
            f"block {b}: {n} table occurrences, refcount " \
            f"{sched.pool.refcount(b)}"
    assert len(occ) == sched.pool.in_use
    assert sched.pool.in_use + sched.pool.free_count == \
        sched.pool.capacity


def test_scheduler_churn_500_requests_zero_leaks():
    """500 requests of random admit/finish/preempt churn WITH the
    prefix cache live: half the prompts repeat a handful of shared
    templates, so admissions adopt shared blocks, full-chain hits fork
    copy-on-write, finishes retire committed blocks to the cached tier,
    and allocation pressure evicts them — and the books still balance
    to zero leaks."""
    rng = np.random.default_rng(0)
    pool = BlockPool(num_blocks=48, block_size=4)
    sched = Scheduler(pool, max_batch=8, prefill_chunk=8,
                      max_prefill_backlog=64, max_positions=96)
    n = 500
    templates = [[int(t) for t in rng.integers(1, 70, ln)]
                 for ln in (4, 8, 8, 11)]
    reqs = []
    for i in range(n):
        if rng.random() < 0.5:            # shared-prefix traffic
            base = templates[int(rng.integers(len(templates)))]
            ext = [] if rng.random() < 0.3 \
                else [int(t) for t in rng.integers(1, 70,
                                                   int(rng.integers(1, 4)))]
            prompt = base + ext
        else:
            prompt = [int(t) for t in
                      rng.integers(1, 70, int(rng.integers(1, 12)))]
        reqs.append(Request(f"r{i}", prompt, int(rng.integers(1, 9))))
    done_before = set()
    shared_adoptions = cow = 0
    i = tick = 0
    while i < n or sched.has_work():
        tick += 1
        assert tick < 100_000, "churn sim failed to drain"
        for _ in range(int(rng.integers(0, 3))):
            if i < n:
                sched.submit(reqs[i])
                i += 1
        for s in sched.admit():
            shared_adoptions += s.committed_blocks
            cow += sched.complete_cow(s)  # host-only: no copy dispatch
        _sim_prefill_tick(sched)
        _sim_decode_tick(sched)
        # extra adversarial churn: evict someone at random
        if sched.sessions and rng.random() < 0.05:
            sched.preempt_for(sched.sessions[0])
        if tick % 50 == 0:
            _pool_books_balance(sched)
        for s in list(sched.sessions):
            assert s.rid not in done_before
    # the trace is not degenerate: blocks were shared, forked, evicted
    assert shared_adoptions > 50
    assert cow > 0
    assert pool.cache_evictions > 0
    pool.check_no_leaks()
    assert pool.in_use == 0
    assert pool.free_exact + pool.cached_count == pool.capacity


@pytest.mark.parametrize("ahead", ["decoding", "ingesting"])
def test_a_prompt_over_the_backlog_budget_waits_only_for_prompts(ahead):
    """``max_prefill_backlog`` is a budget on tokens not yet ingested.
    A prompt longer than all of it joins sessions that only decode (it
    used to wait for an EMPTY engine, so that a batch behind one such
    prompt drained to nothing first), and waits while another prompt is
    still being ingested, then enters when that one has landed."""
    pool = BlockPool(num_blocks=64, block_size=4)
    sched = Scheduler(pool, max_batch=8, prefill_chunk=4,
                      max_prefill_backlog=8, max_positions=96)
    sched.submit(Request("first", [3, 5], 40))
    assert [s.rid for s in sched.admit()] == ["first"]
    _sim_prefill_tick(sched)
    assert [s.rid for s in sched.decode_sessions()] == ["first"]
    long_ = list(range(1, 21))                   # 20 tokens > the budget
    if ahead == "decoding":
        sched.submit(Request("long", long_, 4))
        assert [s.rid for s in sched.admit()] == ["long"]
        assert sched._backlog_tokens() == 20
        return
    sched.submit(Request("within", [7, 8, 9, 2, 4, 6], 4))
    sched.submit(Request("long", long_, 4))
    sched.submit(Request("short", [9, 9], 4))
    # 6 of the 8 are taken: neither the long prompt nor, behind it in
    # the queue, the short one gets in (FIFO holds)
    assert [s.rid for s in sched.admit()] == ["within"]
    assert sched.admit() == []
    _sim_prefill_tick(sched)                     # 4 of the 6 ingested
    assert sched._backlog_tokens() == 2 and sched.admit() == []
    _sim_prefill_tick(sched)                     # "within" has landed
    assert sched._backlog_tokens() == 0
    assert len(sched.decode_sessions()) == 2
    # the long prompt enters beside two decoding sessions, and the short
    # one waits for it in turn
    assert [s.rid for s in sched.admit()] == ["long"]
    assert sched.admit() == []
    _pool_books_balance(sched)


# ---------------------------------------------------------------------------
# packing determinism: a seeded Poisson trace replays to the byte
# ---------------------------------------------------------------------------


def _drive_trace(seed, n=60):
    """Host-only serve loop over a seeded Poisson arrival trace,
    recording every scheduling decision (admissions, preemptions, and
    the packed decode operands — the arrays that become program
    operands)."""
    rng = np.random.default_rng(seed)
    pool = BlockPool(num_blocks=32, block_size=4)
    sched = Scheduler(pool, max_batch=4, prefill_chunk=8,
                      max_prefill_backlog=32, max_positions=96)
    lens = rng.integers(1, 10, n)
    news = rng.integers(1, 6, n)
    prompts = [[int(t) for t in rng.integers(1, 70, int(l))] for l in lens]
    arrive = np.cumsum(rng.poisson(1.0, n))
    decisions = []
    i = tick = 0
    while i < n or sched.has_work():
        assert tick < 50_000
        while i < n and arrive[i] <= tick:
            sched.submit(Request(f"r{i}", prompts[i], int(news[i])))
            i += 1
        admitted = sched.admit()
        if admitted:
            decisions.append(("admit", tick, tuple(s.rid for s in admitted)))
        _sim_prefill_tick(sched)
        preempted, packed = _sim_decode_tick(sched)
        if preempted:
            decisions.append(("preempt", tick, tuple(preempted)))
        if packed is not None:
            b, nb, toks, poss, tables = packed
            decisions.append(("pack", tick, b, nb, tuple(toks),
                              tuple(poss), tuple(map(tuple, tables))))
        tick += 1
    pool.check_no_leaks()
    return decisions


def test_packing_determinism_under_poisson_trace():
    first = _drive_trace(seed=7)
    second = _drive_trace(seed=7)
    assert first == second
    # the trace is not degenerate: it packed and bucketed for real
    packs = [d for d in first if d[0] == "pack"]
    assert packs and {d[2] for d in packs} >= {1, 2}
    assert _drive_trace(seed=8) != first


# ---------------------------------------------------------------------------
# engine: recompile-free decode, prefill interleave, parity, preemption
# ---------------------------------------------------------------------------


def test_decode_recompile_free_after_warmup(model):
    sc.reset_stats()
    sc.clear()
    eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=4,
                      prefill_chunk=4)
    eng.run([Request(f"a{i}", [2 + i, 5, 7, 11], 6) for i in range(8)])
    warm = sc.kind_stats("decode_step")
    assert warm["compiles"] >= 1
    # bucket bound: occupancy buckets {1,2,4} x one table bucket
    assert warm["compiles"] <= 6
    # same shape profile again: every decode dispatch re-hits the cache
    eng.run([Request(f"b{i}", [3 + i, 9, 4, 2], 6) for i in range(8)])
    again = sc.kind_stats("decode_step")
    assert again["compiles"] == warm["compiles"]
    assert again["dispatches"] > warm["dispatches"]
    assert again["cache_hits"] > warm["cache_hits"]
    eng.block_pool.check_no_leaks()


def test_a_dead_engines_programs_leave_the_step_cache(model):
    """A serve program's static key starts with its engine's token, and
    its closure holds the model: when the engine goes, so do its
    entries (nothing could hit them again, and they would keep the
    weights alive until the LRU turned them out); another engine's
    stay."""
    import gc

    def held_by(token):
        return [k for k in sc.step_cache._programs
                if isinstance(k[1], tuple) and k[1][:1] == (token,)]

    def engine():
        eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=4,
                          prefill_chunk=4)
        eng.run([Request("a", [2, 5, 7, 11], 3)])
        return eng

    sc.clear()
    kept, gone = engine(), engine()
    tokens = kept._token, gone._token
    kinds = {k[0] for k in held_by(tokens[1])}
    assert {"decode_step", "prefill_step"} <= kinds
    assert len(held_by(tokens[0])) == len(held_by(tokens[1]))
    del gone
    gc.collect()
    assert held_by(tokens[1]) == []
    assert held_by(tokens[0]) and \
        len(sc.step_cache._programs) == len(held_by(tokens[0]))
    # the survivor still hits its own programs
    before = sc.kind_stats("decode_step")["compiles"]
    kept.run([Request("b", [3, 9, 4, 2], 3)])
    assert sc.kind_stats("decode_step")["compiles"] == before


def test_prefill_chunking_interleaves_decode(model):
    """A 32-token prompt prefilling 2 tokens/tick must not stall a
    short request's decode: the short request keeps emitting one token
    per tick and finishes long before the long prompt's first token —
    the latency bound chunked prefill exists to provide."""
    obs.get_registry().clear_events()
    eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=4,
                      prefill_chunk=2, max_prefill_backlog=64)
    short = Request("short", [5, 9], 6)
    long_ = Request("long", list(range(1, 33)), 4)
    out = eng.run([short, long_], arrivals=[0, 1])
    assert len(out["short"]) == 6 and len(out["long"]) == 4
    ticks = {(e["rid"], e["phase"]): e["tick"]
             for e in obs.events("serve.request")}
    # one decode token per tick from the first token on, no stall:
    # first_token's tick also decodes (prefill completes, then the
    # decode pass runs in the same tick), so 6 tokens span 4 ticks
    assert ticks[("short", "done")] - ticks[("short", "first_token")] == 4
    assert ticks[("short", "done")] < ticks[("long", "first_token")]
    eng.block_pool.check_no_leaks()


def test_engine_greedy_parity_vs_decode_session(model):
    prompts = [[5, 9, 11, 3], [7, 2], [1, 2, 3, 4, 5, 6, 7, 8, 9]]
    max_new = 6
    base = {}
    for i, p in enumerate(prompts):
        s = DecodeSession(model, batch=1)
        s.append(jnp.asarray([p], jnp.int32))
        base[f"r{i}"] = [int(t) for t in np.asarray(s.generate(max_new))[0]]
    eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=4,
                      prefill_chunk=4)
    out = eng.run([Request(f"r{i}", p, max_new)
                   for i, p in enumerate(prompts)])
    assert out == base                    # bitwise greedy parity
    eng.block_pool.check_no_leaks()


def test_int8_pool_parity(model):
    s8 = DecodeSession(model, batch=1, cache_dtype="int8")
    s8.append(jnp.asarray([[5, 9, 11, 3]], jnp.int32))
    base = [int(t) for t in np.asarray(s8.generate(5))[0]]
    eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=4,
                      prefill_chunk=4, cache_dtype="int8")
    out = eng.run([Request("a", [5, 9, 11, 3], 5),
                   Request("b", [7, 2], 5)])
    assert out["a"] == base
    eng.block_pool.check_no_leaks()


def test_preemption_recompute_parity_and_no_leaks(model):
    """A pool too small for the live set forces preemption; every
    request still finishes, recompute reproduces the exact greedy
    continuation, and the drained pool holds zero blocks."""
    obs.get_registry().reset()
    eng = ServeEngine(model, num_blocks=9, block_size=4, max_batch=4,
                      prefill_chunk=4)
    out = eng.run([Request(f"r{i}", [3 + i, 5, 7], 8) for i in range(6)])
    assert sorted(out) == [f"r{i}" for i in range(6)]
    assert all(len(v) == 8 for v in out.values())
    assert obs.counter("serve.preemptions").value > 0
    s = DecodeSession(model, batch=1)
    s.append(jnp.asarray([[3, 5, 7]], jnp.int32))
    assert out["r0"] == [int(t) for t in np.asarray(s.generate(8))[0]]
    eng.block_pool.check_no_leaks()


def test_paged_session_multi_turn_parity(model):
    ds = DecodeSession(model, batch=1)
    ds.append(jnp.asarray([[5, 9, 11, 3]], jnp.int32))
    t1 = np.asarray(ds.generate(5))
    ds.append(jnp.asarray([[8, 8, 2]], jnp.int32))
    t2 = np.asarray(ds.generate(4))
    eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=4,
                      prefill_chunk=4)
    with PagedSession(eng) as ps:
        ps.append([5, 9, 11, 3])
        assert (np.asarray(ps.generate(5)) == t1).all()
        ps.append([8, 8, 2])
        assert (np.asarray(ps.generate(4)) == t2).all()
    eng.block_pool.check_no_leaks()


# ---------------------------------------------------------------------------
# sliding window, admission validation, metrics schema
# ---------------------------------------------------------------------------


def test_window_retired_blocks_closed_form():
    assert window_retired_blocks(0, 8, 4) == 0
    assert window_retired_blocks(8, 8, 4) == 0
    assert window_retired_blocks(12, 8, 4) == 1
    assert window_retired_blocks(20, 8, 4) == 3
    assert window_retired_blocks(20, None, 4) == 0


def test_windowed_engine_retires_blocks(model):
    eng = ServeEngine(model, num_blocks=32, block_size=4, max_batch=2,
                      prefill_chunk=4, window=8)
    out = eng.run([Request("w", list(range(1, 20)), 10)])
    assert len(out["w"]) == 10
    eng.block_pool.check_no_leaks()


def _windowed(model, **kw):
    # window 8 over blocks of 4, chunks of 4: tables packed as rings of 8
    kw = {"num_blocks": 32, "max_batch": 2, **kw}
    return ServeEngine(model, block_size=4, prefill_chunk=4, window=8, **kw)


@pytest.mark.parametrize("shared,length", [(4, 35), (20, 35), (36, 36)],
                         ids=["one_block", "past_the_band", "whole_prompt"])
def test_windowed_engine_takes_a_cached_prefix_longer_than_its_ring(
        model, shared, length):
    """A prompt of nine blocks whose head another request left in the
    prefix cache, under a window whose ring is eight entries wide: the
    hit is granted its blocks a chunk at a time like any other prompt,
    the cached blocks before the band are not kept, and the answer is
    what an engine without the cache gives."""
    rng = np.random.default_rng(11)
    first = [int(t) for t in rng.integers(1, 72, length)]
    second = first[:shared] + [int(t) for t in
                               rng.integers(1, 72, length - shared)]
    plain = _windowed(model, prefix_cache=False)
    want = plain.run([Request("b", second, 6)])["b"]
    eng = _windowed(model)
    assert eng.scheduler.ring == [8]
    eng.run([Request("a", first, 6)])
    out = eng.run([Request("b", second, 6)])["b"]
    assert out == want
    assert eng.metrics()["prefix_cache"]["prefill_tokens_saved"] \
        == min(shared // 4 * 4, length - 1)
    eng.close()
    plain.close()


@pytest.mark.parametrize("how", ["dry_pool", "cached"])
def test_windowed_engine_preempts_and_readmits_a_long_session(model, how):
    """Sessions deeper than the ring are preempted and come back: in a
    pool that holds one band and a half by recompute (the cached tier
    was evicted under the same pressure), in a roomy one through the
    prefix cache, adopting the blocks they left behind — retired ones
    among them, far more than a ring holds.  Either way the answers are
    those of a run nobody interrupted."""
    obs.get_registry().reset()
    rng = np.random.default_rng(12)
    reqs = [Request(f"p{i}", [int(t) for t in rng.integers(1, 72, 30 + i)],
                    24) for i in range(2)]
    roomy = _windowed(model)
    want = roomy.run(reqs)
    assert obs.counter("serve.preemptions").value == 0
    roomy.close()
    if how == "dry_pool":
        eng = _windowed(model, num_blocks=6)
        out = eng.run(reqs)
        assert obs.counter("serve.preemptions").value > 0
    else:
        eng = _windowed(model)
        for r in reqs:
            eng.submit(r)
        while not all(s.position > 44 for s in eng.scheduler.sessions) \
                or len(eng.scheduler.sessions) < 2:
            eng.step()
        for s in list(eng.scheduler.sessions):
            eng.scheduler.preempt_for(s)
        out = eng.run([])
        # each came back past its cached blocks, a whole ring and more
        assert eng.metrics()["prefix_cache"]["prefill_tokens_saved"] >= 80
    assert out == want
    eng.close()


def test_submit_rejects_never_fit_requests(model):
    eng = ServeEngine(model, num_blocks=4, block_size=4, max_batch=2,
                      prefill_chunk=4)
    with pytest.raises(ValueError):     # exceeds the whole pool
        eng.submit(Request("big", list(range(1, 30)), 8))
    with pytest.raises(ValueError):     # exceeds model positions
        eng.submit(Request("long", [1] * 90, 20))
    assert not eng.scheduler.has_work()


def test_metrics_snapshot_schema(model):
    eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=2,
                      prefill_chunk=4)
    eng.run([Request("m", [5, 9], 3)])
    m = eng.metrics()
    assert m["pool_occupancy"] == 0.0 and m["queue_depth"] == 0
    for kind in ("decode", "prefill"):
        assert set(m[kind]) == {"compiles", "cache_hits", "dispatches"}
        assert m[kind]["dispatches"] >= 1


def test_close_returns_all_live_blocks(model):
    """close() mid-run returns every live session's blocks — target
    AND draft tables — so check_no_leaks holds even with sessions
    still decoding; the context-manager form does the same."""
    from apex_tpu.inference import make_self_draft
    eng = ServeEngine(model, num_blocks=48, block_size=8, max_batch=4,
                      prefill_chunk=4, draft=make_self_draft(model))
    for i, p in enumerate([[5, 9, 11, 3], [7, 2], [12, 30, 4]]):
        eng.submit(Request(f"c{i}", p, 12))
    for _ in range(4):                    # mid-flight: live sessions
        eng.step()
    assert eng.scheduler.sessions         # something is decoding
    assert eng.block_pool.in_use > 0
    eng.close()                           # runs check_no_leaks itself
    assert eng.block_pool.in_use == 0
    assert not eng.scheduler.has_work()

    with ServeEngine(model, num_blocks=32, block_size=8, max_batch=2,
                     prefill_chunk=4) as eng2:
        eng2.submit(Request("cm", [3, 4, 5], 8))
        eng2.step()
        eng2.step()
        assert eng2.block_pool.in_use > 0
    assert eng2.block_pool.in_use == 0


def test_an_engine_with_a_draft_speculates(model):
    """Whether to speculate is not a policy: an engine given a draft
    takes the verify tick on every decode tick (the counter of accepted
    drafts moves, no plain decode program is built), and the keyword
    that used to ask a ledger is refused."""
    from apex_tpu.inference import make_self_draft
    draft = make_self_draft(model)
    with pytest.raises(TypeError, match="spec_policy"):
        ServeEngine(model, num_blocks=48, block_size=8, max_batch=4,
                    prefill_chunk=4, draft=draft, spec_policy="on")
    eng = ServeEngine(model, num_blocks=48, block_size=8, max_batch=4,
                      prefill_chunk=4, draft=draft, spec_k=2)
    plain_before = eng.metrics()["decode"]["dispatches"]
    out = eng.run([Request("s0", [5, 9, 11, 3], 9), Request("s1", [7], 6)])
    assert [len(out["s0"]), len(out["s1"])] == [9, 6]
    m = eng.metrics()
    assert m["spec"]["ticks"] >= 1
    assert m["decode"]["dispatches"] == plain_before
    eng.block_pool.check_no_leaks()


# ---------------------------------------------------------------------------
# the span tree of a tick
# ---------------------------------------------------------------------------

#: what may open directly under ``serve.step`` (docs/observability.md)
TICK_CHILDREN = {"serve.admit", "serve.prefill_chunk", "serve.ensure_blocks",
                 "serve.pack", "dispatch", "serve.fetch", "serve.commit",
                 "host.gc"}


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "speculative"])
def test_every_tick_records_a_span_tree(model, spec):
    from apex_tpu.inference import make_self_draft
    from apex_tpu.observe import spans
    obs.get_registry().clear_events()
    obs.get_registry().remove("serve.decode_tick_ms")
    eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=4,
                      prefill_chunk=4,
                      draft=make_self_draft(model) if spec else None)
    reqs = [Request(f"s{i}", [2 + i, 5, 7, 11, 3, 8][:3 + i], 5)
            for i in range(4)]
    out = eng.run(reqs, arrivals=[0, 0, 1, 3])
    assert all(len(out[r.rid]) == 5 for r in reqs)
    # a collection between two ticks is a root of no tick
    recs = [r for r in spans.recorded()
            if r["span"] != "host.gc" or r["parent"] is not None]
    by_id = {r["id"]: r for r in recs}
    steps = [r for r in recs if r["span"] == "serve.step"]
    # one root a tick, in order, none nested in another span
    assert [r["tick"] for r in steps] == list(range(1, eng.tick + 1))
    assert all(r["parent"] is None for r in steps)
    kids = {}
    for r in recs:
        if r["parent"] is not None:
            kids.setdefault(r["parent"], []).append(r)
    for root in steps:
        mine = kids.get(root["id"], [])
        assert {k["span"] for k in mine} <= TICK_CHILDREN
        last_end = root["t0_ns"]
        for k in mine:                  # inside the tick, one after another
            assert last_end <= k["t0_ns"] <= k["t1_ns"] <= root["t1_ns"]
            last_end = k["t1_ns"]
    # every span below a tick is of that tick
    for r in recs:
        top = r
        while top["parent"] is not None:
            top = by_id[top["parent"]]
        assert r["tick"] == top["tick"] and top["span"] == "serve.step"
    # every blocking read follows, under the same parent, the dispatch
    # whose result it reads
    fetches = [r for r in recs if r["span"] == "serve.fetch"]
    assert {r["what"] for r in fetches} == \
        {"first_token", "spec_tokens" if spec else "tokens"}
    for f in fetches:
        before = [k for k in kids[f["parent"]]
                  if k["span"] == "dispatch" and k["t1_ns"] <= f["t0_ns"]]
        assert before, f
    # a request's prefill chunks carry its rid, and cover its prompt
    for r in reqs:
        chunks = [c for c in recs if c["span"] == "serve.prefill_chunk"
                  and c["rid"] == r.rid]
        assert sum(c["n_real"] for c in chunks) == len(r.prompt)
        admitted = [e["tick"] for e in obs.events("serve.request")
                    if e["rid"] == r.rid and e["phase"] == "prefill"]
        assert chunks[0]["tick"] >= admitted[0]
    # what the root says of its tick
    decoding = [r for r in steps if r["decode_batch"]]
    assert decoding and max(r["decode_batch"] for r in steps) <= 4
    assert {r["prefill_rid"] for r in steps} - {None} == \
        {r.rid for r in reqs}
    commits = [r for r in recs if r["span"] == "serve.commit"]
    assert len(commits) == len(decoding)
    assert sum(c["n_finished"] for c in commits) == len(reqs)
    admits = [r for r in recs if r["span"] == "serve.admit"]
    assert sum(a["n"] for a in admits) == len(reqs)
    # serve.decode_tick_ms is the root span's own duration
    hist = obs.get_registry().histogram("serve.decode_tick_ms")
    assert hist.count == len(decoding)
    assert hist.last == decoding[-1]["dur_ms"]
    assert hist.total == pytest.approx(sum(r["dur_ms"] for r in decoding))
    eng.block_pool.check_no_leaks()


def test_a_prefill_only_tick_reads_its_counts_under_a_fetch(model):
    """What a tick's programs counted and no decode fetch took with it
    (a tick that only prefills) is read under ``serve.fetch`` of what
    ``counts``, after the chunk that counted it."""
    from apex_tpu.observe import spans
    eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=4,
                      prefill_chunk=4)
    eng.submit(Request("p", list(range(1, 13)), 3))     # three chunks
    # a routed model's chunk hands back its counts: stand one in
    eng._counted.append(jnp.zeros((1, 2), jnp.int32))
    since = spans.recorded()[-1]["t0_ns"] + 1 if spans.recorded() else 0
    eng.step()
    recs = spans.recorded(since)
    (root,) = [r for r in recs if r["span"] == "serve.step"]
    kids = [r for r in recs if r["parent"] == root["id"]
            and r["span"] != "host.gc"]
    assert [k["span"] for k in kids] == ["serve.admit", "serve.prefill_chunk",
                                         "serve.ensure_blocks", "serve.fetch"]
    assert kids[-1]["what"] == "counts" and kids[-1]["tick"] == root["tick"]
    assert root["decode_batch"] == 0 and root["moe_pairs"] == 0
    assert eng._counted == []
    eng.close()


def test_the_pack_span_holds_the_operands_conversion(model, monkeypatch):
    """A decode tick's tables become the program's arrays inside
    ``serve.pack``; a prefill chunk's stay under its own span."""
    from apex_tpu.observe import spans
    eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=4,
                      prefill_chunk=4)
    seen = []
    tables = eng._tables

    def spy(packed):
        seen.append(spans._open.stack[-1]["span"])
        return tables(packed)
    monkeypatch.setattr(eng, "_tables", spy)
    out = eng.run([Request("a", [3, 4, 5, 6, 7, 8], 4)])
    assert len(out["a"]) == 4
    assert seen == ["serve.prefill_chunk"] * 2 + ["serve.pack"] * 3
    eng.block_pool.check_no_leaks()


# ---------------------------------------------------------------------------
# block tables: int32 rows patched where they change, packed by copies
# ---------------------------------------------------------------------------


def test_a_block_table_reads_as_a_list_and_keeps_its_ring():
    """A table reads as its list of Python ints however it grew, and its
    ring is the same whether an entry is retired before or after the
    block that takes its slot is appended."""
    from apex_tpu.serve import BlockTable
    t = BlockTable([7, 3])
    t.extend(list(range(10, 20)))
    t.append(99)                               # past two doublings
    want = [7, 3, *range(10, 20), 99]
    assert list(t) == want and len(t) == 13 and t[-1] == 99
    assert t[2:5] == [10, 11, 12] and type(t[0]) is int
    assert all(type(b) is int for b in t)
    assert t.ids.dtype == np.int32 and t.ids.tolist() == want
    rings = []
    for retire_first in (True, False):
        r = BlockTable([1, 2, 3, 4], ring=4)
        if retire_first:
            r[0] = NULL_BLOCK
            r.append(5)
        else:
            r.append(5)                        # takes block 1's slot
            r[0] = NULL_BLOCK
        assert list(r) == [NULL_BLOCK, 2, 3, 4, 5]
        rings.append(r.ring.tolist())
    assert rings == [[5, 2, 3, 4]] * 2
    r.clear()
    assert len(r) == 0 and r.ring.tolist() == [NULL_BLOCK] * 4


def _family_model(family, config):
    """The tiny model of a benchmark family (``perfbench/families``)."""
    import json
    import os
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "perfbench"))
    from pb import cells
    fam = cells.family_module(family)
    with open(os.path.join(repo, "perfbench", "configs",
                           f"{config}.json")) as f:
        cfg = json.load(f)
    cfg.update(fam.tiny(cfg))
    return fam.model(cfg)


def _churn_engine(model, case):
    """An engine whose scheduler the churn drives (nothing is
    dispatched): one group without a window, with the prefix cache and
    a draft's tables; one group with a window, with the prefix cache;
    the mixed model's full and window groups; the state model's group
    and slots."""
    from apex_tpu.inference import make_self_draft
    kw = {"num_blocks": 40, "block_size": 4, "max_batch": 6,
          "prefill_chunk": 8}
    if case == "full":
        return ServeEngine(model, draft=make_self_draft(model), spec_k=2,
                           **kw)
    if case == "window":
        return ServeEngine(model, window=8, **kw)
    if case == "full+window":
        return ServeEngine(_family_model(
            "gqa_moe", "mellum2-12b-a2.5b-l8"), **kw)
    return ServeEngine(_family_model(
        "hybrid_ssm_moe", "nemotron3-nano-30b-a3b-ep4-l13"), **kw)


def _plain_pack(sched, sessions, rows, group):
    """One cache group's packing as plain lists, from each table's
    entries: whole and padded to the next block bucket without a window,
    else the entries from the band on at ``i mod width``."""
    window, width = sched.windows[group], sched.ring[group]
    tables = [list(s.tables[group]) for s in sessions]
    if window is None:
        return _plain_whole(tables, rows)
    packed = []
    for s, t in zip(sessions, tables):
        row = [NULL_BLOCK] * width
        lo = min(window_retired_blocks(s.position, window,
                                       sched.pool.block_size), len(t))
        for i in range(lo, len(t)):
            row[i % width] = t[i]
        packed.append(row)
    return width, packed + [[NULL_BLOCK] * width] * (rows - len(sessions))


def _plain_whole(tables, rows):
    nb = bucket(max(len(t) for t in tables))
    return nb, [list(t) + [NULL_BLOCK] * (nb - len(t)) for t in tables] \
        + [[NULL_BLOCK] * nb] * (rows - len(tables))


def _assert_packs_plain(sched, sessions):
    """Every packing of ``sessions`` — a decode tick's, a prefill chunk's
    one row, a speculative tick's — equals the plain-list one, as int32
    arrays."""
    b, nb, _, _, tables = sched.pack_decode(sessions)
    for rows, (nbs, packed) in ((b, (nb, tables)),
                                (1, sched.pack_groups(sessions[:1], 1))):
        if len(sched.pools) == 1:
            nbs, packed = (nbs,), (packed,)
        for g, got in enumerate(packed):
            assert got.dtype == np.int32
            assert (nbs[g], got.tolist()) == \
                _plain_pack(sched, sessions[:rows], rows, g)
    b, nbt, nbd, _, _, t_tables, d_tables = sched.pack_spec(sessions)
    assert (nbt, t_tables.tolist()) == \
        _plain_whole([s.table for s in sessions], b)
    assert (nbd, d_tables.tolist()) == \
        _plain_whole([s.draft_table for s in sessions], b)


def _books_balance(sched):
    """Every table occurrence of a block is one reference of its
    group's pool (the draft tables draw on the first group's)."""
    from collections import Counter
    for g, pool in enumerate(sched.pools):
        occ = Counter(b for s in sched.sessions
                      for b in [*s.tables[g],
                                *(s.draft_table if g == 0 else [])]
                      if b != NULL_BLOCK)
        assert {b: pool.refcount(b) for b in occ} == dict(occ)
        assert len(occ) == pool.in_use


def _grow_or_preempt(sched, s, need):
    """``ServeEngine._grow_or_preempt`` at the scheduler level."""
    while not (sched.grow(s, need) and (not sched.spec_tables
                                        or sched.grow(s, need, draft=True))):
        if sched.preempt_for(s) is s:
            return False
    return True


@pytest.mark.parametrize("case", ["full", "window", "full+window", "state"])
def test_packed_tables_equal_a_plain_list_packing_under_churn(model, case):
    """A seeded churn through the engine's own scheduler — admission
    with prefix hits and copy-on-write forks (one group), growth, window
    retirement, preemption and eviction, finish, KV handoff imports (one
    group, no state) — and after every step the int32 tables that
    ``pack_decode``, ``pack_groups`` and ``pack_spec`` return equal a
    packing of plain lists."""
    eng = _churn_engine(model, case)
    sched = eng.scheduler
    windowed = any(w is not None for w in sched.windows)
    imports = len(sched.pools) == 1 and sched.slots is None
    rng = np.random.default_rng(38)
    vocab = 60
    templates = [[int(t) for t in rng.integers(1, vocab, n)]
                 for n in (4, 8, 13)]
    slack = sched.pos_slack
    top = min(sched.max_positions, 64) - slack
    n, i, tick = 120, 0, 0
    seen = {"hits": 0, "forks": 0, "imports": 0, "retired": 0, "evicts": 0}

    def request(rid):
        if sched.prefix_cache and rng.random() < 0.5:
            prompt = list(templates[int(rng.integers(len(templates)))])
            prompt += [int(t) for t in rng.integers(
                1, vocab, int(rng.integers(0, 3)))]
        else:
            prompt = [int(t) for t in rng.integers(
                1, vocab, int(rng.integers(1, 30)))]
        return Request(rid, prompt,
                       int(rng.integers(1, top - len(prompt))))

    while i < n or sched.has_work():
        tick += 1
        assert tick < 20_000, "churn failed to drain"
        for _ in range(int(rng.integers(0, 3))):
            if i < n:
                sched.submit(request(f"r{i}"))
                i += 1
        for s in sched.admit():
            seen["hits"] += s.prefix_hit_tokens > 0
            seen["forks"] += sched.complete_cow(s)
        _books_balance(sched)
        # one prefill chunk (ServeEngine._prefill)
        s = sched.next_prefill()
        if s is not None:
            t0 = s.position
            k = min(sched.prefill_chunk, s.prefill_remaining)
            last = t0 + k >= len(s.prefill_src)
            if not windowed or _grow_or_preempt(sched, s, t0 + k + last):
                _assert_packs_plain(sched, [s])
                s.position = s.draft_position = t0 + k
                seen["retired"] += sched.retire_window_blocks(s)
                sched.note_commit(s)
                if s.prefill_remaining == 0:
                    s.state = DECODE
                    if s.emit_on_prefill:
                        s.out.append(_sim_tok(s.position))
                        s.pending_tok = s.out[-1]
                        if s.finished():
                            sched.finish(s)
        # one decode tick (_ensure_decode_blocks, _decode_tick)
        for s in list(sched.decode_sessions()):
            if s.state == DECODE:
                _grow_or_preempt(sched, s, s.position + 1 + slack)
        live = sched.decode_sessions()
        if live:
            _assert_packs_plain(sched, live)
        for s in live:
            s.position += 1
            s.draft_position = s.position
            s.out.append(_sim_tok(s.position))
            s.pending_tok = s.out[-1]
            seen["retired"] += sched.retire_window_blocks(s)
            sched.note_commit(s)
            if s.finished():
                sched.finish(s)
        if live:
            _assert_packs_plain(sched, live)
        _books_balance(sched)
        if sched.sessions and rng.random() < 0.05:
            victim = sched.sessions[int(rng.integers(len(sched.sessions)))]
            sched.evict(victim)
            seen["evicts"] += 1
            sched.queue.append(victim)
        # a KV handoff's import (ServeEngine.ingest_handoff)
        if imports and rng.random() < 0.1 \
                and len(sched.sessions) < sched.max_batch:
            prompt = [int(t) for t in rng.integers(1, vocab, 12)]
            out = [int(t) for t in rng.integers(1, vocab, 9)]
            position = len(prompt) + len(out) - 1
            have = blocks_for(position, sched.pool.block_size)
            ids = sched.pool.alloc(have)
            draft = sched.pool.alloc(have) if sched.spec_tables else []
            if ids is None or draft is None:
                sched.pool.free(ids or [])
                sched.pool.free(draft or [])
            else:
                s = sched.import_session(
                    Request(f"h{tick}", prompt, len(out) + 8), ids, draft,
                    position)
                s.out, s.pending_tok = out, out[-1]
                seen["retired"] += sched.retire_window_blocks(s)
                seen["imports"] += 1
                _assert_packs_plain(sched, [s])
    for pool in sched.pools:
        pool.check_no_leaks()
    # the churn is not degenerate
    assert seen["evicts"] > 0
    assert (seen["imports"] > 0) == imports
    assert (seen["retired"] > 0) == windowed
    if sched.prefix_cache:
        assert seen["hits"] > 0 and seen["forks"] > 0


def test_a_decode_dispatch_gets_a_fresh_tables_operand(model):
    """The tables a decode tick hands its program are a fresh array,
    never a view of a session's table (JAX may alias a host array until
    it has read it): a table grown after the dispatch leaves the operand
    as it was."""
    eng = ServeEngine(model, num_blocks=64, block_size=4, max_batch=4,
                      prefill_chunk=8)
    handed = []
    tables = eng._tables

    def keep(packed):
        handed.append((packed, packed.copy()))
        return tables(packed)
    for i in range(3):
        eng.submit(Request(f"t{i}", [3 + i, 4, 5, 6, 7, 8, 9], 12))
    while len(eng.scheduler.decode_sessions()) < 3:
        eng.step()
    eng._tables = keep
    eng.step()
    (operand, was), = handed[-1:]
    sessions = eng.scheduler.decode_sessions()
    assert operand.tolist() == was.tolist()
    for s in sessions:
        assert not np.shares_memory(operand, s.table.ids)
        assert eng.scheduler.grow(s, s.position + 12)
    assert operand.tolist() == was.tolist()
    eng.run([])
    eng.block_pool.check_no_leaks()


def test_the_tables_counters_are_recorded(model):
    """``serve.tables.entries_patched`` counts every table entry written
    in place — admission's, growth's, retirement's, a released table's —
    and each ``serve.pack`` record carries ``entries``, the rows times
    the block buckets the tick packed (docs/observability.md)."""
    from apex_tpu.observe import spans
    patched = obs.counter("serve.tables.entries_patched")
    eng = ServeEngine(model, num_blocks=64, block_size=4, max_batch=4,
                      prefill_chunk=8, window=8, prefix_cache=False)
    sched = eng.scheduler
    was = patched.value
    sched.submit(Request("c", list(range(1, 11)), 30))
    s, = sched.admit()
    assert len(s.table) == 2 and patched.value - was == 2    # first chunk
    was = patched.value
    s.position = 8
    assert sched.grow(s, 24)                                 # 6 blocks
    assert patched.value - was == 4
    was = patched.value
    s.position = 20
    assert sched.retire_window_blocks(s) == 3
    assert patched.value - was == 3
    was = patched.value
    assert sched.grow(s, 24)                                 # covered
    assert patched.value == was
    sched.finish(s)
    assert patched.value - was == 6 and len(s.table) == 0
    # the engine: each decode tick's serve.pack record says what it packed
    packs = []
    pack_decode = sched.pack_decode

    def spy(sessions):
        b, nb, *rest = pack_decode(sessions)
        packs.append(b * nb)
        return (b, nb, *rest)
    sched.pack_decode = spy
    since = spans.recorded()[-1]["t0_ns"] + 1 if spans.recorded() else 0
    eng.run([Request(f"e{i}", [2 + i] * (3 + 5 * i), 9) for i in range(3)])
    recs = [r for r in spans.recorded(since) if r["span"] == "serve.pack"]
    assert packs and [r["entries"] for r in recs] == packs
    eng.block_pool.check_no_leaks()
