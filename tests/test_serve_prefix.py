"""Prefix cache (content-addressed KV block reuse): the pool's
refcount/cached-tier/hash-index invariants, and the acceptance pins —
warm-cache continuations BITWISE identical to the cold-prefill engine
for fp32 and int8 pools, with and without speculative decoding, under
forced preemption-recompute, across a weight-epoch invalidation, and
through a migration that re-links the hash chain into the survivor's
index."""
import os

import numpy as np
import pytest

import jax.numpy as jnp

from apex_tpu import nn
from apex_tpu.inference import make_self_draft
from apex_tpu.inference.session import PagedSession
from apex_tpu.models.gpt import GptModel
from apex_tpu.observe import registry as obs
from apex_tpu.serve import Request, ServeEngine
from apex_tpu.serve.disagg import DisaggregatedEngine
from apex_tpu.serve.pool import BlockPool, NULL_BLOCK, chain_key, chain_keys
from apex_tpu.serve.scheduler import DECODE, Session

pytestmark = pytest.mark.serve

#: 16 tokens = 2 full blocks at block_size 8 — block-aligned, so a
#: repeat submission is a FULL-chain hit and exercises the CoW fork
SHARED = list(range(1, 17))


@pytest.fixture(scope="module")
def model():
    nn.manual_seed(6)
    m = GptModel(vocab_size=73, hidden=32, layers=2, heads=4,
                 max_positions=96, dropout=0.0, attn_dropout=0.0)
    m.eval()
    return m


def _trace():
    """Three requests over one shared prefix: two suffix extensions
    (partial hits) and one exact block-aligned repeat (full hit →
    copy-on-write fork).  Staggered arrivals so each admission sees the
    previous request's committed blocks."""
    return ([Request("r0", SHARED + [20], 6),
             Request("r1", SHARED + [21, 22], 6),
             Request("r2", SHARED, 6)],
            [0, 6, 12])


def _run(model, prefix_cache, **kw):
    eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=4,
                      prefill_chunk=4, prefix_cache=prefix_cache, **kw)
    reqs, arrivals = _trace()
    out = eng.run(reqs, arrivals=arrivals)
    m = eng.metrics()["prefix_cache"]
    eng.close()
    return out, m


# ---------------------------------------------------------------------------
# the rolling chain key
# ---------------------------------------------------------------------------


def test_chain_keys_roll_over_full_blocks_only():
    assert chain_keys([1, 2, 3], 4, "t") == []          # no full block
    k1 = chain_keys([1, 2, 3, 4], 4, "t")
    k2 = chain_keys([1, 2, 3, 4, 5, 6, 7, 8, 9], 4, "t")
    assert len(k1) == 1 and len(k2) == 2                # partial tail out
    assert k2[0] == k1[0]                               # rolling prefix
    # the parent key, the tokens, and the tag each change the key
    assert chain_key("", [1, 2, 3, 4], "t") == k1[0]
    assert chain_key(k1[0], [5, 6, 7, 8], "t") == k2[1]
    assert chain_keys([1, 2, 3, 5], 4, "t") != k1
    assert chain_keys([1, 2, 3, 4], 4, "other") != k1


# ---------------------------------------------------------------------------
# pool: refcounts, cached tier, LRU eviction, leak accounting
# ---------------------------------------------------------------------------


def test_pool_shared_refcounts_and_double_free_raises():
    pool = BlockPool(num_blocks=8, block_size=4)
    ids = pool.alloc(2)
    keys = chain_keys([1, 2, 3, 4, 5, 6, 7, 8], 4, "t")
    assert pool.commit(ids[0], keys[0])
    assert pool.commit(ids[1], keys[1])
    # a second session adopts the whole chain: refcount 2 on both
    shared = pool.acquire_prefix(keys)
    assert shared == ids
    assert pool.refcount(ids[0]) == 2
    assert pool.in_use == 2               # held blocks, not references
    pool.free(shared)                     # second session done
    assert pool.refcount(ids[0]) == 1
    pool.free(ids)                        # first session done -> cached
    assert pool.in_use == 0 and pool.cached_count == 2
    # sharing never grants extra frees: the books are at zero
    with pytest.raises(ValueError):
        pool.free([ids[0]])
    pool.check_no_leaks()                 # cached blocks are not leaks


def test_pool_partial_chain_walk_stops_at_first_miss():
    pool = BlockPool(num_blocks=8, block_size=4)
    ids = pool.alloc(2)
    keys = chain_keys(list(range(1, 13)), 4, "t")       # 3 full blocks
    pool.commit(ids[0], keys[0])
    pool.commit(ids[1], keys[1])
    assert pool.acquire_prefix(keys) == ids             # 2 of 3 matched
    pool.free(ids)
    # a diverging chain shares only the first block
    other = chain_keys([1, 2, 3, 4, 9, 9, 9, 9], 4, "t")
    assert pool.acquire_prefix(other) == [ids[0]]
    pool.free([ids[0]])
    pool.free(ids)
    pool.check_no_leaks()


def test_pool_commit_first_writer_wins():
    pool = BlockPool(num_blocks=8, block_size=4)
    a, b = pool.alloc(2)
    assert pool.commit(a, "k")
    assert not pool.commit(b, "k")        # key taken
    assert not pool.commit(a, "k2")       # block already hashed
    assert not pool.commit(99, "k3")      # not held
    pool.free([a, b])
    assert pool.cached_count == 1         # only the hashed block retires
    pool.check_no_leaks()


def test_pool_lru_eviction_under_allocation_pressure():
    pool = BlockPool(num_blocks=6, block_size=4)        # 5 allocatable
    ids = pool.alloc(5)
    for i, b in enumerate(ids):
        pool.commit(b, f"k{i}")
    pool.free(ids)                        # retire in order: k0 oldest
    assert pool.cached_count == 5 and pool.free_count == 5
    got = pool.alloc(2)                   # evicts k0, k1 (LRU first)
    assert pool.cache_evictions == 2
    assert pool.acquire_prefix(["k0"]) == []            # entry gone
    assert pool.acquire_prefix(["k2"]) == [ids[2]]      # survivor lives
    pool.free([ids[2]])
    pool.free(got)
    pool.check_no_leaks()
    assert pool.alloc(6) is None          # capacity still all-or-nothing


def test_pool_flush_cache_reclaims_and_invalidates():
    pool = BlockPool(num_blocks=6, block_size=4)
    ids = pool.alloc(2)
    pool.commit(ids[0], "k0")
    pool.free(ids)
    assert pool.cached_count == 1
    assert pool.flush_cache() == 1
    assert pool.cached_count == 0 and pool.free_exact == 5
    assert pool.acquire_prefix(["k0"]) == []
    pool.check_no_leaks()


# ---------------------------------------------------------------------------
# acceptance: warm-cache continuations bitwise-equal to cold prefill
# ---------------------------------------------------------------------------


def test_warm_cache_bitwise_fp32(model):
    warm, mw = _run(model, True)
    cold, mc = _run(model, False)
    assert warm == cold                   # bitwise greedy parity
    assert mw["prefill_tokens_saved"] == 31   # 16 (partial) + 15 (full)
    assert mw["hit_rate"] > 0.5
    assert mw["cow_forks"] >= 1           # the full-chain hit forked
    assert mc == {"hit_rate": 0.0, "prefill_tokens_saved": 0,
                  "cached_blocks": 0, "cow_forks": 0,
                  "cache_evictions": 0}


def test_warm_cache_bitwise_int8(model):
    warm, mw = _run(model, True, cache_dtype="int8")
    cold, _ = _run(model, False, cache_dtype="int8")
    assert warm == cold
    assert mw["prefill_tokens_saved"] == 31 and mw["cow_forks"] >= 1


def test_warm_cache_bitwise_speculative(model):
    draft = make_self_draft(model)
    warm, mw = _run(model, True, draft=draft, spec_k=3)
    cold, _ = _run(model, False, draft=draft, spec_k=3)
    assert warm == cold
    assert mw["prefill_tokens_saved"] == 31 and mw["cow_forks"] >= 1


def test_preemption_recompute_with_cache_bitwise(model):
    """A pool too small for the live set forces preemption; recompute
    re-admission walks the chain and typically re-adopts its own
    just-retired blocks from the cached tier — either way the
    continuation is bitwise the no-preemption engine's."""
    obs.get_registry().reset()
    reqs = [Request(f"p{i}", [3 + i, 5, 7, 9], 8) for i in range(6)]
    small = ServeEngine(model, num_blocks=10, block_size=4, max_batch=4,
                        prefill_chunk=4)
    out = small.run(reqs)
    assert obs.counter("serve.preemptions").value > 0
    small.close()
    big = ServeEngine(model, num_blocks=64, block_size=4, max_batch=4,
                      prefill_chunk=4, prefix_cache=False)
    assert out == big.run(reqs)
    big.close()


def test_cache_eviction_pressure_no_leaks(model):
    """Distinct prompts churning through a small pool force cached-tier
    evictions; the drained pool still balances to zero leaks."""
    eng = ServeEngine(model, num_blocks=12, block_size=4, max_batch=2,
                      prefill_chunk=8)
    reqs = [Request(f"e{i}", [10 + i, 20 + i, 30 + i, 40 + i, 5 + i], 4)
            for i in range(12)]
    out = eng.run(reqs)
    assert len(out) == 12
    assert eng.metrics()["prefix_cache"]["cache_evictions"] > 0
    eng.close()                           # runs check_no_leaks


def test_epoch_invalidation_on_publish_weights(model):
    """publish_weights(target) re-tags the chain keys and flushes the
    cached tier: a post-swap duplicate of a pre-swap prompt must NOT
    hit (the entries describe KV computed under the old weights)."""
    eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=2,
                      prefill_chunk=8)
    eng.run([Request("a", SHARED + [20], 4)])
    assert eng.block_pool.cached_count > 0
    saved0 = eng.metrics()["prefix_cache"]["prefill_tokens_saved"]
    tag0 = eng.scheduler.cache_tag
    eng.publish_weights([p.data for p in model.parameters()])
    assert eng.scheduler.cache_tag != tag0
    assert eng.block_pool.cached_count == 0             # flushed
    eng.run([Request("b", SHARED + [20], 4)])
    m = eng.metrics()["prefix_cache"]
    assert m["prefill_tokens_saved"] == saved0          # no stale hit
    # the re-prefilled blocks re-commit under the NEW tag
    assert eng.block_pool.cached_count > 0
    eng.close()


def test_decode_stays_recompile_free_with_cache(model):
    from apex_tpu.runtime import step_cache as sc
    sc.reset_stats()
    sc.clear()
    eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=4,
                      prefill_chunk=4)
    reqs, arrivals = _trace()
    eng.run(reqs, arrivals=arrivals)
    eng.run([Request(f"x{i}", SHARED + [40 + i], 5) for i in range(4)])
    stats = sc.kind_stats("decode_step")
    # same bucket bound as the cache-off engine pins: occupancy
    # buckets {1,2,4} x table buckets — prefix hits change which rows
    # are warm, never the program shapes
    assert stats["compiles"] <= 6
    eng.close()


# ---------------------------------------------------------------------------
# the commit reads only the blocks that just filled
# ---------------------------------------------------------------------------


def _commits_through_fed_tokens(sched, s):
    """What one commit of ``s`` should do, computed the long way: over
    the whole ``fed_tokens`` tuple.  Returns the session's chain after
    it and the ``(block id, key)`` pairs it offers the pool, in order."""
    chain = list(s.hash_chain)
    if not sched.prefix_cache or not s.cacheable:
        return chain, []
    bs = sched.pool.block_size
    toks = s.fed_tokens
    full = min(s.position // bs, len(s.table), len(toks) // bs)
    offered = []
    for i in range(s.committed_blocks, full):
        chain.append(chain_key(chain[i - 1] if i else "",
                               toks[i * bs:(i + 1) * bs], sched.cache_tag))
        if s.table[i] != NULL_BLOCK and not any(
                idx == i for idx, _src, _dst in s.cow_pending):
            offered.append((s.table[i], chain[-1]))
    return chain, offered


def _serve_checked(eng, reqs):
    """Serve ``reqs`` tick by tick, holding every commit to the long
    way's chain and offers, and every live session's chain to
    ``chain_keys(fed_tokens)`` after each tick.  Returns the results
    and, for each commit of a decoding session, the blocks it chained."""
    sched = eng.scheduler
    bs, offers, decode_chained = sched.pool.block_size, [], []
    commit, note_commit = sched.pool.commit, sched.note_commit

    def spy_commit(block_id, key):
        offers.append((block_id, key))
        return commit(block_id, key)

    def checked(s):
        chain, want = _commits_through_fed_tokens(sched, s)
        start = len(offers)
        n = note_commit(s)
        assert offers[start:] == want
        assert s.hash_chain == chain and s.committed_blocks == len(chain)
        if s.state == DECODE:
            decode_chained.append(n)
        return n

    sched.pool.commit, sched.note_commit = spy_commit, checked
    for r in reqs:
        eng.submit(r)
    while True:
        more = eng.step()
        for s in sched.sessions:
            keys = chain_keys(s.fed_tokens, bs, sched.cache_tag)
            assert len(s.hash_chain) == s.committed_blocks
            assert s.hash_chain == keys[:s.committed_blocks]
            if s.state == DECODE:
                assert s.committed_blocks == len(keys)
        if not more:
            return dict(eng.results), decode_chained


@pytest.mark.parametrize("case", ["unaligned", "speculative", "recompute"])
def test_commit_matches_the_fed_tokens_chain(model, case):
    """``unaligned``: prompts of 5-7 tokens on blocks of 4, so the first
    block a session chains in decode straddles the prompt's end and its
    output.  ``speculative``: a tick advances up to ``spec_k + 1``
    positions, so on blocks of 2 one commit chains two blocks.
    ``recompute``: a pool too small for the live set preempts, and a
    re-admitted session re-prefills ``prompt + out[:-1]`` and chains on
    from its output."""
    obs.get_registry().reset()
    kw = dict(max_batch=4, prefill_chunk=4)
    if case == "unaligned":
        kw.update(num_blocks=64, block_size=4)
        reqs = [Request(f"u{i}", SHARED[:5 + i % 3], 11) for i in range(5)]
    elif case == "speculative":
        kw.update(num_blocks=96, block_size=2, spec_k=3)
        reqs = [Request(f"s{i}", SHARED[:3 + 2 * i], 9) for i in range(3)]
    else:
        kw.update(num_blocks=10, block_size=4)
        reqs = [Request(f"p{i}", [3 + i, 5, 7, 9, 11 + i], 8)
                for i in range(6)]
    draft = make_self_draft(model) if case == "speculative" else None
    eng = ServeEngine(model, draft=draft, **kw)
    out, decode_chained = _serve_checked(eng, reqs)
    assert sum(decode_chained) > 0
    if case == "unaligned":
        assert eng.metrics()["prefix_cache"]["prefill_tokens_saved"] > 0
    elif case == "speculative":
        assert max(decode_chained) >= 2
    else:
        admissions = [e["rid"] for e in obs.events("serve.request")
                      if e["phase"] == "prefill"]
        readmitted = {r for r in admissions if admissions.count(r) > 1}
        assert obs.counter("serve.preemptions").value > 0 and readmitted
        assert readmitted <= set(out)
    eng.close()
    cold = ServeEngine(model, draft=draft, prefix_cache=False, **kw)
    assert out == cold.run(reqs)
    cold.close()


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_decode_commit_never_builds_fed_tokens(model, monkeypatch, spec):
    """The tick's commit reads no ``Session.fed_tokens``: that tuple is
    the session's whole depth, rebuilt for every session on every tick."""
    calls = []
    fed_tokens = Session.fed_tokens

    def counted(s):
        calls.append(s.rid)
        return fed_tokens.fget(s)
    monkeypatch.setattr(Session, "fed_tokens", property(counted))
    kw = dict(draft=make_self_draft(model), spec_k=3) if spec else {}
    eng = ServeEngine(model, num_blocks=64, block_size=4, max_batch=4,
                      prefill_chunk=4, **kw)
    name = "_spec_tick" if spec else "_decode_tick"
    tick, in_ticks = getattr(eng, name), []

    def watched(sessions):
        was = len(calls)
        tick(sessions)
        in_ticks.append(len(calls) - was)
    setattr(eng, name, watched)
    chained = obs.counter("serve.prefix.blocks_chained")
    was = chained.value
    eng.run([Request(f"f{i}", SHARED[:5 + 3 * i], 12) for i in range(4)])
    assert in_ticks and sum(in_ticks) == 0
    assert chained.value > was              # the ticks did chain blocks
    Session(Request("x", [1, 2], 3), 0).fed_tokens
    assert calls                            # and a read would be seen
    eng.close()


def test_blocks_chained_counts_what_the_index_received(model):
    """``serve.prefix.blocks_chained`` is the number of blocks the
    commits offered the pool's hash index over the run; on this trace
    (shared prefixes adopted at admission, distinct outputs) the index
    took every one."""
    eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=4,
                      prefill_chunk=4)
    pool, received = eng.block_pool, []
    commit = pool.commit

    def spy(block_id, key):
        took = commit(block_id, key)
        received.append(took)
        return took
    pool.commit = spy
    chained = obs.counter("serve.prefix.blocks_chained")
    was = chained.value
    reqs, arrivals = _trace()
    eng.run(reqs, arrivals=arrivals)
    assert chained.value - was == len(received) == sum(received) > 0
    eng.close()


# ---------------------------------------------------------------------------
# PagedSession: a conversation replay is a natural prefix hit
# ---------------------------------------------------------------------------


def test_paged_session_replay_and_extension_hit(model):
    ref_eng = ServeEngine(model, num_blocks=64, block_size=8,
                          prefix_cache=False)
    with PagedSession(ref_eng) as rs:
        rs.append(SHARED)
        ref = np.asarray(rs.generate(6)).tolist()
    eng = ServeEngine(model, num_blocks=64, block_size=8)
    with PagedSession(eng) as s1:
        s1.append(SHARED)
        assert np.asarray(s1.generate(6)).tolist() == ref
    assert eng.block_pool.cached_count >= 2   # committed blocks retired
    with PagedSession(eng) as s2:             # exact replay: full hit
        s2.append(SHARED)
        assert s2.position == len(SHARED)     # one re-ingested token
        assert np.asarray(s2.generate(6)).tolist() == ref
    assert eng._cow_forks >= 1
    with PagedSession(eng) as s3:             # extension: partial hit
        s3.append(SHARED + [20, 21])
        got = np.asarray(s3.generate(4)).tolist()
    with PagedSession(ref_eng) as rs2:
        rs2.append(SHARED + [20, 21])
        assert got == np.asarray(rs2.generate(4)).tolist()
    eng.block_pool.check_no_leaks()
    ref_eng.block_pool.check_no_leaks()


# ---------------------------------------------------------------------------
# disaggregation + migration: the chain rides the manifest
# ---------------------------------------------------------------------------


def test_disagg_shared_prefix_bitwise_and_hits(model):
    reqs, arrivals = _trace()
    dis = DisaggregatedEngine(model, num_blocks=64, block_size=8,
                              max_batch=4, prefill_chunk=4)
    out = dis.run(reqs, arrivals=arrivals)
    cold, _ = _run(model, False)
    assert out == cold
    # the PREFILL engine is where admission walks the chain
    pm = dis.prefill.metrics()["prefix_cache"]
    assert pm["prefill_tokens_saved"] == 31
    # handed-off chains re-linked into the decode engine's index and
    # retired to its cached tier as sessions finished
    assert dis.decode.block_pool.cached_count > 0
    dis.prefill.close()
    dis.decode.close()


def test_migration_relinks_chain_into_survivor(model, tmp_path):
    """Stream a mid-decode session off engine A (manifest carries its
    hash chain + weight epoch) and adopt it on engine B: the chain
    re-links into B's index, so B's next same-prefix admission hits —
    and the continuation is bitwise the uninterrupted engine's."""
    from apex_tpu.runtime.resilience import stream_kv_handoff
    ref = ServeEngine(model, num_blocks=64, block_size=8,
                      prefix_cache=False)
    full = ref.run([Request("m", SHARED + [20], 8)])["m"]
    ref.close()

    a = ServeEngine(model, num_blocks=64, block_size=8, prefill_chunk=8)
    a.submit(Request("m", SHARED + [20], 8))
    for _ in range(6):
        a.step()
    (s,) = a.scheduler.sessions
    assert s.committed_blocks >= 2 and 0 < len(s.out) < 8
    d = os.path.join(str(tmp_path), "mig")
    stream_kv_handoff(d, a.pool, s.table, source="test:mig")
    chain, epoch, out, pend, pos = (list(s.hash_chain), s.weight_epoch,
                                    list(s.out), s.pending_tok,
                                    s.position)
    a.close()

    b = ServeEngine(model, num_blocks=64, block_size=8, prefill_chunk=8)
    sess = b.ingest_handoff(Request("m", SHARED + [20], 8), out=out,
                            pending_tok=pend, position=pos,
                            handoff_dir=d, n_blocks=len(s.table) or None,
                            hash_chain=chain, weight_epoch=epoch)
    assert sess is not None and sess.cacheable
    assert sess.committed_blocks == len(chain)
    while b.scheduler.has_work():
        b.step()
    assert b.results["m"] == full         # bitwise continuation
    # the re-linked chain is live in B's index: a same-prefix request
    # admits with its prefix already cached
    b.run([Request("m2", SHARED + [20], 4)])
    assert b.metrics()["prefix_cache"]["prefill_tokens_saved"] > 0
    b.close()


def test_migration_epoch_mismatch_never_cached(model, tmp_path):
    """An adopted session whose chain was built under a DIFFERENT
    target epoch keeps serving (mixed-weight semantics) but its blocks
    must never enter the survivor's hash index."""
    from apex_tpu.runtime.resilience import stream_kv_handoff
    a = ServeEngine(model, num_blocks=64, block_size=8, prefill_chunk=8)
    a.submit(Request("m", SHARED + [20], 8))
    for _ in range(6):
        a.step()
    (s,) = a.scheduler.sessions
    d = os.path.join(str(tmp_path), "mig2")
    stream_kv_handoff(d, a.pool, s.table, source="test:mig2")
    chain, out, pend, pos, nb = (list(s.hash_chain), list(s.out),
                                 s.pending_tok, s.position, len(s.table))
    a.close()
    b = ServeEngine(model, num_blocks=64, block_size=8, prefill_chunk=8)
    sess = b.ingest_handoff(Request("m", SHARED + [20], 8), out=out,
                            pending_tok=pend, position=pos,
                            handoff_dir=d, n_blocks=nb,
                            hash_chain=chain, weight_epoch=7)   # stale
    assert sess is not None and not sess.cacheable
    while b.scheduler.has_work():
        b.step()
    assert b.block_pool.cached_count == 0     # nothing was published
    b.block_pool.check_no_leaks()
    b.close()
