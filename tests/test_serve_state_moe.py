"""A state a session beside the rows a token: the state-space hybrid
family (``models/hybrid_ssm_moe.py``: Mamba-2 layers, experts that are
not gated, attention that rotates nothing) through the serve programs and
the engine, against the family's plain reference
(``perfbench/pb/reference_hybrid_ssm_moe.py``: the recurrence one
position after another) on *logits*; slots, the zero start, preemption;
the ungated experts against a hand-worked case; and what a model with no
state layer is handed."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from pb import cells  # noqa: E402

from apex_tpu.kernels.dispatch import force_mode  # noqa: E402
from apex_tpu.nn.modules import Ctx  # noqa: E402
from apex_tpu.parallel.routed_experts import RoutedExperts  # noqa: E402
from apex_tpu.serve import Request, ServeEngine  # noqa: E402
from apex_tpu.serve import kernels as sk  # noqa: E402
from apex_tpu.serve.pool import (BlockPool, SlotPool,  # noqa: E402
                                 init_pool_buffer, init_state_buffers)
from apex_tpu.serve.scheduler import Scheduler  # noqa: E402

FAMILY = cells.family_module("hybrid_ssm_moe")
CONFIG = os.path.join(REPO, "perfbench", "configs",
                      "nemotron3-nano-30b-a3b-ep4-l13.json")
BS, CHUNK = 4, 16

# float32 against float32: what differs is the order of summation (the
# chunked form's cumulative decay sums against the recurrence step by
# step, a blockwise online softmax against one softmax over a masked
# row, sorted pairs against a masked sum over experts): 2e-5 on logits
# of size ~1, the tolerance of tests/test_serve_paged.py
TOL = 2e-5


def _tiny_cfg(**over):
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(FAMILY.tiny(cfg))
    cfg.update(over)
    return cfg


def _served(cfg, seed=3):
    """``(model, leaves)``: the family's model with seeded float32
    leaves in it, and the leaves by name for the reference."""
    leaves = FAMILY.draw(cfg, jax.random.PRNGKey(seed), jnp.float32)
    model = FAMILY.model(cfg)
    for p, v in zip(model.parameters(), FAMILY.to_program(cfg)(leaves)):
        p.data = v
    model.eval()
    return model, leaves


def _reference():
    return cells._module_from(os.path.join(
        REPO, "perfbench", "pb", "reference_hybrid_ssm_moe.py"), "reference")


def _reference_logits(cfg, leaves, toks):
    lg, _ = _reference().logits(cfg, leaves, jnp.asarray([toks], jnp.int32))
    return np.asarray(lg[0], np.float32)


def _toks(seed, n, vocab):
    return [int(t) for t in np.random.default_rng(seed).integers(1, vocab, n)]


class _Hand:
    """The serve programs driven by hand, logits kept: the scheduler's
    own admission, slots, growth and packing, as ``ServeEngine`` drives
    them."""

    def __init__(self, model, max_batch=2, blocks=60):
        self.model = model
        params = list(model.parameters()) + list(model.buffers())
        self.vals = [p.data for p in params]
        group, = sk.cache_groups(model)[0]
        self.sgroups, _ = sk.state_groups(model)
        self.pool = init_pool_buffer(
            len(group.layers), group.rows[1], group.rows[2], blocks, BS,
            jnp.float32, streams=group.rows[0])
        self.states = tuple(init_state_buffers(g.state, len(g.layers),
                                               max_batch)
                            for g in self.sgroups)
        self.bp = BlockPool(blocks, BS)
        self.sched = Scheduler(
            self.bp, max_batch=max_batch, prefill_chunk=CHUNK,
            max_prefill_backlog=4 * CHUNK, max_positions=model.max_positions,
            prefix_cache=False, state_slots=True)
        self.prefill = jax.jit(sk.build_prefill_fn(model, params, BS, 0))
        self.decode = jax.jit(sk.build_decode_fn(model, params, BS, 0))

    def ingest(self, rid, toks):
        """Admit and prefill ``toks`` in chunks -> the last logits."""
        self.sched.submit(Request(rid, toks, 60))
        s, = self.sched.admit()
        last = None
        while s.prefill_remaining > 0:
            t0 = s.position
            n = min(CHUNK, s.prefill_remaining)
            part = list(toks[t0:t0 + n])
            last, self.pool, _, self.states = self.prefill(
                self.vals, self.pool, self.states,
                jnp.asarray([part + [0] * (CHUNK - n)], jnp.int32),
                jnp.asarray(self.sched.pack_tables([s], 1)[1], jnp.int32),
                jnp.int32(t0), jnp.int32(n), jnp.asarray([s.slot], jnp.int32))
            s.position = t0 + n
        s.state = "decode"
        return s, np.asarray(last[0], np.float32)

    def step(self, feeds):
        """One decode tick: ``feeds`` = ``[(session, token)]`` -> logits
        ``(len(feeds), V)``."""
        sessions = [s for s, _ in feeds]
        for s, tok in feeds:
            s.pending_tok = tok
            assert self.sched.grow(s, s.position + 1)
        b, _, tokens, positions, tables = self.sched.pack_decode(sessions)
        _, logits, self.pool, _, self.states = self.decode(
            self.vals, self.pool, self.states, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(tables, jnp.int32),
            jnp.asarray(self.sched.pack_slots(sessions, b), jnp.int32))
        for s in sessions:
            s.position += 1
        return np.asarray(logits, np.float32)[:len(feeds)]


# -- (a) prefill in chunks, then decode, through the state and the pool ---------


@pytest.mark.parametrize("first,second", [(35, 16), (13, 32), (24, 7)],
                         ids=["tail_3_of_16", "under_a_chunk", "whole_scans"])
def test_state_and_pool_logits_match_the_reference_forward(first, second):
    """Two sessions, the second joining while the first decodes.  Prompt
    lengths that are and are not multiples of the scan's chunk (8) and of
    the prefill chunk (16), so that a padded tail occurs, and one that is
    shorter than a chunk; then decoding through the state, alone (a
    bucket of one) and together (two rows of a bucket of two)."""
    cfg = _tiny_cfg()
    model, leaves = _served(cfg)
    vocab = cfg["vocab_size"]
    a, b = _toks(1, first + 30, vocab), _toks(2, second + 12, vocab)
    ref_a = _reference_logits(cfg, leaves, a)
    ref_b = _reference_logits(cfg, leaves, b)
    hand = _Hand(model, max_batch=4)
    # (the two expert layers keep nothing and are in no group)
    assert [(len(g.layers), len(g.state)) for g in hand.sgroups] == [(2, 2)]
    sa, last = hand.ingest("a", a[:first])
    np.testing.assert_allclose(last, ref_a[first - 1], atol=TOL)
    for t in range(first, first + 10):
        lg = hand.step([(sa, a[t])])
        np.testing.assert_allclose(lg[0], ref_a[t], atol=TOL)
    sb, last = hand.ingest("b", b[:second])
    np.testing.assert_allclose(last, ref_b[second - 1], atol=TOL)
    for i in range(12):
        feeds = [(sa, a[first + 10 + i]), (sb, b[second + i])]
        lg = hand.step(feeds)
        np.testing.assert_allclose(lg[0], ref_a[first + 10 + i], atol=TOL)
        np.testing.assert_allclose(lg[1], ref_b[second + i], atol=TOL)
    assert sa.slot != sb.slot and hand.sched.slots.in_use == 2
    for s in (sa, sb):
        hand.sched.finish(s)
    hand.bp.check_no_leaks()
    hand.sched.slots.check_no_leaks()


def test_the_models_own_forward_is_the_reference():
    """``HybridSsmMoeModel.forward`` (no cache: the chunked scan from a
    zero state, the chunk reader over the sequence itself, the grouped
    matmul's XLA tier) against the plain reference."""
    cfg = _tiny_cfg()
    model, leaves = _served(cfg)
    toks = _toks(7, 53, cfg["vocab_size"])
    params = list(model.parameters())
    ctx = Ctx(env={id(p): p.data for p in params}, stats_out={},
              training=False)
    got = model.forward(ctx, jnp.asarray([toks], jnp.int32))[0]
    np.testing.assert_allclose(got, _reference_logits(cfg, leaves, toks),
                               atol=TOL)


def test_the_kernel_tier_serves_the_same_logits():
    """The decode step through ``ssm_state_update`` in ``interpret`` mode
    (and the other kernels that mode turns on) against the reference."""
    cfg = _tiny_cfg()
    model, leaves = _served(cfg)
    a = _toks(4, 30, cfg["vocab_size"])
    ref = _reference_logits(cfg, leaves, a)
    with force_mode("interpret"):
        hand = _Hand(model)
        sa, last = hand.ingest("a", a[:21])
        np.testing.assert_allclose(last, ref[20], atol=TOL)
        for t in range(21, 30):
            np.testing.assert_allclose(hand.step([(sa, a[t])])[0], ref[t],
                                       atol=TOL)


# -- (b) slots: reuse, preemption, what padding touches -------------------------


def _engine(model, **kw):
    kw = dict(dict(num_blocks=48, block_size=BS, max_batch=2,
                   prefill_chunk=CHUNK), **kw)
    return ServeEngine(model, **kw)


def _greedy(cfg, leaves, prompt, n):
    """``n`` tokens the reference's own forward chooses after ``prompt``
    (one full pass a token: no cache anywhere)."""
    toks = list(prompt)
    for _ in range(n):
        toks.append(int(np.argmax(_reference_logits(cfg, leaves, toks)[-1])))
    return toks[len(prompt):]


def test_a_reused_slot_starts_from_zero():
    """One slot, three sessions one after another: each is served what a
    fresh engine serves it (the reference's own greedy tokens), though
    the slot holds its predecessor's state when it is taken."""
    cfg = _tiny_cfg()
    model, leaves = _served(cfg)
    vocab = cfg["vocab_size"]
    prompts = [_toks(10 + i, n, vocab) for i, n in enumerate((21, 9, 33))]
    eng = _engine(model, max_batch=1)
    for i, p in enumerate(prompts):
        eng.submit(Request(f"r{i}", p, 6))
    while eng.step():
        pass
    assert eng.scheduler.slots.started == 3
    assert float(jnp.abs(eng.states[0][0][:, 0]).max()) > 0    # left behind
    for i, p in enumerate(prompts):
        assert eng.results[f"r{i}"] == _greedy(cfg, leaves, p, 6), i
    eng.close()


#: the faults of a state a session, planted in the model's own layers:
#: the same functions the chip's readings plant
#: (perfbench/tools/readings_state.py)
FAULTS = cells._module_from(os.path.join(
    REPO, "perfbench", "tools", "readings_state.py"), "tool").MODEL_FAULTS


def _second_session_error(fault):
    """The largest error of the logits served to a session that takes a
    slot another session held, with ``fault`` planted."""
    cfg = _tiny_cfg()
    model, leaves = _served(cfg)
    vocab = cfg["vocab_size"]
    a, b = _toks(1, 40, vocab), _toks(2, 40, vocab)
    ref_b = _reference_logits(cfg, leaves, b)
    hand = _Hand(model, max_batch=1)
    if fault is not None:
        FAULTS[fault](model)
    sa, _ = hand.ingest("a", a[:21])
    for t in range(21, 30):
        hand.step([(sa, a[t])])
    held = sa.slot
    hand.sched.finish(sa)
    sb, last = hand.ingest("b", b[:19])         # a padded tail of 13 rows
    assert sb.slot == held == 0
    errs = [np.abs(last - ref_b[18]).max()]
    for t in range(19, 30):
        errs.append(np.abs(hand.step([(sb, b[t])])[0] - ref_b[t]).max())
    return max(errs)


def test_a_reused_slot_serves_the_logits_of_a_fresh_one():
    assert _second_session_error(None) <= TOL


@pytest.mark.parametrize("fault", sorted(set(FAULTS) - {"bf16_state"}))
def test_a_planted_fault_moves_the_logits(fault):
    """Each way a state a session can go wrong, planted in the program,
    moves a logit by fifty times the tolerance the tests above hold the
    program to: they would not pass with it.  (A state rounded to
    bfloat16 moves these logits by 1.5e-5, under that tolerance: no
    logit or token holds the state's precision, and the benchmark
    compares the state itself: ``state_gap``,
    tests/perfbench/test_perfbench_hybrid_ssm_moe.py.)"""
    assert _second_session_error(fault) > 50 * TOL


def test_a_preempted_session_continues_and_everything_comes_back():
    """A pool too small for two long sessions: the newer one is
    preempted, its slot and blocks freed, and it re-prefills ``prompt +
    out[:-1]`` into a slot that starts from zero; both are served the
    reference's greedy tokens, and at the end every block and every slot
    is free again."""
    cfg = _tiny_cfg()
    model, leaves = _served(cfg)
    vocab = cfg["vocab_size"]
    prompts = [_toks(20, 30, vocab), _toks(21, 26, vocab)]
    eng = _engine(model, num_blocks=20)
    for i, p in enumerate(prompts):
        eng.submit(Request(f"r{i}", p, 24))
    preempted = 0
    while eng.step():
        preempted += sum(s.state == "queued" and bool(s.out)
                         for s in eng.scheduler.queue)
        assert eng.scheduler.slots.in_use == len(eng.scheduler.sessions)
    assert preempted > 0
    assert eng.scheduler.slots.started > 2
    for i, p in enumerate(prompts):
        assert eng.results[f"r{i}"] == _greedy(cfg, leaves, p, 24), i
    assert eng.block_pool.in_use == 0 and eng.scheduler.slots.in_use == 0
    eng.close()


def test_padding_rows_touch_only_the_null_slot():
    """Three sessions in a batch bucket of four: the fourth row reads and
    writes the null slot (the last row of each state buffer) and no
    session's; a slot no session holds stays as it was."""
    cfg = _tiny_cfg()
    model, _ = _served(cfg)
    vocab = cfg["vocab_size"]
    hand = _Hand(model, max_batch=5)
    sessions = [hand.ingest(f"s{i}", _toks(30 + i, 9 + i, vocab))[0]
                for i in range(3)]
    held = {s.slot for s in sessions}
    null = hand.sched.slots.null
    free = sorted(set(range(5)) - held)
    assert len(held) == 3 and null == 5 and len(free) == 2
    before = [np.asarray(b) for b in hand.states[0]]
    hand.step([(s, 1 + i) for i, s in enumerate(sessions)])
    after = [np.asarray(b) for b in hand.states[0]]
    for was, now in zip(before, after):
        for slot in free:                       # untouched
            np.testing.assert_array_equal(now[:, slot], was[:, slot])
        for slot in held:                       # stepped
            assert np.abs(now[:, slot] - was[:, slot]).max() > 0
    assert np.abs(after[0][:, null] - before[0][:, null]).max() > 0


def test_what_is_selected_off_and_why():
    cfg = _tiny_cfg()
    model, _ = _served(cfg)
    eng = _engine(model, prefix_cache=True)
    assert eng.scheduler.prefix_cache is False          # no state snapshot
    assert [len(s) for s in eng.states] == [2]      # the Mamba layers' group
    assert eng.states[0][0].shape == (2, 3, 16, 256)    # max_batch + 1 slots
    assert eng.states[0][0].dtype == jnp.float32
    with pytest.raises(NotImplementedError, match="rolled back"):
        _engine(model, draft=model)
    with pytest.raises(NotImplementedError, match="state a session"):
        eng.ingest_handoff(Request("h", [1, 2], 2), out=[3], pending_tok=3,
                           position=2, handoff_dir="/nonexistent")
    with pytest.raises(ValueError, match="serve without them"):
        Scheduler(BlockPool(8, BS), max_batch=2, prefill_chunk=4,
                  max_prefill_backlog=8, max_positions=64, state_slots=True)
    # an int8 cache quantises the rows, not the state; bfloat16 leaves
    # keep the convolution's inputs in bfloat16 and the state float32
    q = _engine(model, cache_dtype="int8")
    assert q.states[0][0].dtype == jnp.float32
    assert q.states[0][1].dtype == jnp.float32          # the leaves' own
    for p in model.parameters():
        p.data = p.data.astype(jnp.bfloat16)
    half = _engine(model)
    assert [b.dtype for b in half.states[0]] == [jnp.float32, jnp.bfloat16]
    pool = SlotPool(2)
    a, b = pool.take(), pool.take()
    assert {a, b} == {0, 1} and pool.null == 2
    pool.give(a)
    with pytest.raises(ValueError, match="not held"):
        pool.give(a)
    with pytest.raises(AssertionError, match="slot leak"):
        pool.check_no_leaks()


def test_a_model_of_attention_and_expert_layers_keeps_what_it_had():
    """An expert layer that is a layer of its own keeps nothing: it rides
    the state half of the protocol and joins no state group, so a model of
    attention and such layers is handed no state buffer and no slots, its
    programs take what a model of rows alone takes, and it keeps its
    prefix cache (a second session of the same prompt prefills less)."""
    cfg = _tiny_cfg(**{FAMILY.LAYERS: 4, "hybrid_override_pattern": "*E*E"})
    model, leaves = _served(cfg)
    assert sk.state_groups(model) == ([], [None] * 4)
    assert sk.cache_groups(model)[1] == [(0, 0), None, (0, 1), None]
    eng = _engine(model, prefix_cache=True)
    assert eng.scheduler.prefix_cache is True
    assert eng.scheduler.slots is None
    assert eng.states == [] and eng._states() == ()
    params = list(model.parameters()) + list(model.buffers())
    decode = sk.build_decode_fn(model, params, BS, 48)
    assert decode.__code__.co_varnames[:decode.__code__.co_argcount] == \
        ("vals", "pool", "tokens", "positions", "tables")
    prompt = _toks(5, 21, cfg["vocab_size"])
    for rid in "ab":
        eng.submit(Request(rid, prompt, 5))
        while eng.step():
            pass
    assert eng._prefill_tokens_saved >= 4 * BS
    assert eng.results["a"] == eng.results["b"] == \
        _greedy(cfg, leaves, prompt, 5)
    eng.close()


def test_a_block_that_keeps_neither_is_refused_by_name():
    cfg = _tiny_cfg()
    model, _ = _served(cfg)
    model.blocks[0].__class__ = type("Bare", (object,), {
        "cache_rows": None, "state": (), "finish": None})
    with pytest.raises(ValueError, match="state, step, chunk, finish"):
        _engine(model)


# -- (c) experts that are not gated: a hand-worked case -------------------------


def test_ungated_experts_against_a_hand_worked_example():
    """4 tokens, 8 experts, 2 a token, sigmoid scores with a correction
    bias for the choice only, weights normalised over the chosen and
    scaled by 2.5; an expert is ``W_out relu(W_in x)^2`` with ``W_in``
    kept ``(out, in)``.  Every number below is worked by hand from the
    definitions (numpy, one expert at a time)."""
    rng = np.random.default_rng(12)
    e, i, n, k = 6, 5, 8, 2
    x = rng.standard_normal((4, e)).astype(np.float32)
    router = rng.standard_normal((n, e)).astype(np.float32)
    bias = (rng.standard_normal(n) * 0.5).astype(np.float32)
    w_in = rng.standard_normal((n, i, e)).astype(np.float32)
    w_out = rng.standard_normal((n, i, e)).astype(np.float32)
    mod = RoutedExperts(e, i, n, k, scale=2.5, gated=False, act="relu2")
    assert mod.w_in.shape == (n, i, e) and mod.w_out.shape == (n, i, e)
    for name, v in (("router", router), ("router_bias", bias),
                    ("w_in", w_in), ("w_out", w_out)):
        getattr(mod, name).data = jnp.asarray(v)
    y, pairs = mod.forward(Ctx(training=False), jnp.asarray(x))
    want = np.zeros((4, e), np.float64)
    counts = np.zeros(n, int)
    for t in range(4):
        s = 1.0 / (1.0 + np.exp(-(router.astype(np.float64) @ x[t])))
        chosen = np.argsort(-(s + bias))[:k]
        assert s[chosen[0]] + bias[chosen[0]] >= s[chosen[1]] + bias[chosen[1]]
        w = s[chosen] / s[chosen].sum() * 2.5
        for ex, wt in zip(chosen, w):
            h = np.maximum(w_in[ex].astype(np.float64) @ x[t], 0.0) ** 2
            want[t] += wt * (h @ w_out[ex])
            counts[ex] += 1
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(pairs), counts)
    assert counts.sum() == 8
    with pytest.raises(ValueError, match="act is one of"):
        RoutedExperts(e, i, n, k, act="gelu")
    # the gated default is what it was: gate | up side by side, (in, out)
    gated = RoutedExperts(e, i, n, k)
    assert gated.w_in.shape == (n, e, 2 * i) and gated.act == "silu"


# -- (f) a model with no state layer is handed no state ----------------------------


def _tree(x):
    return jax.tree.map(lambda a: (a.shape, str(a.dtype)), x)


def test_a_model_without_state_layers_takes_and_returns_what_it_did():
    """The GPT block's programs: ``fn(vals, pool, tokens, positions,
    tables) -> (next, logits, pool, counted)`` and ``fn(vals, pool, toks,
    table, t0, n_real) -> (last, pool, counted)``: no state operand, no
    state result, and the engine keeps no slots and no state buffer."""
    from apex_tpu.models import GptModel
    gpt = GptModel(vocab_size=97, hidden=32, layers=2, heads=2,
                   max_positions=64).eval()
    assert sk.state_groups(gpt) == ([], [None, None])
    eng = ServeEngine(gpt, num_blocks=16, block_size=4, max_batch=2,
                      prefill_chunk=4)
    assert eng.states == [] and eng.state_groups == []
    assert eng.scheduler.slots is None and eng._states() == ()
    assert eng.pool is eng.pools[0] and eng.block_pool is eng.block_pools[0]
    params = list(gpt.parameters()) + list(gpt.buffers())
    vals = [p.data for p in params]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    decode = sk.build_decode_fn(gpt, params, 4, 16)
    prefill = sk.build_prefill_fn(gpt, params, 4, 16)
    assert decode.__code__.co_varnames[:decode.__code__.co_argcount] == \
        ("vals", "pool", "tokens", "positions", "tables")
    assert prefill.__code__.co_varnames[:prefill.__code__.co_argcount] == \
        ("vals", "pool", "toks", "table", "t0", "n_real")
    pool = _tree(eng.pool)
    out = jax.eval_shape(decode, vals, eng.pool, i32(2), i32(2), i32(2, 4))
    assert _tree(out) == (((2,), "int32"), ((2, 97), "float32"), pool, None)
    out = jax.eval_shape(prefill, vals, eng.pool, i32(1, 4), i32(1, 4),
                         i32(), i32())
    assert _tree(out) == (((1, 97), "float32"), pool, None)
    eng.submit(Request("r", [5, 6, 7], 4))
    while eng.step():
        pass
    assert len(eng.results["r"]) == 4
    assert all(s.slot is None for s in eng.scheduler.sessions)


def test_the_state_models_programs_take_and_return_the_state_beside_the_pool():
    cfg = _tiny_cfg()
    model, _ = _served(cfg)
    eng = _engine(model)
    params = list(model.parameters()) + list(model.buffers())
    vals = [p.data for p in params]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    states = tuple(eng.states)
    decode = sk.build_decode_fn(model, params, BS, 48)
    out = jax.eval_shape(decode, vals, eng.pool, states, i32(2), i32(2),
                         i32(2, 4), i32(2))
    assert _tree(out[2]) == _tree(eng.pool)
    assert _tree(out[4]) == _tree(states)
    assert out[3].shape == (2, 4)                # two E layers, 4 held experts
    prefill = sk.build_prefill_fn(model, params, BS, 48)
    out = jax.eval_shape(prefill, vals, eng.pool, states, i32(1, CHUNK),
                         i32(1, 8), i32(), i32(), i32(1))
    assert _tree(out[1]) == _tree(eng.pool) and _tree(out[3]) == _tree(states)
    # the tick's record carries the state-space counters, and only here
    import time

    from apex_tpu.observe import spans
    since = time.perf_counter_ns()
    eng.submit(Request("r", [5, 6, 7, 8, 9], 3))
    while eng.step():
        pass
    ticks = [r for r in spans.recorded(since) if r.get("span") == "serve.step"
             and r.get("decode_batch")]
    assert ticks and all(
        (r["ssm_sessions"], r["ssm_layers"], r["ssm_state_bytes"]) ==
        (1, 2, 2 * (16 * 256 * 4 + 3 * 320 * 4)) for r in ticks)
    assert all("moe_pairs" in r for r in ticks)
