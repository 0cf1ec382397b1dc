"""The grouped-query mixture-of-experts family whose layers mix window
and full attention, on the serve path, at a small size on the CPU,
float32, seeded random weights: a cache of two groups under one
scheduler, grouped-query heads in the pool and its reader, a softmax
top-k router.

The yardstick is the benchmark's plain reference of the architecture
(``perfbench/pb/reference_gqa_moe.py``: full causal attention with the
band as a mask, every expert computed for every token, no cache), which
imports nothing of the program; the program's parameters carry the
reference's leaf names, so one set of arrays feeds both.  Logits are
compared, never sampled tokens.
"""
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from pb import cells  # noqa: E402

from apex_tpu.kernels import grouped_matmul as gm  # noqa: E402
from apex_tpu.kernels import paged_attention as pa  # noqa: E402
from apex_tpu.kernels.dispatch import force_mode  # noqa: E402
from apex_tpu.nn.modules import Ctx  # noqa: E402
from apex_tpu.parallel.routed_experts import (RoutedExperts,  # noqa: E402
                                              softmax_topk_route)
from apex_tpu.serve import Request, ServeEngine  # noqa: E402
from apex_tpu.serve import kernels as sk  # noqa: E402
from apex_tpu.serve.pool import (NULL_BLOCK, BlockPool,  # noqa: E402
                                 init_pool_buffer)
from apex_tpu.serve.scheduler import Scheduler  # noqa: E402

FAMILY = cells.family_module("gqa_moe")
CONFIG = os.path.join(REPO, "perfbench", "configs",
                      "mellum2-12b-a2.5b-l8.json")
BS, CHUNK, WINDOW = 4, 8, 16

# float32 against float32: what differs is the order of summation (a
# blockwise online softmax over the band's blocks against one softmax
# over a masked row; sorted pairs against a masked sum over experts):
# 2e-5 on logits of size ~1, the tolerance of tests/test_serve_paged.py
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


def _reference_logits(cfg, leaves, toks):
    ref = cells._module_from(os.path.join(REPO, cfg["reference"]),
                             "reference")
    lg, _ = ref.logits(cfg, leaves, jnp.asarray([toks], jnp.int32))
    return np.asarray(lg[0], np.float32)


def _toks(seed, n, vocab):
    return [int(t) for t in np.random.default_rng(seed).integers(1, vocab, n)]


class _Hand:
    """The serve programs driven by hand, logits kept: the scheduler's
    own admission, growth, retirement and packing over one block pool a
    cache group, as ``ServeEngine`` drives them."""

    def __init__(self, model, max_batch=2, full_blocks=40):
        self.model = model
        params = list(model.parameters()) + list(model.buffers())
        self.vals = [p.data for p in params]
        self.groups, _ = sk.cache_groups(model)
        sizes = [full_blocks if g.window is None
                 else max_batch * (-(-g.window // BS) + 2) + CHUNK // BS + 1
                 for g in self.groups]
        self.pools = tuple(init_pool_buffer(
            len(g.layers), g.rows[1], g.rows[2], n, BS, jnp.float32,
            streams=g.rows[0]) for g, n in zip(self.groups, sizes))
        self.bps = [BlockPool(n, BS) for n in sizes]
        self.sched = Scheduler(
            self.bps, windows=[g.window for g in self.groups],
            max_batch=max_batch, prefill_chunk=CHUNK,
            max_prefill_backlog=4 * CHUNK, max_positions=model.max_positions,
            prefix_cache=False)
        self.prefill = jax.jit(sk.build_prefill_fn(model, params, BS, 0))
        self.decode = jax.jit(sk.build_decode_fn(model, params, BS, 0))
        self.retired = 0

    def _tables(self, sessions, rows):
        return tuple(jnp.asarray(self.sched.pack_tables(sessions, rows, g)[1],
                                 jnp.int32)
                     for g in range(len(self.groups)))

    def ingest(self, rid, toks):
        """Admit and prefill ``toks`` in chunks -> the last logits."""
        self.sched.submit(Request(rid, toks, 60))
        s, = self.sched.admit()
        last = None
        while s.prefill_remaining > 0:
            t0 = s.position
            n = min(CHUNK, s.prefill_remaining)
            assert self.sched.grow(s, t0 + n + (t0 + n >= len(toks)))
            part = list(toks[t0:t0 + n])
            last, self.pools, _ = self.prefill(
                self.vals, self.pools,
                jnp.asarray([part + [0] * (CHUNK - n)], jnp.int32),
                self._tables([s], 1), jnp.int32(t0), jnp.int32(n))
            s.position = t0 + n
            self.retired += self.sched.retire_window_blocks(s)
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
        _, logits, self.pools, _ = self.decode(
            self.vals, self.pools, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            tuple(jnp.asarray(t, jnp.int32) for t in tables))
        for s in sessions:
            s.position += 1
            self.retired += self.sched.retire_window_blocks(s)
        return np.asarray(logits, np.float32)[:len(feeds)]


# -- (a) prefill in chunks, then decode, through the two groups -----------------


@pytest.mark.parametrize("kv_heads,heads", [(8, 8), (2, 8), (1, 8), (1, 16)],
                         ids=["mha", "gqa4", "mqa8", "mqa16"])
def test_two_groups_logits_match_the_reference_forward(kv_heads, heads):
    """Two sessions, the second joining while the first decodes, to
    depths past ``window + 2 blocks`` (blocks have retired, the window
    group's ring has wrapped), for 1, 4, 8 and 16 query heads a stored
    head (16: the state-space hybrid's attention layers, PR 35)."""
    cfg = _tiny_cfg(num_key_value_heads=kv_heads,
                    **{FAMILY.HEADS: heads})
    model, leaves = _served(cfg)
    vocab = cfg["vocab_size"]
    a, b = _toks(1, 60, vocab), _toks(2, 45, vocab)
    ref_a = _reference_logits(cfg, leaves, a)
    ref_b = _reference_logits(cfg, leaves, b)
    hand = _Hand(model)
    assert [g.window for g in hand.groups] == [None, WINDOW]
    assert [len(g.layers) for g in hand.groups] == [1, 3]
    sa, last = hand.ingest("a", a[:19])
    np.testing.assert_allclose(last, ref_a[18], atol=TOL)
    for t in range(19, 30):
        lg = hand.step([(sa, a[t])])
        np.testing.assert_allclose(lg[0], ref_a[t], atol=TOL)
    sb, last = hand.ingest("b", b[:27])         # longer than the window
    np.testing.assert_allclose(last, ref_b[26], atol=TOL)
    for i in range(18):
        lg = hand.step([(sa, a[30 + i]), (sb, b[27 + i])])
        np.testing.assert_allclose(lg[0], ref_a[30 + i], atol=TOL)
        np.testing.assert_allclose(lg[1], ref_b[27 + i], atol=TOL)
    assert sa.position == 48 > WINDOW + 2 * BS and hand.retired > 8
    # the window group holds the band, the full group everything
    assert sum(x != NULL_BLOCK for x in sa.tables[0]) == 12
    assert sum(x != NULL_BLOCK for x in sa.tables[1]) <= WINDOW // BS + 1
    for s in (sa, sb):
        hand.sched.finish(s)
    for bp in hand.bps:
        bp.check_no_leaks()


def test_the_models_own_forward_is_the_reference():
    """``GqaMoeModel.forward`` (no cache: the chunk reader over the
    sequence as its own view) against the reference, same leaves."""
    cfg = _tiny_cfg()
    model, leaves = _served(cfg)
    toks = _toks(5, 50, cfg["vocab_size"])
    got = model.forward(Ctx(training=False), jnp.asarray([toks], jnp.int32))
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               _reference_logits(cfg, leaves, toks), atol=TOL)


# -- (b) the softmax router ------------------------------------------------------


def test_the_softmax_router_against_a_hand_worked_example():
    """4 tokens, 8 experts, 2 a token; the router matrix is the identity,
    so the logits are the rows.  Token 0: logits (2, 1, 0 x 6): the two
    largest probabilities are experts 0 and 1, and renormalised over the
    chosen they are e^2 / (e^2 + e) = 0.7311 and 0.2689 whatever the
    other six add to the softmax's sum.  Token 1: all equal but expert 5
    (+3) and expert 2 (+1).  Token 2: a tie of 1.0 between experts 3 and
    6 over a floor of -1: both in, half each.  Token 3: expert 7 far
    ahead (10) and expert 4 next (0 against -5): weights 1 / (1 +
    e^-10)."""
    x = jnp.asarray([[2.0, 1, 0, 0, 0, 0, 0, 0],
                     [0.0, 0, 1, 0, 0, 3, 0, 0],
                     [-1.0, -1, -1, 1, -1, -1, 1, -1],
                     [-5.0, -5, -5, -5, 0, -5, -5, 10]], jnp.float32)
    experts, w = softmax_topk_route(x, jnp.eye(8), top_k=2)
    assert experts.dtype == jnp.int32 and w.dtype == jnp.float32
    assert [sorted(r) for r in experts.tolist()] == \
        [[0, 1], [2, 5], [3, 6], [4, 7]]
    first = 1 / (1 + math.exp(-1.0))
    np.testing.assert_allclose(
        np.asarray(w), [[first, 1 - first],
                        [1 / (1 + math.exp(-2.0)), 1 / (1 + math.exp(2.0))],
                        [0.5, 0.5],
                        [1 / (1 + math.exp(-10.0)), 1 / (1 + math.exp(10.0))]],
        rtol=1e-6)
    # not renormalised: the probabilities themselves
    _, p = softmax_topk_route(x[:1], jnp.eye(8), top_k=2, norm_topk=False)
    z = math.exp(2) + math.exp(1) + 6
    np.testing.assert_allclose(np.asarray(p),
                               [[math.exp(2) / z, math.exp(1) / z]],
                               rtol=1e-6)
    # a bfloat16 input is scored in float32 all the same
    e16, w16 = softmax_topk_route(x.astype(jnp.bfloat16), jnp.eye(8),
                                  top_k=2)
    assert w16.dtype == jnp.float32
    with pytest.raises(ValueError, match="no groups"):
        RoutedExperts(8, 4, 8, 2, n_group=2, score="softmax")


# -- (c) the band ------------------------------------------------------------------


def _banded(q, k, v, p, window, scaling):
    """``q (H, D)`` at position ``p`` over keys ``k, v (S, KV, D)``:
    float64, exactly the keys ``p - window + 1 .. p`` (all up to ``p``
    without a window)."""
    h, kv = q.shape[0], k.shape[1]
    lo = 0 if window is None else max(0, p - window + 1)
    out = []
    for i in range(h):
        kk, vv = k[lo:p + 1, i // (h // kv)], v[lo:p + 1, i // (h // kv)]
        s = kk.astype(np.float64) @ q[i].astype(np.float64) * scaling
        pr = np.exp(s - s.max())
        out.append((pr / pr.sum()) @ vv.astype(np.float64))
    return np.concatenate(out)


@pytest.mark.parametrize("tier", ["xla", "pallas_interpret", "chunk"])
def test_a_window_layer_reads_exactly_its_band(tier):
    """A query at position p reads keys ``p - window + 1 .. p`` of a
    window layer and all of a full one.  The block wholly before the
    band is retired (its table entry is null) and the physical block it
    had is poisoned, as is the last row before the band, which lies in a
    block the band still holds: neither may reach the window layer's
    output, the first key of the band has to, and a full layer reads the
    poisoned row."""
    heads, kv, d, bs, window, p = 8, 2, 128, 8, 16, 37
    rng = np.random.default_rng(4)
    k = rng.standard_normal((p + 1, kv, d)).astype(np.float32)
    v = rng.standard_normal((p + 1, kv, d)).astype(np.float32)
    q = rng.standard_normal((heads, d)).astype(np.float32)
    scaling = d ** -0.5
    first = p - window + 1                      # 22: the band's first key
    k[first - 1] = 50.0                         # the row before the band
    nb = p // bs + 1                            # blocks 0..4
    pool = np.zeros((1, 2, 1 + nb + 1, bs, kv * d), np.float32)
    # the table at its bucket of 8 entries: under a window it is a ring,
    # whose width is a power of two
    table = np.zeros(8, np.int32)
    table[:nb] = np.arange(1, nb + 1)
    rows = np.zeros((nb * bs, kv * d), np.float32)
    rows_v = rows.copy()
    rows[:p + 1], rows_v[:p + 1] = k.reshape(p + 1, -1), v.reshape(p + 1, -1)
    pool[0, 0, 1:nb + 1] = rows.reshape(nb, bs, -1)
    pool[0, 1, 1:nb + 1] = rows_v.reshape(nb, bs, -1)
    # blocks 0 and 1 (keys 0..15) lie wholly before the band: retired,
    # and what they held is poisoned where it lies
    banded = table.copy()
    banded[:first // bs] = NULL_BLOCK
    pool[0, :, 1:1 + first // bs] = 1e4
    assert first // bs == 2 and (first - 1) // bs == 2   # row 21: block 2

    def read(tab, w):
        args = (jnp.asarray(pool), 0, jnp.asarray(tab[None]))
        if tier == "chunk":
            kk, vv = pa.gather_kv(*args)
            return np.asarray(pa.attend(
                jnp.asarray(q)[None, :, None], kk, vv,
                jnp.asarray([[p]]), scaling, w))[0, 0]
        with force_mode("interpret" if tier == "pallas_interpret"
                        else "off"):
            return np.asarray(pa.paged_decode_attention(
                jnp.asarray(q)[None], *args, jnp.asarray([p]), scaling,
                w))[0]
    want = _banded(q, k, v, p, window, scaling)
    np.testing.assert_allclose(read(banded, window), want, atol=1e-5)
    # one key too few or one too many is another answer
    for off in (window - 1, window + 1):
        assert np.abs(_banded(q, k, v, p, off, scaling) - want).max() > 1e-3
    # a full layer reads the poisoned row 21 (its blocks are all there)
    pool[0, :, 1:3] = np.stack([rows.reshape(nb, bs, -1)[:2],
                                rows_v.reshape(nb, bs, -1)[:2]])
    full = read(table, None)
    np.testing.assert_allclose(full, _banded(q, k, v, p, None, scaling),
                               atol=1e-5)
    assert np.abs(full - want).max() > 1e-3


def test_a_ring_is_a_power_of_two_wide():
    """Logical block ``i`` of a window layer is entry ``i mod width`` of
    its table, taken as a mask: a width that is no power of two is
    refused, by the kernel's walk and by the row writer alike (every
    bucketed table is one; a layer without a window takes any width)."""
    assert [int(pa.ring_entry(i, 8)) for i in (0, 7, 8, 21)] == [0, 7, 0, 5]
    with pytest.raises(ValueError, match="power of two"):
        pa.ring_entry(3, 12)
    tables = jnp.zeros((1, 12), jnp.int32)
    pos = jnp.asarray([[40]], jnp.int32)
    with pytest.raises(ValueError, match="power of two"):
        sk.row_targets(tables, pos, pos >= 0, 4, 16, 2, ring=True)
    sk.row_targets(tables, pos, pos >= 0, 4, 16, 2)


# -- (d) the window group's bound, and every group's blocks come back ----------


def test_the_window_group_stays_within_its_bound_and_nothing_leaks():
    """Over a run of more than 3 x window ticks the window group never
    holds more than ``max_batch x (ceil(window / block) + 2) + 1``
    blocks; a full group too small for the batch preempts, and finish
    and preemption return both groups' blocks."""
    from apex_tpu import observe
    cfg = _tiny_cfg()
    model, leaves = _served(cfg)
    vocab = cfg["vocab_size"]
    max_batch = 3
    eng = ServeEngine(model, num_blocks=34, block_size=BS,
                      max_batch=max_batch, prefill_chunk=CHUNK)
    assert [g.name for g in eng.groups] == ["full", "window16"]
    assert eng.block_pools[1].num_blocks == \
        max_batch * (WINDOW // BS + 2) + CHUNK // BS + 1
    assert eng.pools[0].shape[0] == 1 and eng.pools[1].shape[0] == 3
    assert not eng.scheduler.prefix_cache
    bound = max_batch * (WINDOW // BS + 2) + 1
    before = observe.counter("serve.preemptions").value
    began = time.perf_counter_ns()      # other engines' ticks lie before
    for i, (n, new) in enumerate([(21, 60), (9, 70), (30, 55), (12, 40)]):
        eng.submit(Request(f"r{i}", _toks(20 + i, n, vocab), new))
    ticks, most = 0, 0
    while eng.step():
        ticks += 1
        most = max(most, eng.block_pools[1].in_use)
        assert eng.block_pools[1].in_use <= bound
        assert ticks < 600
    assert ticks > 3 * WINDOW and most > max_batch * WINDOW // BS
    # three sessions of up to 81 rows want 60 blocks of the full group's
    # 33: the newest was preempted, and recomputed to the same answer
    assert observe.counter("serve.preemptions").value > before
    assert observe.counter("serve.window.blocks_retired").value > 0
    assert sorted(len(v) for v in eng.results.values()) == [40, 55, 60, 70]
    seq = _toks(20, 21, vocab)
    for tok in eng.results["r0"][:8]:
        lg = _reference_logits(cfg, leaves, seq)[-1]
        top = np.sort(lg)[-2:]
        assert tok == int(np.argmax(lg)) or top[1] - top[0] < 1e-4
        seq.append(tok)
    steps = [e for e in observe.events("span")
             if e.get("span") == "serve.step" and "kv_rows_full" in e
             and e["t0_ns"] >= began]
    assert steps and all(
        e["kv_layers_full"] == 1 and e["kv_layers_window"] == 3
        and e["kv_window"] == WINDOW
        and e["kv_rows_window"] <= e["kv_rows_full"]
        and e["kv_rows_window"] <= e["decode_batch"] * WINDOW
        for e in steps)
    assert any(e["kv_rows_window"] < e["kv_rows_full"] for e in steps)
    eng.close()
    for bp in eng.block_pools:
        assert bp.in_use == 0 and bp.free_count == bp.capacity


def test_one_group_engines_are_what_they_were():
    """A model whose layers are alike has one group, whose pool and
    block pool are ``eng.pool`` / ``eng.block_pool``; an engine-wide
    ``window=`` is the window of layers that declare none; speculation
    is refused where any group has a window."""
    from apex_tpu.inference import make_self_draft
    from apex_tpu.models import GptModel
    gpt = GptModel(vocab_size=97, hidden=32, layers=2, heads=2,
                   max_positions=64).eval()
    eng = ServeEngine(gpt, num_blocks=16, block_size=4, max_batch=2,
                      prefill_chunk=4)
    assert len(eng.groups) == 1 and eng.groups[0].window is None
    assert eng.pool is eng.pools[0] and eng.block_pool is eng.block_pools[0]
    assert eng.pool.shape[:3] == (2, 2, 16)
    banded = ServeEngine(gpt, num_blocks=16, block_size=4, max_batch=2,
                         prefill_chunk=4, window=8)
    assert [g.window for g in banded.groups] == [8]
    assert "w8" in banded._cache_tag()
    with pytest.raises(NotImplementedError, match="sliding window"):
        ServeEngine(gpt, num_blocks=16, block_size=4, max_batch=2,
                    prefill_chunk=4, window=8, draft=make_self_draft(gpt))
    cfg = _tiny_cfg()
    model, _ = _served(cfg)
    with pytest.raises(NotImplementedError, match="sliding window"):
        ServeEngine(model, num_blocks=16, block_size=4, max_batch=2,
                    prefill_chunk=4, draft=make_self_draft(gpt))

    gpt.blocks[0].__class__ = type("Bare", (object,), {})
    with pytest.raises(ValueError, match="window where the block reads"):
        ServeEngine(gpt, num_blocks=16, block_size=4)


# -- (e) the Pallas tiers in interpret mode against their XLA tiers ------------


@pytest.mark.parametrize("window,heads", [(None, 8), (40, 8), (None, 32)],
                         ids=["full", "window40", "full_16_a_stored_head"])
def test_grouped_query_reader_tiers_agree(window, heads):
    """8 (or 32: the state-space hybrid's 16 a stored head, a 32 x 256
    query tile) query heads on 2 stored heads of 128, ragged depths, a
    dead pad row; bfloat16 pool (exact products in both tiers) and a ring
    table for the window case."""
    kv, d, bs, nb = 2, 128, 16, 8
    rng = np.random.default_rng(9)
    pool = jnp.asarray(rng.standard_normal((2, 2, 1 + 4 * nb, bs, kv * d)),
                       jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((4, heads, d)), jnp.bfloat16)
    pos = np.asarray([100, 7, -1, 63])
    tables = np.zeros((4, nb), np.int32)
    for b, p in enumerate(pos):
        lo = 0 if window is None else max(p - window + 1, 0) // bs
        for i in range(lo, p // bs + 1):
            tables[b, i % nb] = 1 + b * nb + i % nb
    args = (q, pool, 1, jnp.asarray(tables), jnp.asarray(pos), d ** -0.5,
            window)
    assert pa._kernel_takes(q, pool)
    with force_mode("off"):
        want = np.asarray(pa.paged_decode_attention(*args))
    with force_mode("interpret"):
        got = np.asarray(pa.paged_decode_attention(*args))
    live = pos >= 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-6)
    # narrower heads than a lane row are the XLA tier's
    assert not pa._kernel_takes(q[:, :, :64], pool)


def test_grouped_matmul_tiers_agree_at_widths_that_are_no_power_of_two():
    """Tiles are the widest whole lane rows that divide a width: 384 of
    384, 640 of 1280 (the published 2304 and 1792 take 768 and 896)."""
    assert [gm._tile_of(n) for n in (2304, 1792, 896, 7168, 384, 1280, 96)] \
        == [768, 896, 896, 1024, 384, 640, None]
    rng = np.random.default_rng(2)
    group = jnp.asarray(rng.integers(0, 5, 70), jnp.int32)   # 4: elsewhere
    rhs = jnp.asarray(rng.standard_normal((4, 384, 1280)) / 20, jnp.float32)
    outs = {}
    for mode in ("interpret", "off"):
        with force_mode(mode):
            tile = gm.TILE_ROWS if gm.takes_tiles(384, 1280, jnp.float32) \
                else 1
            lay = gm.tile_layout(group, 4, 70, tile)
            lhs = jnp.asarray(rng.standard_normal((lay.pair_of_row.shape[0],
                                                   384)), jnp.float32)
            ys = gm.grouped_matmul(lhs, rhs, lay)
            held = np.asarray(lay.row_of_pair) >= 0
            rows = np.asarray(lay.row_of_pair)[held]
            # each pair's row against its own group's matrix
            want = np.einsum(
                "pk,pkn->pn", np.asarray(lhs)[rows],
                np.asarray(rhs)[np.asarray(group)[held]])
            np.testing.assert_allclose(np.asarray(ys)[rows], want,
                                       atol=2e-4)
            outs[mode] = tile
    assert outs == {"interpret": gm.TILE_ROWS, "off": 1}
