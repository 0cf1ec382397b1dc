"""The paged KV pool's programs against the contiguous-cache path.

``serve/kernels.py`` writes each layer's fresh rows into the pool
(``(layers, 2, num_blocks, block_size, heads*head_dim)``) and attends
through the block table.  Here the prefill and decode program bodies are
driven by hand — tables of scattered physical ids padded with the null
block, a last block partly filled, dead batch rows, a copy-on-write
fork — and their logits are held to what ``GptBlock.decode_chunk``
computes over a contiguous ``(B, H, S, D)`` cache of the same dtype; the
Pallas table reader is held to its XLA fallback in interpret mode.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu import nn
from apex_tpu.inference.quant import kv_value, kv_write, make_kv_cache
from apex_tpu.kernels import paged_attention as pa
from apex_tpu.kernels.dispatch import force_mode
from apex_tpu.models.gpt import GptModel
from apex_tpu.observe import registry as obs
from apex_tpu.serve import Request, ServeEngine
from apex_tpu.serve import kernels as sk
from apex_tpu.serve.pool import NULL_BLOCK, init_pool_buffer

pytestmark = pytest.mark.serve

BS, NUM_BLOCKS, NB, CHUNK = 4, 24, 8, 8     # block size, pool, buckets


@pytest.fixture(scope="module")
def model():
    nn.manual_seed(6)
    m = GptModel(vocab_size=73, hidden=32, layers=2, heads=4,
                 max_positions=96, dropout=0.0, attn_dropout=0.0)
    m.eval()
    return m


def _params(model):
    return list(model.parameters()) + list(model.buffers())


def _contiguous_logits(model, toks, cache_dtype, window):
    """Logits at every position of ``toks`` from the contiguous-cache
    path: the whole sequence as one ``decode_chunk`` over an empty
    ``(1, H, S, D)`` cache.  Without a window that is
    ``GptBlock.decode_chunk`` itself; with one, the same steps with
    rolling.py's band added to the mask (the GPT family has no windowed
    contiguous path of its own)."""
    params = _params(model)
    ctx = sk._ctx(params, [p.data for p in params])
    ids = jnp.asarray([toks], jnp.int32)
    pos = jnp.arange(len(toks), dtype=jnp.int32)
    x = sk._embed(ctx, model, ids, pos[None, :])
    attn = model.blocks[0].attn
    shape = (1, attn.num_heads, len(toks), attn.head_dim)
    for blk in model.blocks:
        kc, vc = make_kv_cache(shape, cache_dtype), \
            make_kv_cache(shape, cache_dtype)
        if window is None:
            x, _, _ = blk.decode_chunk(ctx, x, kc, vc, 0)
            continue
        q, k_new, v_new = blk._chunk_qkv(ctx, x)
        kc = kv_write(kc, k_new, (0, 0, 0, 0))
        vc = kv_write(vc, v_new, (0, 0, 0, 0))
        scores = jnp.einsum("bhqd,bhsd->bhqs", q.astype(jnp.float32),
                            kv_value(kc)) * blk.attn.scaling
        valid = (pos[None, :] <= pos[:, None]) \
            & (pos[None, :] > pos[:, None] - window)
        scores = jnp.where(valid[None, None], scores, -1e30)
        o = jnp.einsum("bhqs,bhsd->bhqd", jax.nn.softmax(scores, axis=-1),
                       kv_value(vc)).astype(x.dtype)
        o = jnp.swapaxes(o, 1, 2).reshape(1, len(toks), -1)
        x = blk._attn_mlp_tail(ctx, x, o)
    x = model.ln_f.forward(ctx, x)
    return np.asarray(sk._head(ctx, model, x)[0], np.float32)


class _Paged:
    """The serve program bodies over one pool, driven by hand."""

    def __init__(self, model, cache_dtype, window):
        params = _params(model)
        self.vals = [p.data for p in params]
        blk = model.blocks[0]
        streams, heads, head_dim = blk.cache_rows
        self.pool = init_pool_buffer(
            len(model.blocks), heads, head_dim, NUM_BLOCKS, BS, cache_dtype,
            streams=streams)
        self.prefill = jax.jit(sk.build_prefill_fn(
            model, params, BS, NUM_BLOCKS, window))
        self.decode = jax.jit(sk.build_decode_fn(
            model, params, BS, NUM_BLOCKS, window))
        self.copy = jax.jit(sk.build_block_copy_fn())

    @staticmethod
    def table(ids):
        return ids + [NULL_BLOCK] * (NB - len(ids))

    def ingest(self, toks, t0, ids):
        """Prefill ``toks`` at positions ``t0 ..`` in chunks of CHUNK
        (the last one zero-padded); the last real row's logits."""
        last = None
        for a in range(0, len(toks), CHUNK):
            part = toks[a:a + CHUNK]
            padded = part + [0] * (CHUNK - len(part))
            last, self.pool, _ = self.prefill(
                self.vals, self.pool, jnp.asarray([padded], jnp.int32),
                jnp.asarray([self.table(ids)], jnp.int32),
                jnp.int32(t0 + a), jnp.int32(len(part)))
        return np.asarray(last[0], np.float32)

    def step(self, rows):
        """One decode tick over ``rows``: ``(token, position, ids)`` per
        live session, ``None`` for a dead row; logits ``(B, V)``."""
        toks = [r[0] if r else 0 for r in rows]
        pos = [r[1] if r else -1 for r in rows]
        tabs = [self.table(r[2]) if r else [NULL_BLOCK] * NB for r in rows]
        _, logits, self.pool, _ = self.decode(
            self.vals, self.pool, jnp.asarray(toks, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(tabs, jnp.int32))
        return np.asarray(logits, np.float32)


def _block_rows(pool, bid):
    part = pool.q if hasattr(pool, "q") else pool
    return np.asarray(part[:, :, bid], np.float32)


#: an fp32 pool repeats the contiguous path to rounding; a bf16 or int8
#: pool stores the same rounded rows as a contiguous cache of its dtype
TOL = 2e-5


@pytest.mark.parametrize("window", [None, 6], ids=["full", "window6"])
@pytest.mark.parametrize("cache_dtype", ["float32", "bfloat16", "int8"])
def test_paged_prefill_and_decode_match_the_contiguous_cache(
        model, cache_dtype, window):
    """Two sessions at different depths in scattered blocks, a dead row
    between them, the last block of each partly filled."""
    rng = np.random.default_rng(3)
    seq_a = [int(t) for t in rng.integers(1, 72, 19)]
    seq_b = [int(t) for t in rng.integers(1, 72, 11)]
    ref_a = _contiguous_logits(model, seq_a, cache_dtype, window)
    ref_b = _contiguous_logits(model, seq_b, cache_dtype, window)
    pg = _Paged(model, cache_dtype, window)
    ids_a, ids_b = [7, 2, 19, 11, 5], [14, 3, 9]
    # prefill: 13 tokens of a (two chunks, the second has 5 real rows),
    # 6 of b (one chunk, 6 real rows: its second block half filled)
    last = pg.ingest(seq_a[:13], 0, ids_a)
    np.testing.assert_allclose(last, ref_a[12], atol=TOL, rtol=0)
    last = pg.ingest(seq_b[:6], 0, ids_b)
    np.testing.assert_allclose(last, ref_b[5], atol=TOL, rtol=0)
    # decode, teacher-forced: rows (a, dead, b, dead)
    for j in range(5):
        pa_, pb_ = 13 + j, 6 + j
        logits = pg.step([(seq_a[pa_], pa_, ids_a), None,
                          (seq_b[pb_], pb_, ids_b), None])
        np.testing.assert_allclose(logits[0], ref_a[pa_], atol=TOL, rtol=0)
        np.testing.assert_allclose(logits[2], ref_b[pb_], atol=TOL, rtol=0)
        assert logits[0].argmax() == ref_a[pa_].argmax()
        assert logits[2].argmax() == ref_b[pb_].argmax()
    # padding and dead rows never wrote: the null block is still zeros,
    # and so is every block no table named
    assert not _block_rows(pg.pool, NULL_BLOCK).any()
    for bid in set(range(NUM_BLOCKS)) - set(ids_a) - set(ids_b):
        assert not _block_rows(pg.pool, bid).any(), bid


@pytest.mark.parametrize("cache_dtype", ["float32", "int8"])
def test_copy_on_write_fork_then_diverge(model, cache_dtype):
    """b shares a's first block and forks a's half-filled second block:
    b's continuation reads the copied rows, a's block is untouched."""
    rng = np.random.default_rng(5)
    seq_a = [int(t) for t in rng.integers(1, 72, 10)]
    seq_b = seq_a[:6] + [int(t) for t in rng.integers(1, 72, 4)]
    ref_a = _contiguous_logits(model, seq_a, cache_dtype, None)
    ref_b = _contiguous_logits(model, seq_b, cache_dtype, None)
    pg = _Paged(model, cache_dtype, None)
    ids_a = [4, 9, 13]
    pg.ingest(seq_a[:6], 0, ids_a)              # block 9 holds rows 4, 5
    pg.pool = pg.copy(pg.pool, jnp.int32(9), jnp.int32(17))
    ids_b = [4, 17, 6]                          # shared, forked, fresh
    shared = _block_rows(pg.pool, 4)
    for j in range(4):
        p = 6 + j
        logits = pg.step([(seq_a[p], p, ids_a), (seq_b[p], p, ids_b)])
        np.testing.assert_allclose(logits[0], ref_a[p], atol=TOL, rtol=0)
        np.testing.assert_allclose(logits[1], ref_b[p], atol=TOL, rtol=0)
    # the shared block is as the prefill left it; the two copies of the
    # forked block agree on the rows written before the fork only
    np.testing.assert_array_equal(_block_rows(pg.pool, 4), shared)
    a9, b17 = _block_rows(pg.pool, 9), _block_rows(pg.pool, 17)
    np.testing.assert_array_equal(a9[:, :, :2], b17[:, :, :2])
    assert (a9[:, :, 2:] != b17[:, :, 2:]).any()


# ---------------------------------------------------------------------------
# the Pallas table reader against its XLA fallback (interpret mode)
# ---------------------------------------------------------------------------


def _reader_case(dtype, bs, heads, d, nb, positions, seed=0):
    rng = np.random.default_rng(seed)
    n = 1 + sum(p // bs + 1 for p in positions if p >= 0)
    pool = jnp.asarray(rng.standard_normal((2, 2, n, bs, heads * d)),
                       dtype).at[:, :, NULL_BLOCK].set(0)
    tables = np.zeros((len(positions), nb), np.int32)
    free = list(rng.permutation(np.arange(1, n)))
    for b, p in enumerate(positions):
        for i in range(p // bs + 1 if p >= 0 else 0):
            tables[b, i] = free.pop()
    q = jnp.asarray(rng.standard_normal((len(positions), heads, d)), dtype)
    return q, pool, jnp.asarray(tables), jnp.asarray(positions, jnp.int32)


@pytest.mark.parametrize("dtype,bs,nb,positions,window", [
    # a first row, a full table, a partly filled last block, a dead row
    ("bfloat16", 16, 16, [0, 255, 37, -1, 32], None),
    # a table wider than one chunk of blocks and not a multiple of it
    ("float32", 8, 12, [95, 3, -1, 64], None),
    # a band that starts inside a block; blocks before it retired
    ("bfloat16", 16, 8, [100, 127, 5, 19], 20),
    ("float32", 8, 4, [31, 17, 9, -1], 9),
], ids=["bf16", "f32_ragged_table", "bf16_window", "f32_window"])
def test_pallas_table_reader_matches_its_xla_fallback(dtype, bs, nb,
                                                      positions, window):
    q, pool, tables, pos = _reader_case(jnp.dtype(dtype), bs, 2, 64, nb,
                                        positions)
    if window is not None:          # retire the blocks before the band
        t = np.asarray(tables).copy()
        for b, p in enumerate(positions):
            t[b, :max(p - window + 1, 0) // bs] = NULL_BLOCK
        tables = jnp.asarray(t)
    args = (q, pool, 1, tables, pos, 64 ** -0.5, window)
    want = np.asarray(pa._decode_xla(*args))
    got = np.asarray(pa._decode_pallas(*args, True))
    live = np.asarray(positions) >= 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-6, rtol=0)
    assert np.isfinite(got).all()               # a dead row reads zeros


def test_reader_tiers_and_what_the_kernel_declines():
    from apex_tpu.kernels.dispatch import catalog
    entry = catalog()["paged_attention"]
    assert entry.xla_fallback == \
        "apex_tpu.kernels.paged_attention._decode_xla"
    q = jnp.zeros((2, 2, 64), jnp.bfloat16)
    assert pa._kernel_takes(q, init_pool_buffer(1, 2, 64, 4, 16,
                                                jnp.bfloat16))
    # an int8 pool, rows that are not whole lane rows, blocks that are
    # not whole sublane tiles: the XLA tier's
    assert not pa._kernel_takes(q, init_pool_buffer(1, 2, 64, 4, 16,
                                                    "int8"))
    assert not pa._kernel_takes(q, init_pool_buffer(1, 4, 8, 4, 16,
                                                    jnp.bfloat16))
    assert not pa._kernel_takes(q, init_pool_buffer(1, 2, 64, 4, 8,
                                                    jnp.bfloat16))


def test_engine_serves_the_same_tokens_through_the_kernel():
    """A model whose rows are whole lane rows (2 heads of 64): the
    engine in interpret mode takes the Pallas tier (the rule's
    counter says so) and emits the XLA tier's tokens."""
    nn.manual_seed(11)
    m = GptModel(vocab_size=61, hidden=128, layers=2, heads=2,
                 max_positions=64, dropout=0.0, attn_dropout=0.0)
    m.eval()
    reqs = [([5, 9, 11, 3, 8, 2, 40, 7, 1], 7), ([7, 2], 9), ([33], 5)]

    def serve():
        eng = ServeEngine(m, num_blocks=24, block_size=8, max_batch=4,
                          prefill_chunk=8)
        out = eng.run([Request(f"r{i}", p, n)
                       for i, (p, n) in enumerate(reqs)])
        eng.block_pool.check_no_leaks()
        return out

    base = serve()
    counter = obs.counter("kernels.dispatch.paged_attention.pallas")
    before = counter.value
    with force_mode("interpret"):
        through_kernel = serve()
    assert counter.value > before
    assert through_kernel == base
