"""paged_attention: reading the serve engine's KV pool through block
tables.

The pool (``serve/pool.py::init_pool_buffer``) is
``(layers, streams, num_blocks, block_size, width)``: what a layer keeps
of one token is ``streams`` contiguous rows (a GPT block's K and V, each
``heads*head_dim`` wide; a latent block's one row), each a whole number
of lane rows, so the device stores the pool row-major and a block is one
contiguous piece.  Every reader here takes
the pool where it lies — no program needs it in another layout, so none
copies it.

**Under a window a table is a ring.**  A layer that reads every key
takes a session's table in logical order: entry ``i`` is the block of
positions ``[i*block_size, (i+1)*block_size)``, null past the session's
length.  A layer that reads a window of keys needs only the band's
blocks, so there logical block ``i`` is entry ``i mod width`` and the
tables stay ``ceil(window / block_size)`` and a few entries wide however
deep the session is (``serve/scheduler.py`` packs them so; a table in
logical order that spans the session's depth is such a ring already).
The width of a ring is a power of two (:func:`ring_entry`), and every
reader of one masks by position.

**Grouped-query heads.**  A block stores ``kv_heads`` heads; ``heads /
kv_heads`` query heads read each (query head ``i`` reads stored head
``i // (heads / kv_heads)``).  The repeated rows are never made: the
decode readers put a query head in the columns of its stored head, the
chunk reader contracts a group of query heads against its one stored
head.

* :func:`gather_kv` — the blocks of a table, one layer, in the pool's
  own dtype, heads side by side as in the pool: the gathered view the
  prefill and speculative-verify programs attend (many query rows, few
  sessions), and what the XLA tier of the decode reader reads.
* :func:`attend` — the score / ``-1e30`` mask / fp32 softmax / combine
  of ``GptBlock.decode_chunk`` over such a view, for chunks of query
  rows: K and V stay in the pool's dtype in memory, products accumulate
  in fp32, the softmax and the probabilities are fp32.
* :func:`paged_decode_attention` — one query row a session, B sessions
  (the decode tick): the registered ``paged_attention`` kernel, which
  takes the block tables by scalar prefetch and DMAs each live block
  from the pool in HBM, with a gather per layer as its XLA tier.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..inference.quant import QuantKV
from .dispatch import choose, register_kernel

_f32 = jnp.float32


def gather_kv(pool, layer, tables):
    """``tables (B, nb)`` physical ids -> the streams of one layer (a
    GPT block's ``(k, v)``; a latent block's one stream), each
    ``(B, nb*block_size, width)``: row ``s`` holds what the layer keeps
    of logical position ``s`` (the table is logical-block-ordered, so
    the gather IS the logical->physical translation), heads side by
    side as in the pool.  Null-padded entries read the zero block, which the
    caller's position mask excludes.  A plain pool is read in its own
    dtype; a :class:`QuantKV` pool gathers int8 payload and scales and
    dequantizes the selected blocks to fp32."""
    b, nb = tables.shape

    def take(part, kv):
        # (L, S, N, bs, W) -> (L*S*N, bs, W) is a bitcast of the row-major
        # pool, so the gather addresses blocks in the pool itself and no
        # per-layer slice is materialised; (layer, kv, block) is row
        # (layer*S + kv)*N + block of that view
        flat = part.reshape((-1,) + part.shape[3:])
        g = flat[(part.shape[1] * layer + kv) * part.shape[2] + tables]
        return g.reshape(b, nb * g.shape[2], g.shape[3])     # (B, S, W)

    def read(kv):
        if not isinstance(pool, QuantKV):
            return take(pool, kv)
        q8, scale = take(pool.q, kv), take(pool.scale, kv)   # scale (B, S, H)
        return q8.astype(_f32) * jnp.repeat(
            scale, q8.shape[-1] // scale.shape[-1], axis=-1)
    streams = (pool.q if isinstance(pool, QuantKV) else pool).shape[1]
    return tuple(read(kv) for kv in range(streams))


def ring_entry(i, width):
    """The entry of a window layer's table that holds logical block
    ``i``: ``i mod width``, as a mask (a remainder on the kernel's scalar
    core read as +1.6% of the kernel, PERF.md section 6)."""
    if width & (width - 1):
        raise ValueError(
            f"a window layer's table is a ring {width} entries wide: the "
            f"width has to be a power of two")
    return i & (width - 1)


def _valid(n_slots, positions, window):
    """``(..., n_slots)`` mask over the gathered view of a table: slot
    ``s`` is valid for a query at position ``p`` where ``s <= p``.  Under
    a window the table is a ring: slot ``s`` holds the key at the one
    position ``k = s (mod n_slots)`` in ``(p - n_slots, p]``, valid
    where ``k >= 0`` and ``k > p - window`` (rolling.py's band, over
    block tables); the ring has to span the band and the chunk's later
    rows (``n_slots >= window + Q - 1``), which then fall outside the
    band."""
    slots = jnp.arange(n_slots, dtype=jnp.int32)
    p = positions[..., None]
    if window is None:
        return slots <= p
    k = p - jnp.mod(p - slots, n_slots)
    return (k >= 0) & (k > p - window)


def attend(q, k, v, positions, scaling, window=None):
    """``q (B, H, Q, D)`` at per-row query positions ``positions
    (B, Q)`` against a gathered view ``k, v (B, S, KV*D)`` ->
    ``(B, Q, H*D)`` fp32: the score / mask / softmax / combine of
    ``GptBlock.decode_chunk`` for chunks of query rows.  ``H / KV``
    query heads share a stored head (one each where ``KV == H``)."""
    b, h, s_q, d = q.shape
    k = k.reshape(b, k.shape[1], -1, d)
    v = v.reshape(b, v.shape[1], -1, d)
    kv = k.shape[2]
    q = q.reshape(b, kv, h // kv, s_q, d)
    scores = jnp.einsum("bkgqd,bskd->bkgqs", q, k,
                        preferred_element_type=_f32) * scaling
    valid = _valid(k.shape[1], positions, window)            # (B, Q, S)
    scores = jnp.where(valid[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", probs, v,
                   preferred_element_type=_f32)
    return o.reshape(b, s_q, h * d)


def _own_columns(heads, width, head_dim):
    """``(H, KV*D)`` mask: the columns of a pool row that query head h
    reads, those of stored head ``h // (H / KV)``."""
    row = jax.lax.broadcasted_iota(jnp.int32, (heads, width), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (heads, width), 1)
    group = heads * head_dim // width       # query heads a stored head
    return lane // head_dim == (row if group == 1 else row // group)


def _in_own_columns(q, own):
    """``q (..., H, D)`` -> ``(..., H, KV*D)``: each query head in the
    columns of its stored head, zeros elsewhere (block-diagonal where
    ``KV == H``)."""
    d, width = q.shape[-1], own.shape[-1]
    return jnp.where(own, jnp.concatenate([q] * (width // d), axis=-1),
                     jnp.zeros((), q.dtype))


def _from_own_columns(o, own, head_dim):
    """``o (..., H, KV*D)`` -> ``(..., H, D)``: what each head found in
    its own columns."""
    o = jnp.where(own, o, 0.0)
    return sum(o[..., i:i + head_dim]
               for i in range(0, o.shape[-1], head_dim))


def _probs_times(p, v, dot, in_kernel=False):
    """``dot(p, v)`` with the fp32 probabilities ``p`` at full
    precision: against a bf16 ``v`` the MXU takes bf16 operands, so ``p``
    goes in as three bf16 pieces (8 + 8 + 8 mantissa bits: every product
    exact, fp32 accumulation) and ``v`` is never upcast in memory; any
    other ``v`` is upcast and multiplied at ``HIGHEST``.  XLA cuts the
    pieces with ``reduce_precision`` (a cast to bf16 and back is an
    identity it may remove, which leaves one piece of 8 bits: read on
    the chip as an error of 1.5e-3); Mosaic has no such primitive and
    keeps the casts."""
    if v.dtype != jnp.bfloat16:
        return dot(p, v.astype(_f32), jax.lax.Precision.HIGHEST)
    out = None
    for _ in range(3):
        if in_kernel:
            piece = p.astype(jnp.bfloat16).astype(_f32)
        else:
            piece = jax.lax.reduce_precision(p, exponent_bits=8,
                                             mantissa_bits=7)
        p = p - piece
        part = dot(piece.astype(jnp.bfloat16), v, None)
        out = part if out is None else out + part
    return out


def _decode_xla(q, pool, layer, tables, positions, scaling, window):
    """The XLA tier of the decode reader (the declared fallback): the
    tables' blocks of one layer gathered in the pool's dtype, every
    entry read, null padding included.

    One query row a session, so the heads are not split out of the rows
    (on the chip a minor dimension of ``head_dim`` is a relayout of the
    whole view): the scores of all heads come from one product of a
    ``(H, KV*D)`` query, each head in the columns of its stored head,
    with the ``(S, KV*D)`` keys — the zeros elsewhere add nothing — and
    the combine from one ``(H, S) x (S, KV*D)`` product of which head
    ``h`` keeps its own columns."""
    b, h, d = q.shape
    k, v = gather_kv(pool, layer, tables)                    # (B, S, KV*D)
    own = _own_columns(h, k.shape[-1], d)
    q_bd = _in_own_columns(q, own[None])                     # (B, H, KV*D)
    exact = q.dtype == jnp.bfloat16 and k.dtype == jnp.bfloat16
    scores = jnp.einsum(
        "bhx,bsx->bhs", q_bd, k, preferred_element_type=_f32,
        precision=None if exact else jax.lax.Precision.HIGHEST) * scaling
    valid = _valid(k.shape[1], positions, window)            # (B, S)
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _probs_times(probs, v, lambda p, v_, prec: jnp.einsum(
        "bhs,bsx->bhx", p, v_, preferred_element_type=_f32,
        precision=prec))
    return _from_own_columns(o, own[None], d).reshape(b, h * d)


# ---------------------------------------------------------------------------
# The Pallas tier: block tables by scalar prefetch, live blocks by DMA
# ---------------------------------------------------------------------------

#: blocks fetched and attended per loop iteration (two slots of K and V
#: each: 2 x 2 x 8 x 32 KiB = 1 MiB of VMEM at 16 x 1024 bf16 blocks)
CHUNK_BLOCKS = 8


def _attend_live_blocks(layer, tables_ref, pos_ref, pool_ref, buf, sem, q,
                        *, scaling, window, chunk, rank):
    """One session (grid step ``b``) of a decode kernel: walk its live
    blocks ``chunk`` at a time, two slots deep, and attend them with an
    online softmax -> ``(H, rank)`` fp32.

    ``q (H, W)`` against the pool's rows ``(layers, streams, blocks,
    block_size, W)`` of ``layer``, by way of ``buf (2, streams, chunk,
    block_size, W)``: the keys are the first stream, the values the first
    ``rank`` columns of the last (a GPT block's K and V; a latent block's
    one row, which is both)."""
    b = pl.program_id(0)
    nb = tables_ref.shape[1]
    bs, w = buf.shape[3], buf.shape[4]
    heads = q.shape[0]
    t = chunk * bs
    pos = pos_ref[b]
    # live blocks: through the query's own (just written) row; under a
    # window the blocks wholly before the band were retired to null
    n_chunks = (pos // bs + chunk) // chunk             # 0 for a dead row
    first = 0 if window is None else \
        jnp.maximum(pos - window + 1, 0) // (bs * chunk)

    def fetch(c, slot, start):
        for j in range(chunk):
            i = c * chunk + j
            if window is None:
                # past the table's width: the null block (zeros), as the
                # table's own padding past the session's length is
                blk = jnp.where(i < nb,
                                tables_ref[b, jnp.minimum(i, nb - 1)], 0)
            else:
                # a ring; a block past the query's own is masked by
                # position whatever its entry holds
                blk = tables_ref[b, ring_entry(i, nb)]
            dma = pltpu.make_async_copy(
                pool_ref.at[layer, :, blk], buf.at[slot, :, j],
                sem.at[slot])
            dma.start() if start else dma.wait()

    exact = q.dtype == jnp.bfloat16 and buf.dtype == jnp.bfloat16

    @pl.when(first < n_chunks)
    def _():
        fetch(first, first % 2, True)

    def body(c, carry):
        m, l, acc = carry
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            fetch(c + 1, 1 - slot, True)
        fetch(c, slot, False)
        k = buf[slot, 0].reshape(t, w)
        v = buf[slot, buf.shape[1] - 1].reshape(t, w)
        if rank != w:
            v = v[:, :rank]
        if exact:       # bf16 x bf16 products are exact in fp32
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=_f32)
        else:
            s = jax.lax.dot_general(
                q.astype(_f32), k.astype(_f32),
                (((1,), (1,)), ((), ())), preferred_element_type=_f32,
                precision=jax.lax.Precision.HIGHEST)
        s = s * scaling                                 # (H, T)
        slots = c * t + jax.lax.broadcasted_iota(jnp.int32, (heads, t), 1)
        valid = slots <= pos
        if window is not None:
            valid = valid & (slots > pos - window)
        s = jnp.where(valid, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + _probs_times(
            p, v, lambda p_, v_, prec: jnp.dot(
                p_, v_, preferred_element_type=_f32, precision=prec),
            in_kernel=True)
        return m_new, l, acc

    m0 = jnp.full((heads, 1), -1e30, _f32)
    l0 = jnp.zeros((heads, 1), _f32)
    acc0 = jnp.zeros((heads, rank), _f32)
    _, l, acc = jax.lax.fori_loop(first, n_chunks, body, (m0, l0, acc0))
    return acc / jnp.where(l > 0, l, 1.0)


def _decode_kernel(layer_ref, tables_ref, pos_ref, q_ref, pool_ref, o_ref,
                   buf, sem, *, heads, scaling, window, chunk):
    """Stored heads lie side by side in a row of the pool: the query
    with each head in its stored head's columns and the combine are
    :func:`_decode_xla`'s, the walk between them
    :func:`_attend_live_blocks`'s.  ``q_ref`` is ``(1, 1, H*D)`` where
    every query head has a stored head of its own (the row is broadcast
    and masked: heads narrower than a lane row cannot be laid side by
    side in a kernel), else ``(1, H, D)``, whole lane rows a head."""
    hd = buf.shape[4]
    d = q_ref.shape[1] * q_ref.shape[2] // heads
    own = _own_columns(heads, hd, d)
    q = q_ref[0]
    # (selected in fp32: the mask has the 32-bit tiling)
    if q.shape[0] == 1:                                 # (1, H*D)
        q_bd = jnp.where(own, jnp.broadcast_to(q.astype(_f32), (heads, hd)),
                         0.0).astype(q.dtype)
    else:                                               # (H, D)
        q_bd = _in_own_columns(q.astype(_f32), own).astype(q.dtype)
    o = _attend_live_blocks(layer_ref[0], tables_ref, pos_ref, pool_ref, buf,
                            sem, q_bd, scaling=scaling, window=window,
                            chunk=chunk, rank=hd)
    if q.shape[0] == 1:
        o_ref[0] = jnp.sum(jnp.where(own, o, 0.0), axis=0, keepdims=True)
    else:
        o_ref[0] = _from_own_columns(o, own, d)


@functools.partial(jax.jit, static_argnames=("scaling", "window",
                                             "interpret"))
def _decode_call(layer, tables, positions, q, pool, *, scaling, window,
                 interpret):
    """The kernel's call, jitted with the layer as an operand: a
    program's per-layer calls then share one trace and one Mosaic
    lowering (lowering 24 of them took 7-10 s a program, which the
    compile cache does not spare a warm start)."""
    b, heads, d = q.shape
    _, _, _, bs, hd = pool.shape
    chunk = min(CHUNK_BLOCKS, tables.shape[1])
    kernel = functools.partial(
        _decode_kernel, heads=heads, scaling=scaling, window=window,
        chunk=chunk)
    # a session's queries: one row of all heads, or a row a head where
    # the heads are grouped (see the kernel)
    rows = (1, heads * d) if heads * d == hd else (heads, d)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1,) + rows, lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1,) + rows, lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, 2, chunk, bs, hd), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((b,) + rows, _f32),
        interpret=interpret,
        name="paged_attention_decode",
    )(layer, tables, positions, q.reshape((b,) + rows), pool)
    return out.reshape(b, heads * d)


def _decode_pallas(q, pool, layer, tables, positions, scaling, window,
                   interpret):
    return _decode_call(jnp.full((1,), layer, jnp.int32), tables, positions,
                        q, pool, scaling=float(scaling), window=window,
                        interpret=interpret)


def paged_decode_attention(q, pool, layer, tables, positions, scaling,
                           window=None):
    """The decode tick's attention: ``q (B, H, D)``, one query row a
    session at ``positions (B,)`` (``-1`` = dead pad row, whose output
    the caller discards), against the session's blocks of ``layer``
    -> ``(B, H*D)`` fp32.

    Two tiers, chosen by :func:`kernel_mode`: the Pallas kernel takes the
    tables by scalar prefetch and DMAs each live block from the pool in
    HBM (a session at depth 300 reads 19 blocks, not its table's 64);
    the XLA tier gathers the whole table.  An int8 pool, rows that are
    not whole tiles, or grouped heads narrower than a lane row are the
    XLA tier's.  ``H`` may be a multiple of the pool's stored heads
    (grouped-query attention)."""
    mode = kernel_mode(q, pool)
    if mode is not None:
        return _decode_pallas(q, pool, layer, tables, positions,
                              scaling, window, mode == "interpret")
    return _decode_xla(q, pool, layer, tables, positions, scaling, window)


def kernel_mode(q, pool):
    """The rule: the mode the kernel runs in, or ``None`` for the XLA
    tier.  The kernel reads the live blocks once where the XLA tier
    writes and re-reads a gathered copy of the whole table, so it is
    taken wherever its tiles fit (PERF.md section 6, PR 27, has the
    chip's reading at gpt2-medium's widths)."""
    return choose("paged_attention", fits=_kernel_takes(q, pool))


def _kernel_takes(q, pool) -> bool:
    """What the kernel's tiles allow: a plain pool whose rows are whole
    lane rows and whose blocks are whole sublane tiles of its dtype;
    where query heads share stored heads, heads that are whole lane
    rows themselves (each is laid in its stored head's columns)."""
    if isinstance(pool, QuantKV):
        return False
    rows = 8 * 4 // jnp.dtype(pool.dtype).itemsize
    grouped = q.shape[1] * q.shape[2] != pool.shape[4]
    return pool.shape[4] % 128 == 0 and pool.shape[3] % rows == 0 \
        and not (grouped and q.shape[2] % 128)


def _audit_programs():
    """Both tiers on one abstract shape for the jaxpr verifier."""
    sds = jax.ShapeDtypeStruct
    q = sds((2, 2, 64), jnp.bfloat16)
    pool = sds((1, 2, 8, 16, 128), jnp.bfloat16)
    i32 = jnp.int32
    ex = (q, pool, sds((2, 4), i32), sds((2,), i32))

    def _pallas(q, pool, tables, positions):
        return _decode_pallas(q, pool, 0, tables, positions, 0.125, None,
                              False)

    def _xla(q, pool, tables, positions):
        return _decode_xla(q, pool, 0, tables, positions, 0.125, None)

    return [("pallas", _pallas, ex), ("xla", _xla, ex)]


register_kernel(
    "paged_attention",
    xla_fallback="apex_tpu.kernels.paged_attention._decode_xla",
    doc="Decode attention through block tables: live KV blocks by DMA",
    audit_programs=_audit_programs)
