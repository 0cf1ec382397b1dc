"""latent_attention: attention over a latent (MLA) paged cache.

A latent layer keeps ONE row a token (``serve/pool.py``: one stream):
the normed compressed key-value ``c_kv`` followed by the rotated shared
rotary key ``k_rope`` and zeros up to a whole number of lane rows.  All
heads read the same row, and in *absorbed* form the row is key and value
at once: a head's query is ``[q_nope W_k^T | q_rope | 0]`` (the key
up-projection folded into the query), its scores are one product with
the rows, and its output is the probabilities times the rows' first
``rank`` columns, which the caller takes through the value
up-projection.  The same mathematics as expanding every row into
per-head keys and values; a row is read once for all heads.

* :func:`latent_attend` — chunks of query rows against a gathered view
  of the sessions' rows (prefill, speculative verify).
* :func:`latent_decode_attention` — one query row a session (the decode
  tick): the registered ``latent_attention`` kernel
  (``latent_attention_decode`` in a device trace), which takes the block
  tables by scalar prefetch and DMAs each live block from the pool in
  HBM, with a gather per layer as its XLA tier.  Scores, softmax and
  probabilities are fp32 in both, as in ``paged_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..inference.quant import QuantKV
from .dispatch import choose, register_kernel
from .paged_attention import (CHUNK_BLOCKS, _attend_live_blocks,
                              _probs_times, _valid, gather_kv)

_f32 = jnp.float32


def latent_attend(q, rows, positions, scaling, rank, window=None):
    """``q (B, H, Q, W)`` absorbed queries at ``positions (B, Q)``
    against a gathered view ``rows (B, S, W)`` -> ``(B, Q, H, rank)``
    fp32: the probabilities times the rows' latent part."""
    scores = jnp.einsum("bhqw,bsw->bhqs", q, rows,
                        preferred_element_type=_f32) * scaling
    valid = _valid(rows.shape[1], positions, window)         # (B, Q, S)
    scores = jnp.where(valid[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqs,bsr->bqhr", probs, rows[..., :rank],
                      preferred_element_type=_f32)


def _decode_xla(q, pool, layer, tables, positions, scaling, rank, window):
    """The XLA tier of the decode reader (the declared fallback): the
    tables' blocks of one layer gathered in the pool's dtype, every
    entry read, null padding included."""
    rows, = gather_kv(pool, layer, tables)                   # (B, S, W)
    exact = q.dtype == jnp.bfloat16 and rows.dtype == jnp.bfloat16
    scores = jnp.einsum(
        "bhw,bsw->bhs", q, rows, preferred_element_type=_f32,
        precision=None if exact else jax.lax.Precision.HIGHEST) * scaling
    valid = _valid(rows.shape[1], positions, window)         # (B, S)
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return _probs_times(
        probs, rows[..., :rank], lambda p, v, prec: jnp.einsum(
            "bhs,bsr->bhr", p, v, preferred_element_type=_f32,
            precision=prec))


# ---------------------------------------------------------------------------
# The Pallas tier: block tables by scalar prefetch, live blocks by DMA
# ---------------------------------------------------------------------------


def _decode_kernel(layer_ref, tables_ref, pos_ref, q_ref, pool_ref, o_ref,
                   buf, sem, *, scaling, window, chunk, rank):
    """All heads read the one row a token keeps, key and value at once:
    ``paged_attention``'s walk over the session's live blocks as it is."""
    o_ref[0] = _attend_live_blocks(
        layer_ref[0], tables_ref, pos_ref, pool_ref, buf, sem, q_ref[0],
        scaling=scaling, window=window, chunk=chunk, rank=rank)


@functools.partial(jax.jit, static_argnames=("scaling", "window", "rank",
                                             "interpret"))
def _decode_call(layer, tables, positions, q, pool, *, scaling, window,
                 rank, interpret):
    """The kernel's call, jitted with the layer as an operand, so that a
    program's per-layer calls share one trace and one Mosaic lowering."""
    b, heads, w = q.shape
    bs = pool.shape[3]
    chunk = min(CHUNK_BLOCKS, tables.shape[1])
    kernel = functools.partial(_decode_kernel, scaling=scaling,
                               window=window, chunk=chunk, rank=rank)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, heads, w), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, heads, rank),
                                   lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, 1, chunk, bs, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, heads, rank), _f32),
        interpret=interpret,
        name="latent_attention_decode",
    )(layer, tables, positions, q, pool)


def _decode_pallas(q, pool, layer, tables, positions, scaling, rank, window,
                   interpret):
    return _decode_call(jnp.full((1,), layer, jnp.int32), tables, positions,
                        q, pool, scaling=float(scaling), window=window,
                        rank=rank, interpret=interpret)


def latent_decode_attention(q, pool, layer, tables, positions, scaling,
                            rank, window=None):
    """The decode tick's attention over a latent pool: ``q (B, H, W)``
    absorbed queries, one a session at ``positions (B,)`` (``-1`` = dead
    pad row, whose output the caller discards), against the session's
    rows of ``layer`` -> ``(B, H, rank)`` fp32.

    Two tiers, chosen by :func:`kernel_mode` as
    ``paged_decode_attention``'s are: the Pallas kernel wherever its
    tiles fit (rows of whole lane rows, blocks of whole sublane tiles,
    a plain pool), the XLA tier otherwise."""
    mode = kernel_mode(q, pool, rank)
    if mode is not None:
        return _decode_pallas(q, pool, layer, tables, positions,
                              scaling, rank, window, mode == "interpret")
    return _decode_xla(q, pool, layer, tables, positions, scaling, rank,
                       window)


def kernel_mode(q, pool, rank):
    """The rule, as ``paged_attention``'s: the mode the kernel runs in
    wherever its tiles fit (it reads the live blocks once where the XLA
    tier gathers the whole table; PERF.md section 6, PR 29), else
    ``None`` for the XLA tier."""
    return choose("latent_attention", fits=_kernel_takes(q, pool, rank))


def _kernel_takes(q, pool, rank) -> bool:
    if isinstance(pool, QuantKV):
        return False
    rows = 8 * 4 // jnp.dtype(pool.dtype).itemsize
    return pool.shape[1] == 1 and pool.shape[4] % 128 == 0 \
        and rank % 128 == 0 and pool.shape[3] % rows == 0 \
        and q.shape[1] % 8 == 0


def _audit_programs():
    """Both tiers on one abstract shape for the jaxpr verifier."""
    sds = jax.ShapeDtypeStruct
    q = sds((2, 8, 256), jnp.bfloat16)
    pool = sds((1, 1, 8, 16, 256), jnp.bfloat16)
    i32 = jnp.int32
    ex = (q, pool, sds((2, 4), i32), sds((2,), i32))

    def _pallas(q, pool, tables, positions):
        return _decode_pallas(q, pool, 0, tables, positions, 0.125, 128,
                              None, False)

    def _xla(q, pool, tables, positions):
        return _decode_xla(q, pool, 0, tables, positions, 0.125, 128, None)

    return [("pallas", _pallas, ex), ("xla", _xla, ex)]


register_kernel(
    "latent_attention",
    xla_fallback="apex_tpu.kernels.latent_attention._decode_xla",
    doc="Decode attention over a latent (MLA) paged cache, absorbed form",
    audit_programs=_audit_programs)
