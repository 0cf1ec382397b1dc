"""Pallas TPU fused attention kernels.

TPU re-design of the reference's ``fast_*_multihead_attn`` extensions
(apex/contrib/csrc/multihead_attn/, ~5900 LoC of fused QKV GEMM +
strided-batched attention GEMMs + fused mask/softmax).  The reference kernel
materializes the full (Sq, Sk) softmax; the modern TPU analogue is a
flash-attention kernel — blockwise online softmax, O(S) memory, saving only
the per-row logsumexp for the backward (SURVEY.md §2.2 maps
fast_multihead_attn → "Pallas fused attention, flash-style").

Layout: q (B, H, Sq, D), k/v (B, H, Sk, D), flattened to (B·H, S, D) for the
kernels.  Two paths behind one pair of entries (``flash_attention_fwd`` /
``flash_attention_bwd``), chosen from what a call's own operands say:

* **tiled** — grid (batch·head, q-blocks, k-blocks) with the k dimension
  innermost: TPU grids execute sequentially, so the running max /
  denominator / accumulator live in VMEM scratch across the k sweep (the
  canonical TPU flash pattern).  The backward recomputes attention
  blockwise from the saved logsumexp: one kernel accumulates dq over the k
  sweep, a second accumulates dk/dv over the q sweep.  Any length, any
  dtype, bias, dropout.
* **resident** (``_resident``: bf16 operands, no bias, no dropout, a whole
  head within the VMEM estimate) — grid (batch·head,): q, k, v of a head
  lie in VMEM whole and the key walk is a static loop inside the kernel.
  A row block of 256 meets the keys from its window's block to its
  diagonal block at once (one max, one exp, one sum: no running
  statistics), only the blocks the mask crosses are masked, and one
  backward kernel computes S, P, dP and dS once for all three gradients:
  seven products a block pair over the causal half where the tiled
  kernels take nine over the whole square at their widest tiles.  On the
  v5e a grid step and a block each cost about the same whatever they
  compute (PERF.md, PRs 30 and 32), which is what the tiled path cannot
  get around and this one does not meet.

The resident kernels have a second entry, *packed*
(``flash_attention_packed_fwd`` / ``_bwd``, ``packed_mode``'s rule), for a
self-attention between its two projections: blocks are cut by ``BlockSpec``
index maps out of the QKV projection's (B, T, 3.H.D) output itself — two
64-wide heads a 128-lane row — and the context is written as the output
projection reads it, so no (B.H, S, D) tensor is made.  Same kernel
bodies, same walk, same arithmetic.

Precision: the nine MXU products of a block pair (forward S, P·V; dq S, dP,
dS·K; dkv S, Pᵀ·dO, dP, dSᵀ·Q; the resident path's seven are the same less
the recomputed S and dP) take their operands in the dtype the tensors
came in (``_operand_dtype``) and accumulate in fp32; scores, softmax
statistics (m, l, lse, delta) and every accumulator are fp32.  bf16 q/k/v/dO
therefore reach the MXU as stored — a bf16 x bf16 product is exact in the
fp32 accumulator, so widening them first buys no bit — and P and dS are
rounded to bf16 for their products, as the left operand of every other
matmul of a bf16 step is.  fp32 tensors run fp32 products, bit for bit what
they always gave.  (On a v5e Mosaic runs a default-precision fp32 product
as one bf16 pass anyway: there the rule changes no result and no time,
PERF.md PR 30; it is what makes the interpreter round as the chip does.)

An additive ``bias`` (broadcastable (B|1, Sq|1, Sk)) carries both mask
flavors of the reference API (key_padding_mask → 0/-inf per key,
attn_mask → additive (Sq, Sk)); ``causal`` applies the in-kernel triangular
mask the reference calls ``mask_future_timesteps``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import choose, pallas_mode, register_kernel, tally

_f32 = jnp.float32
_NEG = -1e30  # finite "-inf": keeps exp(s - m) well-defined in masked blocks


def _ceil_div(a, b):
    return (a + b - 1) // b


_VMEM_SCOPED = 16 * 1024 * 1024  # what the v5e compiler gives one kernel
# the tiled kernels' slice of it: their estimate counts one buffer a block
_VMEM_BUDGET = 10 * 1024 * 1024


def _operand_dtype(*tensors):
    """The dtype the kernels' products take their operands in: bfloat16
    where every tensor is bfloat16 (the MXU's native operand: no widened
    copy is made), float32 otherwise (fp32 tensors as they are; fp16 or
    mixed ones widened, as they always were)."""
    if all(t.dtype == jnp.bfloat16 for t in tensors):
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(_f32)


def _vmem_estimate(bq, bk, d, itemsize=4):
    """Worst-case bytes resident per grid step across the three kernels:
    input and output blocks at the operands' ``itemsize``, the (bq, bk)
    score intermediates and the scratch accumulators at fp32."""
    f = 4
    fwd = (2 * bq * d + 2 * bk * d) * itemsize + 3 * bq * bk * f + 2 * bq * f
    dkv = (3 * bq * d + 2 * bk * d) * itemsize + 4 * bq * bk * f \
        + 2 * bk * d * f
    return max(fwd, dkv)


def _block_sizes(sq, sk, d, itemsize=4):
    """(bq, bk) from what the kernels can see: the lengths, the head
    width and the operands' itemsize.

    bf16 operands take 512 x 1024.  On a v5e a grid step costs about the
    same whatever its key width (S = 1024, D = 64, causal, ms a call of
    192 heads, fwd + dq + dkv: 256 x 512 5.00, 512 x 512 3.91,
    256 x 1024 3.75, 512 x 1024 3.03, 1024 x 1024 2.77, 256 x 256 7.66,
    128 x 128 16.6: PERF.md, PR 30), so fewer, larger steps win even
    where they compute the whole square of a causal call; 1024 x 1024
    is not taken because its backward does not fit the 16 MiB of scoped
    VMEM once a bias and dropout ride along.  fp32 operands keep
    256 x 512: their blocks are twice the bytes, and the order of the
    blocks is what their results' last bits follow."""
    cap_q, cap_k = (256, 512) if itemsize >= 4 else (512, 1024)
    bq = min(cap_q, _round8(sq))
    bk = min(cap_k, _round8(sk))
    # shrink blocks until the per-step working set fits the VMEM budget
    # (large head dims would otherwise OOM VMEM at the default tiles)
    while _vmem_estimate(bq, bk, d, itemsize) > _VMEM_BUDGET and bk > 128:
        bk //= 2
    while _vmem_estimate(bq, bk, d, itemsize) > _VMEM_BUDGET and bq > 128:
        bq //= 2
    return bq, bk


def vmem_fit(sq, sk, d, itemsize=4):
    """VMEM-fit report for the chosen block sizes (bench --kernels guard)."""
    bq, bk = _block_sizes(sq, sk, d, itemsize)
    est = _vmem_estimate(bq, bk, d, itemsize)
    return {"bq": bq, "bk": bk, "est_bytes": est,
            "budget_bytes": _VMEM_BUDGET, "fits": est <= _VMEM_BUDGET}


def _round_up(x, m):
    return _ceil_div(x, m) * m


def _round8(x):
    return max(8, _round_up(x, 8))


def _hash_keep_u32(rows, cols, bh, seed):
    """Counter-based per-element hash (murmur3-finalizer style) of
    (seed, batch·head, global row, global col) → uint32.  Pure uint32
    vector arithmetic: lowers on Mosaic AND in interpret mode, and the
    jnp oracle (``dropout_keep_reference``) reproduces it bit-exactly —
    unlike the hardware PRNG, which interpret mode mocks as zeros.  The
    mask is a function of absolute positions only, so forward and both
    backward kernels regenerate it identically regardless of block
    sizes.  This is the TPU analogue of the reference's fused-dropout
    Philox replay (apex/contrib/csrc/multihead_attn/dropout.cuh:
    curand_uniform4 regenerated from the saved seed/offset in bwd)."""
    h = (rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
         + cols.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
         + seed.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35)
         + bh.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return h


def _seed_vec(seed, row_off, col_off):
    """(3,) int32 SMEM payload [seed, row_off, col_off] for the kernels
    (offsets may be traced scalars — ring chunks compute them per hop)."""
    return jnp.stack([jnp.asarray(seed, jnp.int32).reshape(()),
                      jnp.asarray(row_off, jnp.int32).reshape(()),
                      jnp.asarray(col_off, jnp.int32).reshape(())])


def _mult_from_hash(h, rate):
    """hash → inverted-dropout multiplier: 1/(1-rate) where the hash
    clears the keep threshold, 0 elsewhere.  THE single definition of
    the threshold/scaling — the kernels and the jnp oracle both call it,
    so their bit-exact agreement cannot drift."""
    thresh = jnp.uint32(min(int((1.0 - rate) * 2.0 ** 32), 2 ** 32 - 1))
    return jnp.where(h < thresh, jnp.float32(1.0 / (1.0 - rate)),
                     jnp.float32(0.0))


def _dropout_mult(i, j, b, bq, bk, seed_ref, rate):
    """(bq, bk) f32 multiplier grid: 1/(1-rate) on kept positions, 0 on
    dropped — inverted-dropout scaling applied to the attention probs.
    ``seed_ref`` is the (3,) SMEM vector [seed, row_off, col_off]: the
    offsets shift block coordinates to GLOBAL positions, so a chunked
    caller (ring attention) whose q/k blocks sit at arbitrary global
    offsets draws the exact mask the single-device kernel would."""
    rows = seed_ref[1] + i * bq \
        + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = seed_ref[2] + j * bk \
        + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return _mult_from_hash(
        _hash_keep_u32(rows, cols, jnp.asarray(b), seed_ref[0]), rate)


def dropout_keep_reference(b, sq, sk, seed, rate, row_off=0,
                           col_off=0):
    """jnp oracle of the in-kernel mask: (B·H, Sq, Sk) f32 multipliers,
    bit-identical to what the kernels generate (tests + fallback path).
    ``row_off``/``col_off`` shift to global coordinates for chunked
    callers (ring attention's jnp arm)."""
    rows = row_off + jax.lax.broadcasted_iota(jnp.int32, (b, sq, sk), 1)
    cols = col_off + jax.lax.broadcasted_iota(jnp.int32, (b, sq, sk), 2)
    bh = jax.lax.broadcasted_iota(jnp.int32, (b, sq, sk), 0)
    return _mult_from_hash(
        _hash_keep_u32(rows, cols, bh, jnp.asarray(seed)), rate)


def _mask_block(s, i, j, bq, bk, causal, window=None):
    """Causal (``rows >= cols``) and, with ``window``, Mistral-banded
    (``cols > rows - window``) masking of one score block."""
    if not causal:
        return s
    rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = rows >= cols
    if window is not None:
        keep = jnp.logical_and(keep, cols > rows - window)
    return jnp.where(keep, s, _NEG)


def _block_has_unmasked(i, j, bq, bk, window=None):
    """Block-granular mirror of ``_mask_block``: true iff q-block ``i``
    x k-block ``j`` holds at least one unmasked entry — above-diagonal
    blocks fail the causal edge (max row >= min col), and with
    ``window`` blocks entirely BELOW the band fail the band edge
    (max col > min row - window).  The kernels skip compute on
    fully-masked blocks — banded attention therefore costs
    O(S·window), not O(S²).  This predicate and ``_mask_block`` must
    stay in lockstep if the mask convention ever changes."""
    ok = j * bk <= i * bq + bq - 1
    if window is not None:
        ok = jnp.logical_and(ok, j * bk + bk - 1 > i * bq - window)
    return ok


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, bq, bk, nk,
                has_bias, window=None, dropout_p=0.0):
    refs = list(refs)
    seed_ref = refs.pop(0) if dropout_p > 0.0 else None
    if has_bias:
        bias_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    mxu = _operand_dtype(q_ref, k_ref, v_ref)
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0].astype(mxu)
        k = k_ref[0].astype(mxu)
        v = v_ref[0].astype(mxu)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=_f32) * scale
        if has_bias:
            s = s + bias_ref[0].astype(_f32)
        s = _mask_block(s, i, j, bq, bk, causal, window)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # dropout multiplies the (unnormalized) probs in the ACCUMULATOR
        # only; l keeps the full softmax sum, so out = dropout(P) @ v
        # exactly (P the normalized probs), matching the eager path
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if dropout_p > 0.0:
            p = p * _dropout_mult(i, j, b, bq, bk, seed_ref, dropout_p)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(mxu), v, preferred_element_type=_f32)
        m_scr[...] = m_new

    if causal:
        # skip k-blocks strictly above the diagonal: every entry is
        # masked, so the block's contribution is exactly p = 0 — the
        # update is an arithmetic no-op and the two MXU matmuls are
        # pure waste (~half the blocks as Sq grows; the reason causal
        # flash exists).  Numerics are bit-identical to the unskipped
        # sweep.
        pl.when(_block_has_unmasked(i, j, bq, bk, window))(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _fin():
        l = l_scr[...]
        # defensive only: with finite -1e30 masking l >= 1 always, so a
        # fully-masked row yields a uniform average over v (identical to
        # the jnp fallback path), not zeros
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(safe_l)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
               scale, causal, bq, bk, nk, has_bias, window=None,
               dropout_p=0.0):
    refs = list(refs)
    seed_ref = refs.pop(0) if dropout_p > 0.0 else None
    if has_bias:
        bias_ref, dq_ref, acc_scr = refs
    else:
        dq_ref, acc_scr = refs
    mxu = _operand_dtype(q_ref, k_ref, v_ref, do_ref)
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0].astype(mxu)
        k = k_ref[0].astype(mxu)
        v = v_ref[0].astype(mxu)
        do = do_ref[0].astype(mxu)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=_f32) * scale
        if has_bias:
            s = s + bias_ref[0].astype(_f32)
        s = _mask_block(s, i, j, bq, bk, causal, window)
        p = jnp.exp(s - lse_ref[0])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=_f32)
        if dropout_p > 0.0:
            # d(out)/d(P) routes through the dropout multiplier; delta
            # already includes it (delta = sum(do*out), out dropped)
            dp = dp * _dropout_mult(i, j, b, bq, bk, seed_ref, dropout_p)
        ds = p * (dp - delta_ref[0])
        acc_scr[...] += jax.lax.dot(ds.astype(mxu), k,
                                    preferred_element_type=_f32)

    if causal:
        # fully-masked block: p = 0 → ds = 0, contributes nothing to dq
        pl.when(_block_has_unmasked(i, j, bq, bk, window))(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _fin():
        dq_ref[0] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                scale, causal, bq, bk, nq, has_bias, window=None,
                dropout_p=0.0):
    refs = list(refs)
    seed_ref = refs.pop(0) if dropout_p > 0.0 else None
    if has_bias:
        bias_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = refs
    mxu = _operand_dtype(q_ref, k_ref, v_ref, do_ref)
    # grid is (bh, k-blocks, q-blocks): q innermost for the accumulation
    b = pl.program_id(0)
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        q = q_ref[0].astype(mxu)
        k = k_ref[0].astype(mxu)
        v = v_ref[0].astype(mxu)
        do = do_ref[0].astype(mxu)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=_f32) * scale
        if has_bias:
            s = s + bias_ref[0].astype(_f32)
        s = _mask_block(s, i, j, bq, bk, causal, window)
        p = jnp.exp(s - lse_ref[0])  # (bq, bk)
        if dropout_p > 0.0:
            dmult = _dropout_mult(i, j, b, bq, bk, seed_ref, dropout_p)
            pd = p * dmult  # dropped probs: dv sees dropout(P)
        else:
            pd = p
        dv_scr[...] += jax.lax.dot_general(
            pd.astype(mxu), do, (((0,), (0,)), ((), ())),
            preferred_element_type=_f32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=_f32)
        if dropout_p > 0.0:
            dp = dp * dmult
        ds = p * (dp - delta_ref[0])  # (bq, bk)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(mxu), q, (((0,), (0,)), ((), ())),
            preferred_element_type=_f32)

    if causal:
        # q-block entirely above the diagonal contributes nothing to
        # this k-block's dk/dv (every score masked, p = 0) — skip the
        # four matmuls
        pl.when(_block_has_unmasked(i, j, bq, bk, window))(_compute)
    else:
        _compute()

    @pl.when(i == nq - 1)
    def _fin():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# The resident path: a sequence that fits VMEM whole
# ---------------------------------------------------------------------------

_ROW_BLOCK = 256      # rows of q a resident kernel takes at a time
_MAX_ROW_BLOCKS = 8   # they are unrolled: Mosaic's lowering grows with them
_BWD_KEYS = 512       # keys of one product chain in the resident backward


def _resident_plan(sq, sk, causal, window, keys):
    """The static walk of the resident kernels: ``(bq, sq_p, sk_p, rows)``
    with ``rows`` one ``(r0, r1, segments)`` a row block and a segment
    ``(c0, c1, edge)`` a run of keys multiplied in one product.  A row
    block's segments stop at its diagonal block and start at its
    window's; ``edge`` marks those the mask crosses (the diagonal, the
    band's lower edge, padded keys), the only ones that are masked.  Runs
    that need no mask are merged up to ``keys`` wide (``None``: the whole
    run).  ``None`` where a row block would see no key at all."""
    bq = min(_ROW_BLOCK, _round_up(sq, 128))
    sq_p, sk_p = _round_up(sq, bq), _round_up(sk, 128)
    bk = min(_ROW_BLOCK, sk_p)
    if not causal:
        window = None
    rows = []
    for r0 in range(0, sq_p, bq):
        r1 = r0 + bq
        segs = []
        for c0 in range(0, sk_p, bk):
            c1 = min(c0 + bk, sk_p)
            if causal and c0 > r1 - 1:
                continue                      # above the diagonal
            if window is not None and c1 - 1 <= r0 - window:
                continue                      # below the band
            edge = (c1 > sk or (causal and c1 - 1 > r0)
                    or (window is not None and c0 <= r1 - 1 - window))
            last = segs[-1] if segs else None
            if (last and not edge and not last[2] and last[1] == c0
                    and (keys is None or c1 - last[0] <= keys)):
                segs[-1] = (last[0], c1, False)
            else:
                segs.append((c0, c1, edge))
        if not segs:
            return None
        rows.append((r0, r1, tuple(segs)))
    return bq, sq_p, sk_p, tuple(rows)


def _resident_vmem_estimate(sq_p, sk_p, d, bq, widest, heads=1):
    """Bytes the resident kernels hold in VMEM, the larger of the two.
    Backward: eight bf16 (S, D) blocks (q, k, v, out, dO, dq, dk, dv),
    each in two buffers and padded to 128 lanes, the fp32 dk and dv
    accumulators, and the four fp32 and two bf16 (keys, rows)
    intermediates of a product chain ``_BWD_KEYS`` wide.  Forward: four
    such blocks and the fp32 scores, fp32 and bf16 probabilities of the
    ``widest`` extent a row block meets at once.  Where a block of width
    ``d`` holds ``heads`` heads side by side (the packed entry) their
    walks are unrolled one after the other and the compiler keeps every
    head's intermediates, in the forward with one more fp32 copy of the
    scores each (its own reading for two heads at S = 1792: 27 B a
    score).  ``lse`` is a (1, S) fp32 block on eight sublanes."""
    lanes = _round_up(d, 128)
    rows_b, keys_b = 2 * 2 * sq_p * lanes, 2 * 2 * sk_p * lanes
    lse = 2 * 8 * sq_p * 4
    fwd = (2 * rows_b + 2 * keys_b + lse
           + heads * bq * widest * (4 + 4 + 2 + (4 if heads > 1 else 0)))
    bwd = (4 * rows_b + 4 * keys_b + lse + 2 * sk_p * lanes * 4
           + heads * bq * min(widest, _BWD_KEYS) * (4 * 4 + 2 * 2))
    return max(fwd, bwd)


def _resident(tensors, sq, sk, d, bias, causal, window, dropout_p,
              keys=None, heads=1):
    """The rule of the resident path: the walk (``_resident_plan``'s
    answer, its product chains ``keys`` wide; the rule does not depend on
    ``keys``) where a call takes it, else ``None``.  Taken by bf16
    operands with no bias and no dropout whose whole sequence is at most
    ``_MAX_ROW_BLOCKS`` row blocks and fits VMEM:
    ``_resident_vmem_estimate``, which counts both buffers of
    every block, within seven eighths of ``_VMEM_SCOPED`` (the rest is
    for what Mosaic allocates besides; the AOT tests hold both sides of
    the boundary against the compiler).  fp32 operands stay tiled (their
    last bits follow the block order), bias and dropout too (they bring
    (bq, bk) operands of their own)."""
    if (bias is not None or dropout_p > 0.0
            or _operand_dtype(*tensors) != jnp.bfloat16):
        return None
    plan = _resident_plan(sq, sk, causal, window, keys)
    if plan is None:
        return None
    bq, sq_p, sk_p, rows = plan
    # a row block's extent: the same however its runs are merged
    widest = max(sum(c1 - c0 for c0, c1, _ in segs) for _, _, segs in rows)
    if (len(rows) > _MAX_ROW_BLOCKS or _resident_vmem_estimate(
            sq_p, sk_p, d, bq, widest, heads) > _VMEM_SCOPED * 7 // 8):
        return None
    return plan


def _keep(shape, r0, c0, sk, causal, window, rows_dim=0):
    """The mask of an edge segment whose first score is row ``r0``, key
    ``c0``; the rows of q lie along ``rows_dim`` of the scores."""
    rows = r0 + jax.lax.broadcasted_iota(jnp.int32, shape, rows_dim)
    cols = c0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - rows_dim)
    keep = cols < sk
    if causal:
        keep = jnp.logical_and(keep, rows >= cols)
        if window is not None:
            keep = jnp.logical_and(keep, cols > rows - window)
    return keep


def _nt(a, b):
    """a . b^T on the MXU, fp32 out."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=_f32)


def _head_lanes(x, h, d):
    """``x`` with the lanes outside head ``h`` zeroed, where its lane row
    holds several ``d``-wide heads side by side; ``x`` itself where one
    head fills it.  A product that contracts the lanes then sums that
    head's terms and exact zeros, one that keeps them leaves the other
    heads' lanes zero, and no lane moves."""
    if x.shape[-1] == d:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where((lane >= h * d) & (lane < (h + 1) * d), x,
                     jnp.zeros_like(x))


def _resident_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, d, scale,
                         causal, window, sk, rows):
    """A lane row of heads a grid step: q and out are (rows, L), k and v
    (keys, L), ``lse`` (L // d, rows); one ``d``-wide head where L is
    ``d``, else L // d of them side by side, taken one after the other
    (``_head_lanes``).  A row block meets its whole key extent at once:
    scores, one max, one exp, one sum, P.V; no running statistics.
    ``lse`` leaves as a lane-dense row a head."""
    mxu = q_ref.dtype
    for r0, r1, segs in rows:
        q = q_ref[r0:r1, :]
        outs = []
        for h in range(q.shape[-1] // d):
            qh = _head_lanes(q, h, d)
            scores = []
            for c0, c1, edge in segs:
                s = _nt(qh, k_ref[c0:c1, :]) * scale
                if edge:
                    s = jnp.where(_keep(s.shape, r0, c0, sk, causal, window),
                                  s, _NEG)
                scores.append(s)
            m = functools.reduce(jnp.maximum, [
                jnp.max(s, axis=1, keepdims=True) for s in scores])
            l, acc = 0.0, 0.0
            for s, (c0, c1, _) in zip(scores, segs):
                p = jnp.exp(s - m)
                l = l + jnp.sum(p, axis=1, keepdims=True)
                acc = acc + jax.lax.dot(p.astype(mxu), v_ref[c0:c1, :],
                                        preferred_element_type=_f32)
            # acc holds P.V for every head's lanes of v: keep this head's
            outs.append(_head_lanes(acc / l, h, d))
            # (bq, 1) -> (1, bq): the column, spread over the lanes,
            # transposed
            lse = jnp.broadcast_to(m + jnp.log(l), (r1 - r0, 128))
            lse_ref[h:h + 1, r0:r1] = jnp.transpose(lse)[0:1, :]
        o_ref[r0:r1, :] = sum(outs[1:], outs[0]).astype(o_ref.dtype)


def _resident_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                         dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, d,
                         scale, causal, window, sk, rows):
    """A lane row of heads a grid step, the refs as the forward's; S, P,
    dP and dS once for all three gradients, seven products a block pair.
    The scores are taken transposed, (keys, rows): ``lse`` and ``delta``
    are then lane-dense rows, P^T.dO and dS^T.Q are plain products, and
    dS.K is the one that contracts over its operands' rows.  dq of a row
    block is written whole; dk and dv gather in fp32 scratch over the
    row blocks that see their keys (and over the heads of the lane row,
    each into its own lanes)."""
    mxu = q_ref.dtype
    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)
    for r0, r1, segs in rows:
        q = q_ref[r0:r1, :]
        do = do_ref[r0:r1, :]
        do_o = do.astype(_f32) * o_ref[r0:r1, :].astype(_f32)
        dq = 0.0
        for h in range(q.shape[-1] // d):
            qh, doh = _head_lanes(q, h, d), _head_lanes(do, h, d)
            lse = lse_ref[h:h + 1, r0:r1]
            delta = jnp.sum(jnp.transpose(_head_lanes(do_o, h, d)),
                            axis=0, keepdims=True)
            for c0, c1, edge in segs:
                k = k_ref[c0:c1, :]
                s = _nt(k, qh) * scale
                if edge:
                    s = jnp.where(_keep(s.shape, r0, c0, sk, causal, window,
                                        rows_dim=1), s, _NEG)
                p = jnp.exp(s - lse)
                ds = (p * (_nt(v_ref[c0:c1, :], doh) - delta)).astype(mxu)
                dv_scr[c0:c1, :] += jax.lax.dot(p.astype(mxu), doh,
                                                preferred_element_type=_f32)
                dk_scr[c0:c1, :] += jax.lax.dot(ds, qh,
                                                preferred_element_type=_f32)
                dq = dq + jax.lax.dot_general(
                    ds, _head_lanes(k, h, d), (((0,), (0,)), ((), ())),
                    preferred_element_type=_f32)
        dq_ref[r0:r1, :] = (dq * scale).astype(dq_ref.dtype)
    # the keys' rows of the block: every row of a (keys, D) block, the
    # first of a packed (rows, 128) one, whose rows past the keys belong
    # to no key and are cut off by the caller
    keys = dk_scr.shape[0]
    dk_ref[0:keys, :] = (dk_scr[...] * scale).astype(dk_ref.dtype)
    dv_ref[0:keys, :] = dv_scr[...].astype(dv_ref.dtype)


def _pad_rows(x, n, value=0, axis=1):
    """``x`` with ``axis`` padded to ``n`` (by nothing where it is)."""
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, pad, constant_values=value)


def _head_spec(s, d):
    """A head of a (B.H, S, D) array: its leading axis is squeezed."""
    return pl.BlockSpec((None, s, d), lambda b: (b, 0, 0))


# jitted: the layers of a model make the same call, and a jitted function
# is traced and lowered (the kernel's unrolled body with it) once for all
# of them, where a bare ``pallas_call`` is once a call
_STATIC = ("scale", "causal", "window", "plan", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _resident_fwd(q3, k3, v3, scale, causal, window, plan, interpret):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    _, sq_p, sk_p, rows = plan
    out, lse = pl.pallas_call(
        functools.partial(_resident_fwd_kernel, d=d, scale=scale,
                          causal=causal, window=window, sk=sk, rows=rows),
        grid=(bh,),
        in_specs=[_head_spec(sq_p, d), _head_spec(sk_p, d),
                  _head_spec(sk_p, d)],
        out_specs=[_head_spec(sq_p, d), _head_spec(1, sq_p)],
        out_shape=[jax.ShapeDtypeStruct((bh, sq_p, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, 1, sq_p), _f32)],
        interpret=interpret,
        name="flash_attn_fwd",
    )(_pad_rows(q3, sq_p), _pad_rows(k3, sk_p), _pad_rows(v3, sk_p))
    return out[:, :sq], lse[:, 0, :sq]


@functools.partial(jax.jit, static_argnames=_STATIC)
def _resident_bwd(q3, k3, v3, out, lse, g, scale, causal, window, plan,
                  interpret):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    _, sq_p, sk_p, rows = plan
    rows_spec, keys_spec = _head_spec(sq_p, d), _head_spec(sk_p, d)
    # padded q rows: lse = +big keeps p = exp(s - lse) at 0 there
    lse = _pad_rows(lse, sq_p, -_NEG)[:, None, :]
    dq, dk, dv = pl.pallas_call(
        functools.partial(_resident_bwd_kernel, d=d, scale=scale,
                          causal=causal, window=window, sk=sk, rows=rows),
        grid=(bh,),
        in_specs=[rows_spec, keys_spec, keys_spec, rows_spec, rows_spec,
                  _head_spec(1, sq_p)],
        out_specs=[rows_spec, keys_spec, keys_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, sq_p, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, sk_p, d), k3.dtype),
                   jax.ShapeDtypeStruct((bh, sk_p, d), v3.dtype)],
        scratch_shapes=[pltpu.VMEM((sk_p, d), _f32)] * 2,
        interpret=interpret,
        name="flash_attn_bwd",
    )(_pad_rows(q3, sq_p), _pad_rows(k3, sk_p), _pad_rows(v3, sk_p),
      _pad_rows(out, sq_p), _pad_rows(g, sq_p), lse)
    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


# ---------------------------------------------------------------------------
# The packed entry: the resident kernels on the projection's own layout
# ---------------------------------------------------------------------------

_LANES = 128


def _qkv_views(ref):
    """The q, k and v lane rows of a (rows, 3 x 128) block."""
    return [ref.at[:, i * _LANES:(i + 1) * _LANES] for i in range(3)]


def _packed_fwd_kernel(qkv_ref, o_ref, lse_ref, **kw):
    _resident_fwd_kernel(*_qkv_views(qkv_ref), o_ref, lse_ref, **kw)


def _packed_bwd_kernel(qkv_ref, o_ref, do_ref, lse_ref, dqkv_ref, dk_scr,
                       dv_scr, **kw):
    _resident_bwd_kernel(*_qkv_views(qkv_ref), o_ref, do_ref, lse_ref,
                         *_qkv_views(dqkv_ref), dk_scr, dv_scr, **kw)


def _group_spec(s, width):
    """Rows ``0:s`` of lane-row group ``g`` of batch row ``b`` of a (B,
    S, n x width) array, the batch axis squeezed."""
    return pl.BlockSpec((None, s, width), lambda b, g: (b, 0, g))


def _lse_spec(heads, s):
    return pl.BlockSpec((None, None, heads, s), lambda b, g: (b, g, 0, 0))


_PACKED_STATIC = ("d", "scale", "causal", "plan", "interpret")


@functools.partial(jax.jit, static_argnames=_PACKED_STATIC)
def _packed_fwd(lin, d, scale, causal, plan, interpret):
    b, t, w = lin.shape
    _, t_p, _, rows = plan
    groups, heads = w // (3 * _LANES), _LANES // d
    ctx, lse = pl.pallas_call(
        functools.partial(_packed_fwd_kernel, d=d, scale=scale,
                          causal=causal, window=None, sk=t, rows=rows),
        grid=(b, groups),
        in_specs=[_group_spec(t_p, 3 * _LANES)],
        out_specs=[_group_spec(t_p, _LANES), _lse_spec(heads, t_p)],
        out_shape=[jax.ShapeDtypeStruct((b, t_p, w // 3), lin.dtype),
                   jax.ShapeDtypeStruct((b, groups, heads, t_p), _f32)],
        interpret=interpret,
        name="flash_attn_fwd",
    )(_pad_rows(lin, t_p))
    return ctx[:, :t], lse[..., :t]


@functools.partial(jax.jit, static_argnames=_PACKED_STATIC)
def _packed_bwd(lin, ctx, lse, g, d, scale, causal, plan, interpret):
    b, t, w = lin.shape
    _, t_p, sk_p, rows = plan
    groups, heads = w // (3 * _LANES), _LANES // d
    qkv_spec, rows_spec = (_group_spec(t_p, 3 * _LANES),
                           _group_spec(t_p, _LANES))
    dlin = pl.pallas_call(
        functools.partial(_packed_bwd_kernel, d=d, scale=scale,
                          causal=causal, window=None, sk=t, rows=rows),
        grid=(b, groups),
        in_specs=[qkv_spec, rows_spec, rows_spec, _lse_spec(heads, t_p)],
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct((b, t_p, w), lin.dtype),
        scratch_shapes=[pltpu.VMEM((sk_p, _LANES), _f32)] * 2,
        interpret=interpret,
        name="flash_attn_bwd",
    )(_pad_rows(lin, t_p), _pad_rows(ctx, t_p), _pad_rows(g, t_p),
      # padded q rows: lse = +big keeps p = exp(s - lse) at 0 there
      _pad_rows(lse, t_p, -_NEG, axis=3))
    return dlin[:, :t]


def _packed_plan(lin, d, causal, keys=None):
    """The packed entry's walk, or ``None``: ``lin`` is (B, T, 3.H.D)
    with whole lane rows of heads (D 128, or D 64 and an even H) and the
    resident rule holds for a sequence of T rows that are its own keys.
    The blocks are the resident path's by another cut, a 384-lane block
    of q, k and v for three of 128 and the same again for their
    gradients, so ``_resident_vmem_estimate`` counts them at 128 lanes,
    with the intermediates of each head of a lane row."""
    _, t, w = lin.shape
    if d not in (64, _LANES) or w % (3 * _LANES):
        return None
    return _resident((lin,), t, t, _LANES, None, causal, None, 0.0, keys,
                     heads=_LANES // d)


def packed_mode(lin, d, bias, causal, dropout_p):
    """The rule of the packed entry for a self-attention whose projection
    gives ``lin`` (B, T, 3.H.D; an array or its shape and dtype): the
    mode the kernels run in, or ``None`` for :func:`flash_attention_fwd`
    on split heads, whose call then makes and counts its own choice.
    Taken where :func:`kernel_mode` would take the flash kernel and the
    resident rule holds for whole lane rows of heads (``_packed_plan``),
    with no bias and no dropout; counted here as ``.pallas``, since no
    other call asks for this attention."""
    b, t, w = lin.shape
    mode = pallas_mode()
    if (mode is None or bias is not None or dropout_p > 0.0
            or _packed_plan(lin, d, causal) is None
            or (mode == "compiled"
                and not _compiled_takes(b, w // (3 * d), t, t))):
        return None
    tally("flash_attention", "pallas")
    return mode


def packed_row_order(rows, d):
    """Rows of a QKV projection (weight (3.H.D, E) or bias (3.H.D,))
    from the stored ``[q_h, k_h, v_h]`` a head to the column order
    :func:`flash_attention_packed_fwd` reads: at D 64 the two heads of a
    pair side by side, ``[q_h q_h+1 | k_h k_h+1 | v_h v_h+1]``, each a
    whole lane row; at D 128 the stored order is that already.  Taken at
    trace time on the weight as the step holds it (its transpose lands
    on the gradient); the parameter, its checkpoints and a
    tensor-parallel row block keep the stored order."""
    per = _LANES // d
    if per == 1:
        return rows
    grouped = rows.reshape((-1, per, 3, d) + rows.shape[1:])
    return jnp.swapaxes(grouped, 1, 2).reshape(rows.shape)


def flash_attention_packed_fwd(lin, d, scale, causal, interpret=False):
    """The resident forward on the QKV projection's own output, for the
    shapes ``packed_mode`` admits.  ``lin`` is (B, T, 3.H.D), a row's
    columns in groups of 3 x 128: a lane row of queries, one of keys, one
    of values, each ``128 // d`` heads side by side (at D 128 the stored
    ``[q_h, k_h, v_h]``; at D 64 ``[q_h q_h+1 | k_h k_h+1 | v_h v_h+1]``).
    A grid step takes one group of one sequence as a (T, 384) block and
    writes its heads' context as a (T, 128) block of (B, T, H.D): no
    (B.H, S, D) tensor exists.  Returns (context, lse (B, groups,
    128 // d, T) fp32)."""
    tally("flash_attention", "resident")
    tally("flash_attention", "packed")
    return _packed_fwd(lin, d, scale, causal, _packed_plan(lin, d, causal),
                       interpret)


def flash_attention_packed_bwd(lin, ctx, lse, g, d, scale, causal,
                               interpret=False):
    """-> d ``lin`` (B, T, 3.H.D), each group's dq, dk and dv written
    where the forward read q, k and v; ``g`` is d context, (B, T, H.D)."""
    return _packed_bwd(lin, ctx, lse, g, d, scale, causal,
                       _packed_plan(lin, d, causal, _BWD_KEYS), interpret)


def _bias_spec(bias, bq, bk, for_dkv=False):
    b_, sq_, _ = bias.shape
    if for_dkv:
        def idx(b, j, i):
            return (b if b_ > 1 else 0, i if sq_ > 1 else 0, j)
    else:
        def idx(b, i, j):
            return (b if b_ > 1 else 0, i if sq_ > 1 else 0, j)
    return pl.BlockSpec((1, bq if sq_ > 1 else 1, bk), idx)


def flash_attention_fwd(q3, k3, v3, bias, scale, causal, interpret=False,
                        window=None, dropout_p=0.0, dropout_seed=None,
                        dropout_row_off=0, dropout_col_off=0):
    """q3 (BH, Sq, D), k3/v3 (BH, Sk, D), bias (B|1, Sq|1, Sk) or None.
    ``dropout_p`` > 0 applies in-kernel inverted dropout to the attention
    probs, regenerated from ``dropout_seed`` (int32 scalar) in the
    backward.  Returns (out (BH, Sq, D), lse (BH, Sq) fp32)."""
    if dropout_p and not 0.0 <= dropout_p < 1.0:
        raise ValueError(f"dropout_p must be in [0, 1), got {dropout_p}")
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    plan = _resident((q3, k3, v3), sq, sk, d, bias, causal, window,
                     dropout_p)
    if plan is not None:
        tally("flash_attention", "resident")
        return _resident_fwd(q3, k3, v3, scale, causal, window, plan,
                             interpret)
    bq, bk = _block_sizes(sq, sk, d, _operand_dtype(q3, k3, v3).itemsize)
    sq_p, sk_p = _ceil_div(sq, bq) * bq, _ceil_div(sk, bk) * bk
    q3 = jnp.pad(q3, ((0, 0), (0, sq_p - sq), (0, 0)))
    k3 = jnp.pad(k3, ((0, 0), (0, sk_p - sk), (0, 0)))
    v3 = jnp.pad(v3, ((0, 0), (0, sk_p - sk), (0, 0)))
    has_bias = bias is not None
    if not has_bias and sk_p != sk:
        # mask the padded keys so they don't leak into the softmax
        bias = jnp.zeros((1, 1, sk), _f32)
        has_bias = True
    if has_bias:
        bias = jnp.pad(bias.astype(_f32),
                       ((0, 0), (0, sq_p - bias.shape[1] if
                                 bias.shape[1] > 1 else 0),
                        (0, sk_p - bias.shape[2])),
                       constant_values=_NEG)
    nq, nk = sq_p // bq, sk_p // bk
    grid = (bh, nq, nk)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
    ]
    args = [q3, k3, v3]
    if dropout_p > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(_seed_vec(dropout_seed, dropout_row_off,
                              dropout_col_off))
    if has_bias:
        in_specs.append(_bias_spec(bias, bq, bk))
        args.append(bias)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, nk=nk, has_bias=has_bias,
                          window=window, dropout_p=dropout_p),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_p, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, sq_p, 1), _f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), _f32),
            pltpu.VMEM((bq, 1), _f32),
            pltpu.VMEM((bq, d), _f32),
        ],
        interpret=interpret,
        name="flash_attn_fwd",
    )(*args)
    return out[:, :sq], lse[:, :sq, 0]


def flash_attention_bwd(q3, k3, v3, bias, out, lse, g, scale, causal,
                        interpret=False, window=None, dropout_p=0.0,
                        dropout_seed=None, dropout_row_off=0,
                        dropout_col_off=0):
    """→ (dq, dk, dv) with the shapes/dtypes of q3/k3/v3."""
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    plan = _resident((q3, k3, v3, g), sq, sk, d, bias, causal, window,
                     dropout_p, _BWD_KEYS)
    if plan is not None:
        return _resident_bwd(q3, k3, v3, out, lse, g, scale, causal, window,
                             plan, interpret)
    bq, bk = _block_sizes(sq, sk, d,
                          _operand_dtype(q3, k3, v3, g).itemsize)
    sq_p, sk_p = _ceil_div(sq, bq) * bq, _ceil_div(sk, bk) * bk
    delta = jnp.sum(g.astype(_f32) * out.astype(_f32), axis=-1)  # (BH, Sq)
    q3 = jnp.pad(q3, ((0, 0), (0, sq_p - sq), (0, 0)))
    k3 = jnp.pad(k3, ((0, 0), (0, sk_p - sk), (0, 0)))
    v3 = jnp.pad(v3, ((0, 0), (0, sk_p - sk), (0, 0)))
    g = jnp.pad(g, ((0, 0), (0, sq_p - sq), (0, 0)))
    # padded q rows: lse=0 → p=exp(s-0); keep them harmless with lse=+big
    lse = jnp.pad(lse, ((0, 0), (0, sq_p - sq)),
                  constant_values=-_NEG)[..., None]
    delta = jnp.pad(delta, ((0, 0), (0, sq_p - sq)))[..., None]
    has_bias = bias is not None
    if not has_bias and sk_p != sk:
        bias = jnp.zeros((1, 1, sk), _f32)
        has_bias = True
    if has_bias:
        bias = jnp.pad(bias.astype(_f32),
                       ((0, 0), (0, sq_p - bias.shape[1] if
                                 bias.shape[1] > 1 else 0),
                        (0, sk_p - bias.shape[2])),
                       constant_values=_NEG)
    nq, nk = sq_p // bq, sk_p // bk

    common = [q3, k3, v3, g]
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    lse_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))

    in_specs = [q_spec, k_spec, k_spec, q_spec, lse_spec, lse_spec]
    args = common + [lse, delta]
    seed_arr = (_seed_vec(dropout_seed, dropout_row_off,
                          dropout_col_off)
                if dropout_p > 0.0 else None)
    if dropout_p > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed_arr)
    if has_bias:
        in_specs.append(_bias_spec(bias, bq, bk))
        args.append(bias)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, nk=nk, has_bias=has_bias,
                          window=window, dropout_p=dropout_p),
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), _f32)],
        interpret=interpret,
        name="flash_attn_bwd_dq",
    )(*args)

    # dk/dv: swap loop order — k blocks in the middle, q innermost
    q_spec2 = pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0))
    k_spec2 = pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))
    lse_spec2 = pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0))
    in_specs2 = [q_spec2, k_spec2, k_spec2, q_spec2, lse_spec2, lse_spec2]
    args2 = common + [lse, delta]
    if dropout_p > 0.0:
        in_specs2.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args2.append(seed_arr)
    if has_bias:
        in_specs2.append(_bias_spec(bias, bq, bk, for_dkv=True))
        args2.append(bias)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, nq=nq, has_bias=has_bias,
                          window=window, dropout_p=dropout_p),
        grid=(bh, nk, nq),
        in_specs=in_specs2,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk_p, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, sk_p, d), v3.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), _f32)] * 2,
        interpret=interpret,
        name="flash_attn_bwd_dkv",
    )(*args2)
    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


# ---------------------------------------------------------------------------
# The tier rule and the registration
# ---------------------------------------------------------------------------

# Key length below which a compiled program takes XLA's own attention.
# Receipts (v5e, fwd+bwd, round-4 A/B table, an unledgered run): with
# causal block skipping S=256 ran 1.06x XLA and S=512 0.96x (both
# noise-level), S=1024 causal 1.24x, S=2048/D=128 1.19x, banded
# S=2048/w=256 1.82x — flash wins the shapes it exists for and the
# 256-512 boundary is a wash.  Since PR 30 the MXU operands keep the
# input dtype (statistics and accumulators stay fp32) and bf16 operands
# tile 512 x 1024, which took the S = 1024 causal call from 5.0 to
# 3.0 ms on the v5e (PERF.md, PR 30); the crossover can only have moved
# down.  The 512 boundary is owed a re-measurement.
FLASH_MIN_SK = 512

# the XLA fallback's score tensor (fwd scores + softmax residual for
# backward, f32) must also stay SMALL in absolute terms — key length
# alone ignores the B*H factor.  128 MB keeps the fallback's footprint
# noise-level next to activations; beyond it flash's O(S) memory is the
# point even where it is a little slower per-FLOP.
XLA_SCORES_BYTE_CAP = 128 * 1024 * 1024


def _compiled_takes(b, h, sq, sk) -> bool:
    """What a compiled program gives the flash kernel: attention from
    :data:`FLASH_MIN_SK` keys up, and any whose score tensor in the XLA
    tier would pass :data:`XLA_SCORES_BYTE_CAP`, whatever its speed."""
    return sk >= FLASH_MIN_SK or b * h * sq * sk * 4 > XLA_SCORES_BYTE_CAP


def kernel_mode(b, h, sq, sk):
    """The flash kernel's rule: the mode it runs in for a ``(b, h, sq,
    sk)`` attention, or ``None`` for the XLA tier."""
    return choose("flash_attention", compiled=_compiled_takes(b, h, sq, sk))


def _audit_programs():
    """Both tiers on one abstract causal shape for the jaxpr verifier:
    the Pallas fwd (staged pallas_call, not executed) and the declared
    XLA reference."""
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    q3 = sds((2, 128, 64), f32)              # (B*H, S, D) kernel layout
    q4 = sds((1, 2, 128, 64), f32)           # (B, H, S, D) reference layout
    scale = 64 ** -0.5

    def _pallas(q, k, v):
        return flash_attention_fwd(q, k, v, None, scale, True)[0]

    def _xla(q, k, v):
        from ..contrib.multihead_attn.attn_funcs import attention_reference
        return attention_reference(q, k, v, None, True, scale)

    return [("pallas", _pallas, (q3, q3, q3)),
            ("xla", _xla, (q4, q4, q4))]


register_kernel(
    "flash_attention",
    xla_fallback=(
        "apex_tpu.contrib.multihead_attn.attn_funcs.attention_reference"),
    doc="Blockwise online-softmax attention (fwd + recompute bwd)",
    audit_programs=_audit_programs)
