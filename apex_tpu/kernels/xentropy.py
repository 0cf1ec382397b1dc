"""Pallas TPU fused label-smoothed softmax cross-entropy.

TPU re-design of the reference's ``xentropy_cuda`` extension
(apex/contrib/csrc/xentropy/xentropy_kernel.cu): ONE pass over each
logits row computes max / sum-exp / target-logit / row-sum in VMEM with
the bf16→f32 cast applied block-locally (free in-register), and the
backward is a single elementwise pass reconstructing probabilities from
the saved logsumexp.  The jnp expression of the same math
(contrib/xentropy/softmax_xentropy.py) can materialize f32 casts of the
whole (rows, vocab) logits in unfavorable fusion contexts — measured
~14 ms of convert_element_type per GPT seq-128 step (unledgered run,
round 4); this kernel was built to fuse that away (see VERDICT below
for how that bet measured out).

Grid: (row_blocks, col_blocks) with columns INNERMOST — running
max/denominator/target/sum scratch lives in VMEM across the column
sweep (the flash-attention pattern, ops/pallas/attention.py).  The
backward needs no scratch: ``p = exp(x - lse)`` is elementwise given
the saved per-row lse, and the label column folds in as an iota
compare.

VERDICT (round-4 on-chip A/B, unledgered runs): the kernel LOSES to
XLA's fused lowering of the jnp expression in isolation — 0.38x at
(8192, 50257), 0.74x at (16384, 50257) fwd+bwd — the online-softmax
column sweep is VPU-bound where XLA's reduce kernels are tuned, and
the GPT seq-128 headline ran 8% slower with it engaged.  Dispatch
(contrib/xentropy/softmax_xentropy._use_kernel) therefore defaults it
OFF on-chip; interpret mode always exercises it, and
APEX_TPU_XENT_KERNEL=1 opts in.  It remains the starting point for a
future fused lm-head+loss kernel (where the matmul would amortize the
sweep).

Invalid-label semantics (garbage-in divergence from the jnp path):
a label >= C matches no column in the iota compare, so the kernel
accumulates target-logit 0 (loss = lse), while the jnp path's
``lf[label]`` gather clamps to the LAST column under jit; a negative
label other than padding_idx likewise accumulates 0 here but clamps to
column 0 there.  Neither arm can raise under trace — callers must
validate label ranges (the model families do: emittable-id checks use
the logical vocab).  Smoothing is mask-aware, matching the jnp path:
columns at or below MASKED_LOGIT_THR are excluded from the smoothing
sum and its divisor in both passes, so lane-padded heads are exact
under smoothing on this arm too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import MASKED_LOGIT_THR as _MASK_THR

_f32 = jnp.float32
_NEG = -1e30


def _round_up(x, m):
    return (x + m - 1) // m * m


def _block_sizes(rows, c):
    """(bm, bc): ~2 MB f32 of logits per grid step, lane/sublane aligned;
    bm capped by the (padded) row count so small inputs aren't blown up
    to a 256-row block."""
    bc = min(2048, _round_up(c, 128))
    bm = max(8, min(256, (1 << 19) // bc // 8 * 8, _round_up(rows, 8)))
    return bm, bc


def _fwd_kernel(x_ref, lab_ref, loss_ref, lse_ref, m_scr, l_scr, t_scr,
                s_scr, n_scr, *, c, bc, nj, smoothing, padding_idx):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        t_scr[...] = jnp.zeros_like(t_scr)
        s_scr[...] = jnp.zeros_like(s_scr)
        n_scr[...] = jnp.zeros_like(n_scr)

    x = x_ref[...].astype(_f32)
    lab = lab_ref[...]                                    # (bm, 1) int32
    cols = j * bc + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = cols < c
    xm = jnp.where(valid, x, _NEG)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(xm, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(
        jnp.exp(xm - m_new), axis=1, keepdims=True)
    m_scr[...] = m_new
    # the label column appears in exactly one block; a padding label
    # (never a valid column id) simply accumulates nothing
    t_scr[...] += jnp.sum(jnp.where(cols == lab, x, 0.0), axis=1,
                          keepdims=True)
    # smoothing sum/count over LIVE columns only — in-range AND above
    # the masked-vocab threshold — matching the jnp path's mask-aware
    # smoothing (lane-padded heads' -1e30 columns carry no mass)
    live = valid & (x > _MASK_THR)
    s_scr[...] += jnp.sum(jnp.where(live, x, 0.0), axis=1, keepdims=True)
    n_scr[...] += jnp.sum(live.astype(_f32), axis=1, keepdims=True)

    @pl.when(j == nj - 1)
    def _fin():
        lse = m_scr[...] + jnp.log(l_scr[...])
        loss = lse - (1.0 - smoothing) * t_scr[...] \
            - smoothing * s_scr[...] / jnp.maximum(n_scr[...], 1.0)
        loss_ref[...] = jnp.where(lab == padding_idx, 0.0, loss)
        lse_ref[...] = lse


def _bwd_kernel(x_ref, lab_ref, lse_ref, gm_ref, nv_ref, dx_ref, *, c, bc,
                smoothing):
    j = pl.program_id(1)
    x = x_ref[...].astype(_f32)
    lab = lab_ref[...]
    gm = gm_ref[...]
    cols = j * bc + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    probs = jnp.exp(x - lse_ref[...])
    onehot = (cols == lab).astype(_f32)
    # mask-aware smoothing term: s/n_valid on live columns, 0 on masked
    # ones (their probs already underflow to 0, so dx there is exactly
    # 0); nv comes precomputed per row from the wrapper
    smooth = jnp.where(x > _MASK_THR, smoothing / nv_ref[...], 0.0)
    dx = gm * (probs - smooth) - ((1.0 - smoothing) * gm) * onehot
    dx_ref[...] = dx.astype(dx_ref.dtype)


def xent_forward(logits2d, labels, smoothing, padding_idx, interpret=False):
    """logits2d (rows, C), labels (rows,) int32 →
    (losses (rows,) f32, lse (rows,) f32)."""
    rows, c = logits2d.shape
    bm, bc = _block_sizes(rows, c)
    rows_p, c_p = _round_up(rows, bm), _round_up(c, bc)
    if rows_p != rows or c_p != c:
        logits2d = jnp.pad(logits2d, ((0, rows_p - rows), (0, c_p - c)))
    lab2d = jnp.pad(labels.astype(jnp.int32),
                    (0, rows_p - rows)).reshape(rows_p, 1)
    nj = c_p // bc
    row_spec = pl.BlockSpec((bm, bc), lambda i, j: (i, j))
    lab_spec = pl.BlockSpec((bm, 1), lambda i, j: (i, 0))
    losses, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, c=c, bc=bc, nj=nj,
                          smoothing=smoothing, padding_idx=padding_idx),
        grid=(rows_p // bm, nj),
        in_specs=[row_spec, lab_spec],
        out_specs=[lab_spec, lab_spec],
        out_shape=[jax.ShapeDtypeStruct((rows_p, 1), _f32)] * 2,
        scratch_shapes=[pltpu.VMEM((bm, 1), _f32)] * 5,
        interpret=interpret,
        name="xent_fwd",
    )(logits2d, lab2d)
    return losses[:rows, 0], lse[:rows, 0]


def xent_backward(logits2d, labels, lse, gmask, smoothing, interpret=False):
    """→ dlogits (rows, C) in logits2d.dtype.  ``gmask`` (rows,) f32 is
    the incoming cotangent with padding rows already zeroed."""
    rows, c = logits2d.shape
    bm, bc = _block_sizes(rows, c)
    rows_p, c_p = _round_up(rows, bm), _round_up(c, bc)
    if smoothing:
        # per-row live-column count for the mask-aware smoothing divisor
        # (== c for unmasked inputs); one cheap reduction, smoothing-only
        nv = jnp.sum((logits2d.astype(_f32) > _MASK_THR).astype(_f32),
                     axis=-1)
    else:
        nv = jnp.full((rows,), float(c), _f32)
    if rows_p != rows or c_p != c:
        logits2d = jnp.pad(logits2d, ((0, rows_p - rows), (0, c_p - c)))
    lab2d = jnp.pad(labels.astype(jnp.int32),
                    (0, rows_p - rows)).reshape(rows_p, 1)
    # padded rows: lse -> +big so probs underflow to 0 (and gm is 0)
    lse2d = jnp.pad(lse.astype(_f32), (0, rows_p - rows),
                    constant_values=-_NEG).reshape(rows_p, 1)
    gm2d = jnp.pad(gmask.astype(_f32), (0, rows_p - rows)).reshape(rows_p, 1)
    nv2d = jnp.pad(nv, (0, rows_p - rows),
                   constant_values=1.0).reshape(rows_p, 1)
    row_spec = pl.BlockSpec((bm, bc), lambda i, j: (i, j))
    lab_spec = pl.BlockSpec((bm, 1), lambda i, j: (i, 0))
    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, c=c, bc=bc, smoothing=smoothing),
        grid=(rows_p // bm, c_p // bc),
        in_specs=[row_spec, lab_spec, lab_spec, lab_spec, lab_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((rows_p, c_p), logits2d.dtype),
        interpret=interpret,
        name="xent_bwd",
    )(logits2d, lab2d, lse2d, gm2d, nv2d)
    return dx[:rows, :c]
